"""Checkpoint/restore (ISSUE 5): preemption-tolerant snapshot + elastic
resume of the persistent megakernel.

Acceptance semantics under test: for a deterministic workload,
*checkpoint at round k then restore and run to completion* must be
bit-identical to the uninterrupted run (UTS dynamic tree, Cholesky with
the batched dispatch tier, wave-DAG SW with cross-round prefetch - all
under interpret mode); a checkpoint-disabled build must behave exactly as
before (DeviceFaultPlan discipline); corrupt or version-mismatched
bundles must be rejected with structured errors. Resident-mesh round
trips (same mesh and N -> M re-homing) need the Mosaic interpret mode and
ride the chaos marker like the other mesh tests.
"""

import os
import threading
import time

import numpy as np
import pytest

import hclib_tpu as hc
from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.workloads import (
    UTS_NODE,
    device_uts_mk,
    make_uts_megakernel,
)
from hclib_tpu.runtime import resilience
from hclib_tpu.runtime.checkpoint import (
    CheckpointBundle,
    CheckpointError,
    checkpoint_on_preempt,
    restore_megakernel,
    restore_resident,
    restore_stream,
    snapshot_megakernel,
    snapshot_resident,
    snapshot_stream,
)

UTS_KW = dict(max_depth=8, interpret=True)


def _uts_builder():
    b = TaskGraphBuilder()
    b.add(UTS_NODE, args=[1, 0])
    return b


@pytest.fixture
def uts_ckpt_mk():
    """A checkpoint-enabled UTS megakernel per round-trip test (the
    heaviest repeated build of the suite). Function-scoped since ISSUE
    18: every test gets a FRESH instance - no cross-test object
    aliasing - and the process-wide program cache
    (runtime/progcache.py) dedupes the content-identical compiles that
    session scope used to dedupe by object sharing. With the cache
    forced off each test simply pays its own build."""
    return make_uts_megakernel(checkpoint=True, **UTS_KW)


@pytest.fixture(scope="session")
def uts_ref():
    """(nodes, info) of the uninterrupted seeded traversal - the
    deterministic reference every round trip compares against, run
    once per session."""
    return device_uts_mk(**UTS_KW)


# ------------------------------------------------ megakernel round trips


def test_uts_checkpoint_then_restore_bit_identical(uts_ckpt_mk, uts_ref):
    """ACCEPTANCE (dynamic tree): quiesce the seeded UTS traversal at
    round k, resume from the exported state, and the final node count +
    executed totals are bit-identical to the uninterrupted run."""
    nodes, info_full = uts_ref
    assert nodes > 100  # the tree is a real traversal, not a stub
    mk = uts_ckpt_mk
    iv_q, _, info_q = mk.run(_uts_builder(), quiesce=nodes // 3)
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0  # genuinely mid-tree
    assert info_q["quiesce"]["executed_at"] >= nodes // 3
    iv_r, _, info_r = mk.resume(info_q["state"])
    assert int(iv_r[0]) == nodes
    assert info_r["executed"] == info_full["executed"] == nodes
    assert info_r["pending"] == 0


def test_checkpoint_chains_and_quiesce_past_end_is_clean(
    uts_ckpt_mk, uts_ref,
):
    """A resumed run can be quiesced AGAIN (chained checkpoints); a
    quiesce threshold past the workload size never fires and the run
    completes normally."""
    nodes, _ = uts_ref
    mk = uts_ckpt_mk
    _, _, q1 = mk.run(_uts_builder(), quiesce=nodes // 4)
    _, _, q2 = mk.resume(q1["state"], quiesce=nodes // 2)
    assert q2["quiesced"] and q2["pending"] > 0
    iv, _, done = mk.resume(q2["state"])
    assert int(iv[0]) == nodes and done["pending"] == 0
    # Threshold past the end: completes, not quiesced, no state attached.
    iv2, _, info2 = mk.run(_uts_builder(), quiesce=10 * nodes)
    assert int(iv2[0]) == nodes
    assert info2["quiesced"] is False and "state" not in info2


def test_checkpoint_off_path_bit_identical_and_guarded(
    uts_ckpt_mk, uts_ref,
):
    """DeviceFaultPlan discipline: a checkpoint-enabled build that never
    quiesces produces bit-identical outputs to a plain build, and a plain
    build refuses quiesce= with a clear error instead of silently
    ignoring it."""
    n0, info0 = uts_ref
    mk_on = uts_ckpt_mk
    iv_on, _, info_on = mk_on.run(_uts_builder())
    assert int(iv_on[0]) == n0
    assert info_on["executed"] == info0["executed"]
    assert info_on["quiesced"] is False
    mk_off = make_uts_megakernel(**UTS_KW)
    with pytest.raises(ValueError, match="checkpoint=True"):
        mk_off.run(_uts_builder(), quiesce=5)
    # quiesce=False is OFF (boolean plumbing), never "quiesce now" - on
    # both the plain and the checkpoint-enabled build.
    iv_f, _, info_f = mk_off.run(_uts_builder(), quiesce=False)
    assert int(iv_f[0]) == n0
    iv_f2, _, info_f2 = mk_on.run(_uts_builder(), quiesce=False)
    assert int(iv_f2[0]) == n0 and info_f2["quiesced"] is False


def test_cholesky_batch_tier_checkpoint_bit_identical(tmp_path):
    """ACCEPTANCE (static DAG + batched dispatch tier): quiesce the
    Cholesky factorization mid-graph - batch lanes spill to the ring at
    the quiesce boundary - restore THROUGH THE ON-DISK BUNDLE (the bf16
    split caches exercise the extension-dtype round trip), and L is
    bit-identical to the uninterrupted factor."""
    from hclib_tpu.device.cholesky import (
        _from_tiles,
        build_cholesky_graph,
        cholesky_buffers,
        make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    nt = 2
    a = make_spd(256).astype(np.float32)
    mk_full = make_cholesky_megakernel(nt, interpret=True)
    _, data_full, info_full = mk_full.run(
        build_cholesky_graph(nt), data=cholesky_buffers(a, nt)
    )
    L_full = np.asarray(data_full["tiles"])

    mk = make_cholesky_megakernel(nt, interpret=True, checkpoint=True)
    _, _, info_q = mk.run(
        build_cholesky_graph(nt), data=cholesky_buffers(a, nt), quiesce=2,
    )
    assert info_q["quiesced"] and info_q["pending"] > 0
    path = str(tmp_path / "chol-ckpt")
    snapshot_megakernel(mk, info_q).save(path)
    mk2 = make_cholesky_megakernel(nt, interpret=True, checkpoint=True)
    _, data_r, info_r = restore_megakernel(path, mk2)
    assert info_r["pending"] == 0
    assert info_r["executed"] == info_full["executed"]
    assert np.array_equal(np.asarray(data_r["tiles"]), L_full)
    assert np.array_equal(
        np.tril(_from_tiles(np.asarray(data_r["tiles"]), nt)),
        np.tril(_from_tiles(L_full, nt)),
    )


def test_sw_wave_prefetch_checkpoint_bit_identical():
    """ACCEPTANCE (batch tier + cross-round prefetch): quiesce the wave-
    DAG SW mid-sweep - the in-flight prefetch drains before lane spill
    (no DMA outlives the scheduler) - restore, and the full H matrix is
    bit-identical to the uninterrupted run."""
    from hclib_tpu.device.smithwaterman import (
        build_sw_wave_graph,
        make_sw_wave_megakernel,
        sw_wave_buffers,
    )
    from hclib_tpu.models.smithwaterman import random_seq

    a, b = random_seq(512, 5), random_seq(512, 6)

    def fresh_data():
        d = sw_wave_buffers(a, b)
        d["htiles"] = np.zeros((4, 4, 128, 128), np.int32)
        return d

    mk_full = make_sw_wave_megakernel(4, 4, interpret=True, chunk=1,
                                      width=2)
    iv_f, out_f, info_f = mk_full.run(
        build_sw_wave_graph(4, 4, chunk=1), data=fresh_data()
    )
    h_full = np.asarray(out_f["htiles"])

    mk = make_sw_wave_megakernel(4, 4, interpret=True, chunk=1, width=2,
                                 checkpoint=True)
    _, _, info_q = mk.run(
        build_sw_wave_graph(4, 4, chunk=1), data=fresh_data(), quiesce=6,
    )
    assert info_q["quiesced"] and info_q["pending"] > 0
    iv_r, out_r, info_r = mk.resume(info_q["state"])
    assert np.array_equal(np.asarray(out_r["htiles"]), h_full)
    assert int(iv_r[0]) == int(iv_f[0])  # best score
    assert info_r["executed"] == info_f["executed"]


# -------------------------------------------------------- bundle on disk


def test_bundle_save_load_restore_and_metrics(
    tmp_path, uts_ckpt_mk, uts_ref,
):
    """Versioned on-disk artifact: quiesce -> snapshot -> save (npz +
    manifest, sha256) -> load -> restore onto a FRESHLY built megakernel;
    checkpoint size/duration land in the MetricsRegistry."""
    nodes, _ = uts_ref
    mk = uts_ckpt_mk
    _, _, info_q = mk.run(_uts_builder(), quiesce=nodes // 2)
    bundle = snapshot_megakernel(mk, info_q)
    reg = hc.MetricsRegistry()
    path = str(tmp_path / "ckpt")
    stats = bundle.save(path, metrics=reg)
    assert stats["bundle_bytes"] > 0 and os.path.exists(
        os.path.join(path, "manifest.json")
    )
    snap = reg.snapshot()["metrics"]
    assert snap["checkpoint.bundle_bytes"] == stats["bundle_bytes"]
    assert "checkpoint.save_s" in snap
    # Restore on a fresh (same-code) kernel, straight from disk.
    mk2 = make_uts_megakernel(checkpoint=True, **UTS_KW)
    iv, _, info = restore_megakernel(path, mk2)
    assert int(iv[0]) == nodes and info["pending"] == 0


def test_bundle_corruption_and_version_rejected(
    tmp_path, uts_ckpt_mk, uts_ref,
):
    import json

    nodes, _ = uts_ref
    mk = uts_ckpt_mk
    _, _, info_q = mk.run(_uts_builder(), quiesce=nodes // 2)
    path = str(tmp_path / "ckpt")
    snapshot_megakernel(mk, info_q).save(path)
    npz = os.path.join(path, "state.npz")
    blob = open(npz, "rb").read()
    with open(npz, "wb") as f:  # flip bytes: sha256 must catch it
        f.write(blob[:-8] + b"\x00" * 8)
    with pytest.raises(CheckpointError, match="corrupt"):
        CheckpointBundle.load(path)
    with open(npz, "wb") as f:
        f.write(blob)
    man_path = os.path.join(path, "manifest.json")
    man = json.load(open(man_path))
    man["version"] = 99
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointError, match="version 99"):
        CheckpointBundle.load(path)
    man["version"] = 1
    man["magic"] = "something-else"
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointError, match="magic"):
        CheckpointBundle.load(path)


def test_restore_rejects_mismatched_program(uts_ckpt_mk, uts_ref):
    """A bundle only restores onto the SAME program shape: F_FN words
    index the kernel table positionally, so a different table must be
    refused, not silently misdispatched."""
    nodes, _ = uts_ref
    mk = uts_ckpt_mk
    _, _, info_q = mk.run(_uts_builder(), quiesce=nodes // 2)
    bundle = snapshot_megakernel(mk, info_q)
    other = Megakernel(
        kernels=[("bump", lambda ctx: ctx.set_value(0, ctx.value(0) + 1))],
        capacity=64, num_values=16, succ_capacity=8, interpret=True,
        checkpoint=True,
    )
    with pytest.raises(CheckpointError, match="kernel_names"):
        restore_megakernel(bundle, other)
    wrong_cap = make_uts_megakernel(checkpoint=True, capacity=512,
                                    **UTS_KW)
    with pytest.raises(CheckpointError, match="capacity"):
        restore_megakernel(bundle, wrong_cap)
    with pytest.raises(CheckpointError, match="megakernel"):
        restore_stream(bundle, StreamingMegakernel(mk))
    # And non-quiesced info has no exportable state.
    with pytest.raises(CheckpointError, match="no quiesced state"):
        snapshot_megakernel(mk, {"executed": 1})


# -------------------------------------------------------- streaming-inject


def _bump_mk(checkpoint=False):
    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    return Megakernel(
        kernels=[("bump", bump)], capacity=512, num_values=64,
        succ_capacity=8, interpret=True, checkpoint=checkpoint,
    )


def test_streaming_checkpoint_roundtrip(tmp_path):
    """Quiesce a live stream mid-drain, bundle it, restore on a FRESH
    stream object, inject more work there, and the grand total is exact -
    nothing lost at the cut (unconsumed ring rows ride the bundle)."""
    sm = StreamingMegakernel(_bump_mk(checkpoint=True), ring_capacity=512)
    b = TaskGraphBuilder()
    for i in range(10):
        b.add(0, args=[i + 1])
    for i in range(10, 40):
        sm.inject(0, args=[i + 1])
    sm.quiesce(after_executed=12)
    iv, info = sm.run_stream(b, quantum=4, deadline_s=120.0)
    assert info["quiesced"] and info["executed"] >= 12
    assert info["quiesce_latency_s"] is not None
    # The quiesced stream is closed: producers fail fast.
    with pytest.raises(RuntimeError, match="closed"):
        sm.inject(0, args=[99])
    path = str(tmp_path / "stream-ckpt")
    snapshot_stream(sm, info).save(path)
    sm2 = StreamingMegakernel(_bump_mk(checkpoint=True), ring_capacity=512)
    for i in range(40, 45):
        sm2.inject(0, args=[i + 1])
    sm2.close()
    iv2, info2 = restore_stream(
        CheckpointBundle.load(path), sm2, quantum=64, deadline_s=120.0,
    )
    assert int(iv2[0]) == 45 * 46 // 2
    assert info2["executed"] == 45


def test_streaming_same_object_resume_and_drained_cut():
    """Two review-hardened paths: (1) resuming on the SAME stream object
    clears the quiesce request and the quiesce-induced close, so the
    continued run drains instead of instantly re-quiescing (an explicit
    close() stays sticky across the resume - drain-and-exit works); (2) a
    quiesce threshold the workload never reaches cuts host-side once the
    stream drains (observed round -1) instead of spinning run_stream
    forever."""
    sm = StreamingMegakernel(_bump_mk(checkpoint=True), ring_capacity=256)
    b = TaskGraphBuilder()
    for i in range(30):
        b.add(0, args=[i + 1])
    sm.quiesce(after_executed=10)
    iv, info = sm.run_stream(b, quantum=4, deadline_s=120.0)
    assert info["quiesced"] and info["pending"] > 0
    # resume_state carries its own buffers: passing more is refused, not
    # silently ignored (parity with ResidentKernel.run's guard).
    with pytest.raises(ValueError, match="carries its own"):
        sm.run_stream(resume_state=info["state"],
                      ivalues=np.zeros(64, np.int32))
    sm.close()  # explicit: must survive the same-object resume
    iv2, info2 = sm.run_stream(resume_state=info["state"],
                               deadline_s=120.0)
    assert int(iv2[0]) == 30 * 31 // 2
    assert info2["pending"] == 0 and not info2.get("quiesced")

    sm3 = StreamingMegakernel(_bump_mk(checkpoint=True), ring_capacity=64)
    b3 = TaskGraphBuilder()
    b3.add(0, args=[5])
    sm3.quiesce(after_executed=1 << 30)  # unreachable threshold
    iv3, info3 = sm3.run_stream(b3, quantum=64, deadline_s=120.0)
    assert info3["quiesced"] is True
    assert info3["quiesce_observed_round"] == -1  # host-side drained cut
    assert info3["pending"] == 0 and int(iv3[0]) == 5


def test_preempt_hook_quiesces_running_stream():
    """The preemption path end to end: fire_preempt (what SIGTERM /
    HCLIB_TPU_PREEMPT / the watchdog checkpoint rung call) lands while
    the stream runs; the bound hook quiesces it, and run_stream returns a
    restorable snapshot instead of losing the graph."""
    resilience.reset_preempt()
    # Ring sized so the feeder cannot exhaust it before the preemption
    # lands even on a slow box (~0.1s / 5ms period ≈ 20 rows queued).
    sm = StreamingMegakernel(_bump_mk(checkpoint=True),
                             ring_capacity=2048)
    b = TaskGraphBuilder()
    b.add(0, args=[1])
    stop = threading.Event()

    def feeder():
        while not stop.is_set():
            try:
                sm.inject(0, args=[1])
            except RuntimeError:
                return  # quiesce closed the ring - expected
            time.sleep(0.005)

    def preempter():
        time.sleep(0.1)
        assert resilience.fire_preempt("test preemption") >= 1

    tf = threading.Thread(target=feeder)
    tp = threading.Thread(target=preempter)
    try:
        with checkpoint_on_preempt(sm):
            tf.start()
            tp.start()
            iv, info = sm.run_stream(b, quantum=16, deadline_s=120.0)
        assert info["quiesced"] is True
        assert "state" in info
        # Restorable: drain the snapshot to completion on a fresh stream.
        sm2 = StreamingMegakernel(_bump_mk(checkpoint=True),
                                  ring_capacity=2048)
        sm2.close()
        iv2, info2 = sm2.run_stream(
            resume_state=info["state"], deadline_s=120.0
        )
        assert info2["pending"] == 0
        assert int(iv2[0]) == info2["executed"]  # every bump(1) landed once
    finally:
        stop.set()
        tp.join()
        tf.join()
        resilience.reset_preempt()
    assert not resilience._preempt_hooks  # context manager unregistered


def test_preempt_env_replays_into_new_bindings(monkeypatch):
    """HCLIB_TPU_PREEMPT set before the stream starts (the wrapper-script
    spelling): register-then-replay quiesces it immediately, so even a
    notice that predates the run checkpoints instead of racing it."""
    resilience.reset_preempt()
    monkeypatch.setenv("HCLIB_TPU_PREEMPT", "1")
    sm = StreamingMegakernel(_bump_mk(checkpoint=True), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(0, args=[7])
    try:
        with checkpoint_on_preempt(sm):
            iv, info = sm.run_stream(b, quantum=16, deadline_s=120.0)
        assert info["quiesced"] is True
    finally:
        resilience.reset_preempt()


def test_install_preempt_handler_fires_hooks():
    """The SIGTERM handler wiring: install, raise the signal in-process,
    and the registered hook fires (on the handler's deferred daemon
    thread - signal frames must not take hook locks); uninstall restores
    the previous handler."""
    import signal

    resilience.reset_preempt()
    fired = threading.Event()
    hook = fired.set
    resilience.register_preempt_hook(hook)
    uninstall = resilience.install_preempt_handler()
    try:
        signal.raise_signal(signal.SIGTERM)
        assert resilience.preempt_requested()  # flag set in the frame
        assert fired.wait(10.0), "SIGTERM did not reach the preempt hooks"
    finally:
        uninstall()
        resilience.unregister_preempt_hook(hook)
        resilience.reset_preempt()


# ------------------------------------------------------- resident mesh


def _mesh_uts_rk(ndev, checkpoint=True, capacity=256):
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = make_uts_megakernel(
        max_depth=6, interpret=True, capacity=capacity,
        checkpoint=checkpoint,
    )
    # homed=False: UTS rows are link-free (count-accumulate only), so
    # round-3 whole-row migration suffices - and it keeps the quiesced
    # state proxy-free, which is what makes N -> M re-homing legal.
    return ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[UTS_NODE],
        window=4, homed=False,
    )


def _mesh_uts_builders(ndev):
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].add(UTS_NODE, args=[d + 1, 0])
    return builders


def test_resident_quiesce_validation_needs_no_mesh():
    """Host-side guards (no Mosaic needed): quiesce on a non-checkpoint
    build, malformed waits, and resume_state conflicts all refuse before
    any kernel builds. (Quiesce WITH pending waits is no longer refused -
    the wait table exports with the snapshot; see
    test_resident_quiesce_with_pending_waits_roundtrip.)"""
    rk = _mesh_uts_rk(2, checkpoint=False)
    with pytest.raises(ValueError, match="checkpoint=True"):
        rk.run(_mesh_uts_builders(2), quiesce=1)
    rk2 = _mesh_uts_rk(2, checkpoint=True)
    # Wait validation still applies (this kernel declares no channels).
    with pytest.raises(ValueError, match="bad channel id"):
        rk2.run(_mesh_uts_builders(2), quiesce=1, waits=[[(0, 1, 0)]])
    with pytest.raises(ValueError, match="exactly one"):
        rk2.run(_mesh_uts_builders(2), resume_state={})
    with pytest.raises(ValueError, match="exactly one"):
        rk2.run()
    # resume_state with mismatched wait-table / ring shapes refuses with
    # a diagnostic naming the device counts.
    with pytest.raises(ValueError, match="wait table covers"):
        rk2.run(resume_state={
            "tasks": np.zeros((2, 4, 16), np.int32),
            "succ": np.zeros((2, 8), np.int32),
            "ready": np.zeros((2, 4), np.int32),
            "counts": np.zeros((2, 8), np.int32),
            "ivalues": np.zeros((2, 16), np.int32),
            "waits": np.zeros((4, 65, 3), np.int32),
        })


def test_reshard_refuses_unsafe_rows():
    """N -> M re-homing moves only ready link-free rows (the PR 2
    dead-chip semantics): dependent rows, successor links, home-links,
    and dynamic out slots are refused with a diagnostic."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_DEP, F_HOME, F_OUT, F_SUCC0, NO_TASK,
    )

    def fake_bundle(mutate):
        ndev, cap, V = 2, 8, 16
        tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
        tasks[:, :, F_SUCC0] = NO_TASK
        tasks[:, :, 2:4] = NO_TASK
        tasks[:, :, F_HOME] = NO_TASK
        counts = np.zeros((ndev, 8), np.int32)
        counts[:, 1] = 1  # tail
        counts[:, 2] = 1  # alloc
        counts[:, 3] = 1  # pending
        counts[:, 4] = 2  # value_alloc
        ready = np.zeros((ndev, cap), np.int32)
        mutate(tasks)
        return CheckpointBundle(
            "resident", {"ndev": ndev},
            {
                "tasks": tasks, "succ": np.full((ndev, 8), -1, np.int32),
                "ready": ready, "counts": counts,
                "ivalues": np.zeros((ndev, V), np.int32),
            },
        )

    ok = fake_bundle(lambda t: None).reshard(1)
    assert int(ok.arrays["counts"][0][3]) == 2  # both rows re-homed

    def dep(t):
        t[0, 0, F_DEP] = 1

    with pytest.raises(CheckpointError, match="dependency counter"):
        fake_bundle(dep).reshard(1)

    def linked(t):
        t[0, 0, F_SUCC0] = 1

    with pytest.raises(CheckpointError, match="successor links"):
        fake_bundle(linked).reshard(1)

    def homed(t):
        t[0, 0, F_HOME] = 1

    with pytest.raises(CheckpointError, match="home-link"):
        fake_bundle(homed).reshard(1)

    def dyn_out(t):
        t[0, 0, F_OUT] = 5  # >= value_alloc 2

    with pytest.raises(CheckpointError, match="dynamic out slot"):
        fake_bundle(dyn_out).reshard(1)
    with pytest.raises(CheckpointError, match="power-of-two"):
        fake_bundle(lambda t: None).reshard(3)


def _fake_resident_bundle(ndev=2, cap=8, live_per_dev=1, extra=None):
    """Minimal clean-quiesce resident bundle for host-side reshard tests
    (live rows are ready + link-free)."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_HOME, NO_TASK,
    )

    V = 16
    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, 2:4] = NO_TASK  # F_SUCC0/F_SUCC1
    tasks[:, :, F_HOME] = NO_TASK
    counts = np.zeros((ndev, 8), np.int32)
    counts[:, 1] = live_per_dev  # tail
    counts[:, 2] = live_per_dev  # alloc
    counts[:, 3] = live_per_dev  # pending
    counts[:, 4] = 2  # value_alloc
    ready = np.zeros((ndev, cap), np.int32)
    arrays = {
        "tasks": tasks, "succ": np.full((ndev, 8), -1, np.int32),
        "ready": ready, "counts": counts,
        "ivalues": np.zeros((ndev, V), np.int32),
    }
    arrays.update(extra or {})
    return CheckpointBundle("resident", {"ndev": ndev}, arrays)


def test_reshard_m_edge_cases_diagnosed():
    """SATELLITE: M=1 and M>N re-home cleanly (totals conserved, empty
    new devices legal); illegal/overfull targets get diagnostics naming
    the fix, never shape errors."""
    b = _fake_resident_bundle(ndev=2, live_per_dev=2)
    one = b.reshard(1)  # M=1: everything folds onto the survivor
    assert int(one.arrays["counts"][0][3]) == 4
    big = _fake_resident_bundle(ndev=2, live_per_dev=2).reshard(8)
    assert big.arrays["tasks"].shape[0] == 8  # M > N: empty devices ok
    assert int(big.arrays["counts"][:, 3].sum()) == 4
    assert big.meta["resharded_from"] == 2
    with pytest.raises(CheckpointError, match="power-of-two"):
        _fake_resident_bundle().reshard(3)
    with pytest.raises(CheckpointError, match="power-of-two"):
        _fake_resident_bundle().reshard(0)
    with pytest.raises(CheckpointError, match="integer"):
        _fake_resident_bundle().reshard("two")
    # Overfull scale-in: the diagnostic names the minimum mesh size.
    with pytest.raises(CheckpointError, match="scale in less"):
        _fake_resident_bundle(ndev=2, cap=4, live_per_dev=3).reshard(1)


def test_reshard_rehomes_ring_residue_and_empty_waits():
    """SATELLITE (lifted limits, host half): inject-ring residue
    re-deals across mesh sizes with its count conserved, and an empty
    wait table rides along resized to the new roster (pending waits
    re-home too - the conservation matrix below)."""
    from hclib_tpu.device.inject import RING_ROW

    R = 8
    rr = np.zeros((2, R, RING_ROW), np.int32)
    ic = np.zeros((2, 8), np.int32)
    for d in range(2):
        for i in range(3):
            rr[d, i, 0] = 10 * d + i  # distinguishable payload
        ic[d, 0] = 3
        ic[d, 1] = 1
    wz = np.zeros((2, 5, 3), np.int32)
    b = _fake_resident_bundle(
        ndev=2, live_per_dev=1,
        extra={"ring_rows": rr, "ictl": ic, "waits": wz},
    )
    for m in (1, 4):
        out = b.reshard(m)
        assert int(out.arrays["ictl"][:, 0].sum()) == 6  # residue conserved
        assert out.arrays["ring_rows"].shape[:2] == (m, R)
        assert out.arrays["waits"].shape == (m, 5, 3)
        assert (out.arrays["ictl"][:, 1] == 1).all()  # close flag survives
        # Every payload survives exactly once.
        vals = sorted(
            int(out.arrays["ring_rows"][d, i, 0])
            for d in range(m)
            for i in range(int(out.arrays["ictl"][d, 0]))
        )
        assert vals == [0, 1, 2, 10, 11, 12], vals
    # Ring overflow on aggressive scale-in diagnoses, not IndexErrors.
    ic_full = ic.copy()
    ic_full[:, 0] = R
    bf = _fake_resident_bundle(
        ndev=2, live_per_dev=1,
        extra={"ring_rows": rr, "ictl": ic_full, "waits": wz},
    )
    with pytest.raises(CheckpointError, match="ring"):
        bf.reshard(1)


def test_bundle_diff():
    """SATELLITE: the structural diff the bit-identity storms use -
    equal bundles report equal; value, shape, and key differences are
    named with counts."""
    a = _fake_resident_bundle(ndev=2, live_per_dev=2)
    b = _fake_resident_bundle(ndev=2, live_per_dev=2)
    assert a.diff(b)["equal"] is True
    b.arrays["ivalues"] = b.arrays["ivalues"].copy()
    b.arrays["ivalues"][0, 0] = 7
    d = a.diff(b)
    assert d["equal"] is False
    assert d["mismatched"]["ivalues"]["n"] == 1
    assert d["mismatched"]["ivalues"]["max_abs"] == 7.0
    c = _fake_resident_bundle(ndev=4, live_per_dev=2)
    d2 = a.diff(c)
    assert not d2["equal"] and "shape" in d2["mismatched"]["tasks"]
    e = _fake_resident_bundle(
        ndev=2, live_per_dev=2,
        extra={"waits": np.zeros((2, 5, 3), np.int32)},
    )
    d3 = a.diff(e)
    assert d3["only_other"] == ["waits"] and not d3["equal"]


@pytest.mark.chaos
def test_resident_quiesce_with_pending_waits_roundtrip():
    """ACCEPTANCE (lifted limit #1): a resident mesh with PENDING
    host-declared waits quiesces - the live wait table exports through
    the aliased output (needs rebased) - and the resumed run re-arms the
    parked rows exactly: the late put still wakes its consumer, results
    match the uninterrupted run."""
    import jax

    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    ROWS, COLS = 8, 128
    BUMP, PUT, CONSUME = 0, 1, 2

    def make_rk():
        def bump(ctx):
            ctx.set_value(0, ctx.value(0) + ctx.arg(0))

        def put(ctx):
            ctx.pgas.put(ctx.arg(0), 0, ctx.arg(1), ctx.arg(2))

        def consume(ctx):
            ctx.set_value(ctx.arg(0), ctx.pgas.count(0))

        mk = Megakernel(
            kernels=[("bump", bump), ("put", put), ("consume", consume)],
            data_specs={
                "heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)
            },
            capacity=128, num_values=64, succ_capacity=64,
            interpret=True, checkpoint=True,
        )
        return ResidentKernel(
            mk, cpu_mesh(2, axis_name="q"),
            channels={"c0": ("heap", 1)}, window=4,
        )

    def heap():
        h = np.zeros((2, ROWS, COLS), np.int32)
        for d in range(2):
            for r in range(ROWS):
                h[d, r, :] = 1000 * d + r
        return h

    def build():
        builders = [TaskGraphBuilder(), TaskGraphBuilder()]
        # The put hides behind a serial bump chain, so an early quiesce
        # cuts BEFORE it runs and the wait is still parked.
        prev = builders[0].add(BUMP, args=[1])
        for i in range(20):
            prev = builders[0].add(BUMP, args=[i + 2], deps=[prev])
        builders[0].add(PUT, args=[1, 3, 2], deps=[prev])
        t = builders[1].add(CONSUME, args=[1])
        return builders, [[], [(0, 1, t)]]

    builders, waits = build()
    iv_f, data_f, info_f = make_rk().run(
        builders, data={"heap": heap()}, waits=waits, quantum=2,
        max_rounds=4096,
    )
    assert int(np.asarray(iv_f)[1, 1]) == 1  # consumer saw the arrival

    builders, waits = build()
    rk = make_rk()
    iv_q, _, info_q = rk.run(
        builders, data={"heap": heap()}, waits=waits, quantum=2,
        max_rounds=4096, quiesce=2,
    )
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    w = np.asarray(info_q["state"]["waits"])
    assert int(w[1, 0, 0]) == 1, w[1]  # the wait is STILL parked
    assert int(w[1, 1, 1]) >= 1  # rebased need is still positive
    iv_r, data_r, info_r = rk.run(
        resume_state=info_q["state"], quantum=2, max_rounds=4096,
    )
    assert info_r["pending"] == 0
    assert info_r["executed"] == info_f["executed"]
    assert int(np.asarray(iv_r)[1, 1]) == 1  # re-armed wait fired
    assert np.array_equal(
        np.asarray(data_r["heap"]), np.asarray(data_f["heap"])
    )


@pytest.mark.chaos
def test_resident_inject_cursor_survives_reshard():
    """ACCEPTANCE (lifted limit #2): a mid-stream quiesce keeps
    published-but-unconsumed inject rows as ring residue with the
    consumed cursor; the bundle reshards 4 -> 2 (residue re-dealt,
    conserved) and the resumed smaller mesh drains everything exactly."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    BUMP = 0

    def make_rk(ndev):
        def bump(ctx):
            ctx.set_value(0, ctx.value(0) + ctx.arg(0))

        mk = Megakernel(
            kernels=[("bump", bump)], capacity=256, num_values=1024,
            succ_capacity=8, interpret=True, checkpoint=True,
        )
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[BUMP],
            window=4, homed=False, inject=True,
        )

    ndev = 4
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    v = 0
    for d in range(ndev):
        for _ in range(2):
            v += 1
            builders[d].add(BUMP, args=[v])
    inject_rows = []
    for d in range(ndev):
        rows = []
        for _ in range(6):
            v += 1
            rows.append((BUMP, [v]))
        inject_rows.append(rows)
    want = v * (v + 1) // 2

    rk = make_rk(ndev)
    # quiesce=True: threshold round 0 - the poll never consumes, so ALL
    # inject rows are residue and the cut is maximally mid-stream.
    _, _, info_q = rk.run(
        builders, inject_rows=inject_rows, quantum=4, max_rounds=4096,
        quiesce=True,
    )
    assert info_q["quiesced"] is True
    st = info_q["state"]
    assert int(np.asarray(st["ictl"])[:, 0].sum()) == 4 * 6  # residue
    bundle = snapshot_resident(rk, info_q)
    small = bundle.reshard(2)
    assert int(np.asarray(small.arrays["ictl"])[:, 0].sum()) == 24
    rk2 = make_rk(2)
    iv, _, info = rk2.run(
        resume_state=small.state(), quantum=8, max_rounds=1 << 14,
    )
    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 0].sum()) == want
    assert info["executed"] == v
    # Partial consumption: a later cut consumes some rounds' rows first;
    # the cursor still reconciles (consumed + residue == published).
    rk3 = make_rk(ndev)
    _, _, info_q3 = rk3.run(
        builders, inject_rows=inject_rows, quantum=4, max_rounds=4096,
        quiesce=2,
    )
    if info_q3["quiesced"]:
        ic = np.asarray(info_q3["inject_ctl"])
        residue = int(np.asarray(info_q3["state"]["ictl"])[:, 0].sum())
        assert int(ic[:, 2].sum()) + residue == int(ic[:, 0].sum())
        iv3, _, info3 = rk3.run(
            resume_state=info_q3["state"], quantum=8,
            max_rounds=1 << 14,
        )
        assert int(np.asarray(iv3)[:, 0].sum()) == want


@pytest.mark.chaos
def test_resident_mesh_checkpoint_roundtrip_same_mesh():
    """ACCEPTANCE: quiesce a 4-device resident mesh mid-traversal (the
    fold observes the word, sched stops popping, the wire drains, the
    mesh exits in lockstep), resume on the same mesh size, and the totals
    equal the uninterrupted run exactly."""
    ndev = 4
    rk_full = _mesh_uts_rk(ndev)
    iv_f, _, info_f = rk_full.run(
        _mesh_uts_builders(ndev), quantum=8, max_rounds=4096
    )
    total = int(np.asarray(iv_f)[:, 0].sum())
    assert info_f["pending"] == 0 and total == info_f["executed"]

    rk = _mesh_uts_rk(ndev)
    iv_q, _, info_q = rk.run(
        _mesh_uts_builders(ndev), quantum=8, max_rounds=4096, quiesce=2,
    )
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    fs = info_q["fault_stats"]
    assert all(f["quiesce_round"] >= 2 for f in fs)  # threshold honored
    iv_r, _, info_r = rk.run(
        resume_state=info_q["state"], quantum=8, max_rounds=4096
    )
    assert info_r["pending"] == 0
    assert info_r["executed"] == info_f["executed"]
    assert int(np.asarray(iv_r)[:, 0].sum()) == total


@pytest.mark.chaos
def test_resident_mesh_restore_onto_smaller_and_larger_mesh(tmp_path):
    """ACCEPTANCE (elastic resume): a 4-chip checkpoint restores onto 2
    chips (and a 2-chip one onto 4) - per-chip queues re-homed host-side
    with the dead-chip conservation semantics, the full workload drains,
    totals conserved exactly."""
    ndev = 4
    rk_full = _mesh_uts_rk(ndev)
    iv_f, _, info_f = rk_full.run(
        _mesh_uts_builders(ndev), quantum=8, max_rounds=4096
    )
    total = int(np.asarray(iv_f)[:, 0].sum())

    rk = _mesh_uts_rk(ndev)
    _, _, info_q = rk.run(
        _mesh_uts_builders(ndev), quantum=8, max_rounds=4096, quiesce=2,
    )
    bundle = snapshot_resident(rk, info_q)
    path = str(tmp_path / "mesh-ckpt")
    bundle.save(path)

    # 4 -> 2: restore_resident reshards automatically off the manifest.
    rk_small = _mesh_uts_rk(2)
    iv_s, _, info_s = restore_resident(
        CheckpointBundle.load(path), rk_small, quantum=8,
        max_rounds=4096,
    )
    assert info_s["pending"] == 0
    assert info_s["executed"] == info_f["executed"]
    assert int(np.asarray(iv_s)[:, 0].sum()) == total

    # 2 -> 4: checkpoint the 2-chip run, grow back to 4.
    rk2 = _mesh_uts_rk(2)
    _, _, info_q2 = rk2.run(
        _mesh_uts_builders(2), quantum=8, max_rounds=4096, quiesce=2,
    )
    if info_q2["pending"] > 0:
        rk_big = _mesh_uts_rk(4)
        iv_b, _, info_b = restore_resident(
            snapshot_resident(rk2, info_q2), rk_big, quantum=8,
            max_rounds=4096,
        )
        assert info_b["pending"] == 0
        # 2-chip seeds 1,2 are a subset of the 4-chip run's totals: check
        # against the 2-chip uninterrupted run instead.
        rk2_full = _mesh_uts_rk(2)
        iv2_f, _, info2_f = rk2_full.run(
            _mesh_uts_builders(2), quantum=8, max_rounds=4096
        )
        assert info_b["executed"] == info2_f["executed"]
        assert (
            int(np.asarray(iv_b)[:, 0].sum())
            == int(np.asarray(iv2_f)[:, 0].sum())
        )


# ------------------------------------------------- durable store (ISSUE 17)


from hclib_tpu.runtime.checkpoint import (  # noqa: E402
    BundleFault,
    BundleStore,
    default_store,
)


def _waits_bundle(ndev=4, cap=8, live=1, parked=(), channels=("left",
                  "right"), host_residue=None, max_waits=4, seed=0):
    """Clean-quiesce resident bundle with wait-parked rows: each
    ``parked`` triple (device, channel, need) parks one row carrying
    exactly one dep bump, with its wait entry in the exported table."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_DEP, F_FN, F_HOME, NO_TASK,
    )

    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, 2:4] = NO_TASK
    tasks[:, :, F_HOME] = NO_TASK
    ready = np.full((ndev, cap), NO_TASK, np.int32)
    counts = np.zeros((ndev, 8), np.int32)
    waits = np.zeros((ndev, max_waits + 1, 3), np.int32)
    for d in range(ndev):
        for i in range(live):
            tasks[d, i, F_FN] = 1
            ready[d, i] = i
        npk = 0
        for (pd, ch, need) in parked:
            if pd != d:
                continue
            slot = live + npk
            tasks[d, slot, F_FN] = 2
            tasks[d, slot, F_DEP] = 1
            w = int(waits[d, 0, 0])
            waits[d, 1 + w] = (ch, need, slot)
            waits[d, 0, 0] = w + 1
            npk += 1
        counts[d, 1] = live
        counts[d, 2] = live + npk  # alloc
        counts[d, 3] = live + npk  # pending
        counts[d, 4] = 2  # value_alloc
    rng = np.random.default_rng(seed)
    meta = {"ndev": ndev, "channels": list(channels)}
    if host_residue:
        meta["host_residue"] = dict(host_residue)
    return CheckpointBundle("resident", meta, {
        "tasks": tasks,
        "succ": np.full((ndev, 8), -1, np.int32),
        "ready": ready, "counts": counts,
        "ivalues": rng.integers(0, 1 << 20, (ndev, 16)).astype(np.int32),
        "waits": waits,
    })


def _need_sums(waits):
    acc = {}
    w = np.asarray(waits)
    for d in range(w.shape[0]):
        for i in range(int(w[d, 0, 0])):
            ch, need, _row = (int(x) for x in w[d, 1 + i])
            acc[ch] = acc.get(ch, 0) + need
    return acc


def test_reshard_waits_conservation_matrix():
    """TENTPOLE: exported wait tables RE-HOME across mesh sizes - the
    4 -> 2 and 2 -> 4 matrix conserves wait counts, per-channel need
    sums, and the pending total; parked rows land allocated but NOT in
    the ready ring, keeping exactly one dep bump per parked wait."""
    from hclib_tpu.device.descriptor import F_DEP

    parked = [(0, 0, 3), (1, 1, 2), (2, 0, 1), (3, 1, 4)]
    b = _waits_bundle(ndev=4, parked=parked)
    want_needs = _need_sums(b.arrays["waits"])
    want_pend = int(b.arrays["counts"][:, 3].sum())
    for m in (2, 4, 1, 8):
        out = b.reshard(m) if m != 4 else b.reshard(2).reshard(4)
        w = np.asarray(out.arrays["waits"])
        assert w.shape[0] == m
        assert int(w[:, 0, 0].sum()) == len(parked)
        assert _need_sums(w) == want_needs
        assert int(out.arrays["counts"][:, 3].sum()) == want_pend
        for d in range(m):
            tail = int(out.arrays["counts"][d, 1])
            alloc = int(out.arrays["counts"][d, 2])
            for i in range(int(w[d, 0, 0])):
                _ch, _need, row = (int(x) for x in w[d, 1 + i])
                # The wait entry targets a real parked row on ITS device:
                # allocated past the ready ring, dep bump preserved.
                assert tail <= row < alloc, (d, row, tail, alloc)
                assert int(out.arrays["tasks"][d, row, F_DEP]) == 1


def test_reshard_refuses_satisfier_in_residue():
    """TENTPOLE: the narrowed refusal - waits whose satisfier sits in
    unexported host residue (meta['host_residue']) refuse with ONE
    whole-program diagnostic naming every stranded channel; residue on
    channels nobody waits on does not refuse."""
    b = _waits_bundle(
        ndev=4, parked=[(0, 0, 3), (1, 0, 1), (2, 1, 2)],
        host_residue={"left": 2, "right": 1},
    )
    with pytest.raises(CheckpointError) as ei:
        b.reshard(2)
    msg = str(ei.value)
    assert "host residue" in msg
    assert "'left'" in msg and "'right'" in msg  # every stranded channel
    assert "3 pending wait(s) on 2 channel(s)" in msg
    # Residue on an un-waited channel is harmless: the waits re-home.
    ok = _waits_bundle(
        ndev=4, parked=[(0, 0, 3)], host_residue={"right": 5},
    ).reshard(2)
    assert int(np.asarray(ok.arrays["waits"])[:, 0, 0].sum()) == 1


def test_reshard_diagnoses_wait_dep_mismatch():
    """A declared wait whose parked row does NOT carry the matching dep
    bump is a violation named per-row (the export contract), not a
    silent re-home."""
    from hclib_tpu.device.descriptor import F_DEP

    b = _waits_bundle(ndev=2, parked=[(0, 0, 2)])
    b.arrays["tasks"][0, 1, F_DEP] = 0  # strip the bump
    with pytest.raises(CheckpointError,
                       match="dependency counter 0 != its 1"):
        b.reshard(1)


def test_bundle_store_publish_retention_and_reload(tmp_path):
    """Generational publish: gen-N dirs + CURRENT pointer, bounded
    retention (keep=K prunes oldest), load_latest bit-identical to the
    newest save, provenance stamped on the loaded bundle."""
    root = str(tmp_path / "store")
    store = BundleStore(root, keep=2, fsync=False)
    bundles = [_waits_bundle(seed=i) for i in range(4)]
    gens = [store.save(b) for b in bundles]
    assert gens == [1, 2, 3, 4]
    assert store.generations() == [3, 4]  # keep=2 pruned 1, 2
    assert open(os.path.join(root, "CURRENT")).read().strip() == "4"
    got = BundleStore(root, fsync=False).load_latest()
    assert got.diff(bundles[-1])["equal"]
    assert got.generation == 4
    assert got.source_path == store.path_of(4)
    with pytest.raises(CheckpointError, match="keep"):
        BundleStore(root, keep=0)


def test_bundle_store_self_heals_and_quarantines(tmp_path):
    """Self-healing restore: a corrupted newest generation is moved to
    quarantine/ with a typed BundleFault, load_latest falls back to the
    newest VALID generation bit-identically, and the fallback/quarantine
    counters + TR_CKPT records fire."""
    from hclib_tpu.device import tracebuf as tb

    root = str(tmp_path / "store")
    reg = hc.MetricsRegistry()
    store = BundleStore(root, keep=3, fsync=False, metrics=reg)
    good = _waits_bundle(seed=1)
    store.save(good)
    store.save(_waits_bundle(seed=2))
    npz = os.path.join(store.path_of(2), "state.npz")
    blob = open(npz, "rb").read()
    with open(npz, "wb") as f:
        f.write(blob[:-4] + b"\xff" * 4)
    healer = BundleStore(root, keep=3, fsync=False, metrics=reg)
    back = healer.load_latest()
    assert back.generation == 1 and back.diff(good)["equal"]
    assert [isinstance(f, BundleFault) for f in healer.faults] == [True]
    f = healer.faults[0]
    assert (f.generation, f.reason) == (2, "corrupt")
    assert "quarantine" in f.path and os.path.isdir(f.path)
    assert healer.generations() == [1]  # the damaged one moved aside
    m = reg.snapshot()["metrics"]
    assert m["checkpoint.quarantined.count"] == 1
    assert m["checkpoint.fallback.count"] == 1
    assert m["checkpoint.load.count"] == 1
    assert m["checkpoint.save.count"] == 2
    # Every host record decodes through the CK_* name table.
    codes = [-int(r[2]) - 1 for r in healer.events]
    assert codes == [tb.CK_QUARANTINE, tb.CK_FALLBACK, tb.CK_LOAD]
    assert all(c in tb.CK_NAMES for c in codes)
    info = healer.trace_info()
    assert info["rings"][0]["written"] == 3


def test_bundle_store_unrecoverable_raises_with_every_fault(tmp_path):
    """No valid generation -> CheckpointError naming EVERY fault and
    the poison handoff (the degradation-ladder contract), never a hang
    or a partial restore."""
    root = str(tmp_path / "store")
    store = BundleStore(root, keep=3, fsync=False)
    store.save(_waits_bundle(seed=1))
    store.save(_waits_bundle(seed=2))
    for g in store.generations():
        os.remove(os.path.join(store.path_of(g), "manifest.json"))
    healer = BundleStore(root, fsync=False)
    with pytest.raises(CheckpointError) as ei:
        healer.load_latest()
    msg = str(ei.value)
    assert "unrecoverable" in msg and "poison" in msg
    assert "gen 1" in msg and "gen 2" in msg
    assert all(f.reason == "torn" for f in healer.faults)
    # An empty store raises too (cold start is explicit, not a wedge).
    with pytest.raises(CheckpointError, match="no generations"):
        BundleStore(str(tmp_path / "empty"), fsync=False).load_latest()


def test_bundle_store_crash_sites_leave_staging_invisible(tmp_path):
    """FaultPlan preempt-mid-save dies BEFORE the rename: the store is
    unchanged and the staged dir invisible; preempt-mid-restore retries
    idempotently (quarantine moves are re-entrant)."""
    from hclib_tpu.runtime.resilience import FaultPlan, InjectedFault

    root = str(tmp_path / "store")
    good = _waits_bundle(seed=3)
    BundleStore(root, fsync=False).save(good)
    plan = FaultPlan(seed=0, preempt_save_at=0)
    writer = BundleStore(root, fsync=False, fault_plan=plan)
    with pytest.raises(InjectedFault, match="mid-save"):
        writer.save(_waits_bundle(seed=4))
    after = BundleStore(root, fsync=False)
    assert after.generations() == [1]
    assert after.load_latest().diff(good)["equal"]
    # A later clean save reuses the staging slot and publishes.
    assert BundleStore(root, fsync=False).save(_waits_bundle(seed=5)) == 2
    plan = FaultPlan(seed=0, preempt_restore_at=0)
    reader = BundleStore(root, fsync=False, fault_plan=plan)
    with pytest.raises(InjectedFault, match="mid-restore"):
        reader.load_latest()
    assert reader.load_latest().generation == 2  # the retry succeeds


def test_bundle_store_env_knobs(tmp_path, monkeypatch):
    """SATELLITE: HCLIB_TPU_CKPT_DIR roots default_store();
    HCLIB_TPU_CKPT_KEEP sets retention (malformed text raises, naming
    the variable); HCLIB_TPU_CKPT_FSYNC=0 selects the fast mode."""
    monkeypatch.delenv("HCLIB_TPU_CKPT_DIR", raising=False)
    assert default_store() is None
    root = str(tmp_path / "envstore")
    monkeypatch.setenv("HCLIB_TPU_CKPT_DIR", root)
    monkeypatch.setenv("HCLIB_TPU_CKPT_KEEP", "2")
    monkeypatch.setenv("HCLIB_TPU_CKPT_FSYNC", "0")
    store = default_store()
    assert store is not None and store.root == root
    assert store.keep == 2 and store.fsync is False
    for i in range(3):
        store.save(_waits_bundle(seed=i))
    assert store.generations() == [2, 3]
    monkeypatch.setenv("HCLIB_TPU_CKPT_KEEP", "junk")
    with pytest.raises(ValueError, match="HCLIB_TPU_CKPT_KEEP"):
        default_store()


def test_bundle_load_errors_name_path_and_generation(tmp_path):
    """SATELLITE: version/corruption errors name the offending FILE and
    store generation; a kernel-table mismatch carries the positional
    diff AND the bundle's provenance."""
    import json
    import types

    root = str(tmp_path / "store")
    store = BundleStore(root, fsync=False)
    b = _waits_bundle(seed=1)
    b.meta.update({"kernel_names": ["seed", "waiter"], "capacity": 8,
                   "num_values": 16, "succ_capacity": 8,
                   "data_specs": {}})
    store.save(b)
    man_path = os.path.join(store.path_of(1), "manifest.json")
    man = json.load(open(man_path))
    man["version"] = 9
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointError) as ei:
        CheckpointBundle.load(store.path_of(1), generation=1)
    assert man_path in str(ei.value) and "(generation 1)" in str(ei.value)
    man["version"] = 1
    json.dump(man, open(man_path, "w"))
    loaded = CheckpointBundle.load(store.path_of(1), generation=1)
    mk = types.SimpleNamespace(
        kernel_names=["waiter", "seed"], capacity=8, num_values=16,
        succ_capacity=8, data_specs={},
    )
    from hclib_tpu.runtime.checkpoint import _check_kernel_meta, _where

    with pytest.raises(CheckpointError) as ei:
        _check_kernel_meta(mk, loaded.meta, where=_where(loaded))
    msg = str(ei.value)
    assert "[0] 'waiter' != 'seed' in the bundle" in msg.replace(
        "'waiter' here", "'waiter'"
    )
    assert "generation 1" in msg and store.path_of(1) in msg


def test_bundle_store_model_certifies_publish_ordering():
    """SATELLITE: the BundleStoreModel explores save x crash x
    concurrent-load clean under the shipped rename-LAST ordering, and
    catches the planted publish-before-manifest bug with a concrete
    witness."""
    from hclib_tpu.analysis.explore import BundleStoreModel, explore

    ok = explore(BundleStoreModel(saves=2, crash=True, max_reads=2),
                 depth=64, budget_s=20)
    assert ok.complete and ok.clean, ok.violations
    bad = explore(
        BundleStoreModel(saves=2, crash=True, max_reads=2,
                         publish_before_manifest=True),
        depth=64, budget_s=20,
    )
    assert not bad.clean
    assert any("partial generation" in v.message for v in bad.violations)
    assert all(v.witness for v in bad.violations)


def test_autoscaler_resume_from_store_root(tmp_path):
    """SATELLITE: Autoscaler.run(resume_bundle=<store root>) walks the
    generational store with the self-healing load_latest - and an
    unrecoverable root raises the poison diagnostic instead of
    wedging."""
    from hclib_tpu.runtime.autoscaler import Autoscaler

    root = str(tmp_path / "store")
    BundleStore(root, fsync=False).save(_waits_bundle(seed=7))
    scaler = Autoscaler(lambda ndev: None, checkpoint_dir=root)
    # The store root resolves through load_latest; the resolved bundle
    # then fails the resident-kind gate only if damaged - here it
    # reaches kernel construction (our stub factory returns None).
    with pytest.raises(AttributeError):
        scaler.run(resume_bundle=root)
    for g in BundleStore(root, fsync=False).generations():
        os.remove(os.path.join(root, f"gen-{g:06d}", "manifest.json"))
    with pytest.raises(CheckpointError, match="unrecoverable"):
        scaler.run(resume_bundle=root)


# --------------------------------------- dyngraph bundles (ISSUE 20)


def _dyngraph_fixture(applied, *, serve_query=True, residue=True):
    """A synthetic ``ndev=4`` dyngraph bundle: each device has applied
    the uids in ``applied[d]`` (in that order - the host mirror of the
    device splice arithmetic), labels show divergent partial progress,
    and the scheduler holds residue rows (each device's UNapplied
    updates, a dynamic EXPAND, one pending QUERY). Returns
    ``(bundle, graph, ups, iv, counts)``."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_A0, F_FN, F_OUT, NO_TASK,
    )
    from hclib_tpu.device.dyngraph import (
        DG_QUERY, DG_UPDATE, DynGraph, V_FREE, V_QUERIES, V_UPDATES,
        _bind_updates, make_dyngraph_megakernel,
    )
    from hclib_tpu.device.frontier import (
        EBLOCK, INF, V_EDGES, V_RELAX, VT_BASE,
    )
    from hclib_tpu.device.megakernel import (
        C_ALLOC, C_EXECUTED, C_PENDING, C_VALLOC,
    )

    rng = np.random.default_rng(0)
    n, m = 12, 40
    g = DynGraph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                 rng.integers(1, 8, m), spare_blocks=2, upd_cap=8)
    ups = [(1, 5, 3), (2, 7, 1), (1, 9, 2), (4, 3, 6)]
    for u, v, w in ups:
        g.add_update(u, v, w)
    mk = make_dyngraph_megakernel("sssp", g, width=0, interpret=True)
    _bind_updates(mk, g)

    ndev, cap, V = 4, 32, mk.num_values
    sb, spare, bcs = g.spare_base, g.spare, g.blk_count.astype(np.int64)
    flag_base, st = g.flag_base, g.st_base
    iv = np.zeros((ndev, V), np.int64)
    ind = np.zeros((ndev,) + g.indices.shape, np.int32)
    wgt = np.zeros((ndev,) + g.weights.shape, np.int32)
    for d in range(ndev):
        iv[d] = g.preset_values(V, INF)
        ind[d] = g.indices
        wgt[d] = g.weights

    def apply_on(d, uid):
        u, v, w = ups[uid]
        vt = iv[d, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
        deg, bc = int(vt[u, 2]), int(vt[u, 1])
        if deg == bc * EBLOCK:
            r = sb + u * spare + (bc - int(bcs[u]))
            ind[d, r, :] = -1
            wgt[d, r, :] = 0
            ind[d, r, 0] = v
            wgt[d, r, 0] = w
            vt[u, 1] = bc + 1
            iv[d, V_FREE] += 1
        else:
            blk = deg // EBLOCK
            r = (int(vt[u, 0]) + blk if blk < int(bcs[u])
                 else sb + u * spare + (blk - int(bcs[u])))
            ind[d, r, deg % EBLOCK] = v
            wgt[d, r, deg % EBLOCK] = w
        vt[u, 2] = deg + 1
        iv[d, flag_base + uid] = 1
        iv[d, V_UPDATES] += 1

    for d, uids in applied.items():
        for uid in uids:
            apply_on(d, uid)
    for d in range(ndev):
        iv[d, st] = 0
        for vtx in range(1, n):
            iv[d, st + vtx] = INF if (vtx + d) % 3 else 10 + vtx + d
        iv[d, V_EDGES] = 5 + d
        iv[d, V_RELAX] = 2 + d
    if serve_query:  # one served query on device 1, out slot st + n
        iv[1, V_QUERIES] = 1
        iv[1, st + n] = 13

    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    counts = np.zeros((ndev, 8), np.int32)
    ready = np.full((ndev, cap), NO_TASK, np.int32)
    succ = np.full((ndev, 16), NO_TASK, np.int32)
    for d in range(ndev):
        rows = []
        for uid in range(len(ups)):
            if uid not in applied[d]:
                u, v, w = ups[uid]
                r = np.zeros(DESC_WORDS, np.int32)
                r[F_FN] = DG_UPDATE
                r[F_A0:F_A0 + 4] = (u, v, w, uid)
                r[2] = r[3] = r[13] = NO_TASK
                rows.append(r)
        if residue:
            r = np.zeros(DESC_WORDS, np.int32)  # a dynamic EXPAND
            r[F_FN] = 0
            r[F_A0:F_A0 + 2] = (d % n, 4)
            r[2] = r[3] = r[13] = NO_TASK
            rows.append(r)
            if d == 2:  # one pending QUERY
                r = np.zeros(DESC_WORDS, np.int32)
                r[F_FN] = DG_QUERY
                r[F_A0] = 7
                r[F_OUT] = st + n + 1
                r[2] = r[3] = r[13] = NO_TASK
                rows.append(r)
        for i, r in enumerate(rows):
            tasks[d, i] = r
            ready[d, i] = i
        counts[d, 1] = counts[d, C_ALLOC] = len(rows)
        counts[d, C_PENDING] = len(rows)
        counts[d, C_VALLOC] = g.num_value_slots
        counts[d, C_EXECUTED] = 3 + d
    arrays = {
        "tasks": tasks, "succ": succ, "ready": ready, "counts": counts,
        "ivalues": iv.astype(np.int32),
        "data/indices": ind, "data/weights": wgt,
    }
    meta = {"ndev": ndev, "dyngraph": dict(mk._dyngraph),
            "kernel_names": list(mk.kernel_names)}
    return CheckpointBundle("resident", meta, arrays), g, ups, iv, counts


def test_dyngraph_reshard_shrink_grow_conserves():
    """4 -> 2 -> 4: the canonical rebuilt adjacency broadcasts
    identically, edge count conserves (static + union-applied), labels
    min-fold, accumulators sum-fold, the served query value survives,
    and residue deals without loss."""
    from hclib_tpu.device.frontier import V_EDGES, VT_BASE
    from hclib_tpu.device.dyngraph import V_QUERIES
    from hclib_tpu.device.megakernel import C_EXECUTED, C_PENDING

    applied = {d: [u for u in range(4) if (u + d) % 2 == 0]
               for d in range(4)}
    applied[1] = applied[1][::-1]  # order-divergent application
    applied[3] = applied[3][::-1]
    bundle, g, ups, iv, counts = _dyngraph_fixture(applied)
    n, st = g.n, g.st_base

    b2 = bundle.reshard(2)
    assert b2.meta["ndev"] == 2
    assert b2.meta["dyngraph_reshard"]["union_applied"] == 4
    assert b2.meta["dyngraph_reshard"]["pending_updates"] == 0
    i2 = b2.arrays["data/indices"]
    assert np.array_equal(i2[0], i2[1])  # canonical broadcast
    iv2 = b2.arrays["ivalues"].astype(np.int64)
    vt2 = iv2[0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt2[:, 2].sum()) == int(g.deg.sum()) + 4
    c2 = b2.arrays["counts"]
    assert int(c2[:, C_PENDING].sum()) == 5  # 4 EXPANDs + 1 QUERY dealt
    assert int(c2[:, C_EXECUTED].sum()) == int(counts[:, C_EXECUTED].sum())
    want = iv[:, st:st + n].min(axis=0)
    assert np.array_equal(iv2[0, st:st + n], want)
    assert np.array_equal(iv2[1, st:st + n], want)
    assert int(iv2[:, V_EDGES].sum()) == int(iv[:, V_EDGES].sum())
    assert int(iv2[:, V_QUERIES].sum()) == 1
    assert int(iv2[0, st + n]) == 13  # served query value max-folds

    b3 = b2.reshard(4)  # grow back
    assert b3.meta["ndev"] == 4 and b3.meta["resharded_from"] == 2
    for d in range(4):
        assert np.array_equal(b3.arrays["data/indices"][d], i2[0])
    iv3 = b3.arrays["ivalues"].astype(np.int64)
    vt3 = iv3[0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt3[:, 2].sum()) == int(g.deg.sum()) + 4
    assert int(iv3[:, V_EDGES].sum()) == int(iv[:, V_EDGES].sum())


def test_dyngraph_reshard_broadcasts_unapplied_update():
    """A pending update NO replica has applied dedupes by uid and
    broadcasts to every new device - the mesh invariant 'every replica
    sees every update' survives the resize."""
    from hclib_tpu.device.descriptor import F_A0, F_FN
    from hclib_tpu.device.dyngraph import DG_UPDATE
    from hclib_tpu.device.frontier import VT_BASE
    from hclib_tpu.device.megakernel import C_ALLOC

    applied = {0: [0], 1: [1, 0], 2: [], 3: [2]}  # uid 3 nowhere
    bundle, g, ups, _, _ = _dyngraph_fixture(
        applied, serve_query=False, residue=False,
    )
    b2 = bundle.reshard(2)
    rs = b2.meta["dyngraph_reshard"]
    assert rs["union_applied"] == 3 and rs["pending_updates"] == 1
    t, c = b2.arrays["tasks"], b2.arrays["counts"]
    for j in range(2):
        uids = [int(t[j, i, F_A0 + 3]) for i in range(int(c[j, C_ALLOC]))
                if int(t[j, i, F_FN]) == DG_UPDATE]
        assert uids == [3], uids
    n = g.n
    vt = b2.arrays["ivalues"][0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt[:, 2].sum()) == int(g.deg.sum()) + 3


def test_dyngraph_reshard_refusals():
    """Structured refusals: pagerank mid-run (no device-count-free
    fold), dropped splices (adjacency no longer the stream's), and
    foreign data buffers."""
    from hclib_tpu.device.dyngraph import V_DROPPED

    applied = {0: [0, 1, 2, 3], 1: [], 2: [], 3: []}
    bundle, g, ups, _, _ = _dyngraph_fixture(applied)

    pr = CheckpointBundle(
        bundle.kind,
        {**bundle.meta,
         "dyngraph": {**bundle.meta["dyngraph"], "kind": "pagerank"}},
        bundle.arrays,
    )
    with pytest.raises(CheckpointError, match="pagerank"):
        pr.reshard(2)

    dropped = {k: np.array(v) for k, v in bundle.arrays.items()}
    dropped["ivalues"] = dropped["ivalues"].copy()
    dropped["ivalues"][2, V_DROPPED] = 1
    with pytest.raises(CheckpointError, match="spare"):
        CheckpointBundle(bundle.kind, bundle.meta, dropped).reshard(2)

    extra = dict(bundle.arrays)
    extra["data/other"] = np.zeros((4, 8), np.int32)
    with pytest.raises(CheckpointError, match="extra data buffers"):
        CheckpointBundle(bundle.kind, bundle.meta, extra).reshard(2)


def test_dyngraph_quiesce_mid_update_storm_resume_bit_identical():
    """Quiesce a single-device dyngraph run mid-update-storm, snapshot
    (the layout stamp rides bundle meta), resume, and the fixpoint is
    bit-identical to the host twin on the mutated graph - with the
    vertex-table degrees conserving static + applied edge counts."""
    from hclib_tpu.device.dyngraph import (
        DynGraph, _bind_updates, _seed_builders, fk_data, host_dyngraph,
        make_dyngraph_megakernel,
    )
    from hclib_tpu.device.frontier import INF, VT_BASE

    rng = np.random.default_rng(11)
    n, m = 16, 48
    g = DynGraph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                 rng.integers(1, 8, m), spare_blocks=2, upd_cap=8)
    for u, v, w in [(1, 5, 3), (2, 7, 1), (0, 9, 2), (4, 3, 6)]:
        g.add_update(u, v, w)
    mk = make_dyngraph_megakernel(
        "sssp", g, width=0, interpret=True, checkpoint=True,
    )
    _bind_updates(mk, g)
    builders, _ = _seed_builders(
        g, "sssp", 0, 1 << 14, 64, [5], mk.num_values, 1,
        lambda i, tot: 0,
    )
    iv = g.preset_values(mk.num_values, INF)
    iv[g.st_base] = 0
    _, _, info_q = mk.run(
        builders[0], data=dict(fk_data(g, mk)), ivalues=iv, quiesce=2,
    )
    assert info_q["quiesced"] is True and info_q["pending"] > 0
    bundle = snapshot_megakernel(mk, info_q)
    assert bundle.meta["dyngraph"]["kind"] == "sssp"
    assert len(bundle.meta["dyngraph"]["updates"]) == 4

    iv_r, _, info_r = mk.resume(info_q["state"])
    row = np.asarray(iv_r, np.int64)
    res = row[g.st_base : g.st_base + n].astype(np.int32)
    assert np.array_equal(res, host_dyngraph("sssp", g, 0))
    flags = row[g.flag_base : g.flag_base + g.upd_cap]
    vt = row[VT_BASE : VT_BASE + 3 * n].reshape(n, 3)
    assert int((flags != 0).sum()) == 4
    assert int(vt[:, 2].sum()) == int(g.deg.sum()) + 4  # conservation
    # The in-run query published SOME label for vertex 5 (tentative
    # when it raced the traversal, exact once drained - monotone
    # relaxation means it can only be an upper bound of the fixpoint).
    assert int(row[g.st_base + n]) >= int(res[5])

    # Restore THROUGH the bundle onto a fresh identical build: the
    # mutated adjacency rides data/ and the run completes identically.
    mk2 = make_dyngraph_megakernel(
        "sssp", g, width=0, interpret=True, checkpoint=True,
    )
    _bind_updates(mk2, g)
    iv_b, _, _ = restore_megakernel(bundle, mk2)
    assert np.array_equal(
        np.asarray(iv_b, np.int64)[g.st_base : g.st_base + n], res
    )
