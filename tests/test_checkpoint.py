"""Checkpoint/restore (ISSUE 5): preemption-tolerant snapshot + elastic
resume of the persistent megakernel.

Acceptance semantics under test: for a deterministic workload,
*checkpoint at round k then restore and run to completion* must be
bit-identical to the uninterrupted run (UTS dynamic tree, Cholesky with
the batched dispatch tier, wave-DAG SW with cross-round prefetch - all
under interpret mode); a checkpoint-disabled build must behave exactly as
before (DeviceFaultPlan discipline); corrupt or version-mismatched
bundles must be rejected with structured errors. The resident-mesh
round trips are in test_checkpoint_mesh.py; what needs no kernel (reshard
arithmetic on hand-built bundles, the durable store) is in
test_checkpoint_store.py.
"""

import os
import threading
import time

import numpy as np
import pytest
from conftest import KINDS, bump_mk, front_door, seed_builder, send

import hclib_tpu as hc
from hclib_tpu.device.descriptor import (
    NO_TASK,
    TaskGraphBuilder,
    ring_window,
)
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.megakernel import C_HEAD, C_TAIL, Megakernel
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
    restore_stream,
    snapshot_megakernel,
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


def _ring_at(state, start, length):
    """``state`` with its ready window moved to begin at the all-time
    position ``start`` of a ring ``length`` words long, indexed by
    ``% length``: with ``length`` the capacity, the layout every
    snapshot written before PR 45 has."""
    counts = state["counts"].copy()
    live = ring_window(state["ready"], counts[C_HEAD], counts[C_TAIL])
    ring = np.full(length, NO_TASK, np.int32)
    ring[(start + np.arange(len(live))) % length] = live
    counts[C_HEAD], counts[C_TAIL] = start, start + len(live)
    return dict(state, ready=ring, counts=counts)


@pytest.mark.parametrize("capacity", [96, 1000])
def test_old_layout_snapshot_resumes_like_a_new_one(
    capacity, tmp_path, uts_ref,
):
    """A snapshot whose ``ready`` is ``capacity`` long (built by hand: a
    window that wraps the old ring's end) is told by its shape, re-laid
    into the ``ring_len`` ring and resumed to the same count as the new
    layout's, straight from a state dict and from a bundle on disk."""
    nodes, info_full = uts_ref
    mk = make_uts_megakernel(checkpoint=True, capacity=capacity, **UTS_KW)
    assert capacity < mk.ring_len
    _, _, q = mk.run(_uts_builder(), quiesce=nodes // 3)
    new = q["state"]
    assert new["ready"].shape == (mk.ring_len,) and q["pending"] > 2
    old = _ring_at(new, capacity - 2, capacity)
    assert old["ready"].shape == (capacity,)
    # the same window lies elsewhere in the two layouts
    moved = _ring_at(new, capacity - 2, mk.ring_len)
    assert not np.array_equal(
        np.flatnonzero(old["ready"] != NO_TASK),
        np.flatnonzero(moved["ready"] != NO_TASK),
    )
    results = [mk.resume(st) for st in (new, moved, old)]
    bundle = snapshot_megakernel(mk, dict(q, state=old))
    assert bundle.arrays["ready"].shape == (capacity,)
    bundle.save(str(tmp_path / "old"))
    results.append(restore_megakernel(
        str(tmp_path / "old"),
        make_uts_megakernel(checkpoint=True, capacity=capacity, **UTS_KW),
    ))
    for iv, _, info in results:
        assert int(iv[0]) == nodes
        assert info["executed"] == info_full["executed"] == nodes
        assert info["pending"] == 0
    # a chained checkpoint of the old one comes out in the new layout
    _, _, q2 = mk.resume(old, quiesce=nodes // 2)
    assert q2["quiesced"] and q2["state"]["ready"].shape == (mk.ring_len,)


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


def _bump_mk():
    return bump_mk(512, 64, checkpoint=True)


def test_streaming_checkpoint_roundtrip(tmp_path):
    """Quiesce a live stream mid-drain, bundle it, restore on a FRESH
    stream object, inject more work there, and the grand total is exact -
    nothing lost at the cut (unconsumed ring rows ride the bundle)."""
    sm = StreamingMegakernel(_bump_mk(), ring_capacity=512)
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
    sm2 = StreamingMegakernel(_bump_mk(), ring_capacity=512)
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
    sm = StreamingMegakernel(_bump_mk(), ring_capacity=256)
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

    sm3 = StreamingMegakernel(_bump_mk(), ring_capacity=64)
    b3 = TaskGraphBuilder()
    b3.add(0, args=[5])
    sm3.quiesce(after_executed=1 << 30)  # unreachable threshold
    iv3, info3 = sm3.run_stream(b3, quantum=64, deadline_s=120.0)
    assert info3["quiesced"] is True
    assert info3["quiesce_observed_round"] == -1  # host-side drained cut
    assert info3["pending"] == 0 and int(iv3[0]) == 5


@pytest.mark.parametrize("kind", KINDS)
def test_stream_cut_pulls_resident_state_and_resumes_fresh(kind):
    """The stream's state lives on the chip between entries (ISSUE 32)
    and is pulled only at the cut: a quiesce mid-burst still hands
    ``info['state']`` the documented keys as numpy arrays of the
    documented shapes, and a freshly built stream resumes it with the
    totals conserved."""
    from hclib_tpu.device.descriptor import DESC_WORDS, RING_ROW
    from hclib_tpu.device.telemetry import LAT_BUCKETS, LAT_WORDS

    n = 24
    sm, table = front_door(kind, checkpoint=True, max_in_flight=4)
    futs = send(sm, table, n)
    sm.quiesce(after_executed=9)
    _, info = sm.run_stream(
        seed_builder(), quantum=4, max_rounds=2, deadline_s=120.0,
    )
    assert info["quiesced"] and 9 <= info["executed"] < n + 1
    link = info["stream"]
    assert link["entries"] >= 2 and link["idle_sleeps"] == 0
    assert link["downloads"] <= 2 * link["entries"] + 1
    mk, st = sm.mk, info["state"]
    shapes = {
        "tasks": (mk.capacity, DESC_WORDS), "succ": (mk.succ_capacity,),
        "ready": (mk.ring_len,), "counts": (8,),
        "ivalues": (mk.num_values,),
    }
    if table is not None:
        shapes.update(tctl=(2, 8), tstats=(2, 8))
    if kind in ("egress", "telemetry"):
        shapes["etok"] = (mk.capacity,)
    if kind == "telemetry":
        shapes.update(tele=(3, LAT_BUCKETS), tlat=(mk.capacity, LAT_WORDS))
    for name, shape in shapes.items():
        assert isinstance(st[name], np.ndarray), name
        assert st[name].shape == shape and st[name].dtype == np.int32, name
    assert st["data"] == {}
    assert st["ring_rows"].shape[1:] == (RING_ROW,)
    assert set(st) == set(shapes) | {"data", "ring_rows"} | (
        {"tenant_ids"} if table is not None else set()
    )
    assert int(st["counts"][5]) == info["executed"]  # C_EXECUTED
    # What the cut left undone is exactly what the snapshot carries.
    undone = n + 1 - info["executed"]
    assert info["pending"] + len(st["ring_rows"]) == undone
    sm2, table2 = front_door(kind, checkpoint=True, max_in_flight=4)
    sm2.close()
    iv2, info2 = sm2.run_stream(
        resume_state=st, quantum=4, max_rounds=2, deadline_s=120.0,
    )
    assert int(iv2[0]) == 1000 + n * (n + 1) // 2
    assert info2["executed"] == n + 1 and info2["pending"] == 0
    assert info2["stream"]["entries"] >= 2
    if table is None:
        return
    for tid in "ab":
        s = table2.stats()[tid]
        assert s["accepted"] == s["completed"] == n // 2, s
    if kind != "tenants":
        assert {f.state for f in futs} <= {"RESULT", "PREEMPTED"}
        for f in futs:
            if f.state == "PREEMPTED":
                g = table2.reattach(f.resume_token)
                assert g.result(timeout=2.0) is not None
        assert table.futures.conservation()["ok"]
        assert table2.futures.conservation()["ok"]


def test_preempt_hook_quiesces_running_stream():
    """The preemption path end to end: fire_preempt (what SIGTERM /
    HCLIB_TPU_PREEMPT / the watchdog checkpoint rung call) lands while
    the stream runs; the bound hook quiesces it, and run_stream returns a
    restorable snapshot instead of losing the graph."""
    resilience.reset_preempt()
    # Ring sized so the feeder cannot exhaust it before the preemption
    # lands even on a slow box (~0.1s / 5ms period ≈ 20 rows queued).
    sm = StreamingMegakernel(_bump_mk(),
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
        sm2 = StreamingMegakernel(_bump_mk(),
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
    sm = StreamingMegakernel(_bump_mk(), ring_capacity=64)
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
