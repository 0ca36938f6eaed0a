#!/usr/bin/env python
"""Seeded chaos sweep over the resilience subsystem (ISSUE 1, CI tooling).

Runs every failure-injection scenario the runtime claims to survive -
injected task faults under retry, worker death mid-UTS, runtime deadlines,
poison-task quarantine, and a procworld peer crash - across one or more
seeds, and exits nonzero if any scenario fails OR hangs.

Hang enforcement is the tool's own: ``faulthandler.dump_traceback_later``
arms a process-wide timer that dumps every thread's stack and hard-exits
(status 1) if the sweep overruns ``--timeout-s``, so a regression that
re-introduces an unbounded wait fails CI loudly instead of wedging it.
Each launch additionally runs under its own ``deadline_s`` (the feature
under test bounding the test).

``--mesh`` adds the seeded DEVICE chaos scenarios (ISSUE 2): a dead chip
on an 8-device interpret mesh whose queue re-homes to the survivors, and
a dropped ICI steal credit healed by timeout + regeneration. They need
the Mosaic TPU interpret mode (jax >= 0.5); on older builds they report
as skipped, not failed.

``--preempt`` adds the seeded PREEMPTION scenarios (ISSUE 5): checkpoint
a UTS megakernel mid-traversal and restore it bit-exactly from the
on-disk bundle; fire_preempt (the SIGTERM/watchdog path) quiescing a
live injection stream whose snapshot then drains exactly; and a
resident-mesh checkpoint restored onto a SMALLER mesh (N->M re-homing,
totals conserved - Mosaic-gated like the other mesh scenarios).

``--storm`` adds the seeded PREEMPT-STORM scenarios (ISSUE 6): repeated
fire_preempt cuts on a live injection stream (every cut resumed, grand
total exact), >= 3 chained checkpoints on one UTS traversal with
byte-identical bundles across storms (CheckpointBundle.diff), and the
autoscaled resident mesh riding scale-out, a dead-chip EVACUATION
mid-stream, and scale-in with totals bit-identical to an uninterrupted
fault-free run (the autoscale half is Mosaic-gated like the other mesh
scenarios).

``--tenants`` adds the seeded MULTI-TENANT INGRESS scenarios (ISSUE 8):
a greedy tenant pushing far past its quota while its siblings complete
their exact totals with WRR fairness in exact weight proportion; a
poison tenant throttled then quarantined while the others' task algebra
stays exact; a deadline storm whose per-tenant
``accepted == completed + expired`` identity reconciles exactly across
every expiry point (admission / host queue / on-ring lazy drop); and
fire_preempt landing mid-stream with three tenants live, per-tenant
accepted/completed/residue conserved across the checkpoint/resume cut.
All four run on the interpret-mode streaming kernel (no Mosaic needed).

``--serve`` adds the seeded SERVING-LOOP scenarios (ISSUE 16): a
depth-4 completion mailbox under a poller consuming one result per
step (sustained backpressure parks rows - counted, never dropped - and
every future still resolves RESULT with its exact payload); fire_preempt
landing on the live egress-enabled stream with futures in flight (every
future lands RESULT or PREEMPTED with a valid resume token, the resumed
stream re-adopts and every reattached future resolves); and a mesh
deadline storm resharded LIVE 4 -> 2 -> 4 with futures riding every cut,
closing ``submitted == resolved + expired + poisoned`` EXACTLY, globally
and per tenant. All three run interpret-mode/host-model (no Mosaic).

``--durability`` adds the seeded DURABLE-STORE scenarios (ISSUE 17): the
crash-point matrix over the generational ``BundleStore`` - torn npz,
flipped bit, lost manifest, preempt mid-save, preempt mid-restore, and a
fully-damaged store - proving bit-identical resume from the newest valid
generation with typed quarantines (and the poison diagnostic when none
survives); plus the serving loop restored THROUGH a fallback (newest
generation damaged on disk) with futures reattached and the ledger
closing exactly, and the reshard wait re-homing algebra (counts and
per-channel need sums conserved 4 -> 2 -> 4; satisfier-in-residue
refused whole-program). Both host-model (no Mosaic).

``--slo`` adds the seeded SLO-BURN scenario (ISSUE 19): a request
stream whose latency tail degrades mid-run; the streaming burn-rate
estimator (fed cumulative on-device latency histograms, the
TelemetryPoller shape) crosses the policy threshold and fires a typed
``slo_out`` scale-out BEFORE the deadline-budget watchdog rung (no
deadline has expired - the same observation with the burn signal
zeroed holds), riding TR_SCALE, the metrics registry, and the Perfetto
exporter. Host-model (no Mosaic).

Usage:
    python tools/chaos_soak.py                    # fast smoke (tier-1)
    python tools/chaos_soak.py --scale soak --seeds 8   # standalone soak
    python tools/chaos_soak.py --mesh --seeds 1   # device-mesh chaos (CI)
    python tools/chaos_soak.py --preempt-only --seeds 1  # checkpoint (CI)
    python tools/chaos_soak.py --storm-only --seeds 1  # preempt storms (CI)
    python tools/chaos_soak.py --serve-only --seeds 2  # serving loop (CI)

One JSON line per scenario; a machine-readable summary line last (seed
base/count, faults injected, recoveries, failures, wall time) so CI and
BENCH tooling can diff soak runs across PRs.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Before jax initializes: the mesh scenarios want 8 virtual CPU devices
# (same configuration tests/conftest.py pins).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import hclib_tpu as hc  # noqa: E402
from hclib_tpu.models import fib, uts  # noqa: E402
from hclib_tpu.modules.procworld import (  # noqa: E402
    ProcWorld,
    ProcWorldError,
)


class _FakeKV:
    """Minimal coordination-service stand-in (threads as ranks) so the
    procworld crash scenario runs in one process with no cluster - the
    same seam tests/test_procworld_unit.py uses."""

    def __init__(self) -> None:
        self._kv = {}
        self._ctr = {}
        self._cv = threading.Condition()

    def key_value_set_bytes(self, key, val):
        with self._cv:
            self._kv[key] = bytes(val)
            self._cv.notify_all()

    def key_value_try_get_bytes(self, key):
        with self._cv:
            if key in self._kv:
                return self._kv[key]
        raise RuntimeError(f"NOT_FOUND: key {key} not found")

    def blocking_key_value_get_bytes(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        with self._cv:
            while key not in self._kv:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        f"DEADLINE_EXCEEDED: GetKeyValue() timed out "
                        f"with key: {key}"
                    )
                self._cv.wait(left)
            return self._kv[key]

    def key_value_delete(self, key):
        with self._cv:
            self._kv.pop(key, None)

    def key_value_increment(self, key, n):
        with self._cv:
            self._ctr[key] = self._ctr.get(key, 0) + n
            return self._ctr[key]

    def wait_at_barrier(self, bid, timeout_ms, *a, **k):
        raise RuntimeError("UNIMPLEMENTED: no barriers in the soak fake")


# ------------------------------------------------------------- scenarios

def scenario_fib_retry(seed: int, scale: str) -> dict:
    """Injected task faults healed by runtime-default retry."""
    n = 12 if scale == "smoke" else 18
    plan = hc.FaultPlan(
        seed=seed, task_failure_rate=0.15, max_task_failures=50
    )
    out = fib.run(
        n, "finish", nworkers=2,
        fault_plan=plan,
        default_retry=hc.RetryPolicy(max_attempts=8, backoff_s=0.0005,
                                     jitter=0, seed=seed),
        deadline_s=60.0,
    )
    faults = len(plan.trace_key())
    assert faults > 0, "plan injected nothing; scenario is vacuous"
    want = fib.fib_seq(n)
    assert out["value"] == want, (out["value"], want)
    # Retry is the only recovery path here and quarantine is off, so a
    # fault that did NOT recover would have failed the launch (or the
    # exact-value assert above): completing exactly means every injected
    # fault was healed.
    return {"value": out["value"], "faults": faults, "recoveries": faults}


def scenario_uts_kill_worker(seed: int, scale: str) -> dict:
    """Worker thread death mid-UTS; identity re-binds, traversal exact.
    The kill fires on worker 1's first scheduling poll; on a loaded
    1-vCPU host the short tree can drain before that thread is ever
    scheduled, so the kill is raced over a few attempts - every attempt
    must stay exact, and the kill must land within the attempt budget."""
    params = uts.T_TINY
    plan = hc.FaultPlan(
        seed=seed, kill_worker=1, kill_worker_after=1,
        steal_delay_rate=0.05, steal_delay_s=0.001,
    )
    expect = uts.count_seq(params)[0]
    attempts = 0
    for attempts in range(1, 6):
        nodes, leaves, depth = uts.count_parallel(
            params, nworkers=4, grain=1,
            fault_plan=plan, deadline_s=120.0,
        )
        assert nodes == expect, f"UTS corrupted: {nodes} != {expect}"
        if ("kill_worker", 1) in plan.trace_key():
            break
    assert ("kill_worker", 1) in plan.trace_key(), "worker never died"
    return {"nodes": expect, "attempts": attempts,
            "trace": len(plan.trace_key())}


def scenario_deadline(seed: int, scale: str) -> dict:
    """A wedged program surfaces as StallError in bounded time."""
    t0 = time.monotonic()
    try:
        hc.launch(
            lambda: hc.Promise().future.wait(), nworkers=2, deadline_s=0.5
        )
    except hc.StallError:
        dt = time.monotonic() - t0
        assert dt < 10.0, f"deadline enforcement took {dt:.1f}s"
        return {"bounded_s": round(dt, 3)}
    raise AssertionError("wedged launch returned without StallError")


def scenario_quarantine(seed: int, scale: str) -> dict:
    """Poison tasks quarantine; the rest of the batch completes."""
    n = 64 if scale == "smoke" else 512
    done = []
    lock = threading.Lock()
    poison = {i for i in range(n) if i % 13 == seed % 13}

    def body(i):
        if i in poison:
            raise ValueError(f"poison item {i}")
        with lock:
            done.append(i)

    rt = hc.Runtime(
        nworkers=4,
        default_retry=hc.RetryPolicy(max_attempts=2, backoff_s=0,
                                     jitter=0, quarantine=True),
    )
    rt.run(lambda: hc.forasync(body, [n], tile=1), deadline_s=60.0)
    res = rt.stats_dict()["resilience"]
    assert len(done) == n - len(poison), (len(done), n, len(poison))
    assert res["quarantined"] == len(poison), res
    return {"completed": len(done), "quarantined": res["quarantined"]}


def scenario_procworld_crash(seed: int, scale: str) -> dict:
    """Peer progress-engine crash: the blocked waiter gets a structured
    ProcWorldError (tombstone/poison), never its full timeout."""
    kv = _FakeKV()
    plan = hc.FaultPlan(seed=seed, peer_crash_rank=1, peer_crash_after=0)
    a = ProcWorld(_client=kv, _rank=0, _size=2, timeout_s=20.0)
    b = ProcWorld(_client=kv, _rank=1, _size=2, timeout_s=20.0,
                  fault_plan=plan)
    try:
        import numpy as np

        with b._heap_lock:
            b._heap["x"] = np.zeros(2, np.int32)
        t0 = time.monotonic()
        try:
            a.get(1, "x")
        except ProcWorldError:
            dt = time.monotonic() - t0
            assert dt < 15.0, f"peer-death detection took {dt:.1f}s"
            return {"detected_s": round(dt, 3)}
        raise AssertionError("get() against crashed peer succeeded")
    finally:
        a.close()
        b.close()


# --------------------------------------------- device-mesh chaos (ISSUE 2)

def _mesh_prereq():
    import jax

    if len(jax.devices("cpu")) < 8:
        return "needs 8 virtual cpu devices"
    return None


def _mesh_rk(ndev, plan, capacity=256):
    import numpy as _np  # noqa: F401  (jax pulls it anyway)

    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    mk = Megakernel(
        kernels=[("bump", bump)], capacity=capacity, num_values=1024,
        succ_capacity=8, interpret=True,
    )
    return ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[0], window=4,
        fault_plan=plan,
    )


def scenario_mesh_dead_chip(seed: int, scale: str) -> dict:
    """Seeded dead chip on an 8-device interpret mesh: the survivors must
    drain the whole workload (queue re-homed, totals conserved)."""
    skip = _mesh_prereq()
    if skip:
        return {"skipped": skip}
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    ndev, per = 8, 4
    dead = seed % ndev
    plan = hc.DeviceFaultPlan(
        seed=seed, dead_device=dead, dead_round=2, heartbeat_timeout=2,
    )
    rk = _mesh_rk(ndev, plan)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    v = 0
    for d in range(ndev):
        for _ in range(per):
            v += 1
            builders[d].add(0, args=[v])
    iv, _, info = rk.run(builders, quantum=2, max_rounds=4096)
    assert info["pending"] == 0 and info["executed"] == ndev * per
    assert int(iv[:, 0].sum()) == v * (v + 1) // 2
    fs = info["fault_stats"]
    assert fs[dead]["rehomed_rows"] > 0
    quarantiners = sum(
        1 for d, f in enumerate(fs) if d != dead and dead in f["quarantined"]
    )
    assert quarantiners > 0
    return {"faults": 1, "recoveries": 1, "dead": dead,
            "rehomed": fs[dead]["rehomed_rows"],
            "quarantiners": quarantiners, "rounds": info["rounds"]}


def scenario_mesh_dropped_credit(seed: int, scale: str) -> dict:
    """Seeded dropped ICI steal credit: timeout + regeneration heal the
    channel; totals stay exact."""
    skip = _mesh_prereq()
    if skip:
        return {"skipped": skip}
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    ntasks = 40
    plan = hc.DeviceFaultPlan(
        seed=seed, drop_credit_at=[(1, 0, 1)], credit_timeout=2,
    )
    rk = _mesh_rk(2, plan, capacity=128)
    builders = [TaskGraphBuilder(), TaskGraphBuilder()]
    for i in range(ntasks):
        builders[0].add(0, args=[i + 1])
    iv, _, info = rk.run(builders, quantum=2, max_rounds=4096)
    assert info["pending"] == 0 and info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    fs = info["fault_stats"]
    dropped = sum(f["credits_dropped"] for f in fs)
    regen = sum(f["credits_regenerated"] for f in fs)
    assert dropped == 1 and regen == 1, fs
    return {"faults": dropped, "recoveries": regen,
            "rounds": info["rounds"]}


# --------------------------------------- preemption checkpoint (ISSUE 5)

def scenario_preempt_checkpoint(seed: int, scale: str) -> dict:
    """Seeded preemption mid-UTS-traversal: quiesce at a round boundary,
    bundle to disk (npz + checksummed manifest), restore on a FRESH
    megakernel, and the final totals are bit-identical to the
    uninterrupted run - the fault is the preemption, the recovery is the
    checkpoint/restore round trip."""
    import tempfile

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import (
        UTS_NODE, device_uts_mk, make_uts_megakernel,
    )
    from hclib_tpu.runtime.checkpoint import (
        restore_megakernel, snapshot_megakernel,
    )

    kw = dict(seed=19 + seed, interpret=True,
              max_depth=7 if scale == "smoke" else 9)
    nodes, _ = device_uts_mk(**kw)
    mk = make_uts_megakernel(checkpoint=True, **kw)
    b = TaskGraphBuilder()
    b.add(UTS_NODE, args=[1, 0])
    t0 = time.monotonic()
    _, _, info_q = mk.run(b, quiesce=max(1, nodes // 3))
    quiesce_s = time.monotonic() - t0
    assert info_q["quiesced"] and info_q["pending"] > 0, info_q
    d = tempfile.mkdtemp(prefix="hclib-ckpt-")
    stats = snapshot_megakernel(mk, info_q).save(d)
    iv, _, info_r = restore_megakernel(
        d, make_uts_megakernel(checkpoint=True, **kw)
    )
    assert int(iv[0]) == nodes, (int(iv[0]), nodes)
    assert info_r["executed"] == nodes and info_r["pending"] == 0
    return {"faults": 1, "recoveries": 1, "nodes": nodes,
            "checkpoint_at": info_q["quiesce"]["executed_at"],
            "bundle_bytes": stats["bundle_bytes"],
            "quiesce_s": round(quiesce_s, 3)}


def scenario_preempt_stream(seed: int, scale: str) -> dict:
    """fire_preempt (the SIGTERM/watchdog path) lands mid-stream: the
    bound hook quiesces it, the snapshot restores on a fresh stream, and
    the drain is exact - totals conserved across the preemption."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.runtime import resilience
    from hclib_tpu.runtime.checkpoint import checkpoint_on_preempt

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    def make_sm():
        return StreamingMegakernel(
            Megakernel(kernels=[("bump", bump)], capacity=512,
                       num_values=64, succ_capacity=8, interpret=True,
                       checkpoint=True),
            ring_capacity=512,
        )

    resilience.reset_preempt()
    n = 60 if scale == "smoke" else 300
    sm = make_sm()
    b = TaskGraphBuilder()
    for i in range(10):
        b.add(0, args=[i + 1])
    for i in range(10, n):
        sm.inject(0, args=[i + 1])

    def preempter():
        time.sleep(0.05 + 0.01 * (seed % 3))
        resilience.fire_preempt(f"soak preemption seed {seed}")

    t = threading.Thread(target=preempter)
    t.start()
    try:
        with checkpoint_on_preempt(sm, after_executed=5):
            iv, info = sm.run_stream(b, quantum=8, deadline_s=120.0)
    finally:
        t.join()
        resilience.reset_preempt()
    assert info.get("quiesced"), "preemption never quiesced the stream"
    sm2 = make_sm()
    sm2.close()
    iv2, info2 = sm2.run_stream(resume_state=info["state"],
                                deadline_s=120.0)
    want = n * (n + 1) // 2
    assert int(iv2[0]) == want, (int(iv2[0]), want)
    return {"faults": 1, "recoveries": 1, "injected": n,
            "executed_at_cut": info["executed"]}


def scenario_preempt_mesh_reshard(seed: int, scale: str) -> dict:
    """Resident-mesh preemption with ELASTIC resume: quiesce a 4-chip
    interpret mesh mid-traversal, restore the bundle onto 2 chips (queues
    re-homed host-side, PR 2 conservation semantics), totals exact."""
    skip = _mesh_prereq()
    if skip:
        return {"skipped": skip}
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh
    from hclib_tpu.runtime.checkpoint import (
        restore_resident, snapshot_resident,
    )
    import numpy as np

    def make_rk(ndev):
        mk = make_uts_megakernel(seed=19 + seed, max_depth=6,
                                 interpret=True, checkpoint=True)
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"),
            migratable_fns=[UTS_NODE], window=4, homed=False,
        )

    def builders(ndev):
        bs = [TaskGraphBuilder() for _ in range(ndev)]
        for d in range(ndev):
            bs[d].add(UTS_NODE, args=[d + 1, 0])
        return bs

    iv_f, _, info_f = make_rk(4).run(builders(4), quantum=8,
                                     max_rounds=4096)
    total = int(np.asarray(iv_f)[:, 0].sum())
    rk = make_rk(4)
    _, _, info_q = rk.run(builders(4), quantum=8, max_rounds=4096,
                          quiesce=2)
    assert info_q["quiesced"], info_q
    iv_r, _, info_r = restore_resident(
        snapshot_resident(rk, info_q), make_rk(2), quantum=8,
        max_rounds=4096,
    )
    assert info_r["pending"] == 0
    assert int(np.asarray(iv_r)[:, 0].sum()) == total
    return {"faults": 1, "recoveries": 1, "total": total,
            "executed": info_r["executed"],
            "pending_at_cut": info_q["pending"]}


# ------------------------------------- preempt storms + autoscale (ISSUE 6)

def scenario_storm_stream(seed: int, scale: str) -> dict:
    """Seeded PREEMPT STORM on a live injection stream: repeated
    fire_preempt cuts (the SIGTERM path) interleaved with resumes - every
    cut exports the ring residue + cursor, every resume drains exactly,
    and the grand total is bit-identical to an uninterrupted stream."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.runtime import resilience
    from hclib_tpu.runtime.checkpoint import checkpoint_on_preempt

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    def make_sm():
        return StreamingMegakernel(
            Megakernel(kernels=[("bump", bump)], capacity=512,
                       num_values=64, succ_capacity=8, interpret=True,
                       checkpoint=True),
            ring_capacity=512,
        )

    n = 60 if scale == "smoke" else 240
    cuts = 3
    resilience.reset_preempt()
    sm = make_sm()
    b = TaskGraphBuilder()
    for i in range(8):
        b.add(0, args=[i + 1])
    for i in range(8, n):
        sm.inject(0, args=[i + 1])
    state = None
    quiesced = 0
    try:
        for cut in range(cuts):
            # Each cut: the preemption notice lands WHILE the stream
            # runs (a resume clears any pre-entry quiesce request by
            # design - same-object resumes behave like fresh streams),
            # so fire it from a delayed thread like a real SIGTERM.
            delay = 0.1 + 0.02 * ((seed + cut) % 4)
            t = threading.Thread(
                target=lambda d=delay, c=cut: (
                    time.sleep(d),
                    resilience.fire_preempt(f"storm cut {c}"),
                ),
            )
            with checkpoint_on_preempt(sm, after_executed=2):
                t.start()
                if state is None:
                    iv, info = sm.run_stream(b, quantum=4,
                                             deadline_s=120.0)
                else:
                    iv, info = sm.run_stream(resume_state=state,
                                             quantum=4, deadline_s=120.0)
                t.join()
            resilience.reset_preempt()
            assert info.get("quiesced"), f"cut {cut} never landed"
            quiesced += 1
            state = info["state"]
        sm.close()
        iv, info = sm.run_stream(resume_state=state, quantum=64,
                                 deadline_s=120.0)
    finally:
        resilience.reset_preempt()
    want = n * (n + 1) // 2
    assert int(iv[0]) == want, (int(iv[0]), want)
    assert info["pending"] == 0
    st = sm.stats_dict()
    assert st["quiesces"] == quiesced, st
    return {"faults": quiesced, "recoveries": quiesced, "injected": n,
            "cuts": quiesced, "total": want}


def scenario_storm_megakernel_chain(seed: int, scale: str) -> dict:
    """Chained checkpoint storm on the scalar tier: >= 3 quiesce cuts on
    one UTS traversal (one through the on-disk bundle), final count
    bit-identical; two independent storms produce byte-identical mid-cut
    bundles (CheckpointBundle.diff - determinism of the cut itself)."""
    import tempfile

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import (
        UTS_NODE, device_uts_mk, make_uts_megakernel,
    )
    from hclib_tpu.runtime.checkpoint import (
        CheckpointBundle, restore_megakernel, snapshot_megakernel,
    )

    kw = dict(seed=19 + seed, interpret=True,
              max_depth=7 if scale == "smoke" else 9)
    nodes, _ = device_uts_mk(**kw)

    def storm(mk):
        b = TaskGraphBuilder()
        b.add(UTS_NODE, args=[1, 0])
        # Absolute cut positions; quiesce= counts executed-since-ENTRY,
        # so each resume's threshold is relative to the previous cut.
        cuts = [max(1, nodes // 4), max(2, nodes // 2),
                max(3, (3 * nodes) // 4)]
        _, _, info = mk.run(b, quiesce=cuts[0])
        assert info["quiesced"], info
        bundles = [snapshot_megakernel(mk, info)]
        for at in cuts[1:]:
            rel = max(1, at - info["executed"])
            _, _, info = mk.resume(info["state"], quiesce=rel)
            assert info["quiesced"], info
            bundles.append(snapshot_megakernel(mk, info))
        return info, bundles

    mk = make_uts_megakernel(checkpoint=True, **kw)
    info, bundles = storm(mk)
    # Cut 3 goes through disk onto a FRESH kernel.
    d = tempfile.mkdtemp(prefix="hclib-storm-")
    bundles[-1].save(d)
    iv, _, done = restore_megakernel(
        d, make_uts_megakernel(checkpoint=True, **kw)
    )
    assert int(iv[0]) == nodes and done["pending"] == 0, (int(iv[0]), nodes)
    # Determinism of the storm itself: a second identical storm's
    # bundles are byte-identical (diff reports equal).
    _, bundles2 = storm(make_uts_megakernel(checkpoint=True, **kw))
    for b1, b2 in zip(bundles, bundles2):
        dd = b1.diff(b2)
        assert dd["equal"], dd
    # And a re-loaded bundle equals what was saved.
    assert CheckpointBundle.load(d).diff(bundles[-1])["equal"]

    # Cholesky under the same storm (batch tier + through-disk bf16):
    # two chained cuts + a disk restore, L bit-identical to the
    # uninterrupted factor.
    import numpy as np

    from hclib_tpu.device.cholesky import (
        build_cholesky_graph, cholesky_buffers, make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    nt = 2
    a = make_spd(256).astype(np.float32)
    _, data_full, info_full = make_cholesky_megakernel(
        nt, interpret=True
    ).run(build_cholesky_graph(nt), data=cholesky_buffers(a, nt))
    L_full = np.asarray(data_full["tiles"])
    mkc = make_cholesky_megakernel(nt, interpret=True, checkpoint=True)
    _, _, qc = mkc.run(
        build_cholesky_graph(nt), data=cholesky_buffers(a, nt), quiesce=2,
    )
    chol_cuts = 1
    if qc["quiesced"] and qc["pending"] > 0:
        _, _, q2 = mkc.resume(qc["state"], quiesce=2)
        if q2["quiesced"]:
            chol_cuts += 1
            qc = q2
        dc = tempfile.mkdtemp(prefix="hclib-storm-chol-")
        snapshot_megakernel(mkc, qc).save(dc)
        _, data_r, info_r = restore_megakernel(
            dc, make_cholesky_megakernel(nt, interpret=True,
                                         checkpoint=True)
        )
        assert info_r["executed"] == info_full["executed"]
        assert np.array_equal(np.asarray(data_r["tiles"]), L_full)
    return {"faults": len(bundles) + chol_cuts,
            "recoveries": len(bundles) + chol_cuts,
            "nodes": nodes, "cuts": len(bundles),
            "cholesky_cuts": chol_cuts}


def scenario_storm_autoscale(seed: int, scale: str) -> dict:
    """The full elastic story under a seeded storm: an autoscaled UTS
    mesh scales OUT under backlog, a dead chip mid-stream is detected,
    quarantined, and EVACUATED by reshard, the idle tail scales IN - and
    the final totals are bit-identical to an uninterrupted fault-free
    run (zero task loss through >= 3 scale events)."""
    skip = _mesh_prereq()
    if skip:
        return {"skipped": skip}
    import numpy as np

    import hclib_tpu as hc
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    depth = 6 if scale == "smoke" else 7

    def make_kernel(ndev, faulty=True):
        plan = None
        if ndev == 4 and faulty:
            # The storm's chip death: device 3 dies early in every
            # 4-device slice; survivors quarantine it by heartbeat.
            plan = hc.DeviceFaultPlan(
                seed=seed, dead_device=3, dead_round=2,
                heartbeat_timeout=2,
            )
        mk = make_uts_megakernel(seed=19 + seed, max_depth=depth,
                                 interpret=True, checkpoint=True)
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"),
            migratable_fns=[UTS_NODE], window=4, homed=False,
            fault_plan=plan,
        )

    def builders(ndev):
        bs = [TaskGraphBuilder() for _ in range(ndev)]
        for d in range(ndev):
            for r in range(8):
                bs[d].add(UTS_NODE, args=[d * 8 + r + 1, 0])
        return bs

    # Uninterrupted, fault-free reference on the starting mesh size.
    iv_f, _, info_f = make_kernel(2, faulty=False).run(
        builders(2), quantum=8, max_rounds=1 << 14
    )
    total = int(np.asarray(iv_f)[:, 0].sum())

    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(
        make_kernel,
        hc.AutoscalerPolicy(min_devices=1, max_devices=4,
                            scale_out_backlog=4.0, scale_in_backlog=1.0,
                            hysteresis=1, cooldown=1),
        slice_rounds=8, metrics=reg,
    )
    iv, _, info = asc.run(builders(2), quantum=8)
    assert info["pending"] == 0, info
    assert int(np.asarray(iv)[:, 0].sum()) == total, (
        int(np.asarray(iv)[:, 0].sum()), total
    )
    assert info["executed"] == info_f["executed"]
    kinds = [e["kind"] for e in info["scale_events"]]
    assert len(info["scale_events"]) >= 3, kinds
    assert "evacuate" in kinds, kinds
    resizes = [e for e in info["scale_events"]
               if e["from_ndev"] != e["to_ndev"]]
    snap = reg.snapshot()["metrics"]
    assert snap.get("autoscale.evacuate.count", 0) >= 1, snap
    return {"faults": 1, "recoveries": 1, "total": total,
            "events": kinds, "resizes": len(resizes),
            "ndev_final": info["ndev_final"]}


# ------------------------------------- multi-tenant ingress (ISSUE 8)

def _tenant_sm(specs, ring=768, checkpoint=False):
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    return StreamingMegakernel(
        Megakernel(kernels=[("bump", bump)], capacity=512,
                   num_values=64, succ_capacity=8, interpret=True,
                   checkpoint=checkpoint),
        ring_capacity=ring, tenants=specs,
    )


def scenario_tenant_greedy_quota(seed: int, scale: str) -> dict:
    """A greedy tenant pushes 4x past its quota: the quota pushes back
    (typed backlog rejections, never a wedge), both sibling lanes
    complete their exact totals, and the WRR reference model proves
    install fairness stays in exact weight proportion."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.tenants import (
        TenantSpec, TenantTable, build_row, wrr_poll_reference,
    )

    rng = np.random.default_rng(1000 + seed)
    n1, n2 = int(rng.integers(15, 30)), int(rng.integers(15, 30))
    specs = lambda: [  # noqa: E731
        TenantSpec("victim1", weight=2),
        TenantSpec("victim2", weight=1),
        TenantSpec("greedy", weight=1, max_in_flight=4,
                   queue_capacity=8),
    ]
    sm = _tenant_sm(specs())
    expect, admitted, rejected = 0, 0, 0
    for k in range(n1):
        assert sm.submit("victim1", 0, args=[k + 1])
        expect += k + 1
    for k in range(n2):
        assert sm.submit("victim2", 0, args=[100])
        expect += 100
    for _ in range(4 * (n1 + n2)):
        adm = sm.submit("greedy", 0, args=[1])
        if adm:
            admitted += 1
        else:
            rejected += 1
            assert adm.reason == "backlog", adm.reason
    expect += admitted
    sm.close()
    iv, info = sm.run_stream(TaskGraphBuilder(), deadline_s=120.0)
    assert int(iv[0]) == expect, (int(iv[0]), expect)
    ten = info["tenants"]
    assert ten["victim1"]["completed"] == n1
    assert ten["victim2"]["completed"] == n2
    assert rejected > 0, "quota never pushed back"
    # Fairness bound (reference model, saturated lanes): installs per
    # whole WRR cycle are EXACTLY weight-proportional. Quotas off here -
    # fairness is the WRR weights' property; the quota's pushback was
    # asserted above on the live stream.
    table = TenantTable(
        [TenantSpec("victim1", weight=2), TenantSpec("victim2"),
         TenantSpec("greedy")],
        64, clock=lambda: 0.0,
    )
    ring = np.zeros((3 * 64, 256), np.int32)
    for lane in range(3):
        for i in range(32):
            table.admit(lane, build_row(0, [i]))
    tctl = table.pump(ring)
    for r in range(8):
        wrr_poll_reference(ring, tctl, 64, r, 1 << 20)
    table.absorb(tctl)
    done = {t: s["completed"] for t, s in table.stats().items()}
    assert done["victim1"] == 2 * done["victim2"] == 2 * done["greedy"]
    return {"faults": rejected, "recoveries": 1, "greedy_admitted":
            admitted, "greedy_rejected": rejected,
            "victim_tasks": n1 + n2}


def scenario_tenant_poison_quarantine(seed: int, scale: str) -> dict:
    """A poison tenant (validator explodes on seeded rows) climbs
    throttle -> quarantine; the other tenants complete exactly - no
    poison row ever executes, quarantine never wedges the drain."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.tenants import TenantSpec

    rng = np.random.default_rng(2000 + seed)
    n_ok = int(rng.integers(20, 40))

    def poison(row):
        raise RuntimeError(f"poison row (seed {seed})")

    sm = _tenant_sm([
        TenantSpec("poison", validator=poison, poison_throttle=1,
                   poison_quarantine=2),
        TenantSpec("steady", weight=2),
        TenantSpec("bursty"),
    ])
    for _ in range(6):
        sm.submit("poison", 0, args=[999_999])
    expect, nb = 0, 0
    for k in range(n_ok):
        assert sm.submit("steady", 0, args=[k + 1])
        expect += k + 1
        if rng.random() < 0.5:
            assert sm.submit("bursty", 0, args=[10])
            expect += 10
            nb += 1
    sm.close()
    iv, info = sm.run_stream(TaskGraphBuilder(), deadline_s=120.0)
    assert int(iv[0]) == expect, (int(iv[0]), expect)
    ten = info["tenants"]
    assert ten["steady"]["completed"] == n_ok
    assert ten["bursty"]["completed"] == nb
    assert ten["poison"]["completed"] == 0
    assert ten["poison"]["quarantined"] == 1
    return {"faults": ten["poison"]["poisoned"], "recoveries": 1,
            "steady": n_ok, "bursty": nb}


def scenario_tenant_deadline_storm(seed: int, scale: str) -> dict:
    """Deadline storm under a deterministic clock: seeded mix of live
    and doomed submissions across 3 lanes; every expiry point exercised
    and the per-tenant accepted == completed + expired identity
    reconciles exactly."""
    import numpy as np

    from hclib_tpu.device.tenants import (
        TenantSpec, TenantTable, build_row, wrr_poll_reference,
    )

    rng = np.random.default_rng(3000 + seed)
    t_now = [100.0]
    clock = lambda: t_now[0]  # noqa: E731
    table = TenantTable(
        [TenantSpec("a", weight=2, max_in_flight=8, queue_capacity=512),
         TenantSpec("b", queue_capacity=512),
         TenantSpec("c", deadline_s=0.5, queue_capacity=512)],
        64, clock=clock,
    )
    ring = np.zeros((3 * 64, 256), np.int32)
    n = 60 if scale == "smoke" else 240
    rejected_expired = 0
    for i in range(n):
        lane = int(rng.integers(0, 3))
        doomed = rng.random() < 0.4
        dl = clock() + (0.01 if doomed else 60.0)
        if rng.random() < 0.1:
            dl = clock() - 1.0  # already expired at admission
        adm = table.admit(lane, build_row(0, [i]), deadline_at=dl)
        if not adm:
            assert adm.reason == "expired"
            rejected_expired += 1
        # Seeded clock jitter + a pump/poll slice every few admits.
        t_now[0] += float(rng.random() * 0.02)
        if i % 8 == 7:
            tctl = table.pump(ring)
            for r in range(2):
                wrr_poll_reference(ring, tctl, 64, i + r, 1 << 20)
            table.absorb(tctl)
            t_now[0] += float(rng.random() * 0.05)
    # Drain: advance past every live deadline's horizon is NOT done -
    # live rows must complete, doomed rows must expire.
    for r in range(256):
        tctl = table.pump(ring)
        wrr_poll_reference(ring, tctl, 64, r, 1 << 20)
        table.absorb(tctl)
        if table.drained():
            break
    assert table.drained(), "deadline storm wedged the drain"
    total_exp = total_done = 0
    for tid, s in table.stats().items():
        assert s["accepted"] == s["completed"] + s["expired"], (tid, s)
        total_exp += s["expired"]
        total_done += s["completed"]
    assert total_exp > 0 and total_done > 0
    return {"faults": total_exp + rejected_expired, "recoveries": 1,
            "admitted": total_done + total_exp,
            "expired": total_exp, "completed": total_done,
            "rejected_at_admission": rejected_expired}


def scenario_tenant_preempt_stream(seed: int, scale: str) -> dict:
    """fire_preempt lands mid-stream with THREE tenants live: the bound
    hook quiesces, per-tenant residue rides the snapshot tenant-tagged,
    and the resumed drain conserves per-tenant accepted/completed
    counts exactly (grand total exact by value algebra)."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.tenants import per_tenant_ring_counts
    from hclib_tpu.runtime import resilience
    from hclib_tpu.runtime.checkpoint import checkpoint_on_preempt

    rng = np.random.default_rng(4000 + seed)
    subs = {t: int(rng.integers(20, 40))
            for t in ("alpha", "beta", "gamma")}
    resilience.reset_preempt()
    sm = _tenant_sm(list(subs), checkpoint=True)
    expect = 0
    for i, (tid, cnt) in enumerate(subs.items()):
        for _ in range(cnt):
            assert sm.submit(tid, 0, args=[i + 1])
            expect += i + 1

    def preempter():
        time.sleep(0.05 + 0.01 * (seed % 3))
        resilience.fire_preempt(f"tenant soak preemption seed {seed}")

    t = threading.Thread(target=preempter)
    t.start()
    try:
        with checkpoint_on_preempt(sm, after_executed=5):
            iv, info = sm.run_stream(
                TaskGraphBuilder(), quantum=8, deadline_s=120.0,
            )
    finally:
        t.join()
        resilience.reset_preempt()
    assert info.get("quiesced"), "preemption never quiesced the stream"
    st = info["state"]
    residue = per_tenant_ring_counts(st["ring_rows"])
    installed_at_cut = {
        i: int(st["tctl"][i, 5]) for i in range(3)  # TC_INSTALLED
    }
    for i, cnt in enumerate(subs.values()):
        assert installed_at_cut[i] + residue.get(i, 0) == cnt
    sm2 = _tenant_sm(list(subs), checkpoint=True)
    sm2.close()
    iv2, info2 = sm2.run_stream(resume_state=st, deadline_s=120.0)
    assert int(iv2[0]) == expect, (int(iv2[0]), expect)
    ten = info2["tenants"]
    for tid, cnt in subs.items():
        assert ten[tid]["accepted"] == cnt
        assert ten[tid]["completed"] == cnt
    return {"faults": 1, "recoveries": 1,
            "executed_at_cut": info["executed"],
            "residue_rows": int(sum(residue.values())),
            **{f"tasks_{t}": c for t, c in subs.items()}}


# ------------------------- mesh-wide tenancy + tenant storms (ISSUE 13)

def _mesh_conservation(table) -> None:
    """The per-cut identity: submitted == completed + expired + dropped
    (+ still-queued backlog) reconciles EXACTLY per tenant, at every
    mesh size."""
    for tid, s in table.stats().items():
        assert s["accepted"] == (
            s["completed"] + s["expired"] + s["dropped"] + s["backlog"]
        ), (tid, s)


def _mesh_drive(table, rings, polls=2, start=0, clock=None, dt=0.0):
    from hclib_tpu.device.tenants import wrr_poll_reference

    tctl = table.pump(rings)
    for r in range(start, start + polls):
        for d in range(table.ndev):
            wrr_poll_reference(
                rings[d], tctl[d], table.region_rows, r, 1 << 20
            )
    table.absorb(tctl)
    if clock is not None and dt:
        clock[0] += dt


def scenario_tenant_mesh_storm_reshard(seed: int, scale: str) -> dict:
    """THE ACCEPTANCE STORM (ISSUE 13): greedy tenant + deadline storm +
    poison quarantine hitting a mesh-wide front door across THREE live
    reshard cuts (4 -> 2 -> 4 -> 2), per-tenant
    submitted == completed + expired + dropped reconciled exactly at
    every mesh size (one cut routed through CheckpointBundle.reshard's
    tctl/tstats pass-through), and WRR fairness probed after every cut
    in exact weight proportion - the single-device bounds. Runs on the
    numpy WRR reference model (the executable spec of the in-kernel
    poll), so no Mosaic is needed."""
    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW
    from hclib_tpu.device.tenants import MeshTenantTable, TenantSpec

    rng = np.random.default_rng(5000 + seed)
    t_now = [100.0]
    clock = lambda: t_now[0]  # noqa: E731
    # Region sized so a phase's storm + probe + carry + re-dealt
    # residue fits one lane region even at the 2-device trough (the
    # lifetime budget is per table incarnation: it resets at each cut).
    region = 32

    def boom(row):
        raise RuntimeError(f"poison row (seed {seed})")

    def specs():
        return [
            TenantSpec("steady", weight=2, queue_capacity=512),
            TenantSpec("greedy", weight=1, max_in_flight=4,
                       queue_capacity=6),
            TenantSpec("stormy", weight=1, queue_capacity=512,
                       deadline_budget=1_000_000),
            TenantSpec("poison", weight=1, validator=boom,
                       poison_throttle=2, poison_quarantine=4,
                       queue_capacity=512),
        ]

    def fresh_rings(ndev):
        return np.zeros((ndev, 4 * region, RING_ROW), np.int32)

    sizes = [4, 2, 4, 2]
    table = MeshTenantTable(specs(), sizes[0], region, clock=clock)
    rings = fresh_rings(sizes[0])
    greedy_rejects = 0
    expired_doomed = 0
    poisoned_subs = 6
    fairness_probes = []
    cuts = 0
    rnd = 0
    for phase, ndev in enumerate(sizes):
        # Storm traffic: steady flow, a greedy burst far past its
        # quota, a deadline storm (seeded doomed fraction), and - in
        # phase 0 only - the poison tenant walking into quarantine.
        for k in range(12):
            assert table.submit("steady", 0, args=[k + 1]), "steady"
        for _ in range(40):
            adm = table.submit("greedy", 0, args=[1])
            if not adm:
                greedy_rejects += 1
                assert adm.reason in ("backlog", "ring"), adm.reason
        for i in range(16):
            doomed = rng.random() < 0.4
            if doomed:
                expired_doomed += 1
            adm = table.submit(
                "stormy", 0, args=[i],
                deadline_s=(0.01 if doomed else 1e6),
            )
            assert adm, adm.reason
        if phase == 0:
            for _ in range(poisoned_subs):
                table.submit("poison", 0, args=[999])
        _mesh_drive(table, rings, polls=2, start=rnd, clock=t_now,
                    dt=0.05)
        rnd += 2
        _mesh_drive(table, rings, polls=2, start=rnd, clock=t_now,
                    dt=0.05)
        rnd += 2
        _mesh_conservation(table)
        # Drain this phase's storm (doomed rows expire, live rows
        # complete) so the fairness probe below measures CLEAN lanes -
        # expired rows legitimately consume WRR slots without
        # installing, which is throughput shaping, not unfairness.
        for r in range(128):
            _mesh_drive(table, rings, polls=2, start=rnd, clock=t_now,
                        dt=0.02)
            rnd += 2
            if table.drained():
                break
        assert table.drained(), f"phase {phase} storm wedged the drain"
        _mesh_conservation(table)
        # WRR fairness probe at THIS size (the single-device bounds):
        # with both lanes saturated, installs per whole WRR cycle are
        # exactly weight-proportional (steady w=2 : stormy w=1).
        before = {t: table.stats()[t]["completed"]
                  for t in ("steady", "stormy")}
        for d in range(table.ndev):
            for k in range(8):
                assert table.submit("steady", 0, args=[1], device=d)
            for k in range(4):
                assert table.submit("stormy", 0, args=[1],
                                    deadline_s=1e6, device=d)
        _mesh_drive(table, rings, polls=4, start=rnd, clock=t_now)
        rnd += 4
        after = {t: table.stats()[t]["completed"]
                 for t in ("steady", "stormy")}
        ds = after["steady"] - before["steady"]
        dm = after["stormy"] - before["stormy"]
        assert ds == 2 * dm > 0, (phase, ds, dm)
        fairness_probes.append((ds, dm))
        if phase == len(sizes) - 1:
            break
        # Carry residue INTO the cut: a fresh batch pinned on device 0
        # (so one weight-bounded poll cannot drain it), only partially
        # consumed - the reshard must re-deal live tenant-tagged rows
        # (the conservation identity must reconcile across the cut
        # with work genuinely in flight).
        for k in range(6):
            assert table.submit("steady", 0, args=[k + 1], device=0)
        for k in range(4):
            assert table.submit("stormy", 0, args=[k], deadline_s=1e6,
                                device=0)
        _mesh_drive(table, rings, polls=1, start=rnd)
        rnd += 1
        assert not table.drained(), "carry batch already drained"
        _mesh_conservation(table)
        # LIVE RESHARD CUT to the next size. Cut 1 rides the
        # CheckpointBundle path end-to-end (ring_rows re-deal + the
        # aggregate tctl/tstats pass-through); the others use the
        # table's own export/resume.
        ndev_next = sizes[phase + 1]
        if phase == 1:
            from hclib_tpu.device.descriptor import (
                DESC_WORDS, F_HOME, NO_TASK,
            )
            from hclib_tpu.runtime.checkpoint import CheckpointBundle

            st = table.export_state(rings)
            cap = 8
            tasks = np.zeros((table.ndev, cap, DESC_WORDS), np.int32)
            tasks[:, :, 2:4] = NO_TASK
            tasks[:, :, F_HOME] = NO_TASK
            counts = np.zeros((table.ndev, 8), np.int32)
            counts[:, 4] = 2
            b = CheckpointBundle("resident", {"ndev": table.ndev}, {
                "tasks": tasks,
                "succ": np.full((table.ndev, 8), -1, np.int32),
                "ready": np.zeros((table.ndev, cap), np.int32),
                "counts": counts,
                "ivalues": np.zeros((table.ndev, 16), np.int32),
                "ring_rows": st["ring_rows"], "ictl": st["ictl"],
                "tctl": st["tctl"], "tstats": st["tstats"],
            })
            out = b.reshard(ndev_next)
            assert np.array_equal(out.arrays["tctl"], st["tctl"])
            assert np.array_equal(out.arrays["tstats"], st["tstats"])
            nxt = table.resized(ndev_next)
            nxt.resume_from({
                "ring_rows": out.arrays["ring_rows"],
                "ictl": out.arrays["ictl"],
                "tctl": out.arrays["tctl"],
                "tstats": out.arrays["tstats"],
                "tenant_ids": st["tenant_ids"],
            })
            table = nxt
        else:
            table, _ = table.reshard(rings, ndev_next)
        rings = fresh_rings(ndev_next)
        cuts += 1
        _mesh_conservation(table)
    # Drain to empty: doomed rows expire, live rows complete.
    for r in range(256):
        _mesh_drive(table, rings, polls=2, start=rnd + r, clock=t_now,
                    dt=0.02)
        if table.drained():
            break
    assert table.drained(), "tenant mesh storm wedged the drain"
    _mesh_conservation(table)
    snap = table.stats()
    assert snap["poison"]["quarantined"] == 1, snap["poison"]
    assert snap["poison"]["completed"] == 0, snap["poison"]
    assert snap["stormy"]["expired"] > 0, snap["stormy"]
    assert greedy_rejects > 0, "greedy quota never pushed back"
    assert all(s["backlog"] == 0 for s in snap.values()), snap
    return {
        "faults": greedy_rejects + snap["stormy"]["expired"]
        + snap["poison"]["poisoned"],
        "recoveries": cuts, "cuts": cuts,
        "greedy_rejected": greedy_rejects,
        "stormy_expired": int(snap["stormy"]["expired"]),
        "fairness": fairness_probes,
    }


def scenario_tenant_mesh_autoscale_pressure(seed: int, scale: str) -> dict:
    """Tenant/deadline-aware autoscaling (ISSUE 13 policy half): a
    tenant burning its deadline budget triggers a typed ``deadline_out``
    scale-out BEFORE the watchdog rung (budget exhaustion -> lane
    cancel) - during cooldown, with zero streak - and scale-in is
    refused with a typed ``strand_hold`` while any tenant has in-flight
    ring residue, then fires once drained."""
    import numpy as np

    import hclib_tpu as hc
    from hclib_tpu.device.descriptor import RING_ROW
    from hclib_tpu.device.tenants import MeshTenantTable, TenantSpec

    t_now = [100.0]
    clock = lambda: t_now[0]  # noqa: E731
    region = 16
    budget = 40
    table = MeshTenantTable(
        [TenantSpec("latency", weight=2, deadline_budget=budget,
                    queue_capacity=512),
         TenantSpec("bulk", queue_capacity=512)],
        2, region, clock=clock,
    )
    rings = np.zeros((2, 2 * region, RING_ROW), np.int32)
    policy = hc.AutoscalerPolicy(
        min_devices=1, max_devices=8, scale_out_backlog=1e9,
        scale_in_backlog=4.0, hysteresis=2, cooldown=3,
        tenant_pressure=0.25,
    )
    # Prime the cooldown gate (prove the pressure path bypasses it).
    policy._cooling = 3
    ndev, events, rnd = 2, [], 0

    def observe(backlog_rows):
        return hc.Observation(
            ndev, [backlog_rows] * ndev, executed_delta=8, slice_s=1.0,
            tenants=table.pressure(),
        )

    # Slice 0: baseline (no drain yet - deltas need a previous slice).
    events.append(policy.decide(observe(8))[1])
    # Slice 1: the deadline storm - a burst of doomed rows expires
    # within one slice, draining >= 25% of the budget.
    for i in range(16):
        assert table.submit("latency", 0, args=[i], deadline_s=0.01)
    t_now[0] += 1.0  # every deadline lapses before the pump
    tctl = table.pump(rings)
    table.absorb(tctl)
    target, kind, reason = policy.decide(observe(8))
    events.append(kind)
    assert kind == "deadline_out", (kind, reason, table.pressure())
    assert target == 2 * ndev
    snap = table.stats()["latency"]
    # BEFORE the watchdog rung: the budget is not exhausted, the lane
    # is NOT cancelled - the controller beat the strike ladder.
    assert snap["expired"] < budget, snap
    assert table.submit("latency", 0, args=[0], deadline_s=1e6), (
        "lane already cancelled: scale-out lost the race"
    )
    ndev = target
    # The typed event rides TR_SCALE + the metrics registry.
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, policy, metrics=reg)
    asc._event(hc.ScaleEvent("deadline_out", 1, 2, 4, reason))
    from hclib_tpu.device.tracebuf import TR_SCALE, records_of

    recs = records_of(asc.trace_info(), TR_SCALE)
    assert len(recs) == 1 and int(recs[0][2]) == (2 << 8) | 4
    assert reg.snapshot()["metrics"]["autoscale.deadline_out.count"] == 1
    # Strand refusal: idle backlog + in-flight ring residue (published,
    # unconsumed - the submit above) -> typed strand_hold, repeatedly.
    tctl = table.pump(rings)  # publish; nothing consumed yet
    table.absorb(tctl)
    assert table.stats()["latency"]["in_flight"] > 0
    policy._cooling = 0
    kinds = [policy.decide(observe(0))[1] for _ in range(3)]
    events += kinds
    assert kinds[0] == "hold"  # streak 1/2
    assert kinds[1] == "strand_hold" and kinds[2] == "strand_hold", kinds
    asc._event(hc.ScaleEvent("strand_hold", 2, ndev, ndev, "refused"))
    # Drain the residue: the very next slice scales in.
    from hclib_tpu.device.tenants import wrr_poll_reference

    tctl = table.pump(rings)
    for d in range(2):
        wrr_poll_reference(rings[d], tctl[d], region, rnd, 1 << 20)
    table.absorb(tctl)
    assert table.stats()["latency"]["in_flight"] == 0
    target, kind, reason = policy.decide(observe(0))
    events.append(kind)
    assert kind == "scale_in" and target == ndev // 2, (kind, reason)
    return {"faults": int(table.stats()["latency"]["expired"]),
            "recoveries": 1, "events": events}


# ------------------- request/response serving loop (ISSUE 16)

def scenario_serve_slow_poller(seed: int, scale: str) -> dict:
    """SERVE: a depth-4 completion mailbox fed by bursty retirement
    while the poller consumes ONE result per step - sustained
    backpressure parks rows (counted, never dropped) and every
    submitted future still resolves RESULT with its exact payload:
    zero loss under a poller an order of magnitude too slow."""
    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.egress import EgressSpec, HostMailbox
    from hclib_tpu.device.tenants import (
        TenantSpec, TenantTable, wrr_poll_reference,
    )

    rng = np.random.default_rng(6000 + seed)
    n = 48 if scale == "smoke" else 192
    region = 64
    spec = EgressSpec(depth=4)
    table = TenantTable(
        [TenantSpec("gold", weight=2), TenantSpec("std")],
        region, clock=lambda: 100.0, egress=spec,
    )
    # Host-model park capacity covers the whole storm: the DEVICE
    # bounds park occupancy with its install credit gate; this
    # reference drive retires whole poll batches at once, so the
    # ring must hold everything the slow poller leaves behind.
    box = HostMailbox(spec, park_cap=n)
    ring = np.zeros((2 * region, RING_ROW), np.int32)
    futs, values, submitted, drained = [], {}, 0, 0
    for i in range(n):
        adm = table.submit(int(rng.integers(0, 2)), 0, args=[i])
        assert adm and adm.future.token > 0, adm
        futs.append(adm.future)
        values[adm.future.token] = 3 * i + 1
        submitted += 1
    rnd = 0
    while drained < submitted:
        tctl = table.pump(ring)
        rows = wrr_poll_reference(ring, tctl, region, rnd, 1 << 20)
        table.absorb(tctl)
        rnd += 1
        box.publish([
            (int(r[TEN_TOKEN]), 0, 0, 0, values[int(r[TEN_TOKEN])])
            for r in rows
        ])
        # The slow poller: one result per step, no matter the burst.
        drained += len(box.drain(futures=table.futures, limit=1))
        assert rnd < 16 * n, "slow poller wedged the serve loop"
    assert box.park_events() > 0, "mailbox never backpressured"
    assert box.occupancy() == 0 and box.parked() == 0
    for f in futs:
        assert f.result(timeout=1.0) == values[f.token]
        assert f.state == "RESULT"
    cons = table.futures.conservation()
    assert cons["ok"] and cons["resolved"] == submitted, cons
    return {"faults": int(box.park_events()), "recoveries": 1,
            "submitted": submitted, "park_events":
            int(box.park_events()), "steps": rnd}


def scenario_serve_fire_preempt(seed: int, scale: str) -> dict:
    """SERVE: fire_preempt lands with futures in flight on the live
    egress-enabled stream - the cut lands every future in RESULT or
    PREEMPTED (valid resume token, never a silent hang); the resumed
    stream re-adopts the tokens and every reattached future resolves.
    Conservation closes exactly on both ledgers."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.tenants import TenantSpec, TenantTable
    from hclib_tpu.runtime import resilience
    from hclib_tpu.runtime.checkpoint import checkpoint_on_preempt

    rng = np.random.default_rng(7000 + seed)
    subs = {t: int(rng.integers(12, 24))
            for t in ("alpha", "beta", "gamma")}

    def table():
        return TenantTable(
            [TenantSpec(t) for t in subs], 256,
            egress=EgressSpec(depth=16),
        )

    resilience.reset_preempt()
    t1 = table()
    sm = _tenant_sm(t1, checkpoint=True)
    futs, expect = [], 0
    for i, (tid, cnt) in enumerate(subs.items()):
        for _ in range(cnt):
            adm = sm.submit(tid, 0, args=[i + 1])
            assert adm and adm.future is not None
            futs.append(adm.future)
            expect += i + 1

    def preempter():
        time.sleep(0.05 + 0.01 * (seed % 3))
        resilience.fire_preempt(f"serve soak preemption seed {seed}")

    t = threading.Thread(target=preempter)
    t.start()
    try:
        with checkpoint_on_preempt(sm, after_executed=5):
            iv, info = sm.run_stream(
                TaskGraphBuilder(), quantum=8, deadline_s=120.0,
            )
    finally:
        t.join()
        resilience.reset_preempt()
    assert info.get("quiesced"), "preemption never quiesced the stream"
    st = info["state"]
    assert "etok" in st, "egress tokens missing from the snapshot"
    states = {f.state for f in futs}
    assert states <= {"RESULT", "PREEMPTED"}, states
    tokens = []
    for f in futs:
        if f.state == "PREEMPTED":
            tok = f.resume_token
            assert tok and tok[0] == "hclib-egress-resume", tok
            tokens.append(tok)
    c1 = t1.futures.conservation()
    assert c1["ok"] and c1["preempted"] == len(tokens), c1
    # Resume on a fresh equivalent stream; reattach AFTER resume_from
    # has re-adopted the snapshot's tokens.
    t2 = table()
    sm2 = _tenant_sm(t2, checkpoint=True)
    sm2.close()
    iv2, info2 = sm2.run_stream(resume_state=st, deadline_s=120.0)
    assert int(iv2[0]) == expect, (int(iv2[0]), expect)
    for tok in tokens:
        f = sm2.tenants.reattach(tok)
        assert f.result(timeout=2.0) is not None
        assert f.state == "RESULT", f.state
    c2 = t2.futures.conservation()
    assert c2["ok"] and c2["pending"] == 0, c2
    return {"faults": 1, "recoveries": 1,
            "executed_at_cut": info["executed"],
            "preempted_futures": len(tokens),
            "resolved_before_cut": int(c1["resolved"]),
            **{f"tasks_{t}": c for t, c in subs.items()}}


def scenario_serve_mesh_deadline_storm(seed: int, scale: str) -> dict:
    """SERVE: the soak conservation arm - a 4-device mesh front door
    under a seeded deadline storm, resharded LIVE 4 -> 2 -> 4 with
    futures in flight (preempt -> reattach on the shared ledger at
    every cut). At the end every future is terminal and
    submitted == resolved + expired + poisoned EXACTLY, globally and
    per tenant."""
    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.egress import EgressSpec, HostMailbox
    from hclib_tpu.device.tenants import (
        MeshTenantTable, TenantSpec, wrr_poll_reference,
    )

    rng = np.random.default_rng(8000 + seed)
    region = 16
    clk = [100.0]
    spec = EgressSpec(depth=4)
    table = MeshTenantTable(
        [TenantSpec("gold", weight=2), TenantSpec("std"),
         TenantSpec("batch", queue_capacity=512)],
        4, region, clock=lambda: clk[0], egress=spec,
    )
    futures = table.futures
    assert futures is not None
    per_batch = 10 if scale == "smoke" else 40
    # Client view: token -> latest Future (reattach swaps in the new
    # one); tenant name rides alongside for the per-tenant identity.
    client = {}

    def drive(table, rings, polls=2, start=0, dt=0.05):
        boxes = [HostMailbox(spec, park_cap=8 * region)
                 for _ in range(table.ndev)]
        tctl = table.pump(rings)
        for r in range(start, start + polls):
            for d in range(table.ndev):
                rows = wrr_poll_reference(
                    rings[d], tctl[d], table.region_rows, r, 1 << 20
                )
                boxes[d].publish([
                    (int(row[TEN_TOKEN]), 0, 0, 0, 7) for row in rows
                ])
        table.absorb(tctl)
        for box in boxes:
            box.drain(futures=futures)
        clk[0] += dt

    def rings_for(ndev):
        return np.zeros((ndev, 3 * region, RING_ROW), np.int32)

    submitted = 0
    sizes = [4, 2, 4]
    rings = rings_for(4)
    names = ("gold", "std", "batch")
    for phase, ndev in enumerate(sizes):
        for i in range(per_batch):
            tid = names[int(rng.integers(0, 3))]
            doomed = rng.random() < 0.35
            adm = table.submit(
                tid, 0, args=[i],
                deadline_s=(0.01 if doomed else 600.0),
            )
            if adm:
                submitted += 1
                client[adm.future.token] = (tid, adm.future)
            clk[0] += float(rng.random() * 0.02)
        drive(table, rings, polls=2, start=4 * phase)
        if phase == len(sizes) - 1:
            break
        # The live cut: export preempts in-flight futures; the resized
        # mesh shares the SAME ledger, so resume tokens reattach.
        state = table.export_state(rings)
        tokens = [(tok, tid, f.resume_token)
                  for tok, (tid, f) in client.items()
                  if f.state == "PREEMPTED"]
        nxt = table.resized(sizes[phase + 1])
        assert nxt.futures is futures, "ledger forked across the cut"
        nxt.resume_from(state)
        for tok, tid, rt in tokens:
            client[tok] = (tid, nxt.reattach(rt))
        table, rings = nxt, rings_for(nxt.ndev)
    for r in range(40, 40 + 64):
        drive(table, rings, polls=1, start=r)
        if table.drained():
            break
    assert table.drained(), "deadline storm wedged the mesh drain"
    cons = futures.conservation()
    assert cons["ok"] and cons["pending"] == 0, cons
    assert submitted == (
        cons["resolved"] + cons["expired"] + cons["poisoned"]
    ), (submitted, cons)
    assert cons["expired"] > 0 and cons["resolved"] > 0, cons
    assert cons["reattached"] > 0, "no future rode a cut"
    per = {t: {"RESULT": 0, "EXPIRED": 0, "POISONED": 0}
           for t in names}
    for tok, (tid, f) in client.items():
        assert f.state in per[tid], (tid, f.state)
        per[tid][f.state] += 1
    for tid, s in per.items():
        lane = table.stats()[tid]
        assert s["RESULT"] + s["EXPIRED"] + s["POISONED"] == (
            lane["accepted"]
        ), (tid, s, lane)
    return {"faults": int(cons["expired"]), "recoveries": 2,
            "submitted": submitted, "resolved": int(cons["resolved"]),
            "expired": int(cons["expired"]),
            "reattached": int(cons["reattached"]),
            "per_tenant": {t: s for t, s in per.items()}}


def _durability_bundle(seed: int, ndev: int = 4, cap: int = 16,
                       live: int = 3, parked=(), channels=("left", "right"),
                       host_residue=None, max_waits: int = 4):
    """Schema-complete synthetic resident bundle (the durability matrix
    exercises the STORE and the reshard algebra, not the kernel):
    ``live`` ready link-free rows per device, optional wait-parked rows
    (``parked``: (device, channel, need) triples), seeded ivalues so
    two bundles of different seeds are bit-distinguishable."""
    import numpy as np

    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_DEP, F_FN, F_HOME, F_SUCC0, F_SUCC1, NO_TASK,
    )
    from hclib_tpu.device.megakernel import C_ALLOC, C_PENDING, C_VALLOC
    from hclib_tpu.runtime.checkpoint import CheckpointBundle

    rng = np.random.default_rng(seed)
    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, F_SUCC0] = NO_TASK
    tasks[:, :, F_SUCC1] = NO_TASK
    tasks[:, :, F_HOME] = -1
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
        counts[d, 1] = live  # ready-ring tail
        counts[d, C_ALLOC] = live + npk
        counts[d, C_PENDING] = live + npk
        counts[d, C_VALLOC] = 2
    meta = {
        "kernel_names": ["seed", "waiter"], "capacity": cap,
        "num_values": 4, "succ_capacity": 4, "data_specs": [],
        "ndev": ndev, "channels": list(channels),
    }
    if host_residue:
        meta["host_residue"] = dict(host_residue)
    return CheckpointBundle("resident", meta, {
        "tasks": tasks,
        "succ": np.full((ndev, 4), NO_TASK, np.int32),
        "ready": ready, "counts": counts,
        "ivalues": rng.integers(0, 1 << 20, (ndev, 4)).astype(np.int32),
        "waits": waits,
    })


def scenario_durability_crashpoints(seed: int, scale: str) -> dict:
    """DURABILITY: the seeded crash-point matrix over the BundleStore -
    clean generational publishes reload bit-identically; a torn npz, a
    flipped bit, and a lost manifest (FaultPlan disk sites) each
    quarantine that generation with the right typed reason and fall
    back bit-identically to the newest valid one; preempt-mid-save
    leaves the store at its previous state (a staged save is never
    visible); preempt-mid-restore retries idempotently; and an
    unrecoverable store raises the poison diagnostic (naming every
    fault) instead of hanging. Metrics counters and TR_CKPT trace
    records are asserted alongside."""
    import shutil
    import tempfile

    from hclib_tpu.device import tracebuf as tb
    from hclib_tpu.runtime.checkpoint import BundleStore, CheckpointError
    from hclib_tpu.runtime.metrics import MetricsRegistry
    from hclib_tpu.runtime.resilience import FaultPlan, InjectedFault

    rounds = 3 if scale == "smoke" else 8
    faults = recoveries = 0
    root = tempfile.mkdtemp(prefix="hclib-durability-")
    try:
        metrics = MetricsRegistry()
        # Clean generational publishes, retention, bit-identical reload.
        store = BundleStore(root, keep=3, fsync=False, metrics=metrics)
        bundles = []
        for i in range(rounds):
            b = _durability_bundle(1000 * seed + i)
            store.save(b)
            bundles.append(b)
        gens = store.generations()
        assert len(gens) == min(rounds, 3) and gens[-1] == rounds, gens
        got = BundleStore(root, keep=3, fsync=False).load_latest()
        assert got.diff(bundles[-1])["equal"], "clean reload diverged"

        # Every disk damage class at a seeded crash point: the damaged
        # generation publishes, the next restore quarantines it (typed)
        # and falls back bit-identically to the previous generation.
        for kind, plan_kw, reason in (
            ("torn", {"disk_torn_at": (0,)}, "corrupt"),
            ("flip", {"disk_flip_at": (0,)}, "corrupt"),
            ("manifest", {"disk_manifest_at": (0,)}, "torn"),
        ):
            plan = FaultPlan(seed=seed, **plan_kw)
            writer = BundleStore(root, keep=4, fsync=False,
                                 metrics=metrics, fault_plan=plan)
            gen = writer.save(_durability_bundle(9000 * seed + len(kind)))
            faults += 1
            healer = BundleStore(root, keep=4, fsync=False,
                                 metrics=metrics)
            back = healer.load_latest()
            assert back.diff(bundles[-1])["equal"], (
                kind, "fallback not bit-identical")
            assert [f.generation for f in healer.faults] == [gen], (
                kind, healer.faults)
            assert healer.faults[0].reason == reason, (
                kind, healer.faults[0])
            assert all(
                r[0] == tb.TR_CKPT and (-int(r[2]) - 1) in tb.CK_NAMES
                for r in healer.events
            ), healer.events
            recoveries += 1

        # Preempt mid-save: the InjectedFault lands BEFORE the rename,
        # so the staged generation is invisible and the store unmoved.
        before = BundleStore(root, fsync=False).generations()
        plan = FaultPlan(seed=seed, preempt_save_at=0)
        writer = BundleStore(root, keep=4, fsync=False, fault_plan=plan)
        try:
            writer.save(_durability_bundle(31 * seed + 7))
            raise AssertionError("preempt-mid-save never fired")
        except InjectedFault:
            faults += 1
        after = BundleStore(root, keep=4, fsync=False)
        assert after.generations() == before, "a torn save became visible"
        assert after.load_latest().diff(bundles[-1])["equal"]
        recoveries += 1

        # Preempt mid-restore: the retry is idempotent (same survivor).
        plan = FaultPlan(seed=seed, preempt_restore_at=0)
        reader = BundleStore(root, keep=4, fsync=False, fault_plan=plan)
        try:
            reader.load_latest()
            raise AssertionError("preempt-mid-restore never fired")
        except InjectedFault:
            faults += 1
        assert reader.load_latest().diff(bundles[-1])["equal"]
        recoveries += 1

        # Unrecoverable: every generation damaged -> the poison
        # diagnostic names each fault; the caller's degradation ladder
        # gets a signal instead of a hang.
        dead = BundleStore(root, keep=4, fsync=False, metrics=metrics)
        for g in dead.generations():
            npz = os.path.join(dead.path_of(g), "state.npz")
            with open(npz, "r+b") as f:
                f.truncate(max(1, os.path.getsize(npz) // 2))
            faults += 1
        try:
            dead.load_latest()
            raise AssertionError("unrecoverable store did not raise")
        except CheckpointError as e:
            assert "unrecoverable" in str(e) and "poison" in str(e), e
        recoveries += 1

        m = metrics.snapshot()["metrics"]
        assert m.get("checkpoint.save.count", 0) >= rounds + 3, m
        assert m.get("checkpoint.quarantined.count", 0) >= 3, m
        assert m.get("checkpoint.fallback.count", 0) >= 3, m
        assert m.get("checkpoint.poison.count", 0) >= 1, m
        return {"faults": faults, "recoveries": recoveries,
                "generations": rounds,
                "quarantined": int(m["checkpoint.quarantined.count"])}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def scenario_durability_serve_fallback(seed: int, scale: str) -> dict:
    """DURABILITY: fallback restore under the serving loop - the
    deadline-storm mesh (4 devices, 3 tenants, futures in flight) cuts
    at 4 -> 2, the exported state is published TWICE to a BundleStore,
    the newest generation is then bit-flipped on disk, and the resume
    path restores through ``load_latest`` - which quarantines the
    damaged generation and falls back to the older, bit-identical one.
    Futures reattach onto the restored table, the 2 -> 4 resize rides
    the live path, and the serving ledger closes EXACTLY:
    submitted == resolved + expired + poisoned. Alongside, the reshard
    wait re-homing algebra: a bundle with pending host-declared waits
    reshards 4 -> 2 -> 4 with wait counts and per-channel need sums
    conserved, and a satisfier-in-residue bundle is refused with the
    whole-program diagnostic."""
    import shutil
    import tempfile

    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.egress import EgressSpec, HostMailbox
    from hclib_tpu.device.tenants import (
        MeshTenantTable, TenantSpec, wrr_poll_reference,
    )
    from hclib_tpu.runtime.checkpoint import (
        BundleStore, CheckpointBundle, CheckpointError,
    )

    rng = np.random.default_rng(8600 + seed)
    region = 16
    clk = [100.0]
    spec = EgressSpec(depth=4)
    table = MeshTenantTable(
        [TenantSpec("gold", weight=2), TenantSpec("std"),
         TenantSpec("batch", queue_capacity=512)],
        4, region, clock=lambda: clk[0], egress=spec,
    )
    futures = table.futures
    assert futures is not None
    per_batch = 10 if scale == "smoke" else 30
    client = {}

    def drive(table, rings, polls=2, start=0):
        boxes = [HostMailbox(spec, park_cap=8 * region)
                 for _ in range(table.ndev)]
        tctl = table.pump(rings)
        for r in range(start, start + polls):
            for d in range(table.ndev):
                rows = wrr_poll_reference(
                    rings[d], tctl[d], table.region_rows, r, 1 << 20
                )
                boxes[d].publish([
                    (int(row[TEN_TOKEN]), 0, 0, 0, 7) for row in rows
                ])
        table.absorb(tctl)
        for box in boxes:
            box.drain(futures=futures)
        clk[0] += 0.05

    def rings_for(ndev):
        return np.zeros((ndev, 3 * region, RING_ROW), np.int32)

    submitted = 0
    sizes = [4, 2, 4]
    rings = rings_for(4)
    names = ("gold", "std", "batch")
    root = tempfile.mkdtemp(prefix="hclib-serve-fallback-")
    try:
        for phase, ndev in enumerate(sizes):
            for i in range(per_batch):
                tid = names[int(rng.integers(0, 3))]
                doomed = rng.random() < 0.3
                adm = table.submit(
                    tid, 0, args=[i],
                    deadline_s=(0.01 if doomed else 600.0),
                )
                if adm:
                    submitted += 1
                    client[adm.future.token] = (tid, adm.future)
                clk[0] += float(rng.random() * 0.02)
            drive(table, rings, polls=2, start=4 * phase)
            if phase == len(sizes) - 1:
                break
            # A pre-cut burst with generous deadlines: futures that are
            # GUARANTEED live at the export, so every cut exercises the
            # preempt -> reattach path regardless of the seed's storm.
            for j, tid in enumerate(names):
                adm = table.submit(tid, 0, args=[1000 + j],
                                   deadline_s=600.0)
                if adm:
                    submitted += 1
                    client[adm.future.token] = (tid, adm.future)
            state = table.export_state(rings)
            tokens = [(tok, tid, f.resume_token)
                      for tok, (tid, f) in client.items()
                      if f.state == "PREEMPTED"]
            if phase == 0:
                # The durable cut: publish the exported state TWICE,
                # damage the newest generation on disk, and restore
                # through the self-healing walk - the fallback must be
                # bit-identical to what was exported.
                bundle = CheckpointBundle(
                    "resident", {"schema": "mesh-serve-export"}, state,
                )
                store = BundleStore(root, keep=3, fsync=False)
                store.save(bundle)
                gen2 = store.save(bundle)
                npz = os.path.join(store.path_of(gen2), "state.npz")
                with open(npz, "r+b") as f:
                    f.seek(12)
                    byte = f.read(1)
                    f.seek(12)
                    f.write(bytes([byte[0] ^ 0x40]))
                healer = BundleStore(root, keep=3, fsync=False)
                back = healer.load_latest()
                assert [f.generation for f in healer.faults] == [gen2], (
                    healer.faults)
                assert back.diff(bundle)["equal"], (
                    "fallback generation not bit-identical")
                state = {k: back.arrays[k] for k in state}
            nxt = table.resized(sizes[phase + 1])
            assert nxt.futures is futures, "ledger forked across the cut"
            nxt.resume_from(state)
            for tok, tid, rt in tokens:
                client[tok] = (tid, nxt.reattach(rt))
            table, rings = nxt, rings_for(nxt.ndev)
        for r in range(40, 40 + 64):
            drive(table, rings, polls=1, start=r)
            if table.drained():
                break
        assert table.drained(), "fallback restore wedged the mesh drain"
        cons = futures.conservation()
        assert cons["ok"] and cons["pending"] == 0, cons
        assert submitted == (
            cons["resolved"] + cons["expired"] + cons["poisoned"]
        ), (submitted, cons)
        assert cons["reattached"] > 0, "no future rode the fallback cut"

        # Reshard wait re-homing algebra (the checkpoint tentpole):
        # counts and per-channel need sums conserved 4 -> 2 -> 4; a
        # satisfier-in-residue bundle refused whole-program.
        wb = _durability_bundle(
            77 * seed + 5,
            parked=[(0, 0, 3), (1, 1, 2), (2, 0, 1), (3, 1, 4)],
        )
        w0 = int(np.asarray(wb.arrays["waits"])[:, 0, 0].sum())
        down = wb.reshard(2)
        up = down.reshard(4)
        for b2 in (down, up):
            arr = np.asarray(b2.arrays["waits"])
            assert int(arr[:, 0, 0].sum()) == w0, (w0, arr[:, 0, 0])
        from hclib_tpu.device.megakernel import C_PENDING

        assert int(up.arrays["counts"][:, C_PENDING].sum()) == int(
            wb.arrays["counts"][:, C_PENDING].sum()
        )
        rb = _durability_bundle(
            78 * seed, parked=[(0, 0, 3)],
            host_residue={"left": 2},
        )
        try:
            rb.reshard(2)
            raise AssertionError("residue refusal never fired")
        except CheckpointError as e:
            assert "host residue" in str(e) and "left" in str(e), e
        return {"faults": int(cons["expired"]) + 1, "recoveries": 3,
                "submitted": submitted,
                "resolved": int(cons["resolved"]),
                "reattached": int(cons["reattached"]),
                "rehomed_waits": w0}
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ------------------------------- SLO burn-rate autoscaling (ISSUE 19)

def scenario_slo_burn_scaleout(seed: int, scale: str) -> dict:
    """SLO: the seeded burn-rate storm (ISSUE 19) - a healthy request
    stream degrades its tail mid-run; the streaming estimator (fed
    cumulative on-device latency histograms, the TelemetryPoller
    shape) reports latency_pressure over the policy threshold and the
    policy fires a typed ``slo_out`` scale-out BEFORE the
    deadline-budget watchdog rung (no deadline has expired - with the
    burn signal zeroed the same observation HOLDS), during cooldown.
    The typed event rides TR_SCALE, the metrics registry, and the
    Perfetto exporter. A no-objective estimator replaying the same
    degraded stream stays at zero pressure (the off path)."""
    import numpy as np

    import hclib_tpu as hc
    from hclib_tpu.device.telemetry import LAT_BUCKETS, bucket_of
    from hclib_tpu.runtime.slo import SloEstimator

    rng = np.random.default_rng(9100 + seed)
    objective = 64  # rounds: whole buckets at/above this edge are bad
    windows = (5.0, 30.0)
    est = SloEstimator(objective_rounds=objective, quantile=0.99,
                       windows_s=windows)
    counts = np.zeros(LAT_BUCKETS, np.int64)
    snapshots = []
    per_tick = 16 if scale == "smoke" else 64
    t, bad_total = 0.0, 0

    def tick(lo, hi):
        nonlocal t
        for d in rng.integers(lo, hi, size=per_tick):
            counts[bucket_of(int(d))] += 1
        t += 1.0
        snapshots.append((t, counts.copy()))
        est.observe(counts.copy(), t)

    # Healthy phase: every request lands well under the objective.
    for _ in range(6):
        tick(4, 32)
    healthy_pressure = est.latency_pressure(t)
    assert healthy_pressure < 2.0, healthy_pressure
    # Degradation: the tail walks past the objective bucket edge.
    for _ in range(6):
        tick(128, 2048)
        bad_total += per_tick
    pressure = est.latency_pressure(t)
    assert pressure >= 2.0, (pressure, est.stats())
    p99 = est.quantiles((0.99,))[0.99]
    assert p99 >= 128, p99

    policy = hc.AutoscalerPolicy(
        min_devices=1, max_devices=8, scale_out_backlog=1e9,
        scale_in_backlog=4.0, hysteresis=2, cooldown=3,
        tenant_pressure=0.25, slo_burn=2.0,
    )
    # Prime the cooldown gate (prove the burn path bypasses it).
    policy._cooling = 3

    def observe(p):
        return hc.Observation(2, [8, 8], executed_delta=8, slice_s=1.0,
                              latency_pressure=p)

    # BEFORE the watchdog rung: nothing expired, no deadline budget
    # drained - the SAME observation with the burn signal zeroed holds.
    assert policy.decide(observe(0.0))[1] == "hold"
    target, kind, reason = policy.decide(observe(pressure))
    assert kind == "slo_out", (kind, reason)
    assert target == 4 and "burn" in reason, (target, reason)

    # The typed event rides TR_SCALE + metrics + Perfetto.
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, policy, metrics=reg)
    asc._event(hc.ScaleEvent("slo_out", 1, 2, target, reason))
    from hclib_tpu.device.tracebuf import TR_SCALE, records_of

    recs = records_of(asc.trace_info(), TR_SCALE)
    assert len(recs) == 1 and int(recs[0][2]) == (2 << 8) | target
    assert reg.snapshot()["metrics"]["autoscale.slo_out.count"] == 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    import timeline

    doc = timeline.export_perfetto("", traces=[asc.trace_info()])
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(n.startswith(f"slo out 2→{target}") for n in names), names

    # Off path: no objective -> zero pressure on the SAME stream.
    quiet = SloEstimator(objective_rounds=None, quantile=0.99,
                         windows_s=windows)
    for ts, c in snapshots:
        quiet.observe(c, ts)
    assert quiet.latency_pressure(t) == 0.0
    return {"faults": bad_total, "recoveries": 1,
            "pressure": round(float(pressure), 3),
            "healthy_pressure": round(float(healthy_pressure), 4),
            "p99_rounds": float(p99), "target": target}


# --------------------------------------- dynamic graph service (ISSUE 20)


def scenario_dyngraph_storm_reshard(seed: int, scale: str) -> dict:
    """Dyngraph: an UPDATE storm cut LIVE mid-run, twice - two replicas
    of the same registered stream quiesce at different points (divergent
    applied subsets, labels, spare cursors), the stacked 2-replica
    bundle reshards 2 -> 4 -> 1 through the canonical merge (union
    flags broadcast, edge-count conservation, labels min-folded), and
    the live replica resumes to a fixpoint bit-identical to the
    from-scratch host run ON THE MUTATED GRAPH - the fault is the
    mid-storm preemption, the recoveries are the reshard folds and the
    exact drain."""
    import numpy as np

    from hclib_tpu.device.dyngraph import (
        DynGraph, _bind_updates, _seed_builders, fk_data, host_dyngraph,
        make_dyngraph_megakernel,
    )
    from hclib_tpu.device.frontier import INF, VT_BASE
    from hclib_tpu.runtime.checkpoint import (
        CheckpointBundle, snapshot_megakernel,
    )

    rng = np.random.default_rng(29 + seed)
    n, m = (16, 48) if scale == "smoke" else (32, 128)
    n_ups = 4 if scale == "smoke" else 8
    g = DynGraph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                 rng.integers(1, 8, m), spare_blocks=2,
                 upd_cap=max(8, n_ups))
    for u, v, w in zip(rng.integers(0, n, n_ups),
                       rng.integers(0, n, n_ups),
                       rng.integers(1, 8, n_ups)):
        g.add_update(int(u), int(v), int(w))
    mk = make_dyngraph_megakernel(
        "sssp", g, width=0, interpret=True, checkpoint=True,
    )
    _bind_updates(mk, g)

    def cut(quiesce):
        builders, _ = _seed_builders(
            g, "sssp", 0, 1 << 14, 64, [1], mk.num_values, 1,
            lambda i, tot: 0,
        )
        iv = g.preset_values(mk.num_values, INF)
        iv[g.st_base] = 0
        _, _, info_q = mk.run(
            builders[0], data=dict(fk_data(g, mk)), ivalues=iv,
            quiesce=quiesce,
        )
        assert info_q["quiesced"] and info_q["pending"] > 0, info_q
        return info_q

    qa, qb = cut(1), cut(3)  # divergent cuts of the same stream
    ba, bb = snapshot_megakernel(mk, qa), snapshot_megakernel(mk, qb)
    arrays = {k: np.stack([np.asarray(ba.arrays[k]),
                           np.asarray(bb.arrays[k])])
              for k in ba.arrays}
    mesh = CheckpointBundle("resident", {**ba.meta, "ndev": 2}, arrays)

    flag_base, st = g.flag_base, g.st_base
    ivs = arrays["ivalues"].astype(np.int64)
    union = ivs[:, flag_base:flag_base + g.upd_cap].max(axis=0)
    recoveries = 0
    for ndev_new in (4, 1):
        out = mesh.reshard(ndev_new)
        oiv = np.asarray(out.arrays["ivalues"]).astype(np.int64)
        assert oiv.shape[0] == ndev_new
        for d in range(ndev_new):
            # Union flags + the canonical adjacency broadcast to every
            # new device; degrees conserve static + union-applied.
            assert np.array_equal(
                oiv[d, flag_base:flag_base + g.upd_cap], union)
            vt = oiv[d, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
            assert int(vt[:, 2].sum()) == (
                int(g.deg.sum()) + int(union.sum()))
            assert np.array_equal(out.arrays["data/indices"][d],
                                  out.arrays["data/indices"][0])
        assert np.array_equal(  # labels min-fold across the replicas
            oiv[0, st:st + n], ivs[:, st:st + n].min(axis=0))
        recoveries += 1

    # The live replica drains: bit-identical to the mutated-graph twin.
    iv_r, _, _ = mk.resume(qa["state"])
    res = np.asarray(iv_r, np.int64)[st:st + n].astype(np.int32)
    assert np.array_equal(res, host_dyngraph("sssp", g, 0))
    recoveries += 1
    return {"faults": 2, "recoveries": recoveries, "updates": n_ups,
            "union_applied": int(union.sum()),
            "pending_at_cut": int(qa["pending"])}


SCENARIOS = [
    ("fib_retry", scenario_fib_retry),
    ("uts_kill_worker", scenario_uts_kill_worker),
    ("deadline", scenario_deadline),
    ("quarantine", scenario_quarantine),
    ("procworld_crash", scenario_procworld_crash),
]

MESH_SCENARIOS = [
    ("mesh_dead_chip", scenario_mesh_dead_chip),
    ("mesh_dropped_credit", scenario_mesh_dropped_credit),
]

PREEMPT_SCENARIOS = [
    ("preempt_checkpoint", scenario_preempt_checkpoint),
    ("preempt_stream", scenario_preempt_stream),
    ("preempt_mesh_reshard", scenario_preempt_mesh_reshard),
]

STORM_SCENARIOS = [
    ("storm_stream", scenario_storm_stream),
    ("storm_megakernel_chain", scenario_storm_megakernel_chain),
    ("storm_autoscale", scenario_storm_autoscale),
]

TENANT_SCENARIOS = [
    ("tenant_greedy_quota", scenario_tenant_greedy_quota),
    ("tenant_poison_quarantine", scenario_tenant_poison_quarantine),
    ("tenant_deadline_storm", scenario_tenant_deadline_storm),
    ("tenant_preempt_stream", scenario_tenant_preempt_stream),
    # Mesh-wide tenancy (ISSUE 13): the reshard storm + the
    # tenant/deadline-aware policy, both host-model (no Mosaic needed).
    ("tenant_mesh_storm_reshard", scenario_tenant_mesh_storm_reshard),
    ("tenant_mesh_autoscale_pressure",
     scenario_tenant_mesh_autoscale_pressure),
]

SERVE_SCENARIOS = [
    ("serve_slow_poller", scenario_serve_slow_poller),
    ("serve_fire_preempt", scenario_serve_fire_preempt),
    ("serve_mesh_deadline_storm", scenario_serve_mesh_deadline_storm),
]

DURABILITY_SCENARIOS = [
    ("durability_crashpoints", scenario_durability_crashpoints),
    ("durability_serve_fallback", scenario_durability_serve_fallback),
]

SLO_SCENARIOS = [
    ("slo_burn_scaleout", scenario_slo_burn_scaleout),
]

DYNGRAPH_SCENARIOS = [
    ("dyngraph_storm_reshard", scenario_dyngraph_storm_reshard),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=1,
                    help="number of seeds (starting at --seed-base)")
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("--scale", choices=("smoke", "soak"), default="smoke")
    ap.add_argument("--mesh", action="store_true",
                    help="add the seeded device-mesh chaos scenarios "
                         "(dead chip, dropped steal credit)")
    ap.add_argument("--mesh-only", action="store_true",
                    help="run ONLY the device-mesh chaos scenarios")
    ap.add_argument("--preempt", action="store_true",
                    help="add the seeded preemption scenarios "
                         "(checkpoint mid-run, restore, totals "
                         "conserved; incl. N->M mesh reshard)")
    ap.add_argument("--preempt-only", action="store_true",
                    help="run ONLY the preemption scenarios")
    ap.add_argument("--storm", action="store_true",
                    help="add the seeded preempt-storm scenarios "
                         "(repeated cuts on a live stream, chained "
                         "megakernel checkpoints, and the autoscaled "
                         "mesh with a dead-chip evacuation mid-stream)")
    ap.add_argument("--storm-only", action="store_true",
                    help="run ONLY the preempt-storm scenarios")
    ap.add_argument("--tenants", action="store_true",
                    help="add the seeded multi-tenant ingress scenarios "
                         "(greedy tenant vs quota with WRR fairness, "
                         "poison tenant quarantined, deadline storm "
                         "reconciliation, preempt with 3 tenants live)")
    ap.add_argument("--tenants-only", action="store_true",
                    help="run ONLY the multi-tenant ingress scenarios")
    ap.add_argument("--serve", action="store_true",
                    help="add the seeded serving-loop scenarios "
                         "(slow poller vs mailbox backpressure, "
                         "fire_preempt with futures in flight, mesh "
                         "deadline storm with live 4->2->4 reshards "
                         "and exact future conservation)")
    ap.add_argument("--serve-only", action="store_true",
                    help="run ONLY the serving-loop scenarios")
    ap.add_argument("--durability", action="store_true",
                    help="add the seeded durable-store scenarios "
                         "(crash-point matrix over the BundleStore: "
                         "torn/flipped/lost members quarantined with "
                         "bit-identical fallback, preempt mid-save/"
                         "mid-restore, serving-ledger conservation "
                         "across a fallback restore, reshard wait "
                         "re-homing algebra)")
    ap.add_argument("--durability-only", action="store_true",
                    help="run ONLY the durable-store scenarios")
    ap.add_argument("--slo", action="store_true",
                    help="add the seeded SLO burn-rate scenario (tail "
                         "degradation crossing the multi-window burn "
                         "threshold fires a typed slo_out scale-out "
                         "before the deadline watchdog rung, riding "
                         "TR_SCALE/metrics/Perfetto)")
    ap.add_argument("--slo-only", action="store_true",
                    help="run ONLY the SLO burn-rate scenario")
    ap.add_argument("--dyngraph", action="store_true",
                    help="add the seeded dynamic-graph scenario (an "
                         "update storm cut live at two divergent "
                         "points, the stacked replicas resharded "
                         "2->4->1 with canonical-merge conservation, "
                         "and the live replica drained bit-identical "
                         "to the mutated-graph host twin)")
    ap.add_argument("--dyngraph-only", action="store_true",
                    help="run ONLY the dynamic-graph scenario")
    ap.add_argument("--no-skip", action="store_true",
                    help="treat skipped scenarios as failures (CI gating "
                         "jobs must fail CLOSED: an environment that "
                         "cannot run the fault paths is not a pass)")
    ap.add_argument("--timeout-s", type=float, default=300.0,
                    help="hard whole-sweep ceiling; overrun = exit 1 "
                         "with all-thread stack dumps")
    args = ap.parse_args(argv)

    # An -only flag drops the base suite; the group flags are additive
    # on top of whatever remains, so every combination runs exactly the
    # groups it names (e.g. --mesh-only --preempt = mesh + preempt).
    scenarios = (
        []
        if (args.mesh_only or args.preempt_only or args.storm_only
            or args.tenants_only or args.serve_only
            or args.durability_only or args.slo_only
            or args.dyngraph_only)
        else list(SCENARIOS)
    )
    if args.mesh or args.mesh_only:
        scenarios += MESH_SCENARIOS
    if args.preempt or args.preempt_only:
        scenarios += PREEMPT_SCENARIOS
    if args.storm or args.storm_only:
        scenarios += STORM_SCENARIOS
    if args.tenants or args.tenants_only:
        scenarios += TENANT_SCENARIOS
    if args.serve or args.serve_only:
        scenarios += SERVE_SCENARIOS
    if args.durability or args.durability_only:
        scenarios += DURABILITY_SCENARIOS
    if args.slo or args.slo_only:
        scenarios += SLO_SCENARIOS
    if args.dyngraph or args.dyngraph_only:
        scenarios += DYNGRAPH_SCENARIOS

    # The tool's own hang enforcement: dump + hard-exit on overrun.
    faulthandler.dump_traceback_later(args.timeout_s, exit=True)
    failures = skipped = faults = recoveries = 0
    t0 = time.monotonic()
    for seed in range(args.seed_base, args.seed_base + args.seeds):
        for name, fn in scenarios:
            row = {"scenario": name, "seed": seed, "scale": args.scale}
            ts = time.monotonic()
            try:
                row.update(fn(seed, args.scale))
                row["ok"] = True
                if "skipped" in row:
                    skipped += 1
                faults += int(row.get("faults", 0))
                recoveries += int(row.get("recoveries", 0))
            except Exception as e:  # scenario failed; keep sweeping
                failures += 1
                row["ok"] = False
                row["error"] = f"{type(e).__name__}: {e}"
            row["seconds"] = round(time.monotonic() - ts, 3)
            print(json.dumps(row), flush=True)
    faulthandler.cancel_dump_traceback_later()
    # The one-line machine-readable summary CI/BENCH tooling diffs.
    print(json.dumps({
        "summary": True, "failures": failures, "skipped": skipped,
        "seed_base": args.seed_base, "seeds": args.seeds,
        "scenarios": len(scenarios) * args.seeds,
        "faults_injected": faults, "recoveries": recoveries,
        "seconds": round(time.monotonic() - t0, 3),
    }), flush=True)
    return 1 if failures or (args.no_skip and skipped) else 0


if __name__ == "__main__":
    sys.exit(main())
