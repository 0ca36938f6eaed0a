"""Device-level fault tolerance (ISSUE 2): in-quantum abort propagation,
seeded ICI chaos (DeviceFaultPlan - dropped/duplicated steal credits,
delayed transfers, dead chip), credit-timeout regeneration, heartbeat
detection + quarantine + task re-homing, and the host-side plumbing
(abort-on-cancel hooks, locality-graph quarantine).

Every mesh test is seeded and asserts byte-for-byte reproducibility of the
fault trace, matching the host FaultPlan's determinism contract.
"""

import threading
import time

import pytest
from conftest import bump_mk, skewed_builders as _skewed

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.runtime.resilience import (
    CancelledError,
    CancelScope,
    DeviceFaultPlan,
    StallError,
)

pytestmark = pytest.mark.chaos

BUMP = 0


def _bump_mk(capacity=128):
    return bump_mk(capacity, 1024)


def _mesh_rk(ndev, plan=None, capacity=192, window=4, **kw):
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    return ResidentKernel(
        _bump_mk(capacity), cpu_mesh(ndev, axis_name="q"),
        migratable_fns=[BUMP], window=window, fault_plan=plan, **kw,
    )


# ------------------------------------------------- streaming abort (host)


def test_streaming_abort_mid_stream_closes_ring_and_raises():
    """Satellite: abort() while run_stream is live. The ring must close
    (concurrent producers fail fast with the reason), run_stream must
    raise CancelledError per its docstring, and stats_dict must surface
    the abort latency measured through the in-kernel abort word."""
    sm = StreamingMegakernel(_bump_mk(512), ring_capacity=512)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1])
    closed_msgs = []

    def feeder():
        try:
            while True:
                sm.inject(BUMP, args=[1])
                time.sleep(0.002)
        except RuntimeError as e:
            closed_msgs.append(str(e))

    def aborter():
        time.sleep(0.25)
        sm.abort("operator abort")

    tf = threading.Thread(target=feeder)
    ta = threading.Thread(target=aborter)
    tf.start()
    ta.start()
    try:
        with pytest.raises(CancelledError, match="operator abort"):
            sm.run_stream(b, quantum=64, deadline_s=120.0)
    finally:
        ta.join()
        tf.join()
    assert closed_msgs and "operator abort" in closed_msgs[0]
    st = sm.stats_dict()
    assert st["aborts"] == 1
    assert st["abort_reason"] == "operator abort"
    # The kernel observed the ctl abort word inside its round loop.
    assert st["abort_observed_round"] is not None
    assert st["abort_observed_round"] >= 0
    assert st["abort_latency_s"] is not None and st["abort_latency_s"] < 60
    assert st["abort_drain_executed"] is not None
    # Closed for good: even direct injects fail now.
    with pytest.raises(RuntimeError, match="operator abort"):
        sm.inject(BUMP, args=[1])


def test_streaming_abort_on_cancel_scope():
    """Root-finish-style cancellation stops a RUNNING stream: cancelling
    the bound CancelScope fires the registered abort hook, the abort word
    lands in the kernel's round loop, and run_stream raises
    CancelledError instead of draining the open stream forever."""
    from hclib_tpu.modules.tpu import abort_on_cancel

    sm = StreamingMegakernel(_bump_mk(), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1])
    scope = CancelScope()

    def canceller():
        time.sleep(0.2)
        scope.cancel("watchdog escalated")

    t = threading.Thread(target=canceller)
    t.start()
    try:
        with abort_on_cancel(sm, scope=scope):
            with pytest.raises(CancelledError, match="watchdog escalated"):
                sm.run_stream(b, quantum=16, deadline_s=120.0)
    finally:
        t.join()
    assert sm.stats_dict()["aborts"] == 1


def test_abort_on_cancel_replays_already_cancelled_scope():
    """A scope cancelled BEFORE the hook registers must still abort the
    stream (register-then-replay closes the check/register race)."""
    from hclib_tpu.modules.tpu import abort_on_cancel

    sm = StreamingMegakernel(_bump_mk(), ring_capacity=8)
    scope = CancelScope()
    scope.cancel("already dead")
    with abort_on_cancel(sm, scope=scope):
        pass
    with pytest.raises(RuntimeError, match="already dead"):
        sm.inject(BUMP)


def test_abort_hook_unregisters_after_stream():
    """A finished stream's hook must not linger: cancelling a scope later
    must not abort an unrelated fresh stream."""
    from hclib_tpu.runtime import resilience

    sm = StreamingMegakernel(_bump_mk(), ring_capacity=8)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[5])
    scope = CancelScope()
    sm.close()
    iv, info = sm.run_stream(b, cancel_scope=scope)
    assert int(iv[0]) == 5
    n_before = len(resilience._abort_hooks)
    scope.cancel("late cancel")  # must be a no-op for the closed stream
    assert len(resilience._abort_hooks) == n_before
    assert sm.stats_dict()["aborts"] == 0


# --------------------------------------------------- DeviceFaultPlan (host)


def test_device_fault_plan_validation_and_env(monkeypatch):
    with pytest.raises(ValueError):
        DeviceFaultPlan(drop_credit_rate=1.5)
    with pytest.raises(ValueError):
        DeviceFaultPlan(credit_timeout=-1)
    monkeypatch.setenv("HCLIB_TPU_CREDIT_TIMEOUT", "7")
    monkeypatch.setenv("HCLIB_TPU_HEARTBEAT_TIMEOUT", "9")
    p = DeviceFaultPlan(drop_credit_rate=0.25)
    assert p.credit_timeout == 7
    assert p.heartbeat_timeout == 9
    assert p.enabled() and p.drops_credits() and not p.dups_credits()
    assert not DeviceFaultPlan().enabled()
    assert DeviceFaultPlan(dead_device=2).enabled()
    assert DeviceFaultPlan(dup_credit_at=[(1, 0, 1)]).dups_credits()


def test_plan_requires_steal_and_valid_dead_device():
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    with pytest.raises(ValueError, match="steal"):
        ResidentKernel(
            _bump_mk(), cpu_mesh(2, axis_name="q"), steal=False,
            fault_plan=DeviceFaultPlan(drop_credit_rate=0.1),
        )
    with pytest.raises(ValueError, match="dead_device"):
        ResidentKernel(
            _bump_mk(32), cpu_mesh(2, axis_name="q"),
            migratable_fns=[BUMP],
            fault_plan=DeviceFaultPlan(dead_device=5),
        )


def test_quarantine_locales_removes_dead_chip_paths():
    from hclib_tpu.parallel.mesh import (
        cpu_mesh, mesh_locality_graph, quarantine_locales,
    )

    g = mesh_locality_graph(cpu_mesh(4), nworkers=4)
    removed = quarantine_locales(g, [2])
    assert removed > 0
    dead = {
        l.id for l in g.locales
        if l.type == "tpu" and l.metadata.get("ordinal") == 2
    }
    for w in range(4):
        assert not (dead & set(g.pop_paths[w]))
        assert not (dead & set(g.steal_paths[w]))
        assert g.pop_paths[w] and g.steal_paths[w]  # paths stay usable
    assert any(l.is_special("DEAD") for l in g.locales)
    assert quarantine_locales(g, [2]) == 0  # idempotent


# ------------------------------------------------ mesh kernels (interpret)


def test_abort_word_stops_resident_mesh_mid_run():
    """The host abort word stops a running 4-device mesh within one round
    (folded into the termination collective -> lockstep exit), leaving
    pending work abandoned instead of drained - and no hang, no raise."""
    ndev, ntasks = 4, 64
    rk = _mesh_rk(ndev)
    iv, _, info = rk.run(
        _skewed(ndev, ntasks), quantum=2, abort=True, max_rounds=512,
    )
    assert info["aborted"]
    assert info["rounds"] <= 2  # bounded abort latency, surfaced below
    assert info["pending"] > 0
    assert all(f["abort_round"] == 0 for f in info["fault_stats"])


def test_dead_chip_rehomes_and_survivors_drain_workload():
    """ACCEPTANCE: seeded dead chip on a 4-device interpret mesh. Every
    device holds work; device 3's scheduler dies at round 2 (wire stays
    up). The surviving 3 chips must complete the WHOLE workload - the
    dead chip's queue re-homed, totals conserved - instead of hanging;
    survivors must detect the frozen heartbeat and quarantine the chip;
    and the entire run must be byte-for-byte reproducible from the seed.
    (No assertion reads the mesh size; 8 devices cost four times the
    interpreter time.)"""
    ndev, per, dead = 4, 6, 3
    plan = DeviceFaultPlan(
        seed=7, dead_device=dead, dead_round=2, heartbeat_timeout=2,
    )
    rk = _mesh_rk(ndev, plan, capacity=256, window=4)

    def build():
        builders = [TaskGraphBuilder() for _ in range(ndev)]
        v = 0
        for d in range(ndev):
            for _ in range(per):
                v += 1
                builders[d].add(BUMP, args=[v])
        return builders, v * (v + 1) // 2

    builders, total = build()
    iv, _, info = rk.run(builders, quantum=2, max_rounds=4096)
    assert info["pending"] == 0          # drained, not hung
    assert info["executed"] == ndev * per  # totals conserved
    assert int(iv[:, 0].sum()) == total    # every task's effect landed once
    fs = info["fault_stats"]
    assert fs[dead]["rehomed_rows"] > 0    # the dead queue moved out
    assert any(
        dead in f["quarantined"] for d, f in enumerate(fs) if d != dead
    ), fs
    detect = [
        f["dead_detected_round"] for d, f in enumerate(fs)
        if d != dead and f["dead_detected_round"] >= 0
    ]
    assert detect and min(detect) >= 2     # detected only after the death
    per_dev = info["per_device_counts"][:, 5]
    assert per_dev[dead] <= 2 * 2          # 2 alive rounds x quantum 2
    # Determinism: same seed, same mesh -> identical fault trace and
    # identical final task counts, twice.
    builders2, _ = build()
    iv2, _, info2 = rk.run(builders2, quantum=2, max_rounds=4096)
    assert info2["fault_stats"] == fs
    assert (info2["per_device_counts"] == info["per_device_counts"]).all()
    assert (iv2 == iv).all()


def _skewed_run_exact(plan, ndev=2, ntasks=40):
    """The skewed bump load on a 2-device mesh under ``plan``, held to
    exact totals; -> (rk, iv, info)."""
    rk = _mesh_rk(ndev, plan, capacity=128, window=4)
    iv, _, info = rk.run(_skewed(ndev, ntasks), quantum=2, max_rounds=4096)
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    return rk, iv, info


def test_dropped_credit_regenerates_and_run_is_exact():
    """ACCEPTANCE (credit half): a dropped steal credit stalls its channel
    for credit_timeout rounds, then the writer regenerates it; the
    workload completes exactly and both endpoints' traces agree."""
    rk, iv, info = _skewed_run_exact(DeviceFaultPlan(
        seed=3, drop_credit_at=[(1, 0, 1)], credit_timeout=2,
    ))
    fs = info["fault_stats"]
    assert fs[1]["credits_dropped"] == 1       # granter side of the fault
    assert fs[0]["credits_regenerated"] == 1   # starved writer recovered
    iv2, _, info2 = rk.run(_skewed(2, 40), quantum=2, max_rounds=4096)
    assert info2["fault_stats"] == fs          # reproducible from the seed
    assert (iv2 == iv).all()


def test_dropped_credit_without_regeneration_raises_stallerror():
    """credit_timeout=0 disables regeneration: the mesh must exit in
    lockstep and raise StallError NAMING the starved channel - never
    hang on the dead semaphore."""
    plan = DeviceFaultPlan(
        seed=3, drop_credit_at=[(1, 0, 1)], credit_timeout=0,
    )
    rk = _mesh_rk(2, plan, capacity=128, window=4)
    with pytest.raises(StallError, match="hop-0 .*granter device 1"):
        rk.run(_skewed(2, 40), quantum=2, max_rounds=4096)


def test_duplicated_credit_tolerated_exactly():
    """A duplicated credit must not corrupt flow control: the surplus is
    absorbed and the exit drain still balances every semaphore."""
    _, _, info = _skewed_run_exact(DeviceFaultPlan(
        seed=5, dup_credit_at=[(1, 0, 1)], credit_timeout=2,
    ))
    assert info["fault_stats"][1]["credits_duplicated"] == 1


def test_delayed_xfers_only_slow_the_run():
    """Seeded transfer delays reorder migration but never lose work."""
    _, _, info = _skewed_run_exact(
        DeviceFaultPlan(seed=11, delay_xfer_rate=0.5, credit_timeout=2)
    )
    assert sum(f["xfers_delayed"] for f in info["fault_stats"]) > 0
