"""Elastic autoscaling (ISSUE 6): the metrics-driven quiesce -> reshard
-> resume control loop, the host-only half.

The policy half is PURE (observation in, decision out) and is tested
headless - hysteresis, cooldown, the no-flap guarantee, and the
evacuation fast path need no mesh and no Mosaic. The control loop's
telemetry (typed ScaleEvents -> MetricsRegistry + TR_SCALE host ring ->
Perfetto) and the program-cache probe are host-only too. The end-to-end
mesh runs are in test_autoscaler_mesh.py.
"""

import threading

import numpy as np
import pytest

import hclib_tpu as hc
from hclib_tpu.device.tracebuf import TR_SCALE, records_of


# ---------------------------------------------------------- policy, pure


def _policy(**kw):
    base = dict(min_devices=1, max_devices=8, scale_out_backlog=16.0,
                scale_in_backlog=2.0, hysteresis=2, cooldown=2)
    base.update(kw)
    return hc.AutoscalerPolicy(**base)


def test_policy_hysteresis_gates_scale_out():
    p = _policy()
    hot = hc.Observation(2, [40, 40])
    assert p.decide(hot)[1] == "hold"  # streak 1/2
    target, kind, reason = p.decide(hot)
    assert (target, kind) == (4, "scale_out")
    assert "2 slices" in reason


def test_policy_one_spike_never_resizes():
    """An alternating hot/cold load (the classic flap inducer) never
    builds a streak, so the mesh size never moves."""
    p = _policy()
    for _ in range(6):
        assert p.decide(hc.Observation(4, [50] * 4))[1] == "hold"
        assert p.decide(hc.Observation(4, [0] * 4))[1] == "hold"


def test_policy_cooldown_blocks_back_to_back_resizes():
    p = _policy(hysteresis=1, cooldown=2)
    assert p.decide(hc.Observation(2, [40, 40]))[1] == "scale_out"
    # Cooldown: two slices hold even under sustained pressure...
    assert p.decide(hc.Observation(4, [40] * 4))[1] == "hold"
    assert p.decide(hc.Observation(4, [40] * 4))[1] == "hold"
    # ...then the streak machinery re-engages.
    assert p.decide(hc.Observation(4, [40] * 4))[1] == "scale_out"


def test_policy_scale_in_waits_for_empty_inject_backlog():
    p = _policy(hysteresis=1, cooldown=0)
    idle_but_queued = hc.Observation(4, [0] * 4, inject_backlog=9)
    assert p.decide(idle_but_queued)[1] == "hold"
    target, kind, _ = p.decide(hc.Observation(4, [0] * 4))
    assert (target, kind) == (2, "scale_in")


def test_policy_bounds_respected():
    p = _policy(min_devices=2, max_devices=4, hysteresis=1, cooldown=0)
    assert p.decide(hc.Observation(4, [99] * 4))[1] == "hold"  # at max
    assert p.decide(hc.Observation(2, [0, 0]))[1] == "hold"  # at min
    with pytest.raises(ValueError, match="power of two"):
        hc.AutoscalerPolicy(min_devices=3)
    with pytest.raises(ValueError, match="oscillate|must be <"):
        hc.AutoscalerPolicy(scale_out_backlog=4.0, scale_in_backlog=8.0)


def test_policy_evacuation_bypasses_gates():
    """A quarantined chip reshard-around fires at the FIRST observation
    naming it - during cooldown, with zero streak - and drops to the
    largest pof2 that fits the survivors."""
    p = _policy(hysteresis=2, cooldown=3)
    p.decide(hc.Observation(8, [40] * 8))  # prime a streak + no resize
    target, kind, reason = p.decide(
        hc.Observation(8, [1] * 8, quarantined=[5])
    )
    assert (target, kind) == (4, "evacuate")
    assert "quarantined" in reason
    # At min_devices there is nowhere to evacuate TO: hold, and say why.
    p2 = _policy(min_devices=1)
    target, kind, reason = p2.decide(
        hc.Observation(1, [5], quarantined=[0])
    )
    assert (target, kind) == (1, "hold") and "watchdog" in reason


def test_observation_from_info_reads_counts_and_quarantine():
    from hclib_tpu.device.megakernel import C_HEAD, C_TAIL

    counts = np.zeros((2, 8), np.int32)
    counts[0, C_TAIL] = 7
    counts[1, C_HEAD], counts[1, C_TAIL] = 2, 5
    info = {
        "per_device_counts": counts,
        "pending": 11,
        "executed": 30,
        "fault_stats": [
            {"quarantined": [1]}, {"quarantined": []},
        ],
        "inject_ctl": np.array(
            [[4, 1, 1, 0, 0, 0, 0, 0], [2, 1, 2, 0, 0, 0, 0, 0]],
            np.int32,
        ),
    }
    obs = hc.Observation.from_info(2, info, executed_before=10,
                                   slice_s=0.5)
    assert obs.backlog == [7, 3]
    assert obs.pending == 11
    assert obs.executed_delta == 20
    assert obs.inject_backlog == 3  # (4-1) + (2-2)
    assert obs.quarantined == (1,)
    assert obs.backlog_per_device == (7 + 3 + 3) / 2


# ------------------------------------------------- events and telemetry


def test_scale_events_metrics_and_trace_ring():
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, _policy(), metrics=reg)
    asc._event(hc.ScaleEvent("scale_out", 0, 2, 4, "r1"))
    asc._event(hc.ScaleEvent("hold", 1, 4, 4, "r2"))
    asc._event(hc.ScaleEvent("evacuate", 2, 4, 2, "r3",
                             resize_latency_s=0.01))
    snap = reg.snapshot()["metrics"]
    assert snap["autoscale.scale_out.count"] == 1.0
    assert snap["autoscale.evacuate.last.from_ndev"] == 4.0
    assert snap["autoscale.state.events"] == 3.0
    assert snap["autoscale.state.resizes"] == 2.0
    tr = asc.trace_info()
    recs = records_of(tr, TR_SCALE)
    assert len(recs) == 3
    assert int(recs[0][2]) == (2 << 8) | 4
    assert [int(r[1]) for r in recs] == [0, 1, 2]  # slice timebase
    # The Perfetto exporter renders the host ring (no dump needed).
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import timeline

    doc = timeline.export_perfetto("", traces=[tr])
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(n.startswith("scale out 2→4") for n in names), names
    assert any(n.startswith("evacuate 4→2") for n in names), names


def test_autoscaler_close_unregisters_gauge():
    """A retired controller must not stay reachable through the
    registry: close() removes the live gauge source."""
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, _policy(), metrics=reg)
    assert "autoscale.state.ndev" in reg.snapshot()["metrics"]
    asc.close()
    assert "autoscale.state.ndev" not in reg.snapshot()["metrics"]


def test_scale_event_validation_and_shape():
    with pytest.raises(ValueError, match="kind"):
        hc.ScaleEvent("embiggen", 0, 1, 2, "no")
    ev = hc.ScaleEvent("scale_in", 5, 4, 2, "idle", backlog=3,
                       pending=7, executed=100, resize_latency_s=0.25)
    d = ev.as_dict()
    assert d["kind"] == "scale_in" and d["resize_latency_s"] == 0.25
    assert ev.resized and not hc.ScaleEvent("hold", 0, 2, 2, "x").resized


# ------------------------------------------------------------- off-path


def test_autoscaler_off_path_is_inert():
    """ACCEPTANCE: the autoscaler is pure host-side composition - no
    controller thread is spawned by construction or by policy decisions,
    a non-checkpoint kernel factory is refused up front (never half-run),
    and a Megakernel run outside the autoscaler carries no autoscale
    state (byte-identical PR 5 behavior - the checkpoint-off device path
    is covered by test_checkpoint's off-path bit-identity test)."""
    from hclib_tpu.device.workloads import device_uts_mk

    before = set(threading.enumerate())
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, _policy(), metrics=reg)
    for _ in range(4):
        asc.policy.decide(hc.Observation(2, [1, 1]))
    assert set(threading.enumerate()) == before  # no controller thread

    class FakeRK:
        class mk:
            checkpoint = False

        ndev = 2

    with pytest.raises(ValueError, match="checkpoint=True"):
        hc.Autoscaler(lambda n: FakeRK(), _policy())._kernel_for(2)
    with pytest.raises(ValueError, match="exactly one"):
        asc.run()

    n1, i1 = device_uts_mk(max_depth=6, interpret=True)
    assert "scale_events" not in i1  # plain runs carry no autoscale state
    n2, i2 = device_uts_mk(max_depth=6, interpret=True)
    assert n1 == n2 and i1["executed"] == i2["executed"]


# ------------------- tenant/deadline-aware policy (ISSUE 13), pure


def _pressure(expired, budget=20.0, in_flight=0.0, backlog=0.0):
    return {"expired": float(expired), "budget": float(budget),
            "in_flight": float(in_flight), "ring_residue": in_flight,
            "backlog": float(backlog),
            "pressure": min(1.0, expired / budget) if budget else 0.0}


def test_policy_deadline_pressure_beats_cooldown_and_watchdog():
    """ACCEPTANCE: a tenant draining >= tenant_pressure of its deadline
    budget in ONE slice triggers an immediate typed ``deadline_out``
    scale-out - during cooldown, with zero streak (before the watchdog
    rung: budget exhaustion would cancel the lane). The drain is a
    DELTA: a resumed deployment's cumulative expiry count is not a
    fresh storm, and a stable count never re-fires."""
    p = _policy(hysteresis=3, cooldown=3, tenant_pressure=0.25)
    p._cooling = 3  # mid-cooldown: the pressure path must not wait
    # First observation: cumulative expired=10 is BASELINE, not drain.
    base = hc.Observation(2, [1, 1], tenants={"t": _pressure(10)})
    assert p.decide(base)[1] == "hold"
    # 6 new expirations on a budget of 20 = 30% drained in one slice.
    target, kind, reason = p.decide(
        hc.Observation(2, [1, 1], tenants={"t": _pressure(16)})
    )
    assert (target, kind) == (4, "deadline_out"), (target, kind, reason)
    assert "watchdog" in reason and "'t'" in reason
    # Stable cumulative count after the resize: no re-fire (the resize
    # set a cooldown; and with zero drain there is no pressure at all;
    # backlog held in band so only the pressure path could resize).
    for _ in range(6):
        assert p.decide(
            hc.Observation(4, [5] * 4, tenants={"t": _pressure(16)})
        )[1] == "hold"
    # At max_devices the pressure path cannot help: it falls through
    # to the ordinary machinery (here: in-band hold), never a loop.
    p2 = _policy(max_devices=2, tenant_pressure=0.25)
    p2.decide(hc.Observation(2, [5, 5], tenants={"t": _pressure(0)}))
    assert p2.decide(
        hc.Observation(2, [5, 5], tenants={"t": _pressure(19)})
    )[1] == "hold"


def test_policy_delta_scale_out_below_level_threshold():
    """The live-delta arm: a backlog RISING by >= scale_out_delta per
    slice scales out after hysteresis even while the LEVEL is still
    under scale_out_backlog - the storm is caught while it builds."""
    p = _policy(hysteresis=2, cooldown=0, scale_out_delta=4.0)
    # Levels 2 -> 8 -> 14 per device: always far below the 16 level
    # threshold, but rising 6/slice with a flat executed rate.
    assert p.decide(hc.Observation(2, [2, 2], executed_delta=80,
                                   slice_s=1.0))[1] == "hold"
    assert p.decide(hc.Observation(2, [8, 8], executed_delta=80,
                                   slice_s=1.0))[1] == "hold"  # streak 1
    target, kind, reason = p.decide(
        hc.Observation(2, [14, 14], executed_delta=80, slice_s=1.0)
    )
    assert (target, kind) == (4, "scale_out"), (target, kind, reason)
    assert "rising" in reason
    # A rising backlog WITH a rising rate is ramp-up, not a storm.
    p2 = _policy(hysteresis=1, cooldown=0, scale_out_delta=4.0)
    p2.decide(hc.Observation(2, [2, 2], executed_delta=10, slice_s=1.0))
    assert p2.decide(
        hc.Observation(2, [8, 8], executed_delta=200, slice_s=1.0)
    )[1] == "hold"


def test_policy_strand_refusal_then_scale_in():
    """ACCEPTANCE: scale-in NEVER strands a tenant's in-flight quota or
    ring residue - the refusal is a typed ``strand_hold`` that keeps
    the streak armed, so the mesh shrinks at the first drained slice."""
    p = _policy(hysteresis=2, cooldown=0)
    idle_busy = hc.Observation(
        4, [0] * 4, tenants={"t": _pressure(0, in_flight=3)}
    )
    assert p.decide(idle_busy)[1] == "hold"          # streak 1/2
    for _ in range(3):                               # typed, repeated
        target, kind, reason = p.decide(idle_busy)
        assert (target, kind) == (4, "strand_hold"), (kind, reason)
        assert "'t'" in reason
    drained = hc.Observation(
        4, [0] * 4, tenants={"t": _pressure(0, in_flight=0)}
    )
    assert p.decide(drained)[:2] == (2, "scale_in")


def test_policy_no_flap_two_competing_tenants():
    """No-flap proof with two tenants trading small budget drains and
    an oscillating backlog: neither the pressure path (drains below
    threshold) nor the streak machinery (alternating hot/cold) ever
    resizes."""
    p = _policy(hysteresis=2, cooldown=2, tenant_pressure=0.5)
    exp_a = exp_b = 0.0
    for i in range(12):
        # Each slice one tenant expires 2 rows (10% of its budget) and
        # the backlog flips between busy and idle-with-residue.
        if i % 2:
            exp_a += 2
            obs = hc.Observation(4, [40] * 4, tenants={
                "a": _pressure(exp_a), "b": _pressure(exp_b),
            })
        else:
            exp_b += 2
            obs = hc.Observation(4, [0] * 4, tenants={
                "a": _pressure(exp_a, in_flight=1),
                "b": _pressure(exp_b),
            })
        target, kind, _ = p.decide(obs)
        assert target == 4, (i, kind)
        assert kind in ("hold", "strand_hold"), (i, kind)


def test_scale_event_new_kinds_ride_trace_and_metrics():
    """The new typed kinds (deadline_out / strand_hold) ride TR_SCALE,
    the metrics registry, and the Perfetto exporter - one SC_NAMES
    edit, no drifting copies."""
    from hclib_tpu.device.tracebuf import (
        SC_DEADLINE_OUT, SC_STRAND_HOLD,
    )

    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, _policy(), metrics=reg)
    asc._event(hc.ScaleEvent("deadline_out", 0, 2, 4, "pressure"))
    asc._event(hc.ScaleEvent("strand_hold", 1, 4, 4, "residue"))
    snap = reg.snapshot()["metrics"]
    assert snap["autoscale.deadline_out.count"] == 1.0
    assert snap["autoscale.strand_hold.count"] == 1.0
    recs = records_of(asc.trace_info(), TR_SCALE)
    assert [int(r[3]) for r in recs] == [SC_DEADLINE_OUT, SC_STRAND_HOLD]
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import timeline

    doc = timeline.export_perfetto("", traces=[asc.trace_info()])
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(n.startswith("deadline out 2→4") for n in names), names
    assert any(n.startswith("strand hold") for n in names), names
    with pytest.raises(ValueError, match="kind"):
        hc.ScaleEvent("strand", 0, 1, 1, "typo")


# ----------------------------------- program cache across resizes (ISSUE 18)


def test_scale_event_carries_cache_hit():
    """cache_hit rides the typed event: set on resizes, None elsewhere,
    present in as_dict (the flattener drops None, so non-resize events
    cost no gauge)."""
    ev = hc.ScaleEvent("scale_in", 5, 4, 2, "idle",
                       resize_latency_s=0.1, cache_hit=True)
    assert ev.as_dict()["cache_hit"] is True
    assert hc.ScaleEvent("hold", 0, 2, 2, "x").cache_hit is None


def test_program_cached_probe_reads_process_cache():
    """ResidentKernel.program_cached: False cold; True on a DIFFERENT
    content-identical instance once the (mk, variant) program is in the
    process-wide registry; parameter changes miss. Host-only - the probe
    never builds."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh
    from hclib_tpu.runtime import progcache

    def rk():
        mk = make_uts_megakernel(seed=19, max_depth=4, interpret=True,
                                 checkpoint=True)
        return ResidentKernel(
            mk, cpu_mesh(2, axis_name="q"), migratable_fns=[UTS_NODE],
            window=4, homed=False,
        )

    progcache.reset()
    try:
        a = rk()
        assert a.program_cached(quantum=8) is False
        key = (8, 1 << 14, a._hop_bits(None))
        _, stats = progcache.shared_build(
            a.mk, a._cache_variant(key), object
        )
        assert stats["hit"] is False
        assert rk().program_cached(quantum=8) is True
        assert rk().program_cached(quantum=16) is False
    finally:
        progcache.reset()
