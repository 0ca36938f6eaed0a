"""Elastic autoscaling (ISSUE 6), the end-to-end mesh runs: scale out
under backlog, dead-chip evacuation mid-stream, preemption checkpoint of
an autoscaled deployment, totals bit-identical to an uninterrupted run.
They need the Mosaic interpret mode and ride the chaos marker like the
other mesh tests. The policy, event and cache-probe tests, which need no
mesh, are in test_autoscaler.py.

Sizes (ISSUE 27): a depth-5 UTS forest of two roots a device, 368 nodes,
on which the storm walks its whole script at slice_rounds=4 (scale_out,
evacuate, two holds, scale_in, finish); no assertion reads the tree's size.
"""

import os

import numpy as np
import pytest
from conftest import uts_mesh_builders, uts_mesh_rk

import hclib_tpu as hc
from hclib_tpu.device.tracebuf import TR_SCALE, records_of
from hclib_tpu.runtime import progcache, resilience

DEPTH = 5


def _uts_kernel_factory(dead_on_4=None, depth=DEPTH):
    def make_kernel(ndev):
        plan = None
        if dead_on_4 is not None and ndev == 4:
            plan = hc.DeviceFaultPlan(
                seed=0, dead_device=dead_on_4, dead_round=2,
                heartbeat_timeout=2,
            )
        return uts_mesh_rk(ndev, depth, fault_plan=plan, seed=19)

    return make_kernel


def _backlog_policy():
    return hc.AutoscalerPolicy(min_devices=1, max_devices=4,
                               scale_out_backlog=4.0, scale_in_backlog=1.0,
                               hysteresis=1, cooldown=1)


@pytest.fixture(scope="module")
def uts_mesh_ref():
    """(summed ivalues, executed) of the uninterrupted fault-free run of
    ``uts_mesh_builders(2, 2)``'s four roots - the reference the storm and the
    preemption test compare against, run once, on ONE device: the totals
    do not depend on the mesh, and a 2-device interpreter run costs four
    times as much."""
    iv, _, info = _uts_kernel_factory()(1).run(
        uts_mesh_builders(1, 4), quantum=8, max_rounds=1 << 14,
    )
    assert info["pending"] == 0
    return int(np.asarray(iv)[:, 0].sum()), info["executed"]


@pytest.mark.chaos
def test_autoscale_storm_evacuates_dead_chip_totals_exact(uts_mesh_ref):
    """ACCEPTANCE (the storm): an autoscaled UTS mesh scales OUT under
    seeded backlog, the dead chip on the 4-device mesh is quarantined
    and EVACUATED mid-stream, the idle tail scales IN - >= 3 typed
    ScaleEvents including the evacuation - and the final totals are
    bit-identical to an uninterrupted fault-free run (zero task loss)."""
    total, executed = uts_mesh_ref
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(
        _uts_kernel_factory(dead_on_4=3), _backlog_policy(),
        slice_rounds=4, metrics=reg,
    )
    iv, _, info = asc.run(uts_mesh_builders(2, 2), quantum=8)
    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 0].sum()) == total
    assert info["executed"] == executed
    kinds = [e["kind"] for e in info["scale_events"]]
    assert len(info["scale_events"]) >= 3, kinds
    assert "evacuate" in kinds, info["scale_events"]
    ev = next(e for e in info["scale_events"] if e["kind"] == "evacuate")
    assert ev["from_ndev"] == 4 and ev["to_ndev"] == 2
    assert ev["resize_latency_s"] is not None
    snap = reg.snapshot()["metrics"]
    assert snap["autoscale.evacuate.count"] >= 1.0
    recs = records_of(asc.trace_info(), TR_SCALE)
    assert len(recs) == len(info["scale_events"])


@pytest.mark.chaos
def test_autoscale_preempt_checkpoints_and_resumes(uts_mesh_ref, tmp_path):
    """Preemption of an autoscaled deployment: the notice lands between
    slices, the controller checkpoints (bundle on disk) and stops; a
    fresh Autoscaler continues from the bundle and the totals are
    exact."""
    total, executed = uts_mesh_ref
    make_kernel = _uts_kernel_factory()

    def stay_at_two():
        return hc.AutoscalerPolicy(min_devices=1, max_devices=2,
                                   scale_out_backlog=1e9,
                                   scale_in_backlog=0.0, hysteresis=1)

    resilience.reset_preempt()
    asc = hc.Autoscaler(make_kernel, stay_at_two(), slice_rounds=4,
                        checkpoint_dir=str(tmp_path))
    try:
        resilience.fire_preempt("test preemption")
        iv, _, info = asc.run(uts_mesh_builders(2, 2), quantum=2)
    finally:
        resilience.reset_preempt()
    assert info.get("preempted") is True
    assert info["pending"] > 0  # genuinely mid-graph
    assert os.path.isdir(info["bundle_path"])
    assert [e["kind"] for e in info["scale_events"]][-1] == "checkpoint"

    asc2 = hc.Autoscaler(make_kernel, stay_at_two(), slice_rounds=1 << 12)
    iv2, _, info2 = asc2.run(resume_bundle=info["bundle_path"],
                             quantum=8)
    assert info2["pending"] == 0
    assert int(np.asarray(iv2)[:, 0].sum()) == total
    assert info2["executed"] == executed


@pytest.mark.chaos
def test_autoscale_resizes_with_both_shapes_warm_hit_cache():
    """ACCEPTANCE (ISSUE 18): with both mesh shapes pre-warmed by
    content-identical kernels, every controller resize reports
    cache_hit=True and the whole autoscaled run performs ZERO new
    trace/lower work (the process-wide miss counter does not move)."""
    depth = DEPTH - 1  # 199 nodes: one scale-out is all this test needs
    make_kernel = _uts_kernel_factory(depth=depth)
    progcache.reset()
    try:
        # Pre-warm BOTH shapes with fresh instances (their private jit
        # tables die with them; only the process cache carries over).
        # The program is keyed by (quantum, max_rounds, hops), not by the
        # graph, so a forest of leaves warms it in one round.
        for ndev in (2, 4):
            make_kernel(ndev).run(
                uts_mesh_builders(ndev, 2, depth), quantum=8,
                max_rounds=1 << 14,
            )
        warm = progcache.cache_stats()
        assert warm["misses"] >= 2 and warm["entries"] >= 2

        asc = hc.Autoscaler(make_kernel, _backlog_policy(), slice_rounds=4)
        iv, _, info = asc.run(uts_mesh_builders(2, 2), quantum=8)
        assert info["pending"] == 0
        resizes = [
            e for e in info["scale_events"]
            if e["from_ndev"] != e["to_ndev"]
        ]
        assert resizes, info["scale_events"]
        assert all(e["cache_hit"] is True for e in resizes), resizes
        # Zero rebuilds anywhere in the run: every slice's program came
        # from the registry (hits moved, misses did not).
        after = progcache.cache_stats()
        assert after["misses"] == warm["misses"]
        assert after["hits"] > warm["hits"]
    finally:
        progcache.reset()
