"""Batched same-kind dispatch tier: per-F_FN lane partitioning, batch
bodies, cross-round prefetch, tier counters, and the SW / Cholesky wirings
(batch-vs-scalar results must be bit-identical)."""

import numpy as np
import pytest
from jax.experimental import pallas as pl

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import BatchSpec, Megakernel
from hclib_tpu.runtime.resilience import StallError

DOUBLE, NEG = 0, 1


def _scalar_double(ctx):
    ctx.set_out(ctx.arg(0) * 2)


def _scalar_neg(ctx):
    ctx.set_out(-ctx.arg(0))


def _batch_double(ctx):
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            ctx.set_out(s, ctx.arg(s, 0) * 2)


def _toy_mk(width=4, capacity=64):
    return Megakernel(
        kernels=[("double", _scalar_double), ("neg", _scalar_neg)],
        route={"double": BatchSpec(_batch_double, width=width)},
        capacity=capacity,
        num_values=64,
        interpret=True,
    )


def _toy_graph():
    """6 independent doubles; 3 negs each gated on one double; a second
    wave of 5 doubles gated on all negs - same-kind groups separated by a
    foreign kind, so routing, batching, and scalar dispatch all engage."""
    b = TaskGraphBuilder()
    first = [b.add(DOUBLE, args=[i], out=i) for i in range(6)]
    negs = [
        b.add(NEG, args=[10 + i], out=6 + i, deps=[first[i]])
        for i in range(3)
    ]
    b2 = [b.add(DOUBLE, args=[100 + i], out=9 + i, deps=negs) for i in range(5)]
    del b2
    return b


def test_lane_partitioning_results_and_counters():
    mk = _toy_mk()
    iv, _, info = mk.run(_toy_graph())
    assert list(iv[:6]) == [0, 2, 4, 6, 8, 10]
    assert list(iv[6:9]) == [-10, -11, -12]
    assert list(iv[9:14]) == [200, 202, 204, 206, 208]
    t = info["tiers"]
    # Every 'double' went through the batch tier, every 'neg' scalar.
    assert t["batch_tasks"] == 11
    assert t["routed"] == 11
    assert t["direct"] == 0  # host-built rows: every one through the ring
    assert t["scalar_tasks"] == 3
    assert t["spilled"] == 0
    assert 0 < t["batch_occupancy"] <= 1.0
    assert t["batch_rounds"] * t["batch_width"] >= t["batch_tasks"]
    assert info["executed"] == 14
    # stats_dict() mirrors the last run's info for harness consumers.
    assert mk.stats_dict()["tiers"]["batch_tasks"] == 11


def test_batch_width_one_still_batches():
    mk = _toy_mk(width=1)
    iv, _, info = mk.run(_toy_graph())
    assert list(iv[:6]) == [0, 2, 4, 6, 8, 10]
    t = info["tiers"]
    assert t["batch_tasks"] == 11
    assert t["batch_rounds"] == 11
    assert t["full_rounds"] == 11


def test_fuel_exhaustion_spills_lanes_and_stalls_cleanly():
    """Fuel running out mid-lane must spill unrun entries back to the ring
    and surface as a StallError with the right pending count - tasks are
    never silently lost in a lane."""
    mk = _toy_mk(width=2)
    b = TaskGraphBuilder()
    for i in range(10):
        b.add(DOUBLE, args=[i], out=i)
    with pytest.raises(StallError) as ei:
        mk.run(b, fuel=3)
    # 2 batch rounds of 2 ran (the second crosses the fuel bound); the
    # other 6 stay pending.
    assert ei.value.stats["pending"] == 6
    assert ei.value.stats["executed"] == 4


def test_batchspec_validation():
    with pytest.raises(ValueError, match="drain"):
        BatchSpec(_batch_double, width=2, prefetch=True)
    with pytest.raises(ValueError, match="width"):
        BatchSpec(_batch_double, width=0)
    with pytest.raises(ValueError, match="route"):
        Megakernel(
            kernels=[("a", _scalar_double)],
            route={"b": BatchSpec(_batch_double)},
            interpret=True,
        )


def test_sw_batched_tier_matches_scalar_tile_engine():
    """Per-tile SW on the 3-neighbor DAG, grouped by the scheduler's lane:
    H and score bit-identical to the scalar tile engine; executed counts
    tiles; tier counters see every tile."""
    from hclib_tpu.device.smithwaterman import device_sw, device_sw_batched
    from hclib_tpu.models.smithwaterman import random_seq

    a, b = random_seq(256, 3), random_seq(384, 4)
    score_s, h_s, info_s = device_sw(a, b, interpret=True)
    score_b, h_b, info_b = device_sw_batched(a, b, interpret=True)
    assert np.array_equal(h_b, h_s)
    assert score_b == score_s
    assert info_b["executed"] == info_s["executed"] == 6
    t = info_b["tiers"]
    assert t["batch_tasks"] == 6
    assert t["scalar_tasks"] == 0


def test_sw_wave_chunked_prefetch_engages_and_stays_exact():
    """Anti-diagonals wider than one batch (chunk=1, width=2 on a 4x4 tile
    grid: mid-waves queue 3-4 descriptors): the cross-round double-
    buffered prefetch must engage (hits > 0) and the full H matrix must
    stay bit-identical to the scalar tile engine - prefetched operands are
    the same bytes the on-demand path loads."""
    from hclib_tpu.device.smithwaterman import (
        build_sw_wave_graph,
        device_sw,
        make_sw_wave_megakernel,
        sw_wave_buffers,
    )
    from hclib_tpu.models.smithwaterman import random_seq

    a, b = random_seq(512, 5), random_seq(512, 6)
    _, h_s, _ = device_sw(a, b, interpret=True)
    mk = make_sw_wave_megakernel(4, 4, interpret=True, chunk=1, width=2)
    data = sw_wave_buffers(a, b)
    data["htiles"] = np.zeros((4, 4, 128, 128), np.int32)
    iv, out, info = mk.run(build_sw_wave_graph(4, 4, chunk=1), data=data)
    h_w = np.asarray(out["htiles"]).swapaxes(1, 2).reshape(512, 512)
    assert np.array_equal(h_w, h_s)
    assert int(iv[0]) == int(h_s.max())
    t = info["tiers"]
    assert t["prefetch_hits"] > 0
    assert t["batch_tasks"] == mk.stats_dict()["tiers"]["batch_tasks"]


def test_cholesky_batched_updrow_bit_identical():
    """The batched trailing-update tier (resident L-split pipelined across
    slots) must produce the bit-identical factor of the scalar dispatch."""
    from hclib_tpu.device.cholesky import (
        device_cholesky,
        make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    a = make_spd(512).astype(np.float32)  # nt=4: 6 updrow tasks
    L_b, info_b = device_cholesky(a, interpret=True)
    mk_s = make_cholesky_megakernel(4, interpret=True, batch_updrow=False)
    L_s, info_s = device_cholesky(a, interpret=True, mk=mk_s)
    assert np.array_equal(L_b, L_s)
    assert info_b["executed"] == info_s["executed"]
    rel = np.max(np.abs(L_b @ L_b.T - a)) / np.max(np.abs(a))
    assert rel < 1e-5
    t = info_b["tiers"]
    assert t["batch_tasks"] == 6  # every updrow batched
    assert t["scalar_tasks"] == 4 + 3  # potrf + trsmcol stay scalar
    assert "tiers" not in info_s


# --------------------------------------------- mesh batch dispatch (ISSUE 7)


def _forest_run(batch_width, ndev=4, roots=10, n=8, quantum=16, window=8,
                capacity=1024):
    """Skewed fib forest (all roots on device 0) through the sharded steal
    runner, batch-routed when batch_width > 0."""
    from hclib_tpu.device.megakernel import VBLOCK
    from hclib_tpu.device.sharded import ShardedMegakernel
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = make_fib_megakernel(
        capacity=capacity, interpret=True,
        num_values=VBLOCK * capacity + max(64, roots),
        batch_width=batch_width or None,
    )
    smk = ShardedMegakernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[FIB]
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for r in range(roots):
        builders[0].add(FIB, args=[n], out=r)
    for b in builders:
        b.reserve_values(roots)
    iv, _, info = smk.run(
        builders, steal=True, quantum=quantum, window=window
    )
    return np.asarray(iv), info, roots, n


def test_mesh_forest_batch_bit_identical_to_scalar():
    """THE ISSUE 7 acceptance (sharded arm): the batch-routed forest-steal
    mesh computes bit-identical per-root results to the scalar mesh, with
    exact totals, nonzero batch rounds on every device that executed
    work, and tier counters reconciling with the executed count."""
    from hclib_tpu.models.fib import fib_seq, task_count

    iv_s, info_s, roots, n = _forest_run(0)
    iv_b, info_b, _, _ = _forest_run(8)
    # A migrated root writes its out slot on the thief's value buffer:
    # the per-root result is the column sum across the mesh, and it must
    # be bit-identical between the arms (placement may differ).
    per_root_s = iv_s[:, :roots].sum(axis=0)
    per_root_b = iv_b[:, :roots].sum(axis=0)
    assert np.array_equal(per_root_b, per_root_s)
    assert int(per_root_b.sum()) == roots * fib_seq(n)
    per_call = task_count(n)
    per_call += (per_call - 1) // 2
    assert info_b["executed"] == info_s["executed"] == roots * per_call
    assert "tiers" not in info_s
    tiers = info_b["tiers"]
    per_dev = np.asarray(info_b["per_device_counts"])[:, 5]  # C_EXECUTED
    batched = sum(t["batch_tasks"] for t in tiers)
    scalar = sum(t["scalar_tasks"] for t in tiers)
    assert batched + scalar == info_b["executed"]
    assert batched > 0
    for d, t in enumerate(tiers):
        if per_dev[d] > 0:
            # Every device that executed work fired same-kind batches:
            # the tier engaged mesh-wide, not just on the seed device.
            assert t["batch_rounds"] > 0, (d, t)


def test_mesh_lane_spill_at_steal_boundary():
    """A stolen row that was lane-resident on the victim: with a small
    quantum the victim's sched() exits every round with unrun lane
    entries, which spill to the ready ring's cold (head) end - exactly
    the window the steal exchange scans - so the forest still spreads
    and totals stay exact. The spilled counter proves rows crossed a
    steal boundary through a lane."""
    from hclib_tpu.models.fib import fib_seq

    iv, info, roots, n = _forest_run(8, roots=16, n=7, quantum=8)
    tiers = info["tiers"]
    per_dev = np.asarray(info["per_device_counts"])[:, 5]
    # The victim (seed device 0) spilled lane entries at steal
    # boundaries, and the load still spread beyond it.
    assert tiers[0]["spilled"] > 0, tiers[0]
    assert int((per_dev > 0).sum()) >= 2, per_dev
    assert int(iv[:, :roots].sum(dtype=np.int64)) == roots * fib_seq(n)
    assert info["pending"] == 0


def test_megakernel_quiesce_with_lanes_resumes_bit_identical():
    """Checkpoint with lanes active on the single-device scheduler: a
    quiesce cut spills lane-resident descriptors to the ready ring's
    cold end (C_HEAD walks negative), the exported state restages the
    wrapped window, and the resumed run completes bit-identically to the
    uninterrupted one."""
    from hclib_tpu.device.megakernel import C_HEAD, VBLOCK
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel
    from hclib_tpu.models.fib import fib_seq, task_count

    def mk_of():
        cap = 512
        return make_fib_megakernel(
            capacity=cap, interpret=True,
            num_values=VBLOCK * cap + 16,
            batch_width=4, checkpoint=True,
        )

    def builder():
        b = TaskGraphBuilder()
        b.add(FIB, args=[10], out=0)
        return b

    iv_f, _, info_f = mk_of().run(builder())
    assert int(iv_f[0]) == fib_seq(10)

    mk = mk_of()
    iv_q, _, info_q = mk.run(builder(), quiesce=40)
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    st = info_q["state"]
    if info_q["tiers"]["spilled"] > 0:
        # Lane spills insert at the ring's cold end: the head walks
        # below zero and stage() must widen its restage copy over the
        # wrapped window (asserted implicitly by the exact resume).
        assert int(st["counts"][C_HEAD]) < 0
    iv_r, _, info_r = mk.resume(st)
    assert info_r["pending"] == 0
    assert int(iv_r[0]) == fib_seq(10)
    t = task_count(10)
    assert info_r["executed"] == t + (t - 1) // 2 == info_f["executed"]


def test_vector_and_batch_tiers_coexist():
    """One megakernel can route different kinds to different tiers: a
    vector-tier fib family next to a batch-tier kind, both feeding scalar
    join tasks."""
    from hclib_tpu.device.vector_engine import fib_spec

    def scalar_fib_stub(ctx):  # semantic definition, replaced by routing
        ctx.set_out(0)

    def scalar_sum(ctx):
        ctx.set_out(ctx.value(ctx.arg(0)) + ctx.value(ctx.arg(1)))

    mk = Megakernel(
        kernels=[
            ("fib", scalar_fib_stub),
            ("double", _scalar_double),
            ("sum", scalar_sum),
        ],
        route={
            "fib": fib_spec(max_n=12, lanes=(1, 8)),
            "double": BatchSpec(_batch_double, width=2),
        },
        capacity=64,
        num_values=64,
        interpret=True,
    )
    b = TaskGraphBuilder()
    f = b.add(0, args=[10], out=0)  # fib(10) = 55 via the vector tier
    d = b.add(1, args=[21], out=1, deps=[])  # 42 via the batch tier
    b.add(2, args=[0, 1], out=2, deps=[f, d])  # 97 via the scalar tier
    iv, _, info = mk.run(b)
    assert iv[0] == 55 and iv[1] == 42 and iv[2] == 97
    assert info["tiers"]["batch_tasks"] == 1
