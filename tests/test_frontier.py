"""Graph-analytics frontier tier (ISSUE 10): BFS/SSSP/PageRank over a
blocked-CSR adjacency on the batch lanes, the age-triggered lane firing
policy, locality-ordered resident XOR hops, and checkpoint mid-frontier.

The acceptance spine: BFS and SSSP distance arrays bit-identical to the
host reference across scalar dispatch, the batched frontier tier, and
the 4-device mesh (PageRank bit-identical to its integer push twin and
within tolerance of float PageRank), with the firing-policy knob
bounding lane starvation and off-behavior unchanged.
"""

import os

import numpy as np
import pytest
from jax.experimental import pallas as pl

import hclib_tpu as hc
from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.frontier import (
    EBLOCK,
    FR_EXPAND,
    INF,
    Graph,
    _KINDS,
    host_bfs,
    host_pagerank,
    host_pagerank_push,
    host_sssp,
    make_frontier_megakernel,
    run_frontier,
)
from hclib_tpu.device.megakernel import C_EXECUTED, Megakernel
from hclib_tpu.device.workloads import batch_of, rmat_edges
from hclib_tpu.runtime.locality import (
    MeshPlacement,
    load_locality_file,
    xor_hop_order,
)

GRAPHS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "locality_graphs",
)

# One small seeded R-MAT graph shared by every arm in this file (each
# distinct megakernel build is an XLA compile - keep the set tight).
N, SRC, DST, W = rmat_edges(5, efactor=6, seed=3)
G = Graph(N, SRC, DST, W)
BFS_REF = host_bfs(G, 0)
SSSP_REF = host_sssp(G, 0)
M0, REPS = 1 << 12, 64


# Shared builds, now by CONTENT not by fixture lifetime (ISSUE 18):
# the process-wide program cache (runtime/progcache.py) keys jitted
# executables on the megakernel's content fingerprint, so every test
# gets a FRESH instance (function scope - no cross-test object
# aliasing) while content-identical rebuilds share one compile. With
# the cache forced off the fixtures still work - each test just pays
# its own build.


@pytest.fixture
def bfs_w4_mk():
    """The batched BFS build (width=4, default capacity) used by the
    three-arm, metrics, and any other single-device batched-BFS test -
    a fresh instance per test; the program cache dedupes the compile."""
    return make_frontier_megakernel(
        _KINDS["bfs"](), G, width=4, interpret=True
    )


@pytest.fixture
def sssp_arms():
    """The scalar + batched SSSP builds (bit-identity arms)."""
    return {
        0: make_frontier_megakernel(
            _KINDS["sssp"](), G, width=0, interpret=True
        ),
        4: make_frontier_megakernel(
            _KINDS["sssp"](), G, width=4, interpret=True
        ),
    }


# -------------------------------------------------- graph container math


def test_rmat_and_blocked_csr_layout():
    # Seeded determinism: same args, same graph.
    n2, s2, d2, w2 = rmat_edges(5, efactor=6, seed=3)
    assert n2 == N and np.array_equal(s2, SRC) and np.array_equal(w2, W)
    # Blocked CSR: per-vertex block runs hold exactly the adjacency,
    # -1-padded to the block, and block_cnt sums back to the degree.
    for v in range(G.n):
        d = int(G.deg[v])
        b0, bc = int(G.blk_start[v]), int(G.blk_count[v])
        assert bc == (d + EBLOCK - 1) // EBLOCK
        flat = G.indices[b0 : b0 + bc].reshape(-1)
        assert np.array_equal(np.sort(flat[:d]), np.sort(G.adj[v]))
        assert (flat[d:] == -1).all()
        assert sum(G.block_cnt(v, i) for i in range(bc)) == d
    # Vertex table + state layout fit the preset row.
    iv = G.preset_values(G.num_value_slots, INF)
    assert iv[8 + 3 * 5] == G.blk_start[5]
    assert (iv[G.st_base : G.st_base + G.n] == INF).all()
    with pytest.raises(ValueError, match="out of range"):
        Graph(4, [0, 9], [1, 2])
    with pytest.raises(ValueError, match="num_values"):
        G.preset_values(4, 0)


# ------------------------------------------------- three-arm bit-identity


def test_bfs_three_arms_bit_identical(bfs_w4_mk):
    d_sc, info_sc = run_frontier("bfs", G, 0, width=0, interpret=True)
    assert np.array_equal(d_sc, BFS_REF)
    assert info_sc["edges"] > 0 and info_sc["relaxations"] > 0

    d_bt, info_bt = run_frontier("bfs", G, 0, mk=bfs_w4_mk,
                                 interpret=True)
    assert np.array_equal(d_bt, BFS_REF)
    t = info_bt["tiers"]
    assert t["scalar_tasks"] == 0 and t["batch_tasks"] == info_bt["executed"]
    # The cross-round edge-slab prefetch engaged.
    assert t["prefetch_hits"] > 0
    # Frontier builds default the age-triggered policy ON (4 * width).
    assert info_bt["executed"] > 0


def test_sssp_three_arms_bit_identical(sssp_arms):
    d_sc, _ = run_frontier("sssp", G, 0, mk=sssp_arms[0],
                           interpret=True)
    assert np.array_equal(d_sc, SSSP_REF)
    d_bt, info = run_frontier("sssp", G, 0, mk=sssp_arms[4],
                              interpret=True)
    assert np.array_equal(d_bt, SSSP_REF)
    assert info["tiers"]["batch_tasks"] == info["executed"]
    # Unreached vertices stay INF in every arm (the min-combine identity
    # depends on the sentinel surviving untouched).
    unreached = BFS_REF == INF
    assert np.array_equal(d_bt == INF, unreached)


def test_pagerank_exact_twin_and_float_tolerance():
    twin, deliveries = host_pagerank_push(G, m0=M0, reps=REPS)
    # Mass conserves exactly: every vertex seeded M0, every unit lands
    # in some rank.
    assert twin.sum() == G.n * M0
    r_sc, i_sc = run_frontier(
        "pagerank", G, width=0, m0=M0, reps=REPS, interpret=True,
        capacity=768,
    )
    assert np.array_equal(r_sc, twin)
    assert i_sc["relaxations"] == deliveries
    r_bt, _ = run_frontier(
        "pagerank", G, width=8, m0=M0, reps=REPS, interpret=True,
        capacity=768,
    )
    assert np.array_equal(r_bt, twin)
    # Within tolerance of real (float) PageRank at this threshold, and
    # the error SHRINKS as the fixed-point resolution grows (the
    # convergence direction - the approximation is the fold threshold,
    # not a bug).
    ref = host_pagerank(G, m0=1.0)
    err = np.abs(r_sc / M0 - ref).sum() / ref.sum()
    assert err < 0.2, err
    fine, _ = host_pagerank_push(G, m0=1 << 16, reps=REPS)
    err_fine = np.abs(fine / (1 << 16) - ref).sum() / ref.sum()
    assert err_fine < err


# ------------------------------------------------------------- mesh arms


@pytest.fixture
def mesh_kernel():
    """A batched BFS megakernel + 4-device sharded runner per mesh
    test (the steal build is the expensive compile here - deduped
    across tests by the program cache, not by fixture lifetime)."""
    from hclib_tpu.device.sharded import ShardedMegakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = make_frontier_megakernel(
        _KINDS["bfs"](), G, width=4, capacity=256, interpret=True
    )
    smk = ShardedMegakernel(mk, cpu_mesh(4, axis_name="q"),
                            migratable_fns=[FR_EXPAND])
    return mk, smk


def test_mesh_bfs_bit_identical(mesh_kernel):
    mk, _ = mesh_kernel
    d, info = run_frontier(
        "bfs", G, 0, mk=mk, interpret=True,
        placement=MeshPlacement(4, policy="block"), quantum=2, window=4,
    )
    assert np.array_equal(d, BFS_REF)
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    assert int(per_dev.sum()) == info["executed"] > 0


def test_mesh_skewed_seeds_complete_by_stealing(mesh_kernel):
    """All seeds on device 0 (the natural single-source shape): dynamic
    EXPANDs migrate through the locality-ordered steal exchange, so the
    frontier spreads and the result stays exact."""
    mk, _ = mesh_kernel
    d, info = run_frontier(
        "bfs", G, 0, mk=mk, interpret=True,
        placement=MeshPlacement(4, policy="single", device=0),
        quantum=2, window=4,
    )
    assert np.array_equal(d, BFS_REF)
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    assert int((per_dev > 0).sum()) > 1, per_dev.tolist()


def test_mesh_sssp_and_pagerank():
    """SSSP distances min-combine and PageRank ranks sum-combine across
    per-device caches - both end exactly at the single-device result."""
    d, _ = run_frontier(
        "sssp", G, 0, width=4, interpret=True, capacity=256,
        placement=MeshPlacement(4, policy="block"), quantum=2, window=4,
    )
    assert np.array_equal(d, SSSP_REF)
    twin, _ = host_pagerank_push(G, m0=M0, reps=REPS)
    r, _ = run_frontier(
        "pagerank", G, width=4, m0=M0, reps=REPS, interpret=True,
        capacity=512, placement=MeshPlacement(4, policy="cyclic"),
        quantum=4, window=8,
    )
    assert np.array_equal(r, twin)


# ------------------------------------------- checkpoint mid-frontier


def test_checkpoint_mid_frontier_resume_bit_identical():
    fk = _KINDS["bfs"]()
    mk = make_frontier_megakernel(
        fk, G, width=4, capacity=256, interpret=True, checkpoint=True
    )
    iv = G.preset_values(mk.num_values, INF)
    iv[G.st_base] = 0

    def builder():
        b = TaskGraphBuilder()
        b.reserve_values(G.num_value_slots)
        for i in range(int(G.blk_count[0])):
            b.add(FR_EXPAND, args=[0, int(G.blk_start[0]) + i, 0,
                                   G.block_cnt(0, i)])
        return b

    data = {"indices": G.indices}
    iv_full, _, info_full = mk.run(builder(), data=dict(data),
                                   ivalues=iv.copy())
    full = np.asarray(iv_full)[G.st_base : G.st_base + G.n]
    assert np.array_equal(full.astype(np.int32), BFS_REF)

    _, _, q = mk.run(
        builder(), data=dict(data), ivalues=iv.copy(),
        quiesce=max(2, info_full["executed"] // 2),
    )
    assert q["quiesced"] and q["pending"] > 0
    # The device-side age gauge rode the export (tstats is part of the
    # quiesced info); live age counters re-arm from zero on resume - a
    # fresh entry cannot already be starved.
    assert q["tiers"]["max_starved_age"] >= 0
    iv_r, _, info_r = mk.resume(q["state"])
    assert info_r["pending"] == 0
    resumed = np.asarray(iv_r)[G.st_base : G.st_base + G.n]
    assert np.array_equal(resumed, full)


# ------------------------------- age-triggered firing policy (the fix)

PUMP, PTILE = 0, 1


def _pump_hot(ctx):
    """Dynamic spawner that keeps the ready ring CONTINUOUSLY hot: each
    PUMP immediately spawns one batch-routed PTILE and the next PUMP
    (no dependency), so under pure ring-drain-first firing the lane
    cannot fire until every pump has run - the starvation shape the age
    trigger exists to bound."""
    d = ctx.arg(0)

    @pl.when(d > 0)
    def _():
        ctx.spawn(PTILE, [d], nargs=1)
        ctx.spawn(PUMP, [d - 1], nargs=1)


def _ptile(ctx):
    ctx.set_value(0, ctx.value(0) + 1)


def _pump_mk(depth, lane_max_age, trace=4096, width=4):
    return Megakernel(
        kernels=[("pump", _pump_hot), ("ptile", _ptile)],
        route={"ptile": batch_of(_ptile, width=width)},
        capacity=256, num_values=16, succ_capacity=8,
        interpret=True, trace=trace, lane_max_age=lane_max_age,
    )


def _run_pump(mk, depth=24):
    b = TaskGraphBuilder()
    b.add(PUMP, args=[depth])
    iv, _, info = mk.run(b)
    assert int(iv[0]) == depth
    return info


def test_age_trigger_bounds_starvation_on_hot_ring():
    from hclib_tpu.device.tracebuf import TR_FIRE_AGE, TR_FIRE_BATCH, records_of

    depth = 24
    off = _run_pump(_pump_mk(depth, lane_max_age=0))
    on = _run_pump(_pump_mk(depth, lane_max_age=8))
    # Same work either way (results bit-identical by construction).
    assert on["executed"] == off["executed"] == 2 * depth + 1
    # Without the trigger the lane's first fire waits out the WHOLE pump
    # chain (ring never drains); with it the first batch fires mid-chain
    # and the device age gauge stays bounded by the knob.
    first_off = records_of(off["trace"], TR_FIRE_BATCH)[0, 1]
    first_on = records_of(on["trace"], TR_FIRE_BATCH)[0, 1]
    assert first_off > depth, (first_off, depth)
    assert first_on < first_off
    assert off["tiers"]["age_fires"] == 0
    assert on["tiers"]["age_fires"] > 0
    assert 0 < on["tiers"]["max_starved_age"] <= 8
    age_recs = records_of(on["trace"], TR_FIRE_AGE)
    assert len(age_recs) == on["tiers"]["age_fires"]
    assert (age_recs[:, 3] >= 8).all()  # b word: age at fire


def test_pr9_chained_spawner_bounded_age_with_knob():
    """PR 9's seeded chained-spawner scenario (PUMP dep-chained on its
    PTILE, tests/test_forasync_device.py) completes with bounded device
    age when lane_max_age is set, and bit-identically to the knob-off
    run."""

    def pump_chain(ctx):
        d = ctx.arg(0)

        @pl.when(d > 0)
        def _():
            nxt = ctx.spawn(PUMP, [d - 1], dep_count=1, nargs=1)
            ctx.spawn(PTILE, [d], succ0=nxt, nargs=1)

    def build(lane_max_age):
        return Megakernel(
            kernels=[("pump", pump_chain), ("ptile", _ptile)],
            route={"ptile": batch_of(_ptile, width=4)},
            capacity=128, num_values=16, succ_capacity=8,
            interpret=True, trace=4096, lane_max_age=lane_max_age,
        )

    infos = {}
    for age in (0, 4):
        b = TaskGraphBuilder()
        b.add(PUMP, args=[24])
        iv, _, infos[age] = build(age).run(b)
        assert int(iv[0]) == 24
    assert infos[0]["executed"] == infos[4]["executed"]
    assert infos[4]["tiers"]["max_starved_age"] <= 4
    # The detector gauge still sees the width-1 partial cadence (the
    # chain exposes no batch width to recover) - the knob bounds AGE,
    # it cannot invent same-kind concurrency.
    assert infos[4]["tiers"]["lane_partial_ages"][PTILE] >= 1


def test_lane_max_age_off_reproduces_today_bit_identically():
    """lane_max_age=0 (and unset) is the pre-knob scheduler: identical
    results AND identical dispatch counters on the starvation scenario."""
    base = _run_pump(_pump_mk(24, lane_max_age=0))
    unset = _run_pump(
        Megakernel(
            kernels=[("pump", _pump_hot), ("ptile", _ptile)],
            route={"ptile": batch_of(_ptile, width=4)},
            capacity=256, num_values=16, succ_capacity=8,
            interpret=True, trace=4096,
        )
    )
    # The tiers are device counters and nothing else: the build's
    # host-side timings live in info["program_cache"] alone.
    assert base["tiers"] == unset["tiers"]
    assert base["executed"] == unset["executed"]


def test_age_never_trips_on_static_tiles():
    """A static same-kind tile set (the forasync shape): the ring drains
    before any reasonable age bound, so the trigger never fires and the
    tier counters match the knob-off build exactly."""

    def run(age):
        mk = Megakernel(
            kernels=[("pump", _pump_hot), ("ptile", _ptile)],
            route={"ptile": batch_of(_ptile, width=4)},
            capacity=128, num_values=16, succ_capacity=8,
            interpret=True, lane_max_age=age,
        )
        b = TaskGraphBuilder()
        for k in range(8):
            b.add(PTILE, args=[k + 1])
        iv, _, info = mk.run(b)
        assert int(iv[0]) == 8
        return info

    on, off = run(16), run(0)
    assert on["tiers"]["age_fires"] == 0
    skip = ("max_starved_age",)
    t_on = {k: v for k, v in on["tiers"].items() if k not in skip}
    t_off = {k: v for k, v in off["tiers"].items() if k not in skip}
    assert t_on == t_off


def test_starved_lane_beats_drain_priority_across_lanes():
    """With several batch-routed kinds, a starved lane must beat the
    lowest-F_FN drain priority, or its age is unbounded: lane 0 (80
    entries) monopolizes the drained ring for ~20 rounds while lane 1
    (4 entries, routed first, aging since round ~1) crosses the knob -
    the starved pass fires it mid-monopoly, keeping the gauge within
    N + nlanes - 1."""

    def bump_b(ctx):
        ctx.set_value(1, ctx.value(1) + 1)

    N_AGE = 90
    mk = Megakernel(
        kernels=[("a", _ptile), ("b", bump_b)],
        route={"a": batch_of(_ptile, width=4),
               "b": batch_of(bump_b, width=4)},
        capacity=256, num_values=16, succ_capacity=8,
        interpret=True, lane_max_age=N_AGE,
    )
    b = TaskGraphBuilder()
    for _ in range(80):
        b.add(0)
    for _ in range(4):  # seeded last => LIFO ring routes them FIRST
        b.add(1)
    iv, _, info = mk.run(b)
    assert int(iv[0]) == 80 and int(iv[1]) == 4
    t = info["tiers"]
    # Bounded at ~N by the starved pass (the drain-priority-only policy
    # would read ~104 here: lane 1 waits out lane 0's whole monopoly).
    # age_fires stays 0 - it counts RING jumps, and this jump was over
    # another lane's drain priority on an already-drained ring.
    assert t["max_starved_age"] <= N_AGE + 4, t
    assert t["age_fires"] == 0, t


def test_prebuilt_mk_refuses_other_graph_and_mesh_fuel(mesh_kernel):
    # Reuse the mesh fixture's build: the refusal is a host-side layout
    # check, so no fresh compile is needed.
    mk, _ = mesh_kernel
    n2, s2, d2, w2 = rmat_edges(4, efactor=4, seed=9)
    other = Graph(n2, s2, d2, w2)
    with pytest.raises(ValueError, match="frontier layout"):
        run_frontier("bfs", other, 0, mk=mk, interpret=True)
    with pytest.raises(ValueError, match="single-device"):
        run_frontier("bfs", G, 0, width=4, interpret=True, fuel=1000,
                     placement=MeshPlacement(4, policy="block"))


def test_lane_max_age_env_and_validation(monkeypatch):
    monkeypatch.setenv("HCLIB_TPU_LANE_MAX_AGE", "12")
    mk = _pump_mk(8, lane_max_age=None, trace=None)
    assert mk.lane_max_age == 12
    monkeypatch.setenv("HCLIB_TPU_LANE_MAX_AGE", "banana")
    with pytest.raises(ValueError):
        _pump_mk(8, lane_max_age=None, trace=None)
    monkeypatch.delenv("HCLIB_TPU_LANE_MAX_AGE")
    with pytest.raises(ValueError, match="lane_max_age"):
        _pump_mk(8, lane_max_age=-1, trace=None)
    # Frontier builds default it on at 4*width; env wins when set.
    fk = _KINDS["bfs"]()
    mk2 = make_frontier_megakernel(fk, G, width=8, interpret=True)
    assert mk2.lane_max_age == 32


# ---------------------------------------- resident XOR-hop ordering


def test_xor_hop_order_from_graphs():
    assert xor_hop_order(os.path.join(GRAPHS, "v5e_4.json")) in (
        [1, 2], [2, 1],
    )
    g8 = load_locality_file(os.path.join(GRAPHS, "v5e_8.json"))
    order = xor_hop_order(g8)
    assert sorted(order) == [1, 2, 4]  # always a FULL permutation
    with pytest.raises(ValueError, match="tpu devices"):
        xor_hop_order(g8, ndev=4)
    p = MeshPlacement.from_file(
        os.path.join(GRAPHS, "v5e_4.place_block.json")
    )
    assert sorted(p.xor_hop_order()) == [1, 2]
    assert MeshPlacement(4, policy="block").xor_hop_order() is None


def test_resident_hop_order_validation():
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = Megakernel(kernels=[("noop", lambda ctx: None)], capacity=64,
                    num_values=16, succ_capacity=8, interpret=True)
    rk = ResidentKernel(mk, cpu_mesh(4, axis_name="q"), migratable_fns=[])
    # Graph-absent behavior unchanged: None maps to bit-position order.
    assert rk._hop_bits(None) == (0, 1)
    assert rk._hop_bits([2, 1]) == (1, 0)
    for bad in ([2], [3, 1], [1, 1], [1, 2, 4]):
        with pytest.raises(ValueError, match="permutation"):
            rk._hop_bits(bad)



def test_resident_frontier_bfs_with_graph_hop_order():
    """The resident runner consumes frontier descriptors (placement
    seeding is runner-agnostic data) and its XOR exchange takes the
    graph-ordered hop sequence: results bit-identical to the host
    reference with and without the reordering."""
    d, info = run_frontier(
        "bfs", G, 0, width=4, interpret=True, capacity=256,
        placement=MeshPlacement.from_file(
            os.path.join(GRAPHS, "v5e_4.place_block.json")
        ),
        runner="resident", quantum=8, window=4,
    )
    assert np.array_equal(d, BFS_REF)
    assert info["hop_order"] is not None
    d2, info2 = run_frontier(
        "bfs", G, 0, width=4, interpret=True, capacity=256,
        placement=MeshPlacement(4, policy="block"),
        runner="resident", quantum=8, window=4,
    )
    assert np.array_equal(d2, BFS_REF)  # graph-absent default unchanged
    assert info2["hop_order"] is None


# ------------------------------------------------------- metrics gauges


def test_metrics_edge_rate_and_age_gauges(bfs_w4_mk):
    _, info = run_frontier("bfs", G, 0, mk=bfs_w4_mk, interpret=True)
    info["elapsed_s"] = 0.5
    reg = hc.MetricsRegistry()
    reg.add_run_info("graph", info)
    m = reg.snapshot()["metrics"]
    assert m["graph.teps"] == info["edges"] / 0.5
    assert "graph.lane_max_starved_age.0" in m
    assert "graph.lane_occupancy.0" in m
