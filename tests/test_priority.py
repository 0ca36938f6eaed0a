"""Priority-bucketed dispatch tier (ISSUE 15): bucket rings over the
per-kind batch lanes, popped lowest-nonempty-first.

The acceptance spine: delta-stepping SSSP bit-identical to the
unordered frontier arm (scalar / batched / 4-device sharded mesh) with
a measured executed-EXPAND reduction; bounded-frontier PageRank
bit-identical to the integer twin with a smaller peak live row set;
branch-and-bound returning the proven optimum with pruning counted;
``priority_buckets`` off-path byte-identical; checkpoint/reshard
conserving per-bucket residue (the bucket id is a pure function of
descriptor words, so residue re-buckets on its next routing pop).
"""

import numpy as np
import pytest
from jax.experimental import pallas as pl

import hclib_tpu as hc
from hclib_tpu.device.bnb import (
    host_bnb,
    host_knapsack_opt,
    make_bnb_megakernel,
    make_knapsack,
    run_bnb,
)
from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.frontier import (
    _KINDS,
    Graph,
    host_pagerank_push,
    host_sssp,
    make_frontier_megakernel,
    priority_bucket,
    run_frontier,
)
from hclib_tpu.device.megakernel import (
    BK_MAX,
    BatchContext,
    BatchSpec,
    Megakernel,
)
from hclib_tpu.device.workloads import rmat_edges
from hclib_tpu.runtime.locality import MeshPlacement

# The shared seeded weighted graph for every frontier arm here.
N, SRC, DST, W = rmat_edges(5, efactor=6, seed=3)
G = Graph(N, SRC, DST, W)
SSSP_REF = host_sssp(G, 0)
M0, REPS = 1 << 14, 64

KP = make_knapsack(12, seed=5)
KP_OPT = host_knapsack_opt(KP)


@pytest.fixture(scope="session")
def sssp_pair():
    """Unordered + bucketed batched SSSP builds over the same graph
    (the shared-build discipline of test_frontier)."""
    return {
        "unordered": make_frontier_megakernel(
            _KINDS["sssp"](), G, width=4, interpret=True
        ),
        "bucketed": make_frontier_megakernel(
            _KINDS["sssp"](), G, width=4, interpret=True,
            priority_buckets=8,
        ),
    }


@pytest.fixture(scope="session")
def bnb_pair():
    return {
        "unordered": make_bnb_megakernel(
            KP, width=4, interpret=True, capacity=1024
        ),
        "bucketed": make_bnb_megakernel(
            KP, width=4, interpret=True, capacity=1024,
            priority_buckets=8,
        ),
    }


# ------------------------------------------------ the tier's mechanics


def _seq_kernel(ctx):
    """Record retirement order: value 0 is a cursor, values 2.. the
    observed arg sequence."""
    seq = ctx.value(0)
    ctx.set_value(2 + seq, ctx.arg(0))
    ctx.set_value(0, seq + 1)


def _seq_body(ctx: BatchContext):
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            _seq_kernel(ctx.slot_ctx(s))


def _seq_mk(buckets, priority, trace=None, lane_max_age=0):
    # The order recorder deliberately funnels every slot through one
    # cursor-indexed write (the shim can't see the cursor dependency,
    # so the batch-race rule fires) - suppressed on the spec, the
    # documented spelling for a deliberate violation.
    return Megakernel(
        kernels=[("k", lambda ctx: None)],
        route={"k": BatchSpec(_seq_body, width=2, priority=priority,
                              verify_suppress=("batch-race",))},
        capacity=64, num_values=64, succ_capacity=8, interpret=True,
        priority_buckets=buckets, trace=trace, lane_max_age=lane_max_age,
    )


def _run_seq(mk, args=(7, 1, 5, 3, 0, 6, 2, 4)):
    b = TaskGraphBuilder()
    for a in args:
        b.add(0, args=[a])
    iv, _, info = mk.run(b)
    n = int(iv[0])
    return [int(x) for x in iv[2 : 2 + n]], info


def test_bucketed_pops_retire_in_priority_order():
    order, info = _run_seq(_seq_mk(4, lambda arg: arg(0) // 2))
    assert order == sorted(order), order
    t = info["tiers"]
    # All eight descriptors retired through bucket rings; three of the
    # four fired rounds came from a nonzero bucket.
    assert t["batch_tasks"] == 8 and t["bucket_fires"] == 3
    assert t["bucket_inversions"] == 0


def test_off_path_byte_identical_and_priority_ignored():
    """priority_buckets=0 with priority fns compiles the EXACT program
    a priority-free build compiles (lowered text equality - the ISSUE
    15 off-path gate), and behaves identically."""
    mk_p = _seq_mk(0, lambda arg: arg(0) // 2)
    mk_n = _seq_mk(0, None)
    lowered_p = mk_p._build_raw(1 << 20).lower(
        *_seq_args(mk_p)
    ).as_text()
    lowered_n = mk_n._build_raw(1 << 20).lower(
        *_seq_args(mk_n)
    ).as_text()
    assert lowered_p == lowered_n
    o_p, i_p = _run_seq(mk_p)
    o_n, i_n = _run_seq(mk_n)

    assert o_p == o_n and i_p["tiers"] == i_n["tiers"]
    assert i_p["tiers"]["bucket_fires"] == 0
    assert i_p["tiers"]["bucket_inversions"] == 0


def _seq_args(mk):
    import jax

    b = TaskGraphBuilder()
    for a in (1, 2):
        b.add(0, args=[a])
    tasks, succ, ring, counts = b.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    iv = np.zeros(mk.num_values, np.int32)
    return [
        jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)
        for x in (tasks, succ, ring, counts, iv)
    ]


def test_knob_validation_and_env(monkeypatch):
    with pytest.raises(ValueError, match="priority_buckets"):
        _seq_mk(1, None)
    with pytest.raises(ValueError, match="priority_buckets"):
        _seq_mk(BK_MAX + 1, None)
    with pytest.raises(ValueError, match="priority"):
        BatchSpec(_seq_body, width=2, priority=3)
    monkeypatch.setenv("HCLIB_TPU_PRIORITY_BUCKETS", "4")
    mk = _seq_mk(None, None)
    assert mk.priority_buckets == 4
    # The process-wide spelling reaches the workload builders too (they
    # must resolve it themselves: bucketed builds disable the
    # cross-round prefetch and rescale the age default).
    fmk = make_frontier_megakernel(
        _KINDS["sssp"](), G, width=4, interpret=True
    )
    assert fmk.priority_buckets == 4
    assert fmk.si_claim[3] == 4  # the bucketed 5-tuple claim
    bmk = make_bnb_megakernel(KP, width=4, interpret=True)
    assert bmk.priority_buckets == 4
    monkeypatch.setenv("HCLIB_TPU_PRIORITY_BUCKETS", "banana")
    with pytest.raises(ValueError):
        _seq_mk(None, None)
    monkeypatch.delenv("HCLIB_TPU_PRIORITY_BUCKETS")
    # The scalar frontier arm has no lanes to bucket.
    with pytest.raises(ValueError, match="batched arm"):
        make_frontier_megakernel(
            _KINDS["sssp"](), G, width=0, interpret=True,
            priority_buckets=4,
        )


def test_age_guard_fires_as_bucket_inversion():
    """A high bucket starved behind repeatedly-fired low buckets
    crosses lane_max_age and fires OUT of bucket order - counted in
    bucket_inversions, results unaffected (priorities are a hint)."""
    args = tuple([0] * 20 + [3, 3])  # bucket 0 monopoly + 2 in bucket 3
    mk = _seq_mk(4, lambda arg: arg(0), trace=1024, lane_max_age=3)
    order, info = _run_seq(mk, args)
    t = info["tiers"]
    assert sorted(order) == sorted(args)
    assert t["bucket_inversions"] >= 1
    assert t["max_starved_age"] <= 3 + 4  # N + nrows bound
    # The forced fire happened while bucket 0 still held entries: the
    # 3s retired before the last 0s.
    assert order.index(3) < len(order) - 1
    from hclib_tpu.device.tracebuf import TR_FIRE_BUCKET, records_of

    recs = records_of(info["trace"], TR_FIRE_BUCKET)
    assert len(recs) == t["batch_rounds"]
    assert t["bucket_occupancy"][0] > 0


# ------------------------------------------- delta-stepping SSSP


def test_delta_sssp_bit_identical_with_fewer_expands(sssp_pair):
    d_u, iu = run_frontier(
        "sssp", G, 0, mk=sssp_pair["unordered"], interpret=True
    )
    d_b, ib = run_frontier(
        "sssp", G, 0, mk=sssp_pair["bucketed"], interpret=True
    )
    assert np.array_equal(d_u, SSSP_REF)
    assert np.array_equal(d_b, SSSP_REF)
    # Ordered retirement does less label-correction re-relaxation (the
    # guard of record pins <= 0.8x at scale 8; this small graph just
    # pins the direction).
    assert ib["executed"] <= iu["executed"]
    assert ib["tiers"]["bucket_fires"] > 0
    # The drain-period age default left the order intact.
    assert ib["tiers"]["bucket_inversions"] == 0


def test_delta_sssp_mesh_bit_identical():
    """The 4-device sharded mesh arm: bucketed EXPANDs migrate through
    the steal exchange, re-bucket on their new device's routing pop
    (the bucket is a pure function of descriptor args), and the
    min-combined distances stay bit-identical."""
    d, info = run_frontier(
        "sssp", G, 0, width=4, interpret=True, capacity=256,
        priority_buckets=8,
        placement=MeshPlacement(4, policy="block"), quantum=2, window=4,
    )
    assert np.array_equal(d, SSSP_REF)
    assert info["executed"] > 0


def test_delta_sssp_checkpoint_resume_rebuckets_residue():
    """Quiesce mid-traversal (bucket rings spill to the ready ring -
    the steal/export/checkpoint invariant), resume, and the fixpoint is
    bit-identical: spilled residue re-buckets on the resumed routing
    pops."""
    from hclib_tpu.device.frontier import seed_frontier

    fk = _KINDS["sssp"]()
    mk = make_frontier_megakernel(
        fk, G, width=4, capacity=256, interpret=True, checkpoint=True,
        priority_buckets=8,
    )
    iv = G.preset_values(mk.num_values, fk.state0)
    iv[G.st_base] = 0

    def builder():
        b = TaskGraphBuilder()
        b.reserve_values(G.num_value_slots)
        seed_frontier(b, G, "sssp")
        return b

    data = {"indices": G.indices, "weights": G.weights}
    iv_full, _, info_full = mk.run(
        builder(), data=dict(data), ivalues=iv.copy()
    )
    full = np.asarray(iv_full)[G.st_base : G.st_base + G.n]
    assert np.array_equal(full.astype(np.int32), SSSP_REF)
    _, _, q = mk.run(
        builder(), data=dict(data), ivalues=iv.copy(),
        quiesce=max(2, info_full["executed"] // 2),
    )
    assert q["quiesced"] and q["pending"] > 0
    iv_r, _, info_r = mk.resume(q["state"])
    assert info_r["pending"] == 0
    assert np.array_equal(
        np.asarray(iv_r)[G.st_base : G.st_base + G.n], full
    )


def test_bucketed_kind_keeps_reshard_class(sssp_pair, bnb_pair):
    """The priority callable is routing state, not body code: the
    classification (what reshard/steal filters consult) is identical
    bucketed vs not, and describe() surfaces the priority flag."""
    from hclib_tpu.analysis import classify_megakernel

    cu = classify_megakernel(sssp_pair["unordered"])
    cb = classify_megakernel(sssp_pair["bucketed"])
    assert cu == cb == {"fr_sssp": "link-free"}
    assert classify_megakernel(bnb_pair["bucketed"]) == {
        "bnb_node": "link-free"
    }
    d = sssp_pair["bucketed"].describe()
    assert d["kinds"]["fr_sssp"]["priority"] is True
    assert d["priority_buckets"] == 8
    assert sssp_pair["unordered"].describe()["priority_buckets"] == 0


def test_si_claim_certifies_bucketed_order(sssp_pair):
    cert = sssp_pair["bucketed"].describe()["schedule_independence"]
    assert cert["status"] == "certified"
    assert cert["buckets"] == 8
    # One extra order beyond the random permutations: the bucketed pop.
    assert cert["orders"] >= 3
    # The unbucketed claim stays the 3-tuple spelling.
    assert len(sssp_pair["unordered"].si_claim) == 3
    assert len(sssp_pair["bucketed"].si_claim) == 5


def test_priority_bucket_host_spelling():
    assert priority_bucket("sssp", 17, delta=4) == 4
    assert priority_bucket("bfs", 3, delta=1) == 3
    # PageRank bands ascend with residual magnitude (PR_BAND=2 steps).
    assert priority_bucket("pagerank", 63, reps=64) == 0
    assert priority_bucket("pagerank", 128, reps=64) == 1
    assert priority_bucket("pagerank", 1 << 14, reps=64) == BK_MAX - 1


# ---------------------------------------- bounded-frontier PageRank


def test_bounded_pagerank_bit_identical_smaller_live_set():
    twin, _ = host_pagerank_push(G, m0=M0, reps=REPS)
    r_u, pu = run_frontier(
        "pagerank", G, width=8, m0=M0, reps=REPS, interpret=True,
        capacity=2048,
    )
    r_b, pb = run_frontier(
        "pagerank", G, width=8, m0=M0, reps=REPS, interpret=True,
        capacity=2048, priority_buckets=8,
    )
    assert np.array_equal(r_u, twin) and np.array_equal(r_b, twin)
    # The live-set fix: allocated is the row high-water mark (rows
    # recycle through the free stack, so the bump cursor IS peak live).
    assert pb["allocated"] < pu["allocated"]


def test_bounded_pagerank_fits_where_fifo_overflows():
    """Interpret-scale capacity suffices: a capacity the FIFO
    breadth-first arm overflows runs to completion bucketed."""
    twin, _ = host_pagerank_push(G, m0=M0, reps=REPS)
    cap = 640
    with pytest.raises(RuntimeError, match="task-table rows"):
        run_frontier(
            "pagerank", G, width=8, m0=M0, reps=REPS, interpret=True,
            capacity=cap,
        )
    r_b, _ = run_frontier(
        "pagerank", G, width=8, m0=M0, reps=REPS, interpret=True,
        capacity=cap, priority_buckets=8,
    )
    assert np.array_equal(r_b, twin)


# ------------------------------------------------- branch and bound


def test_bnb_proven_optimum_and_pruning_speedup(bnb_pair):
    assert host_bnb(KP)["best"] == host_bnb(KP, best_first=True)[
        "best"
    ] == KP_OPT
    best_u, iu = run_bnb(KP, mk=bnb_pair["unordered"], interpret=True)
    best_b, ib = run_bnb(KP, mk=bnb_pair["bucketed"], interpret=True)
    assert best_u == best_b == KP_OPT
    assert iu["pruned"] > 0 and ib["pruned"] > 0
    assert iu["leaves"] >= 1 and ib["leaves"] >= 1
    # Priority IS the speedup: best-first finds the incumbent early
    # and prunes subtrees the unordered run explores.
    assert ib["executed"] < iu["executed"]


def test_bnb_certificate_and_instance_guard(bnb_pair):
    cert = bnb_pair["bucketed"].describe()["schedule_independence"]
    assert cert["status"] == "certified"
    assert cert["optimum"] == KP_OPT
    other = make_knapsack(12, seed=6)
    with pytest.raises(ValueError, match="knapsack"):
        run_bnb(other, mk=bnb_pair["bucketed"], interpret=True)
    with pytest.raises(ValueError, match="batched arm"):
        make_bnb_megakernel(KP, width=0, priority_buckets=4)


# ------------------------------------------------------- observability


def test_bucket_gauges_ride_metrics():
    _, info = run_frontier(
        "sssp", G, 0, width=4, interpret=True, priority_buckets=4,
        trace=2048,
    )
    t = info["tiers"]
    assert set(t["bucket_occupancy"]) == {0, 1, 2, 3}
    reg = hc.MetricsRegistry()
    reg.add_run_info("prio", info)
    m = reg.snapshot()["metrics"]
    assert "prio.bucket_inversions.0" in m
    # Per-device then per-bucket (the lane_occupancy discipline):
    # device 0, bucket 0 on this single-device run.
    assert "prio.bucket_occupancy.0.0" in m
    assert m["prio.trace.fire_bucket"] == t["batch_rounds"]


# ------------------------------------------------------------- SMEM model


def test_smem_model_charges_the_core_scratch_it_declares(monkeypatch):
    """``core_scratch`` is the one declaration of the scheduler core's
    scratch (free stacks; the re-arm words, a mark a batch slot; lanes,
    each a ring ``ring_len(capacity)`` long, and lstate over kinds x
    buckets): ``smem_footprint`` charges exactly those words for it, at
    this build's capacity and at another, where the ring is longer than
    the table (40 rows, rings of 64)."""
    from hclib_tpu.device.descriptor import ring_len
    from hclib_tpu.device.megakernel import (
        LS_WORDS, RA_MARK, VBLOCK, smem_bytes,
    )

    mk = _seq_mk(4, lambda arg: arg(0) // 2)
    assert mk.lane_scratch_rows == 4  # one routed kind x four buckets
    for cap in (mk.capacity, 40):
        shapes = [s.shape for s in mk.core_scratch(cap)]
        assert shapes == [
            (cap + 1,), (mk.num_values // VBLOCK + 1,),
            (RA_MARK + max(sp.width for _, sp in mk.batch_specs),),
            (4, ring_len(cap)), (4, LS_WORDS),
        ]
        whole = mk.smem_footprint(cap)
        with monkeypatch.context() as m:
            m.setattr(mk, "core_scratch", lambda capacity=None: [])
            rest = mk.smem_footprint(cap)
        assert whole - rest == sum(map(smem_bytes, shapes))
