"""Spawn-time routing (ISSUE 50): ``KernelContext.spawn`` of a batch kind
named by a constant, whose lane pops FIFO off a single ring, pushes the
new row onto that LANE and not onto the ready ring, so the scheduler
spends no round on reading the row's ``F_FN`` back. The counter this
mechanism has is ``info["tiers"]["direct"]``: rows pushed straight to a
lane, beside ``routed``, the ring pops diverted into one. What it must
not change is any answer, ``executed`` or ``batch_tasks``; and a LIFO
lane, whose round rewrites its tail, must keep the ring.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import graph500 as ref  # noqa: E402
from hclib_tpu.device.descriptor import TaskGraphBuilder  # noqa: E402
from hclib_tpu.device.frontier import (  # noqa: E402
    INF,
    Graph,
    GraphSearch,
    host_bfs,
)
from hclib_tpu.device.megakernel import BatchSpec, Megakernel  # noqa: E402
from hclib_tpu.runtime.resilience import StallError  # noqa: E402

SEED, TREE = 0, 1
FANOUT, DEPTH, STAGED = 5, 3, 3
# A TREE(n) adds n + 1 into value slot 0 and spawns two TREE(n - 1).
TREES_OF = 2 ** (DEPTH + 1) - 1              # rows of one TREE(DEPTH)
SUM_OF = sum((n + 1) * 2 ** (DEPTH - n) for n in range(DEPTH + 1))
SPAWNED = FANOUT * TREES_OF + STAGED * (TREES_OF - 1)
TREES = SPAWNED + STAGED


def _tree(ctx):
    """One TREE task, as a scalar kernel or as one slot of a batch body:
    the spawns name their kind by a constant."""
    n = ctx.arg(0)
    ctx.set_value(0, ctx.value(0) + n + 1)

    @pl.when(n > 0)
    def _():
        ctx.spawn(TREE, [n - 1], nargs=1)
        ctx.spawn(TREE, [n - 1], nargs=1)


def _seed(ctx):
    for _ in range(FANOUT):
        ctx.spawn(TREE, [DEPTH], nargs=1)


def _tree_batch(ctx):
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            _tree(ctx.slot_ctx(s))


def _mk(lane, **kw):
    """``lane``: None for the scalar arm, else ``fifo`` or ``lifo``."""
    route = {
        None: None,
        "fifo": {"tree": BatchSpec(_tree_batch, width=4, prefetch=True,
                                   drain=lambda ctx: None)},
        "lifo": {"tree": BatchSpec(_tree_batch, width=4)},
    }[lane]
    return Megakernel(
        kernels=[("seed", _seed), ("tree", _tree)], route=route,
        capacity=96, num_values=8, succ_capacity=8, interpret=True,
        # every TREE adds into value slot 0, on purpose
        verify=False, **kw,
    )


def _graph():
    """One SEED, which spawns FANOUT trees from the scalar tier, and
    STAGED trees the host stages itself."""
    b = TaskGraphBuilder()
    b.reserve_values(1)
    b.add(SEED)
    for _ in range(STAGED):
        b.add(TREE, args=[DEPTH])
    return b


@pytest.fixture(scope="module")
def scalar_arm():
    iv, _, info = _mk(None).run(_graph())
    assert "tiers" not in info
    return int(iv[0]), info["executed"]


def test_the_scalar_arm_counts_what_the_graph_holds(scalar_arm):
    assert scalar_arm == ((FANOUT + STAGED) * SUM_OF, 1 + TREES)


@pytest.mark.parametrize(
    "lane,direct,routed",
    [
        # spawned by the scalar SEED and by the kind's own batch body:
        # straight to the lane; the host's rows through the ring
        ("fifo", SPAWNED, STAGED),
        # a LIFO round writes LS_TAIL after its body: the ring, as ever
        ("lifo", 0, TREES),
    ],
)
def test_a_spawned_row_takes_the_lane_only_where_the_lane_pops_fifo(
    scalar_arm, lane, direct, routed
):
    iv, _, info = _mk(lane).run(_graph())
    t = info["tiers"]
    assert (int(iv[0]), info["executed"]) == scalar_arm
    assert info["pending"] == 0 and not info["overflow"]
    assert (t["direct"], t["routed"]) == (direct, routed)
    assert t["batch_tasks"] == TREES == t["direct"] + t["routed"]
    assert t["scalar_tasks"] == 1 and t["spilled"] == 0
    assert info["allocated"] <= 96


def test_a_traced_kind_and_a_waiting_child_keep_the_ring():
    """What ``spawn`` cannot decide at trace time stays where it was: a
    kind that is a traced value, and a child with predecessors, which
    ``retire()`` releases onto the ring."""
    def seed(ctx):
        kind = jnp.where(ctx.arg(0) >= 0, TREE, SEED)  # TREE, but traced
        ctx.spawn(kind, [0], nargs=1)
        gate = ctx.spawn(TREE, [0], dep_count=1, nargs=1)  # waits
        ctx.spawn(TREE, [0], succ0=gate, nargs=1)  # direct; releases it

    mk = Megakernel(
        kernels=[("seed", seed), ("tree", _tree)],
        route={"tree": BatchSpec(_tree_batch, width=4, prefetch=True,
                                 drain=lambda ctx: None)},
        capacity=16, num_values=8, succ_capacity=8, interpret=True,
        verify=False,
    )
    b = TaskGraphBuilder()
    b.reserve_values(1)
    b.add(SEED, args=[0])
    iv, _, info = mk.run(b)
    assert int(iv[0]) == 3 and info["executed"] == 4
    assert (info["tiers"]["direct"], info["tiers"]["routed"]) == (1, 2)


def test_a_run_cut_by_fuel_spills_the_rows_a_spawn_put_in_the_lane():
    """The lane holds rows no ring pop put there when the budget runs out:
    the exit spills them to the ring like any other, none is lost."""
    with pytest.raises(StallError) as ei:
        _mk("fifo").run(_graph(), fuel=10)
    st = ei.value.stats
    assert st["executed"] + st["pending"] > st["executed"] >= 10
    assert st["tiers"]["spilled"] > 0 and st["tiers"]["direct"] > 0
    # every row still owed is on the ring or waits on one that is
    assert st["pending"] >= st["tiers"]["spilled"]


@pytest.mark.parametrize("cut", [1, 10, 40])
def test_a_quiesced_run_resumes_from_the_spill_to_the_same_answer(
    scalar_arm, cut
):
    mk = _mk("fifo", checkpoint=True)
    _, _, info_q = mk.run(_graph(), quiesce=cut)
    assert info_q["quiesced"] and info_q["pending"] > 0
    tq = info_q["tiers"]
    assert tq["spilled"] > 0 and tq["direct"] > 0
    iv, _, info = mk.resume(info_q["state"])
    assert info["pending"] == 0 and not info["overflow"]
    assert int(iv[0]) == scalar_arm[0]
    assert info["executed"] == scalar_arm[1]  # C_EXECUTED rides the state
    t = info["tiers"]  # the tier's words count since the entry
    # what the cut spilled comes back through the ring; the rest is
    # spawned after it, and every TREE ran in a batch on one side or other
    assert t["routed"] == tq["spilled"]
    assert tq["batch_tasks"] + t["batch_tasks"] == TREES
    assert tq["direct"] + t["direct"] == SPAWNED


def test_a_search_sends_every_expand_straight_to_its_lane():
    n = 1 << 8
    u, v = ref.edge_list(50, 8)
    g = Graph.undirected(n, u, v)
    s = GraphSearch(g, width=8, capacity=64, interpret=True)
    for key in ref.search_keys(50, n, u, v)[:3].tolist():
        parent, info = s.bfs(key)
        books, t = info["search"], info["tiers"]
        assert info["pending"] == 0 and not info["overflow"]
        assert books["expands"] == s.blocks_of(parent) > 64
        # the maker's spawns never see the ring; only it does (the seed,
        # staged by the host, is a scalar task, so nothing is routed)
        assert (t["direct"], t["routed"]) == (books["expands"], 0)
        assert t["batch_tasks"] == books["expands"]
        assert t["age_fires"] == 0  # a lane fed directly never starves
        depth, unrooted = ref.levels_of_tree(parent, key)
        want = host_bfs(g, key).astype(np.int64)
        want[want == INF] = -1
        assert unrooted == 0 and np.array_equal(depth, want)
        held = ref.search_and_validate(n, u, v, key, parent)
        assert not any(held[r] for r in ref.RULES), held
        assert held["levels_differ"] == 0
        assert books["levels"] == held["levels"]
