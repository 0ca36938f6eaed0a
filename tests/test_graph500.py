"""Graph500's kernel 2 on the frontier tier's search kind (ISSUE 48): a
breadth-first search to a parent array over a graph whose vertex table,
frontier and answer live in HBM, held to the plain reference
(``benchmarks/reference/graph500.py``, which imports nothing of the
program) from every one of the 64 search keys, on the CPU interpreter at
scale 6 to 9.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import graph500 as ref  # noqa: E402
from hclib_tpu.device.frontier import (  # noqa: E402
    EBLOCK,
    INF,
    SR_SPARE,
    Graph,
    GraphSearch,
    host_bfs,
    host_pagerank_push,
    host_sssp,
    make_frontier_megakernel,
    search_kernel,
)
from hclib_tpu.device.workloads import rmat_edges  # noqa: E402
from hclib_tpu.runtime.resilience import StallError  # noqa: E402

SEED, SCALE = 48, 7
N = 1 << SCALE
U, V = ref.edge_list(SEED, SCALE)
KEYS = ref.search_keys(SEED, N, U, V)


@pytest.fixture(scope="module")
def search():
    return GraphSearch(Graph.undirected(N, U, V), width=4, capacity=32,
                       interpret=True)


# ------------------------------------------------------- the reference


def test_the_generator_is_a_pure_function_of_the_seed():
    again = ref.edge_list(SEED, SCALE)
    assert np.array_equal(U, again[0]) and np.array_equal(V, again[1])
    other = ref.edge_list(SEED + 1, SCALE)
    assert not np.array_equal(U, other[0])
    # The specification's counts: 16 tuples a vertex, labels in range,
    # self-loops and duplicates left in the list.
    assert len(U) == len(V) == 16 * N and U.dtype == np.int32
    assert 0 <= min(U.min(), V.min()) and max(U.max(), V.max()) < N
    pairs = np.stack([np.minimum(U, V), np.maximum(U, V)])
    assert len(np.unique(pairs, axis=1).T) < len(U)
    # A big seed, as the benchmark's driver draws them.
    big = ref.edge_list(2**31 + 48, 6)
    assert len(big[0]) == 16 * 64


def test_the_initiator_shapes_the_degrees():
    # A = 0.57 piles the tuples on few vertices: the largest degree is
    # many times the mean (32 entries a vertex), and many vertices have
    # no tuple at all.
    u, v = ref.edge_list(3, 12)
    deg = np.bincount(np.concatenate([u, v]), minlength=1 << 12)
    assert deg.max() > 20 * deg.mean()
    assert np.count_nonzero(deg == 0) > (1 << 12) // 10


def test_the_search_keys_follow_the_rule():
    assert len(KEYS) == 64 == len(set(KEYS.tolist()))
    real = U != V
    deg = np.bincount(np.concatenate([U[real], V[real]]), minlength=N)
    assert (deg[KEYS] >= 1).all()
    assert np.array_equal(KEYS, ref.search_keys(SEED, N, U, V))
    # A graph with fewer such vertices gives fewer keys.
    two = ref.search_keys(1, 8, np.array([0, 3, 3], np.int32),
                          np.array([1, 3, 3], np.int32))
    assert sorted(two.tolist()) == [0, 1]


def test_the_reference_search_against_a_queue_search():
    key = int(KEYS[0])
    want = host_bfs(Graph.undirected(N, U, V), key).astype(np.int64)
    want[want == INF] = -1
    assert np.array_equal(ref.bfs_levels(N, U, V, key), want)


# Eight vertices by hand: levels 0 | 1 2 | 3 4 | 5 from key 0, a pair (6,
# 7) in a component of its own, a self-loop and a duplicate.
HAND_U = np.array([0, 0, 1, 2, 2, 3, 3, 4, 6, 2, 0], np.int32)
HAND_V = np.array([1, 2, 3, 3, 4, 4, 5, 5, 7, 2, 1], np.int32)
HAND_PARENT = np.array([0, 0, 0, 1, 2, 3, -1, -1], np.int32)
HAND_LEVEL = np.array([0, 1, 1, 2, 2, 3, -1, -1], np.int32)

# rule -> (parent entries to overwrite, claimed levels to overwrite)
CORRUPTIONS = {
    # 6 and 7 name each other: a cycle that hangs on nothing.
    "rule1_tree": ({6: 7, 7: 6}, {}),
    # The search CLAIMS leaf 5 at its parent's level.
    "rule2_tree_edges": ({}, {5: 2}),
    # 4 hung under 3, of its own level: its depth becomes 3, two above
    # vertex 2's, which a tuple still joins to it.
    "rule3_level_gap": ({4: 3}, None),
    # Leaf 5 of the component left out of the tree.
    "rule4_span": ({5: -1}, {5: -1}),
    # 4 hung under 1: the right level, and no tuple joins them.
    "rule5_not_an_edge": ({4: 1}, {}),
}


def test_the_rules_pass_a_valid_tree_and_count_its_component():
    got = ref.validate(8, HAND_U, HAND_V, 0, HAND_PARENT, HAND_LEVEL)
    assert not any(got[r] for r in ref.RULES), got
    # Every tuple but (6, 7): the self-loop and the duplicate count.
    assert got["component_edges"] == 10 and got["reached"] == 6
    assert got["levels"] == 4
    assert np.array_equal(ref.bfs_levels(8, HAND_U, HAND_V, 0), HAND_LEVEL)
    assert np.array_equal(ref.levels_of_tree(HAND_PARENT, 0)[0], HAND_LEVEL)


@pytest.mark.parametrize("rule", sorted(CORRUPTIONS))
def test_each_rule_fails_on_a_tree_corrupted_to_break_it_alone(rule):
    parents, levels = CORRUPTIONS[rule]
    parent, claimed = HAND_PARENT.copy(), HAND_LEVEL.copy()
    for x, y in parents.items():
        parent[x] = y
    for x, y in (levels or {}).items():
        claimed[x] = y
    got = ref.validate(8, HAND_U, HAND_V, 0, parent,
                       None if levels is None else claimed)
    assert [r for r in ref.RULES if got[r]] == [rule], got


def test_a_tree_not_rooted_at_the_key_fails_rule_one():
    parent = HAND_PARENT.copy()
    parent[0] = 1
    assert ref.validate(8, HAND_U, HAND_V, 0, parent)["rule1_tree"] > 0
    out = HAND_PARENT.copy()
    out[5] = 99  # a parent that is no vertex
    assert ref.validate(8, HAND_U, HAND_V, 0, out)["rule1_tree"] > 0


# --------------------------------------------- the system, all 64 keys


@pytest.mark.parametrize("i", range(64))
def test_search_from_every_key_against_the_reference(search, i):
    key = int(KEYS[i])
    parent, info = search.bfs(key)
    assert parent.dtype == np.int32 and parent.shape == (N,)
    assert parent[key] == key and parent.min() >= -1
    held = ref.search_and_validate(N, U, V, key, parent)
    assert not any(held[r] for r in ref.RULES), held
    assert held["levels_differ"] == 0
    books = info["search"]
    assert info["pending"] == 0 and not info["overflow"]
    assert books["reached"] == held["reached"] >= 2
    assert books["levels"] == held["levels"]
    # Level-synchronous by the maker: every directed entry of the
    # component examined once, every reached vertex expanded once.
    assert books["edges"] == 2 * held["component_edges"]
    assert books["expands"] == search.blocks_of(parent)
    assert books["live_rows_max"] < books["capacity"] == 32
    starts = books["level_starts"]
    assert starts[0] == 0 and starts == sorted(starts)
    assert books["frontier_max"] == max(np.diff(starts + [books["reached"]]))


# ------------------------------------------------ the frontier in HBM


def test_a_frontier_many_times_the_table_never_overflows():
    scale = 9
    n = 1 << scale
    u, v = ref.edge_list(9, scale)
    s = GraphSearch(Graph.undirected(n, u, v), width=8, capacity=64,
                    interpret=True)
    key = int(ref.search_keys(9, n, u, v)[0])
    parent, info = s.bfs(key)
    books = info["search"]
    assert not info["overflow"] and info["pending"] == 0
    assert books["frontier_max"] > 4 * 64
    assert books["live_rows_max"] <= 64 - SR_SPARE + 1 < 64
    assert info["tiers"]["batch_tasks"] == books["expands"] > 8 * 64
    assert ref.search_and_validate(n, u, v, key, parent)["levels_differ"] == 0
    # What a search moves of its state is counted, and it is the reached
    # vertices' rows: a table row each, the queue's rows once each way.
    rows = -(-books["reached"] // 64)
    assert books["hbm_words_written"] == rows * EBLOCK
    assert books["hbm_words_read"] >= books["reached"] * EBLOCK
    # The same searcher again: its state is reset on the device.
    again, info2 = s.bfs(key)
    assert np.array_equal(parent, again)
    assert info2["search"] == books


def test_a_task_budget_below_the_search_stalls():
    s = GraphSearch(Graph.undirected(N, U, V), width=4, capacity=32,
                    interpret=True, fuel=16)
    with pytest.raises(StallError):
        s.bfs(int(KEYS[0]))


def test_a_key_with_no_edge_and_a_key_out_of_range(search):
    lone = int(np.flatnonzero(~ref.has_edge(N, U, V))[0])
    parent, info = search.bfs(lone)
    assert info["search"]["reached"] == 1 and info["search"]["levels"] == 1
    assert parent[lone] == lone and np.count_nonzero(parent >= 0) == 1
    with pytest.raises(ValueError):
        search.bfs(N)


def test_the_hbm_layout_gives_the_distances_the_frontier_tests_pin():
    # tests/test_frontier.py's graph and source: a DIRECTED R-MAT list.
    n, src, dst, w = rmat_edges(5, efactor=6, seed=3)
    g = Graph(n, src, dst, w)
    parent, info = GraphSearch(g, width=4, capacity=32,
                               interpret=True).bfs(0)
    depth, unrooted = ref.levels_of_tree(parent, 0)
    want = host_bfs(g, 0).astype(np.int64)
    want[want == INF] = -1
    assert unrooted == 0 and np.array_equal(depth, want)
    assert info["search"]["edges"] == int(g.deg[parent >= 0].sum())


def test_undirected_is_the_general_construction_on_both_directions():
    a = Graph.undirected(N, U, V)
    b = Graph(N, np.concatenate([U, V]), np.concatenate([V, U]))
    for f in ("deg", "blk_count", "blk_start"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert a.nblocks == b.nblocks and a.m == b.m == 2 * len(U)
    for x, y in zip(a.adj, b.adj):  # a vertex's targets ascend in ``a``
        assert np.array_equal(x, np.sort(y))
    assert np.array_equal(a.weights, (a.indices >= 0).astype(np.int32))
    assert np.array_equal(a.vtab().reshape(-1, 2)[:N, 1], a.deg)
    # The host twins' answers do not depend on which built the graph.
    key = int(KEYS[0])
    assert np.array_equal(host_bfs(a, key), host_bfs(b, key))
    assert np.array_equal(host_sssp(a, key), host_sssp(b, key))
    ra, rb = host_pagerank_push(a, 256, 64), host_pagerank_push(b, 256, 64)
    assert np.array_equal(ra[0], rb[0]) and ra[1] == rb[1]
    empty = Graph.undirected(4, np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert empty.nblocks == 1 and (empty.indices == -1).all()


def test_the_search_build_passes_the_verifier_and_describes_itself():
    g = Graph.undirected(N, U, V)
    mk = make_frontier_megakernel(search_kernel(), g, width=4, capacity=32,
                                  interpret=True)
    assert mk.verify and mk.analysis is not None  # on under pytest
    assert not [f for f in mk.analysis.findings if f.severity == "error"]
    d = mk.describe()
    assert set(d["kinds"]) == {"fr_search", "sr_make"}
    assert mk.read_only == ("indices", "vtab")
    assert mk.si_claim is None  # parents depend on the order; levels not
    with pytest.raises(ValueError):
        make_frontier_megakernel(search_kernel(), g, width=4, capacity=8,
                                 interpret=True)
    with pytest.raises(ValueError):
        make_frontier_megakernel(search_kernel(), g, width=4, capacity=32,
                                 interpret=True, priority_buckets=4)
