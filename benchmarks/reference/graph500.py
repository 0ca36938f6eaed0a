"""Plain reference for the Graph500 deployments (graph500.org, "Graph 500
Benchmark 1: Search"): the Kronecker edge list from a seed, the search
keys by the specification's rule, a level-synchronous breadth-first
search over the edge list itself, and the five validation rules with the
component's edge count. numpy only; imports nothing of the program.

The edge list is the DATA (as weights are to a model): ``edge_list``
makes it, and the driver hands the same two arrays to the system under
test. Everything else here reads the list as it is, self-loops and
duplicates included, and never builds an adjacency: the search and the
rules are passes over the ``M`` tuples, a block at a time on a few
threads (numpy's gathers release the GIL), so that a scale-22 list
(67,108,864 tuples) is searched and validated in a few seconds a key.

The generator is the specification's, written from memory (no network
here; ``configs/graph500-bfs.json`` lists it under ``assumed``): for each
of SCALE bits, ``ii_bit = rand > A + B`` and ``jj_bit = rand > (C / (C +
D) if ii_bit else A / (A + B))``; then the vertex labels are permuted and
the list is shuffled. The tuples are independent draws, a block of them
from a stream of its own, so any fixed permutation of the list is as good
as a uniform one: the shuffle is ``position -> (position * stride +
shift) mod M`` with a random odd stride coprime to ``M``, which a block
can apply for itself. The uniform draws are the two 32-bit halves of one
raw 64-bit word of a PCG64 stream a block, compared as integers, so a
probability is realised to 2^-32.
"""

from __future__ import annotations

import concurrent.futures
import os
from typing import Dict, Optional, Tuple

import numpy as np

BLOCK = 1 << 20  # edge tuples a pass holds at once, and a generator stream
THREADS = min(16, os.cpu_count() or 1)


def _blocks(m: int):
    return [(lo, min(lo + BLOCK, m)) for lo in range(0, m, BLOCK)]


def _each(fn, m: int):
    """``fn(lo, hi)`` over the blocks of ``m`` tuples, on the pool, in
    block order."""
    blocks = _blocks(m)
    if len(blocks) <= 1 or THREADS == 1:
        return [fn(lo, hi) for lo, hi in blocks]
    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(lambda b: fn(*b), blocks))


# ------------------------------------------------------------ the data


def edge_list(seed: int, scale: int, edgefactor: int = 16,
              initiator=(0.57, 0.19, 0.19, 0.05)
              ) -> Tuple[np.ndarray, np.ndarray]:
    """``(u, v)``: the ``edgefactor * 2**scale`` undirected edge tuples of
    the Kronecker graph, int32, a pure function of the arguments."""
    a, b, c, d = initiator
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"the initiator must sum to 1: {initiator}")
    n, m = 1 << scale, edgefactor << scale
    full = float(1 << 32)
    t_ab = np.uint32(min(full - 1, (a + b) * full))
    t_c = np.uint32(min(full - 1, c / (c + d) * full))
    t_a = np.uint32(min(full - 1, a / (a + b) * full))
    rng = np.random.Generator(np.random.PCG64([seed, scale, 1 << 30]))
    labels = rng.permutation(n).astype(np.int32)
    mult = int(rng.integers(m // 4, m // 2)) | 1  # the shuffle's stride
    while np.gcd(mult, m) != 1:
        mult += 2
    shift = int(rng.integers(0, m))
    made_u = np.empty(m, np.int32)
    made_v = np.empty(m, np.int32)

    def block(lo, hi):
        rng = np.random.Generator(np.random.PCG64([seed, scale, lo // BLOCK]))
        ii = np.zeros(hi - lo, np.int32)
        jj = np.zeros(hi - lo, np.int32)
        for bit in range(scale):
            raw = rng.bit_generator.random_raw(hi - lo)
            r1 = (raw >> np.uint64(32)).astype(np.uint32)
            r2 = raw.astype(np.uint32)
            ib = r1 > t_ab
            jb = r2 > np.where(ib, t_c, t_a)
            ii |= ib.astype(np.int32) << bit
            jj |= jb.astype(np.int32) << bit
        made_u[lo:hi], made_v[lo:hi] = labels[ii], labels[jj]

    _each(block, m)
    u = np.empty(m, np.int32)
    v = np.empty(m, np.int32)

    def shuffle(lo, hi):
        at = (np.arange(lo, hi, dtype=np.int64) * mult + shift) % m
        u[lo:hi], v[lo:hi] = made_u[at], made_v[at]

    _each(shuffle, m)
    return u, v


def has_edge(n: int, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Per vertex: whether its degree is at least one, self-loops not
    counted."""
    out = np.zeros(n, bool)
    real = u != v
    out[u[real]] = True
    out[v[real]] = True
    return out


def search_keys(seed: int, n: int, u: np.ndarray, v: np.ndarray,
                count: int = 64) -> np.ndarray:
    """The search keys: ``count`` vertices drawn without replacement from
    those of degree at least one (self-loops not counted), in the order
    they are searched. Fewer where the graph has fewer such vertices."""
    rng = np.random.Generator(np.random.PCG64([seed, n, 1 << 29]))
    order = rng.permutation(n)
    ok = has_edge(n, u, v)
    return order[ok[order]][:count].astype(np.int32)


# ---------------------------------------------------------- the search


def bfs_levels(n: int, u: np.ndarray, v: np.ndarray, key: int) -> np.ndarray:
    """Level of every vertex in a breadth-first search from ``key`` (-1:
    not reached), level-synchronous over the edge list: a level's pass
    marks the far end of every tuple that has one end in the level and
    the other unreached. The passes read two one-byte tables (reached,
    in the level), which a cache holds."""
    level = np.full(n, -1, np.int32)
    reached = np.zeros(n, bool)
    front = np.zeros(n, bool)
    level[key] = 0
    reached[key] = front[key] = True
    d = 0
    while True:
        def step(lo, hi):
            a, b = u[lo:hi], v[lo:hi]
            ra = reached[a]
            one = np.flatnonzero(ra != reached[b])  # one end reached
            ra, a, b = ra[one], a[one], b[one]
            near, far = np.where(ra, a, b), np.where(ra, b, a)
            return far[front[near]]

        new = np.concatenate(_each(step, len(u)) or [u[:0]])
        if not len(new):
            return level
        front[:] = False
        front[new] = True
        reached[new] = True
        level[new] = d + 1
        d += 1


def levels_of_tree(parent: np.ndarray, key: int
                   ) -> Tuple[np.ndarray, int]:
    """``(level, unrooted)``: the depth of every vertex of the parent
    array's tree below ``key`` (-1 outside it), and how many vertices
    with a parent never got a depth: they hang on a cycle or under a
    vertex outside the tree (rule 1)."""
    n = len(parent)
    level = np.full(n, -1, np.int32)
    if not (0 <= key < n) or parent[key] != key:
        return level, int(np.count_nonzero(parent >= 0)) + 1
    level[key] = 0
    inside = np.flatnonzero((parent >= 0) & (parent < n))
    inside = inside[inside != key]
    d = 0
    while len(inside):
        hit = level[parent[inside]] == d
        if not hit.any():
            break
        level[inside[hit]] = d + 1
        inside = inside[~hit]
        d += 1
    bad = int(np.count_nonzero((parent < -1) | (parent >= n)))
    return level, len(inside) + bad


# ------------------------------------------------------- the five rules


def validate(n: int, u: np.ndarray, v: np.ndarray, key: int,
             parent: np.ndarray, level: Optional[np.ndarray] = None
             ) -> Dict[str, int]:
    """The specification's five rules on one search's parent array, as
    counts of violations (all 0 for a valid search), with the component's
    numbers:

    1. ``rule1_tree``: the parent array is a tree rooted at ``key``
       (``parent[key] == key``; every vertex with a parent reaches the
       key);
    2. ``rule2_tree_edges``: every tree edge joins levels that differ by
       exactly one;
    3. ``rule3_level_gap``: every input tuple with both ends in the tree
       joins levels that differ by at most one;
    4. ``rule4_span``: no input tuple joins a vertex of the tree with one
       outside it, so the tree spans the key's whole component;
    5. ``rule5_not_an_edge``: every vertex and its parent are joined by
       an input tuple.

    ``level`` is what the search CLAIMS as levels; left out, the tree's
    own depths are taken (rule 2 then holds by construction).
    ``component_edges`` counts the input tuples with both ends in the
    tree, self-loops and duplicates as the list has them (the numerator
    of TEPS), ``reached`` the tree's vertices, ``levels`` its depth + 1.
    """
    parent = np.asarray(parent)
    depth, unrooted = levels_of_tree(parent, key)
    if level is None:
        level = depth
    level = np.asarray(level)
    in_tree = depth >= 0
    kids = np.flatnonzero(in_tree)
    kids = kids[kids != key]
    rule2 = int(np.count_nonzero(
        level[kids] != level[parent[kids]] + 1)) + int(level[key] != 0) \
        if 0 <= key < n else 1
    joined = np.zeros(n, bool)  # vertex and its parent share a tuple

    def rules(lo, hi):
        a, b = u[lo:hi], v[lo:hi]
        ta, tb = in_tree[a], in_tree[b]
        both = ta & tb
        gap = np.abs(level[a[both]].astype(np.int64) - level[b[both]]) > 1
        pa, pb = parent[a], parent[b]
        return (int(np.count_nonzero(gap)), int(np.count_nonzero(ta != tb)),
                int(np.count_nonzero(both)), a[pa == b], b[pb == a])

    parts = _each(rules, len(u))
    for p in parts:
        joined[p[3]] = True
        joined[p[4]] = True
    return {
        "rule1_tree": unrooted,
        "rule2_tree_edges": rule2,
        "rule3_level_gap": sum(p[0] for p in parts),
        "rule4_span": sum(p[1] for p in parts),
        "rule5_not_an_edge": int(np.count_nonzero(~joined[kids])),
        "component_edges": sum(p[2] for p in parts),
        "reached": int(np.count_nonzero(in_tree)),
        "levels": int(depth.max()) + 1,
    }


RULES = ("rule1_tree", "rule2_tree_edges", "rule3_level_gap", "rule4_span",
         "rule5_not_an_edge")


def search_and_validate(n: int, u: np.ndarray, v: np.ndarray, key: int,
                        parent: np.ndarray) -> Dict[str, int]:
    """What a cell's check holds one returned parent array to: the five
    rules, and ``levels_differ``, the vertices whose depth in the parent
    array is not the level this module's own search gives them."""
    out = validate(n, u, v, key, parent)
    depth, _ = levels_of_tree(np.asarray(parent), key)
    out["levels_differ"] = int(np.count_nonzero(
        depth != bfs_levels(n, u, v, key)))
    return out
