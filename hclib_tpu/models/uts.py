"""UTS - Unbalanced Tree Search.

Re-implementation of the UTS benchmark tree specification (reference:
test/uts/uts.c, test/uts/rng/brg_sha1.c) from its published algorithm:

- Node state: 20-byte SHA-1 digest. Root: SHA1(16 zero bytes || BE32(seed))
  (rng_init, test/uts/rng/brg_sha1.c:49-65). Child i of a node:
  SHA1(parent_state || BE32(i)) (rng_spawn, :67-81).
- rng_rand: last 4 state bytes, big-endian, masked positive
  (:83-93); toProb = r / 2^31 (test/uts/uts.c:143-148).
- GEO child count (test/uts/uts.c:171-221): target branching b_i from the
  shape function - LINEAR: b0*(1 - d/gen_mx); EXPDEC: b0*d^(-ln b0/ln gen_mx);
  CYCLIC; FIXED: b0 while d < gen_mx else 0 - then p = 1/(1+b_i) and
  numChildren = floor(log(1-u)/log(1-p)), capped at 100 (uts.h:31).
- BIN child count (``-t 0``, uts_numChildren_bin): the root has floor(b0)
  children ("only a BIN root can have more than MAXNUMCHILDREN": the cap
  does not apply to it); every other node has ``m`` children if
  toProb(rng_rand(state)) < q and none otherwise. With m*q a hair over 1
  every subtree is a critical branching process: nearly all die within a
  few nodes, a few hold millions, and no look at a root says which.

Canonical trees (test/uts/sample_trees.sh): T1 = GEO/FIXED d=10 b=4 r=19
(4,130,071 nodes); T1L = GEO/FIXED d=13 b=4 r=29 (102,181,082 nodes);
binomial T3 = -t 0 -b 2000 -q 0.124875 -m 8 -r 42 (4,112,897 nodes, depth
1,572) and T3L = -t 0 -b 2000 -q 0.200014 -m 5 -r 7 (111,345,631 nodes,
depth 17,844).

The parallel traversal spawns one task per node (work-stealing stress). The
device path (device/) runs the same tree with an on-chip SHA-1 in the
megakernel.
"""

from __future__ import annotations

import hashlib
import math
import struct
import time
from dataclasses import dataclass
from typing import List, Tuple

import hclib_tpu as hc

__all__ = [
    "UTSParams", "T1", "T1L", "T1XL", "T1XXL", "T2", "T3", "T3L", "T5",
    "T_TINY", "BIN", "GEO", "bin_threshold",
    "count_seq", "count_parallel", "run",
]

MAX_CHILDREN = 100  # MAXNUMCHILDREN (reference: test/uts/uts.h:31)

LINEAR, EXPDEC, CYCLIC, FIXED = 0, 1, 2, 3  # geoshape enum (uts.h:65)
BIN, GEO = 0, 1  # tree type, -t (uts.h: enum uts_trees_e)


@dataclass(frozen=True)
class UTSParams:
    shape: int = FIXED  # -a
    gen_mx: int = 10  # -d (tree depth)
    b0: float = 4.0  # -b (branching factor; a BIN root's child count)
    root_seed: int = 19  # -r
    tree: int = GEO  # -t
    q: float = 15.0 / 64.0  # -q (BIN: probability of a non-leaf node)
    m: int = 4  # -m (BIN: children of a non-leaf node)


# Canonical trees (reference: test/uts/sample_trees.sh:18,37)
T1 = UTSParams(shape=FIXED, gen_mx=10, b0=4.0, root_seed=19)  # 4,130,071 nodes
T1L = UTSParams(shape=FIXED, gen_mx=13, b0=4.0, root_seed=29)  # 102,181,082 nodes
# Canonical depth-varying trees (test/uts/sample_trees.sh:20-24):
T5 = UTSParams(shape=LINEAR, gen_mx=20, b0=4.0, root_seed=34)  # 4,147,582
T2 = UTSParams(shape=CYCLIC, gen_mx=16, b0=6.0, root_seed=502)  # 4,117,769
# test/uts/sample_trees.sh XL/XXL geometric trees. Per-lane counters stay
# well under int32 for both; T1XXL's 4.23B TOTAL exceeds int32, which is
# why engine totals are summed in int64 on the host.
T1XL = UTSParams(shape=FIXED, gen_mx=15, b0=4.0, root_seed=29)  # 1,635,119,272
T1XXL = UTSParams(shape=FIXED, gen_mx=15, b0=4.0, root_seed=19)  # 4,230,646,601
T_TINY = UTSParams(shape=FIXED, gen_mx=5, b0=4.0, root_seed=42)  # for tests
# The binomial sample trees (test/uts/sample_trees.sh, -t 0):
T3 = UTSParams(tree=BIN, b0=2000.0, q=0.124875, m=8, root_seed=42)  # 4,112,897
T3L = UTSParams(tree=BIN, b0=2000.0, q=0.200014, m=5, root_seed=7)  # 111,345,631


def root_state(seed: int) -> bytes:
    return hashlib.sha1(b"\x00" * 16 + struct.pack(">i", seed)).digest()


def spawn_state(parent: bytes, i: int) -> bytes:
    return hashlib.sha1(parent + struct.pack(">i", i)).digest()


def rng_rand(state: bytes) -> int:
    return struct.unpack(">I", state[16:20])[0] & 0x7FFFFFFF


def _branching(params: UTSParams, depth: int) -> float:
    if depth <= 0:
        return params.b0
    if params.shape == LINEAR:
        return params.b0 * (1.0 - depth / params.gen_mx)
    if params.shape == EXPDEC:
        return params.b0 * depth ** (-math.log(params.b0) / math.log(params.gen_mx))
    if params.shape == CYCLIC:
        if depth > 5 * params.gen_mx:
            return 0.0
        return params.b0 ** math.sin(2.0 * math.pi * depth / params.gen_mx)
    if params.shape == FIXED:
        return params.b0 if depth < params.gen_mx else 0.0
    raise ValueError(f"unknown shape {params.shape}")


def bin_threshold(q: float) -> int:
    """The least r in [0, 2^31] with r / 2^31 >= q in float64: a binomial
    node is a non-leaf iff rng_rand(state) < bin_threshold(q), one integer
    compare (the vector engines' form of toProb(r) < q)."""
    lo, hi = 0, 1 << 31  # invariant: hi / 2^31 >= q (q <= 1)
    while lo < hi:
        mid = (lo + hi) // 2
        if mid / 2147483648.0 >= q:
            hi = mid
        else:
            lo = mid + 1
    return lo


def num_children(params: UTSParams, state: bytes, depth: int) -> int:
    if params.tree == BIN:
        if depth == 0:
            return int(math.floor(params.b0))  # uncapped (uts.c)
        return params.m if rng_rand(state) / 2147483648.0 < params.q else 0
    b_i = _branching(params, depth)
    if b_i <= 0.0:
        return 0
    p = 1.0 / (1.0 + b_i)
    u = rng_rand(state) / 2147483648.0
    n = int(math.floor(math.log(1.0 - u) / math.log(1.0 - p)))
    return min(n, MAX_CHILDREN)


def count_seq(params: UTSParams) -> Tuple[int, int, int]:
    """Sequential traversal; returns (nodes, leaves, max_depth)."""
    nodes = leaves = max_depth = 0
    stack = [(root_state(params.root_seed), 0)]
    while stack:
        state, depth = stack.pop()
        nodes += 1
        max_depth = max(max_depth, depth)
        nc = num_children(params, state, depth)
        if nc == 0:
            leaves += 1
        for i in range(nc):
            stack.append((spawn_state(state, i), depth + 1))
    return nodes, leaves, max_depth


def count_parallel(params: UTSParams, nworkers=None, grain: int = 1,
                   **launch_kwargs) -> Tuple[int, int, int]:
    """Task-parallel traversal. grain=1 spawns one async per node (the
    reference's per-node tasking); grain>1 makes each task expand up to
    ``grain`` nodes depth-first locally before spawning the rest of its
    frontier as new tasks (amortizes task overhead, keeps stealable slack).
    Extra keywords (deadline_s, fault_plan, default_retry, ...) pass through
    to ``hclib_tpu.launch`` - the chaos harness injects faults this way."""

    def main():
        nodes = hc.SumReducer()
        leaves = hc.SumReducer()
        depth_r = hc.MaxReducer(0)

        def visit(state: bytes, depth: int) -> None:
            stack: List[Tuple[bytes, int]] = [(state, depth)]
            processed = 0
            while stack:
                if processed >= grain:
                    # Hand the remaining frontier to new tasks.
                    for s, d in stack:
                        hc.async_(visit, s, d)
                    return
                s, d = stack.pop()
                processed += 1
                nodes.add(1)
                depth_r.put(d)
                nc = num_children(params, s, d)
                if nc == 0:
                    leaves.add(1)
                    continue
                for i in range(nc):
                    stack.append((spawn_state(s, i), d + 1))

        with hc.finish():
            hc.async_(visit, root_state(params.root_seed), 0)
        return nodes.gather(), leaves.gather(), depth_r.gather()

    return hc.launch(main, nworkers=nworkers, **launch_kwargs)


def run(params: UTSParams = T_TINY, nworkers=None, **launch_kwargs) -> dict:
    t0 = time.perf_counter()
    nodes, leaves, max_depth = count_parallel(params, nworkers=nworkers,
                                              **launch_kwargs)
    dt = time.perf_counter() - t0
    return {
        "nodes": nodes,
        "leaves": leaves,
        "max_depth": max_depth,
        "seconds": dt,
        "tasks_per_sec": nodes / dt if dt > 0 else float("inf"),
    }


if __name__ == "__main__":  # pragma: no cover
    import sys

    name = sys.argv[1] if len(sys.argv) > 1 else "T_TINY"
    params = {"T1": T1, "T1L": T1L, "T2": T2, "T3": T3, "T3L": T3L, "T5": T5,
              "T_TINY": T_TINY}[name]
    print(run(params))
