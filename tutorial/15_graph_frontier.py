"""Lesson 15: graph analytics - frontier expansion on the batch lanes.

UTS (lesson 11) proved dynamic trees; this lesson traverses a GRAPH: an
adjacency kept in HBM, walked by dynamically-spawned EXPAND tasks
(device/frontier.py). Three ideas:

- **Blocked CSR.** Every vertex's edge run pads to 128-edge blocks, so
  one EXPAND descriptor names one block and its edge slab is a STATIC
  DMA shape. A hub vertex (the R-MAT skew) is simply many same-kind
  descriptors - skew becomes batch occupancy, not a ragged transfer.
- **The frontier IS a batch lane.** Every EXPAND of one traversal is
  the same kernel kind, so each round's frontier groups onto one batch
  lane and fires ``width`` at a time through ONE tiled body, with the
  double-buffered prefetch streaming the next batch's edge slabs under
  the current batch's relax loop. Relaxation is monotone label
  correction (BFS/SSSP) or exact mass routing (push PageRank), so the
  RESULT is independent of schedule, batching, and migration - the
  bit-identity across arms is by construction.
- **The age-triggered firing policy.** Frontier expansion keeps the
  ready ring hot (every batch deposits a fan-out of children), which
  starves lanes under the old ring-drain-first rule. The ISSUE 10 fix:
  ``Megakernel(lane_max_age=N)`` / ``HCLIB_TPU_LANE_MAX_AGE`` lets a
  lane that held entries for N rounds jump the ring and fire - frontier
  builds default it to ``4 * width``. Watch ``tiers['age_fires']`` and
  the bounded ``tiers['max_starved_age']`` gauge.

The headline metric is TEPS (traversed edges/s): ``info['edges']``
counts every edge each EXPAND relaxed; no benchmark cell times it yet
(ROADMAP.md, Reach 2).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The mesh part wants 4 virtual devices; harmless if already set wider.
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)

import numpy as np  # noqa: E402

from hclib_tpu.device.frontier import (  # noqa: E402
    Graph,
    host_bfs,
    host_pagerank_push,
    host_sssp,
    run_frontier,
)
from hclib_tpu.device.workloads import rmat_edges  # noqa: E402
from hclib_tpu.runtime.locality import MeshPlacement  # noqa: E402

# A seeded R-MAT-style graph: skewed degrees, deterministic from the seed.
n, src, dst, w = rmat_edges(5, efactor=6, seed=3)
g = Graph(n, src, dst, w)
print(f"graph: {g.n} vertices, {g.m} edges, max degree {int(g.deg.max())}")


def part_one_bfs_two_arms():
    """Scalar dispatch vs the batched frontier: bit-identical distances."""
    ref = host_bfs(g, 0)
    d_scalar, _ = run_frontier("bfs", g, 0, width=0, interpret=True)
    d_batch, info = run_frontier("bfs", g, 0, width=4, interpret=True)
    assert np.array_equal(d_scalar, ref) and np.array_equal(d_batch, ref)
    t = info["tiers"]
    print(
        f"bfs: {info['edges']} edges traversed, occupancy "
        f"{t['batch_occupancy']:.2f}, {t['prefetch_hits']} prefetch hits, "
        f"{t['age_fires']} age fires (max starved age "
        f"{t['max_starved_age']} <= lane_max_age)"
    )


def part_two_sssp_and_pagerank():
    """Weighted SSSP (exact) and push PageRank (exact integer twin)."""
    d, _ = run_frontier("sssp", g, 0, width=4, interpret=True)
    assert np.array_equal(d, host_sssp(g, 0))
    m0, reps = 1 << 12, 64
    twin, _ = host_pagerank_push(g, m0=m0, reps=reps)
    r, info = run_frontier(
        "pagerank", g, width=8, m0=m0, reps=reps, interpret=True,
        capacity=768,
    )
    assert np.array_equal(r, twin)
    assert twin.sum() == g.n * m0  # mass conserves exactly
    print(f"sssp exact; pagerank: {info['relaxations']} deliveries, "
          f"mass conserved ({g.n * m0} units)")


def part_three_mesh():
    """4-device mesh: seeds placed by descriptor, dynamic EXPANDs spread
    by stealing, per-device distance caches min-combine - still exact."""
    d, info = run_frontier(
        "bfs", g, 0, width=4, interpret=True, capacity=256,
        placement=MeshPlacement(4, policy="single", device=0),
        quantum=2, window=4,
    )
    assert np.array_equal(d, host_bfs(g, 0))
    from hclib_tpu.device.megakernel import C_EXECUTED

    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    print(f"mesh bfs exact from skewed seeds; per-device executed "
          f"{per_dev.tolist()} (stealing spread the frontier)")


if __name__ == "__main__":
    part_one_bfs_two_arms()
    part_two_sssp_and_pagerank()
    part_three_mesh()
    print("lesson 15 OK")
