"""Benchmark-scale multi-device acceptance workloads (VERDICT r4 #1).

The reference's core claim is load balancing under real stress (UTS as the
canonical test, test/uts/sample_trees.sh:36-37; the steal paths,
src/hclib-locality-graph.c:843-888). The round-4 dryrun proved the
multi-device *protocols* at smoke scale (~1.3k tasks); these workloads run
them at benchmark scale on the virtual CPU mesh, with exact totals, and
report wall time + per-device load for the perf harness.

Two tiers, matched to what the two interpreters can bear on a 1-vCPU host:

- ``forest_steal`` - >= 1e5 dynamically-spawned tasks through the
  bulk-synchronous sharded runner (device/sharded.py) on the FAST
  XLA-backed interpreter: a maximally-skewed forest of fib roots (every
  root seeded on device 0). Roots are successor-free descriptors, so they
  migrate over the hypercube diffusion; each stolen root then explodes
  into its dependency-rich subtree (spawns, joins, continuation passing)
  on the thief. This is the UTS shape: cheap-to-move seeds, expensive
  subtrees, discovered imbalance.
- ``unified_load`` - the unified resident kernel (device/resident.py:
  dependency-BEARING migration via the home-link proxy protocol, remote
  fetch-adds, put/wait-until channels, all in one kernel per device) under
  a load sized for the Mosaic interpreter (which simulates the remote DMAs
  and runs ~3 orders slower than hardware; the suite's protocol tests stay
  smoke-sized for this reason). Scale here means tens of times the
  dryrun's phase load, with every total exact.

Both return an ``info`` dict timeline.py's device report renders directly.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

__all__ = ["forest_steal", "forest_resident", "unified_load",
           "FOREST_STEAL_BENCH", "FOREST_STEAL_QUICK"]

# The forest-steal configuration shared by the scalar and batched arms
# in tools/perf_regression.py --multichip: the mesh-batch-dispatch guard
# compares the two arms' tasks/s, which is only meaningful while they run the
# SAME workload - tune these here, not at a call site.
FOREST_STEAL_BENCH = dict(ndev=8, roots=160, n=12, capacity=4096)
FOREST_STEAL_QUICK = dict(ndev=8, roots=24, n=9, capacity=1024)


def _forest(ndev: int, roots: int, n: int):
    """The maximally-skewed forest both runners take: ``roots`` fib(``n``)
    seeds all on device 0 -> (builders, expected tasks, expected value)."""
    from ..models.fib import fib_seq, task_count
    from .descriptor import TaskGraphBuilder
    from .workloads import FIB

    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for r in range(roots):
        builders[0].add(FIB, args=[n], out=r)
    for b in builders:
        # Symmetric heap: a migrated root writes its out slot on the
        # THIEF's value buffer, so every device must hold the root
        # slot range below its row-block region.
        b.reserve_values(roots)
    per_call = task_count(n)
    per_call += (per_call - 1) // 2  # SUM joins
    return builders, roots * per_call, roots * fib_seq(n)


def _check_forest(iv, info, roots, expect_tasks, expect_value) -> int:
    """Exact totals: executed count, and the out slots summed across the
    mesh (a migrated root writes its slot on the thief)."""
    assert info["executed"] == expect_tasks, (info["executed"], expect_tasks)
    got = int(np.asarray(iv)[:, :roots].sum(dtype=np.int64))
    assert got == expect_value, (got, expect_value)
    assert info["pending"] == 0
    return got


def forest_steal(
    ndev: int = 8,
    roots: int = 160,
    n: int = 12,
    quantum: int = 256,
    window: int = 16,
    capacity: int = 4096,
    batch_width: int = 0,
    mesh=None,
    interpret: bool = True,
) -> Dict:
    """Maximally-skewed fib forest through the sharded steal runner, on
    ``mesh`` (default: ``ndev`` virtual CPU devices, which is what the
    interpreter default wants; a caller with real chips passes its own
    mesh of ``ndev`` devices and says ``interpret=False``).

    ``roots`` fib(``n``) seeds all on device 0; exact checks: the executed
    count equals roots * (FIB nodes + SUM joins) and the out slots sum to
    roots * fib(n) across the mesh (a migrated root writes its slot on the
    thief's value buffer). Defaults: 160 x fib(12) = 111,520 tasks.

    ``batch_width`` > 0 routes the FIB kind through the batched same-kind
    dispatch tier (ISSUE 7): every device's scheduler fires same-kind fib
    batches between steal rounds, lanes spill to the ring's cold end at
    every kernel exit so the steal exchange sees the same candidates the
    scalar mesh would, and the returned info carries per-device
    ``tiers`` (occupancy / batch rounds / spills) beside the totals -
    which stay exact and identical to the scalar arm."""
    from ..parallel.mesh import cpu_mesh
    from .megakernel import VBLOCK
    from .sharded import ShardedMegakernel
    from .workloads import FIB, make_fib_megakernel

    mk = make_fib_megakernel(
        capacity=capacity, interpret=interpret,
        num_values=VBLOCK * capacity + max(64, roots),
        batch_width=batch_width or None,
    )
    smk = ShardedMegakernel(
        mk, cpu_mesh(ndev, axis_name="q") if mesh is None else mesh,
        migratable_fns=[FIB],
    )

    iv, _, info = smk.run(_forest(ndev, roots, n)[0], steal=True,
                          quantum=quantum, window=window)  # compile + warm
    builders, expect_tasks, expect_value = _forest(ndev, roots, n)
    t0 = time.perf_counter()
    iv, _, info = smk.run(builders, steal=True, quantum=quantum,
                          window=window)
    dt = time.perf_counter() - t0
    _check_forest(iv, info, roots, expect_tasks, expect_value)
    per_dev = np.asarray(info["per_device_counts"])[:, 5]
    tier_label = f" [batch w={batch_width}]" if batch_width else ""
    info = dict(info)
    info.update(
        name=f"forest_steal {roots}x fib({n}) on {ndev} devices"
        + tier_label,
        seconds=dt,
        tasks=expect_tasks,
        tasks_per_sec=expect_tasks / dt,
        rounds=info.get("steal_rounds"),
        devices_used=int((per_dev > 0).sum()),
        imbalance=float(per_dev.max() * ndev / max(per_dev.sum(), 1)),
        per_device_counts=np.asarray(info["per_device_counts"]).tolist(),
    )
    if batch_width:
        # The mesh-batch acceptance: every device that executed work must
        # have fired batch rounds (the tier engaged mesh-wide, not just on
        # the seed device), and the tier totals must reconcile with the
        # executed count.
        tiers = info["tiers"]
        batched = sum(t["batch_tasks"] for t in tiers)
        scalar = sum(t["scalar_tasks"] for t in tiers)
        assert batched + scalar == expect_tasks, (batched, scalar)
        for d in range(ndev):
            if per_dev[d] > 0:
                assert tiers[d]["batch_rounds"] > 0, (d, tiers[d])
        occ = [t["batch_occupancy"] for t in tiers if t["batch_rounds"]]
        info.update(
            batch_tasks=batched,
            min_occupancy=min(occ),
            mean_occupancy=sum(occ) / len(occ),
            spilled=sum(t["spilled"] for t in tiers),
        )
    return info


def forest_resident(
    mesh,
    roots: int = 160,
    n: int = 12,
    capacity: int = 640,
    quantum: int = 256,
    window: int = 16,
    interpret: bool = True,
) -> Dict:
    """``forest_steal``'s forest through the RESIDENT kernel on ``mesh``:
    every fib(``n``) root seeded on device 0, link-free roots migrating
    whole over the in-kernel ICI exchange (``homed=False``), each subtree
    exploding on its thief. The caller owns the mesh (virtual CPU devices
    under test, the real chips in ``chip_smoke.py --four-chips``) and
    states ``interpret``. Same exact checks as ``forest_steal``; the
    default capacity fits a v5e's SMEM beside the resident scratch."""
    from .megakernel import VBLOCK
    from .resident import ResidentKernel
    from .workloads import FIB, make_fib_megakernel

    ndev = int(np.prod(mesh.devices.shape))
    mk = make_fib_megakernel(
        capacity=capacity, interpret=interpret,
        num_values=VBLOCK * capacity + max(64, roots),
    )
    rk = ResidentKernel(
        mk, mesh, migratable_fns=[FIB], homed=False, window=window,
    )
    builders, expect_tasks, expect_value = _forest(ndev, roots, n)
    t0 = time.perf_counter()
    iv, _, info = rk.run(builders, quantum=quantum)
    dt = time.perf_counter() - t0
    got = _check_forest(iv, info, roots, expect_tasks, expect_value)
    info = dict(info)
    info.update(
        name=f"forest_resident {roots}x fib({n}) on {ndev} devices",
        seconds=dt,  # first entry: compile included
        tasks=expect_tasks,
        value=got,
        per_device_counts=np.asarray(info["per_device_counts"]).tolist(),
    )
    return info


def unified_load(
    ndev: int = 8,
    n: int = 10,
    fadds: int = 32,
    capacity: int = 1024,
    quantum: int = 32,
    window: int = 8,
    batch_width: int = 0,
) -> Dict:
    """Dependency-bearing migration + PGAS under load, one resident kernel
    per device: a skewed fib(``n``) tree (every task carrying successor
    links; stolen tasks leave home proxies, results return as remote
    completions) plus ``fadds`` remote fetch-adds hammering device 0's
    counter slot from every device. Totals exact: the fib value lands in
    the home slot, the counter equals the sum of all increments, and
    executed matches the tree + AM task count.

    ``batch_width`` > 0 routes the FIB kind through the batched same-kind
    dispatch tier inside the RESIDENT kernel (ISSUE 7): lanes spill to the
    ready ring at every sched() exit, so the homed steal export, the AM
    drains, and the termination fold only ever see ring rows; the info
    carries per-device ``tiers`` and totals stay exact."""
    from ..models.fib import fib_seq, task_count
    from ..parallel.mesh import cpu_mesh
    from .descriptor import TaskGraphBuilder
    from .megakernel import Megakernel, VBLOCK
    from .resident import ResidentKernel
    from .workloads import _fib_kernel, _sum_kernel, batch_of

    FIB5, SUM5, FADD5 = 0, 1, 2

    def fadd_k(ctx):
        ctx.pgas.fadd(0, 2, ctx.arg(0))

    mk = Megakernel(
        kernels=[("fib", _fib_kernel), ("sum", _sum_kernel),
                 ("fadd", fadd_k)],
        capacity=capacity,
        num_values=VBLOCK * capacity + 16 + capacity,
        succ_capacity=64,
        interpret=True,
        uses_row_values=True,
        route=(
            {"fib": batch_of(_fib_kernel, width=batch_width)}
            if batch_width else None
        ),
    )
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB5: (), SUM5: (0, 1)},
        window=window, am_window=8,
    )
    def build(nn: int, nf: int):
        builders = [TaskGraphBuilder() for _ in range(ndev)]
        builders[0].add(FIB5, args=[nn], out=3)
        total = 0
        for i in range(nf):
            builders[i % ndev].add(FADD5, args=[i + 1])
            total += i + 1
        for b in builders:
            b.reserve_values(8)
        return builders, total

    # Warm-up on a tiny graph: same jit signature, so the timed run below
    # measures the protocol under load, not the Mosaic compile.
    wb, _ = build(2, ndev)
    rk.run(wb, quantum=quantum)
    builders, total_inc = build(n, fadds)
    t0 = time.perf_counter()
    iv, _, info = rk.run(builders, quantum=quantum)
    dt = time.perf_counter() - t0

    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 3].sum()) == fib_seq(n)
    assert int(np.asarray(iv)[0, 2]) == total_inc  # every AM landed, once
    expect = task_count(n)
    expect += (expect - 1) // 2
    expect += fadds
    assert info["executed"] == expect, (info["executed"], expect)
    per_dev = np.asarray(info["per_device_counts"])[:, 5]
    if batch_width:
        tiers = info["tiers"]
        batched = sum(t["batch_tasks"] for t in tiers)
        scalar = sum(t["scalar_tasks"] for t in tiers)
        assert batched + scalar == expect, (batched, scalar, expect)
        assert batched > 0, tiers
    info = dict(info)
    info.update(
        name=f"unified_load fib({n}) + {fadds} remote fetch-adds "
        f"on {ndev} devices"
        + (f" [batch w={batch_width}]" if batch_width else ""),
        seconds=dt,
        tasks=expect,
        tasks_per_sec=expect / dt,
        devices_used=int((per_dev > 0).sum()),
        imbalance=float(per_dev.max() * ndev / max(per_dev.sum(), 1)),
        per_device_counts=np.asarray(info["per_device_counts"]).tolist(),
    )
    return info
