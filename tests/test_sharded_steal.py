"""Bulk-synchronous work stealing across sharded megakernel queues
(device/sharded.py steal rounds; CPU interpret mode over an 8-device virtual
mesh)."""

import jax
import numpy as np
import pytest
from conftest import bump_kernel, bump_mk, skewed_builders

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.sharded import ShardedMegakernel
from hclib_tpu.parallel.mesh import cpu_mesh

BUMP = 0


def test_steal_rebalances_skewed_load():
    ndev, ntasks = 8, 200
    mesh = cpu_mesh(ndev, axis_name="queues")
    smk = ShardedMegakernel(bump_mk(512), mesh, migratable_fns=[BUMP])
    iv, _, info = smk.run(
        skewed_builders(ndev, ntasks), steal=True, quantum=8, window=16
    )
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    total = int(iv[:, 0].sum())
    assert total == ntasks * (ntasks + 1) // 2
    per_dev = info["per_device_counts"][:, 5]  # C_EXECUTED
    assert int(per_dev.sum()) == ntasks
    # The point of stealing: the skewed load spread beyond device 0.
    assert int((per_dev > 0).sum()) >= 3, per_dev
    assert info["steal_rounds"] >= 1


def test_no_steal_keeps_static_partition():
    ndev, ntasks = 8, 64
    mesh = cpu_mesh(ndev, axis_name="queues")
    smk = ShardedMegakernel(bump_mk(512), mesh, migratable_fns=[BUMP])
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), steal=False)
    per_dev = info["per_device_counts"][:, 5]
    assert int(per_dev[0]) == ntasks  # everything ran where it was placed
    assert int(iv[0, 0]) == ntasks * (ntasks + 1) // 2


def test_steal_with_balanced_load_still_correct():
    ndev, ntasks = 4, 120
    mesh = cpu_mesh(ndev, axis_name="queues")
    smk = ShardedMegakernel(bump_mk(512), mesh, migratable_fns=[BUMP])
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for i in range(ntasks):
        builders[i % ndev].add(BUMP, args=[1])
    iv, _, info = smk.run(builders, steal=True, quantum=16, window=8)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == ntasks


def test_steal_respects_whitelist():
    """With no migratable kernels, steal rounds must not move anything -
    and dependency graphs (fib-style) stay correct under the round loop."""
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    ndev = 4
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = make_fib_megakernel(capacity=2048, interpret=True)
    smk = ShardedMegakernel(mk, mesh)  # empty whitelist
    builders = []
    expected = {10: 55, 11: 89, 12: 144, 13: 233}
    ns = [10, 11, 12, 13]
    for d in range(ndev):
        b = TaskGraphBuilder()
        b.add(FIB, args=[ns[d]], out=0)
        builders.append(b)
    iv, _, info = smk.run(builders, steal=True, quantum=32, window=8)
    assert info["pending"] == 0
    for d in range(ndev):
        assert int(iv[d, 0]) == expected[ns[d]]
    per_dev = info["per_device_counts"][:, 5]
    assert all(int(x) > 1 for x in per_dev)  # each ran its own tree


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_reentrant_staging_on_tpu():
    """Re-entrant kernel entries on REAL TPU: SMEM output windows do not
    inherit the aliased input's contents, so value slots carried between
    entries (row-owned fib blocks) depend on stage_all_values - interpret
    mode cannot catch this. This drives the bare kernel through a host
    re-entry loop, which is what the sharded round loop does on-device
    (chip_smoke.py --four-chips carries the shard_map form on a real
    mesh)."""
    import jax.numpy as jnp

    from hclib_tpu.device.megakernel import C_PENDING
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    # capacity far below the task total: freed rows must be rediscovered
    # from tombstones at each re-entry (live set is ~tree depth).
    mk = make_fib_megakernel(capacity=128, interpret=False)
    kernel = jax.jit(mk._build_raw(200, stage_all_values=True))
    b = TaskGraphBuilder()
    b.add(FIB, args=[13], out=0)  # 1129 dynamic tasks, ~6 entries
    tasks, succ, ring, counts = b.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    iv = np.zeros(mk.num_values, np.int32)
    for _ in range(64):
        outs = kernel(
            jnp.asarray(tasks), jnp.asarray(succ), jnp.asarray(ring),
            jnp.asarray(counts), jnp.asarray(iv),
        )
        tasks, ring, counts, iv = (np.asarray(o) for o in outs[:4])
        if counts[C_PENDING] == 0:
            break
    assert counts[C_PENDING] == 0
    assert int(iv[0]) == 233


def test_rounds_reuse_freed_rows():
    """fib(13) executes 1129 tasks through a 256-row table with quantum=32
    (~35 kernel re-entries): rows freed in earlier rounds must be
    rediscovered from completion tombstones, or the alloc cursor ratchets
    to overflow long before the graph finishes."""
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    mesh = cpu_mesh(2, axis_name="queues")
    mk = make_fib_megakernel(capacity=256, interpret=True)
    smk = ShardedMegakernel(mk, mesh)
    builders = [TaskGraphBuilder(), TaskGraphBuilder()]
    builders[0].add(FIB, args=[13], out=0)
    builders[1].add(FIB, args=[12], out=0)
    iv, _, info = smk.run(builders, steal=True, quantum=32, window=8)
    assert info["pending"] == 0
    assert int(iv[0, 0]) == 233 and int(iv[1, 0]) == 144


def test_non_migratable_head_does_not_block_export():
    """A non-migratable task parked at the ring head must not pin the
    migratable backlog behind it: export compacts eligible candidates
    across the scanned window (ADVICE r1), so the BUMPs still diffuse."""
    ndev, ntasks = 8, 200
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = Megakernel(
        kernels=[("stay", lambda ctx: ctx.set_value(1, ctx.value(1) + 1)),
                 ("bump", bump_kernel)],
        capacity=512, num_values=4, succ_capacity=8, interpret=True,
    )
    smk = ShardedMegakernel(mk, mesh, migratable_fns=[1])  # bump only
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(0)  # STAY lands at the head (owner pops LIFO from tail)
    for i in range(ntasks):
        builders[0].add(1, args=[i + 1])
    iv, _, info = smk.run(builders, steal=True, quantum=4, window=16)
    assert info["pending"] == 0
    assert info["executed"] == ntasks + 1
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    assert int(iv[:, 1].sum()) == 1  # STAY ran exactly once, on its owner
    assert int(iv[0, 1]) == 1
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


def _spawner_kernel(ctx):
    # Emit one migratable BUMP per step and chain to self: a generator
    # whose cumulative output far exceeds the table capacity.
    from jax.experimental import pallas as pl

    n = ctx.arg(0)
    ctx.spawn(1, [1])  # BUMP is fn 1 in this table

    @pl.when(n > 1)
    def _():
        ctx.spawn(0, [n - 1])


@pytest.mark.parametrize("capacity", [64, 48])
def test_steal_heavy_run_reuses_rows_everywhere(capacity):
    """A generator on device 0 emits 600 migratable tasks through 64-row
    tables: victims reclaim exported rows (tombstoned at export) and
    importers reuse freed rows instead of ratcheting the bump cursor -
    without either, cumulative traffic overflows 64 rows quickly. At 48
    rows the table is not a power of two long and the ring (64 words,
    ``ring_len``) is: the export's head and the import's tail pass both
    lengths many times over."""
    ndev, ntasks = 8, 600
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = Megakernel(
        kernels=[("spawner", _spawner_kernel), ("bump", bump_kernel)],
        capacity=capacity, num_values=4, succ_capacity=8, interpret=True,
    )
    assert mk.ring_len == 64
    smk = ShardedMegakernel(mk, mesh, migratable_fns=[1])
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(0, args=[ntasks])
    iv, _, info = smk.run(
        builders, steal=True, quantum=8, window=16, max_rounds=1 << 12
    )
    assert info["pending"] == 0
    assert info["executed"] == 2 * ntasks  # generators + bumps
    assert int(iv[:, 0].sum()) == ntasks
    per_dev = info["per_device_counts"][:, 5]
    # The serial generator limits backlog, so diffusion stays shallow; what
    # matters here is that migration happened at all while every table
    # stayed within 64 rows for 1200 cumulative tasks.
    assert int((per_dev > 0).sum()) >= 2  # work actually migrated
