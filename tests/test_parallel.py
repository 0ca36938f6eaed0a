"""Mesh, collectives, and sharded-megakernel tests (8 virtual CPU devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.sharded import ShardedMegakernel, round_robin_partition
from hclib_tpu.device.workloads import FIB, make_fib_megakernel
from jax import shard_map
from hclib_tpu.parallel import collectives
from hclib_tpu.parallel.mesh import cpu_mesh, mesh_locality_graph


def _mesh(n):
    if len(jax.devices("cpu")) < n:
        pytest.skip(f"needs {n} cpu devices (xla_force_host_platform_device_count)")
    return cpu_mesh(n)


def test_mesh_locality_graph():
    mesh = _mesh(4)
    g = mesh_locality_graph(mesh)
    assert g.nworkers == 4
    tpus = g.locales_of_type("tpu")
    assert len(tpus) == 4
    assert tpus[0].metadata["ordinal"] == 0
    ici = g.by_name["ici"]
    assert ici.is_special("COMM")
    # every tpu locale is on every worker's steal path
    for w in range(4):
        path_types = {g.locale(l).type for l in g.steal_paths[w]}
        assert "tpu" in path_types and "host" in path_types
        assert len([l for l in g.steal_paths[w] if g.locale(l).type == "tpu"]) == 4


def test_collectives_on_mesh():
    mesh = _mesh(4)

    def step(x):
        s = collectives.psum(x[0], "d")
        g = collectives.all_gather(x[0], "d")
        r = collectives.ring_permute(x[0], "d", 1)
        return s[None], g[None], r[None]

    f = jax.jit(
        shard_map(
            step, mesh=mesh, in_specs=(P("d"),), out_specs=(P("d"),) * 3,
            check_vma=False,
        )
    )
    x = jax.device_put(
        np.arange(4, dtype=np.float32).reshape(4, 1), NamedSharding(mesh, P("d"))
    )
    s, g, r = f(x)
    assert np.all(np.asarray(s) == 6.0)  # 0+1+2+3 everywhere
    assert np.asarray(g).shape == (4, 4, 1)
    assert list(np.asarray(r)[:, 0]) == [3, 0, 1, 2]  # rotated shards


def test_composed_collectives():
    """The composed tier (bcast/reduce/exscan/barrier/ring_allreduce -
    MPI_Bcast/Reduce/Exscan/Barrier parity, hclib_mpi.cpp:220-286): exact
    against numpy references, including the explicit ring-step allreduce
    matching psum."""
    mesh = _mesh(8)

    def step(x):
        b = collectives.bcast(x[0], "d", root=3)
        r = collectives.reduce(x[0], "d", root=2)
        e = collectives.exscan(x[0], "d")
        t = collectives.barrier("d")
        ra = collectives.ring_allreduce(x[0], "d")
        return b[None], r[None], e[None], t[None], ra[None]

    f = jax.jit(
        shard_map(
            step, mesh=mesh, in_specs=(P("d"),), out_specs=(P("d"),) * 5,
            check_vma=False,
        )
    )
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    b, r, e, t, ra = map(np.asarray, f(x))
    assert (b == x[3]).all()
    assert (r[2] == x.sum(0)).all() and (r[0] == 0).all()
    assert np.allclose(e, np.cumsum(x, axis=0) - x)  # exclusive prefix
    assert (t == 8).all()
    assert np.allclose(ra, np.tile(x.sum(0), (8, 1)))


def test_sharded_megakernel_fib():
    mesh = _mesh(4)
    mk = make_fib_megakernel(capacity=1024, interpret=True)
    smk = ShardedMegakernel(mk, mesh)
    builders = []
    for d in range(4):
        b = TaskGraphBuilder()
        b.add(FIB, args=[9 + d], out=0)
        builders.append(b)
    iv, _, info = smk.run(builders, fuel=1 << 18)
    assert [int(iv[d, 0]) for d in range(4)] == [34, 55, 89, 144]
    assert info["pending"] == 0
    assert not info["overflow"]


def test_sharded_megakernel_with_data_buffers():
    """Exercises the stacked-data path: per-device arrayadd tile tasks over
    per-device HBM buffers."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.workloads import ADD_TILE, _TILE, _addtile_kernel

    mesh = _mesh(2)
    ntiles = 3
    shape = (ntiles,) + _TILE
    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    mk = Megakernel(
        kernels=[("add_tile", _addtile_kernel)],
        data_specs={"a": spec, "b": spec, "c": spec},
        scratch_specs={
            "va": pltpu.VMEM(_TILE, jnp.float32),
            "vb": pltpu.VMEM(_TILE, jnp.float32),
            "sems": pltpu.SemaphoreType.DMA((3,)),
        },
        capacity=64,
        num_values=8,
        succ_capacity=8,
        interpret=True,
    )
    smk = ShardedMegakernel(mk, mesh)
    builders = []
    for d in range(2):
        b = TaskGraphBuilder()
        for t in range(ntiles):
            b.add(ADD_TILE, args=[t])
        builders.append(b)
    rng = np.random.default_rng(1)
    a = rng.standard_normal((2,) + shape).astype(np.float32)
    bb = rng.standard_normal((2,) + shape).astype(np.float32)
    c = np.zeros((2,) + shape, np.float32)
    _, data, info = smk.run(builders, data={"a": a, "b": bb, "c": c}, fuel=1 << 12)
    assert info["executed"] == 6
    assert np.allclose(np.asarray(data["c"]), a + bb)


def test_sharded_partition_validation():
    mesh = _mesh(2)
    mk = make_fib_megakernel(capacity=64, interpret=True)
    smk = ShardedMegakernel(mk, mesh)
    with pytest.raises(ValueError, match="partitions"):
        smk.run([TaskGraphBuilder()])


def test_round_robin_partition():
    parts = round_robin_partition(list(range(10)), 3)
    assert parts == [[0, 3, 6, 9], [1, 4, 7], [2, 5, 8]]


def test_graft_entry_dryrun():
    # 2 devices: every phase still executes end-to-end as a regression
    # guard; the driver itself runs the full 8-device dry run each round.
    import __graft_entry__ as ge

    if len(jax.devices("cpu")) < 2:
        pytest.skip("needs virtual cpu devices")
    # smoke-scale phase 5: the full >=100k-task size belongs to the
    # driver's own dry run and perf_regression --multichip, not the suite
    ge.dryrun_multichip(2, benchmark_scale=False)


def test_graft_entry_compiles():
    import __graft_entry__ as ge

    fn, args = ge.entry(interpret=True)
    jax.jit(fn).lower(*args)  # trace/lower must succeed
