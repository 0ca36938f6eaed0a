"""The v5e compiler's verdict on the kernels the chip runs, without a chip.

Interpret mode accepts kernels the TPU compiler refuses: a slice that is
not aligned to the (8, 128) tiling, a scalar read from VMEM at a dynamic
lane, a task table that pads past the 1 MiB of SMEM. These tests hand each
main-path kernel, built ``interpret=False`` at the size ``chip_smoke.py``
runs it, to the installed TPU compiler for a *described* ``v5e:2x2``
(``on-chip-measurement`` guide, section 2). Nothing executes, so they say
nothing about results or speed - ``chip_smoke.py`` on the chip does that.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports this file.
Keep every such test in THIS file.
"""

import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # libtpu installs a handler that prints a stack trace when this process
    # is sent SIGTERM, which is how xdist ends its workers and how a suite
    # cut by its clock ends: the text lands on pytest's progress line.
    # Python had the default disposition; put it back.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(arrays, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            tuple(a.shape), a.dtype, sharding=sharding
        ),
        list(arrays),
    )


def _compile_mk(mk, sharding, fuel=1 << 22, on_device=()):
    """Compile ``Megakernel.run``'s program: the kernel inside the wrapper
    that splits the upload slab and packs what the host reads, laid out
    as ``run`` lays it out for a caller whose buffers are on the host (the
    small int32 ones ride the slab, the others cross alone) but for those
    named in ``on_device``, which it hands in as ``jax.Array``s."""
    from hclib_tpu.device.megakernel import SLAB_RIDE_BYTES

    assert mk.interpret is False
    lay = mk._exec_layout([
        "data:" + k for k, s in mk.data_specs.items()
        if s.dtype == jnp.int32 and 4 * np.prod(s.shape) < SLAB_RIDE_BYTES
        and k not in on_device
    ], ["data:" + k for k in on_device])
    words = sum(int(np.prod(s)) for s in lay.up.values())
    args = [jax.ShapeDtypeStruct((words,), jnp.int32)]
    args += [mk.data_specs[n[5:]] for n in lay.alone]
    return mk._build_exec(fuel, False, lay).lower(
        *_shapes(args, sharding)
    ).compile()


def _whole_copies(compiled, mk):
    """The compiled program's ``copy`` / ``copy-start`` instructions that
    produce a whole data buffer of ``mk`` of a MiB or more: what XLA puts
    in front of the kernel for an aliased buffer that was not donated."""
    import re

    big = {
        "[" + ",".join(map(str, s.shape)) + "]"
        for s in mk.data_specs.values()
        if np.prod(s.shape) * jnp.dtype(s.dtype).itemsize >= 1 << 20
    }
    assert big
    return [
        line.strip()[:120] for line in compiled.as_text().splitlines()
        if re.search(r"= \(?\w+(\[[\d,]*\])\S* copy(-start)?\(", line)
        and re.search(r"= \(?\w+(\[[\d,]*\])", line).group(1) in big
    ]


# ---- one builder per kernel; each returns after the compiler accepted it


def _fib_scalar(sh):
    from hclib_tpu.device.workloads import make_fib_megakernel

    _compile_mk(make_fib_megakernel(768, interpret=False), sh)


def _fib_batch(sh):
    from hclib_tpu.device.workloads import make_vfib_megakernel

    _compile_mk(make_vfib_megakernel(max_n=32, interpret=False), sh,
                fuel=1 << 30)


def _uts_t1l(sh):
    """Through uts_pallas itself, so the shapes are the ones its seeding
    derives for T1L. The seeding's jits run here on the CPU, and each
    shape they are called with is first compiled for the described chip;
    the jitted kernel is swapped for one that compiles the real kernel for
    the described chip and stops there."""
    import hclib_tpu.device.uts_pallas as up
    import hclib_tpu.device.uts_vec as uv
    from hclib_tpu.models.uts import T1L

    class Compiled(Exception):
        pass

    real = up._uts_dfs_pallas, uv.uts_seed_expand, uv.uts_seed_roots
    seeding = []

    def compile_only(*args, **kw):
        assert kw["interpret"] is False
        real[0].lower(*_shapes(args, sh), **kw).compile()
        raise Compiled

    def compiled_too(jitted):
        def run(*args, **kw):
            jitted.lower(*_shapes(args, sh), **kw).compile()
            seeding.append((jitted.__name__, args[1].shape[0], kw))
            return jitted(*args, **kw)

        return run

    up._uts_dfs_pallas = compile_only
    uv.uts_seed_expand = compiled_too(real[1])
    uv.uts_seed_roots = compiled_too(real[2])
    try:
        with pytest.raises(Compiled):
            up.uts_pallas(T1L, target_roots=256 * 1024, lanes=(64, 128),
                          min_idle_div=32, interpret=False)
    finally:
        up._uts_dfs_pallas, uv.uts_seed_expand, uv.uts_seed_roots = real
    # the device expanded T1L's lower levels and handed the roots over
    assert [name for name, _, _ in seeding][-2:] == [
        "uts_seed_expand", "uts_seed_roots"
    ], seeding
    assert seeding[-1][1] >= 300_125  # level 9, whole


def _uts_t3l(sh):
    """The binomial kernel of the cell uts-t3l, through uts_pallas itself
    at the cell's lanes and the engine's defaults: the seeding (the root's
    2,000 children, on the host) runs here, the jitted kernel is swapped
    for one that compiles the real kernel for the described chip and
    stops there. What the interpreter cannot say: whether Mosaic takes the
    balance round's in-row gathers, its one-row turn, the slab DMAs at a
    dynamic index and a ring of this height as loop-carried planes."""
    import hclib_tpu.device.uts_pallas as up
    from hclib_tpu.models.uts import T3L

    class Compiled(Exception):
        pass

    real = up._uts_bin_pallas
    seen = {}

    def compile_only(*args, **kw):
        assert kw["interpret"] is False
        seen.update(kw, slabs=args[0].shape)
        real.lower(*_shapes(args, sh), **kw).compile()
        raise Compiled

    up._uts_bin_pallas = compile_only
    try:
        with pytest.raises(Compiled):
            up.uts_pallas(T3L, lanes=(64, 128), interpret=False)
    finally:
        up._uts_bin_pallas = real
    # the root's non-leaf children fit one slab of 8,192 frames
    assert seen["slabs"] == (1, 7, 64, 128), seen
    assert seen["lanes"] == (64, 128) and seen["stack_size"] >= 2, seen


def _cholesky_8192(sh):
    from hclib_tpu.device.cholesky import make_cholesky_megakernel

    nt = 8192 // 512
    mk = make_cholesky_megakernel(
        nt, interpret=False, tile=512, fused_only=True
    )
    # device_cholesky hands its three buffers in on the device and they
    # are written, so donated: no copy of one (the parent of PR 40 had
    # two, 1.6 ms each on the chip). From the host, they are XLA's.
    on_device = _compile_mk(mk, sh, on_device=list(mk.data_specs))
    assert _whole_copies(on_device, mk) == []
    assert len(_whole_copies(_compile_mk(mk, sh), mk)) == 2


def _sw_fused(sh):
    from hclib_tpu.device.sw_pallas import _sw_pallas

    a = jax.ShapeDtypeStruct((1024, 1024), jnp.int32, sharding=sh)
    _sw_pallas.lower(a, a, interpret=False).compile()  # default block_b


def _sw_wave(sh):
    from hclib_tpu.device.smithwaterman import T, make_sw_wave_megakernel

    nt = 8192 // T
    mk = make_sw_wave_megakernel(nt, nt, interpret=False, with_h=False)
    _compile_mk(mk, sh)


def _forasync_1d(sh):
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import map_loop

    tk, _, _ = map_loop(64)
    _compile_mk(make_forasync_megakernel(tk, width=8, interpret=False), sh)


def _forasync_2d(sh):
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import stencil_loop

    tk, _, _ = stencil_loop(64, 1024)
    _compile_mk(make_forasync_megakernel(tk, width=8, interpret=False), sh)
    _compile_mk(make_forasync_megakernel(tk, width=0, interpret=False), sh)


def _forasync_hbm(sh):
    """The cell forasync-2d-hbm's program (benchmarks/configs/
    forasync-stencil.json): RECURSIVE over 32768 x 32768 in (256, 1024)
    tiles at width 8, its 8.6 GB of grids on the chip. The table is the
    live set's, the VMEM limit the slabs', ``gin`` an input only and
    ``gout`` donated: no temporary and no copy of either."""
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import stencil_loop

    tk, bounds, tile = stencil_loop(32768, 32768, 256, 1024)
    mk = make_forasync_megakernel(tk, width=8, interpret=False,
                                  space=(bounds, tile))
    assert mk.capacity == 64 and mk.read_only == ("gin",)
    assert 27 << 20 < mk.vmem_limit_bytes < 64 << 20
    compiled = _compile_mk(mk, sh, on_device=["gin", "gout"])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 32768 * 32768
    assert mem.temp_size_in_bytes < 1 << 20
    assert _whole_copies(compiled, mk) == []
    # chip_smoke's RECURSIVE arm
    tk, bounds, tile = stencil_loop(64, 1024)
    _compile_mk(make_forasync_megakernel(
        tk, width=8, interpret=False, space=(bounds, tile)), sh)


def _jacobi_steps(sh):
    """The cell jacobi-dep-hbm's program (benchmarks/configs/
    jacobi-taskdep.json): eight time steps of 32768 x 32768 in (256, 1024)
    tiles at width 8, both planes of the grid (8.66 GB) in ONE buffer on
    the chip, donated and written in place; the table sized from the
    replayed schedule, 8,192 countdowns in the value slots."""
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import jacobi_loop

    tk, bounds, tile = jacobi_loop(32768, 32768, 256, 1024, steps=8)
    mk = make_forasync_megakernel(tk, width=8, interpret=False,
                                  space=(bounds, tile))
    live = mk.fa_plan.simulate(8)["live_rows_max"]
    assert live < mk.capacity < 128 and mk.read_only == ()
    assert mk.num_values == 8 + 2 * 4096
    compiled = _compile_mk(mk, sh, on_device=["grid"])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 2 * 32784 * 33024
    assert mem.temp_size_in_bytes < 1 << 20
    assert _whole_copies(compiled, mk) == []
    # the scalar arm and the tutorial's size
    tk, bounds, tile = jacobi_loop(64, 1024, steps=3)
    for width in (8, 0):
        _compile_mk(make_forasync_megakernel(
            tk, width=width, interpret=False, space=(bounds, tile)), sh)


def _serve_stream(sh, delta=False):
    """chip_smoke's serve phase: three tenants, egress mailbox, telemetry
    (the tenant poll fetches row ``c`` from slot ``c`` modulo the region)."""
    from hclib_tpu.device.descriptor import RING_ROW, TaskGraphBuilder
    from hclib_tpu.device.egress import EGR_WORDS, EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.telemetry import LAT_BUCKETS, LAT_WORDS
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    def respond(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))
        ctx.set_out(ctx.arg(0) * 3 + 1)

    T, region, cap, depth = 3, 1024, 320, 64
    table = TenantTable(
        [TenantSpec(t, weight=w) for t, w in
         (("gold", 4), ("silver", 2), ("bronze", 1))],
        region, egress=EgressSpec(depth=depth),
    )
    mk = Megakernel(kernels=[("respond", respond)], capacity=cap,
                    num_values=8, succ_capacity=8, interpret=False)
    sm = StreamingMegakernel(mk, ring_capacity=T * region, tenants=table,
                             telemetry=True)
    tasks, succ, ring0, counts = TaskGraphBuilder().finalize(
        capacity=cap, succ_capacity=8
    )
    z = lambda *s: np.zeros(s, np.int32)  # noqa: E731
    lay = sm._entry_layout()
    blocks = dict(zip(lay.ins, [  # _build's argument order
        tasks, succ, ring0, counts, z(8), z(sm.ring_capacity, RING_ROW),
        z(8), z(T, 8), z(depth, EGR_WORDS), z(depth, EGR_WORDS), z(8),
        z(cap), z(1 + T, LAT_BUCKETS), z(cap, LAT_WORDS),
    ]))
    # What the chip runs is the entry program: the kernel between the
    # slab's split and join (one transfer each way an entry).
    up = {**lay.up, **(sm._delta_blocks() if delta else {})}
    slab = z(sum(int(np.prod(shape)) for shape in up.values()))
    args = [slab] + [blocks[n] for n in lay.kept]
    text = sm._build_entry(1 << 10, 64, delta).lower(
        *_shapes(args, sh)).compile().as_text()
    assert "tpu_custom_call" in text and ("scatter" in text) == delta


def _serve_stream_delta(sh):
    """The same stream's other entry program (serve-open-steady runs both):
    the slab also carries up to RING_DELTA_ROWS ring rows, which a scatter
    stores into the resident, donated ring in front of the kernel."""
    _serve_stream(sh, delta=True)


def _frontier(sh):
    from hclib_tpu.device.frontier import (
        _KINDS, Graph, make_frontier_megakernel,
    )
    from hclib_tpu.device.workloads import rmat_edges

    g = Graph(*rmat_edges(9, efactor=8, seed=7))
    _compile_mk(make_frontier_megakernel(
        _KINDS["sssp"](), g, width=8, capacity=768, interpret=False,
    ), sh)


def _search(sh):
    """The cell g500-bfs-search's build: Graph500's scale 22 by the
    shapes alone (a graph of that size is seconds of kernel 1), the
    adjacency, vertex table and queue handed in on the chip as
    ``GraphSearch`` does."""
    import types

    from hclib_tpu.device.frontier import (
        make_frontier_megakernel, search_kernel,
    )

    g = types.SimpleNamespace(n=1 << 22, nblocks=3_200_000)
    _compile_mk(make_frontier_megakernel(
        search_kernel(), g, width=8, capacity=128, interpret=False,
    ), sh, fuel=1 << 30, on_device=("indices", "vtab", "queue"))


def _dyngraph(sh):
    from hclib_tpu.device.dyngraph import DynGraph, make_dyngraph_megakernel
    from hclib_tpu.device.workloads import rmat_edges

    g = DynGraph(*rmat_edges(7, efactor=8, seed=7), spare_blocks=2,
                 upd_cap=24)
    _compile_mk(make_dyngraph_megakernel(
        "sssp", g, width=8, capacity=768, interpret=False,
    ), sh)


def _bnb(sh):
    from hclib_tpu.device.bnb import make_bnb_megakernel, make_knapsack

    kp = make_knapsack(16, seed=5)
    for buckets in (0, 8):  # default capacity: it must fit as shipped
        _compile_mk(make_bnb_megakernel(
            kp, width=4, priority_buckets=buckets, interpret=False,
        ), sh)


def _sparselu(sh):
    """The cell sparselu-dep-128's program (benchmarks/configs/
    sparselu-taskdep.json): 128 x 128 blocks of 128 x 128, the 1,768
    present blocks read where they lie, the factor's 8,320 (545 MB) and
    the inverses donated and written in place; the table sized from the
    replayed schedule, the masks and a word a block in the value slots,
    all of it inside a v5e's SMEM; both lanes' tiles in two halves for the
    cross-round prefetch, 8.2 MiB of VMEM scratch under the build's 32."""
    from jax.experimental.pallas import tpu as pltpu

    from hclib_tpu.device.megakernel import DEVICE_TABLE
    from hclib_tpu.device.sparselu import make_sparselu_megakernel

    mk = make_sparselu_megakernel(128, 128, interpret=False)
    tile = 128 * 128
    assert sum(
        int(np.prod(sp.shape)) * jnp.dtype(sp.dtype).itemsize
        for sp in mk.scratch_specs.values()
        if getattr(sp, "memory_space", None) == pltpu.VMEM
    ) == (2 * 16 * 3 * 4 + 2 * 8 * (4 + 2 + 2) + 4 + 4 * 2) * tile
    assert mk.vmem_limit_bytes == 32 << 20
    assert all(spec.prefetch for _, spec in mk.batch_specs)
    assert mk.slu_replay["live_rows_max"] < mk.capacity < 160
    assert mk.read_only == ("a",)
    assert mk.num_values == 16 + 4 * 128 * 4 + 128 * 128
    assert mk.smem_footprint() < DEVICE_TABLE["TPU v5 lite"]["smem_bytes"] / 2
    assert [spec.width for _, spec in mk.batch_specs] == [8, 16]
    compiled = _compile_mk(mk, sh, on_device=["a", "blocks", "linv"])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 8320 * 128 * 128 + 2 * 4 * 128**3
    assert mem.temp_size_in_bytes < 1 << 20
    assert _whole_copies(compiled, mk) == []


KERNELS = {
    f.__name__.lstrip("_"): f
    for f in (_fib_scalar, _fib_batch, _uts_t1l, _uts_t3l, _cholesky_8192,
              _sw_fused,
              _sw_wave, _forasync_1d, _forasync_2d, _forasync_hbm,
              _jacobi_steps, _sparselu,
              _serve_stream, _serve_stream_delta,
              _frontier, _search, _dyngraph, _bnb)
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_v5e_compiler_accepts(kernel, one_chip):
    KERNELS[kernel](one_chip)


def test_kernel_listing_compiles_its_wave_entry(topo):
    """``tools/kernel_listing.py --kernel wave`` (PR 54) is the build of
    ``_sw_wave`` above, the table as the engine sizes it: its child runs
    this very call with the compiler's dump on."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "tools", "kernel_listing.py")
    spec = importlib.util.spec_from_file_location("kernel_listing", path)
    kl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kl)
    name, compile_kernel, capacity = kl.KERNELS["wave"]
    assert (name, capacity) == ("tpu_custom_call", 568)
    compile_kernel(capacity)
    with pytest.raises(SystemExit, match="568"):
        compile_kernel(capacity + 1)


@pytest.mark.parametrize("tenants", [False, True], ids=["steal", "tenants"])
def test_v5e_compiler_accepts_resident_kernel_on_four_devices(topo, tenants):
    """chip_smoke.py --four-chips' program: the shard_mapped resident
    kernel over a mesh of the four described devices, every input sharded
    over all of them, with the kernel and the termination collective in
    the compiled module. ``tenants``: the same mesh with an injection
    ring cut into three tenant regions of 1,024 rows a device, so the
    mesh's tenant poll (its 8-row chunk fetched through ``region_slot``,
    the wrap Mosaic refused as first written) is in the kernel; no cell
    runs that build on the chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hclib_tpu.device.descriptor import RING_ROW, TaskGraphBuilder
    from hclib_tpu.device.megakernel import VBLOCK
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.sharded import abort_words, partition_builders
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    ndev, roots, cap = 4, 160, 640  # stress.forest_resident's defaults
    mesh = Mesh(np.array(topo.devices).reshape(ndev), ("q",))
    mk = make_fib_megakernel(
        capacity=cap, interpret=False,
        num_values=VBLOCK * cap + max(64, roots),
    )
    lanes = dict(
        inject=True, tenants=["gold", "silver", "bronze"],
        ring_capacity=3 * 1024,
    ) if tenants else {}
    rk = ResidentKernel(mk, mesh, migratable_fns=[FIB], homed=False,
                        window=16, **lanes)
    tasks, succ, ring, counts = partition_builders(
        mk, ndev, [TaskGraphBuilder() for _ in range(ndev)]
    )
    args = [  # ResidentKernel.run's argument order, steal-only build
        tasks, succ, ring, counts, np.zeros((ndev, mk.num_values), np.int32),
        np.zeros((ndev, rk.max_waits + 1, 3), np.int32),
    ]
    if tenants:  # iring, ictl, the stacked tctl block
        assert rk.region_rows == 1024
        args += [
            np.zeros((ndev, rk.ring_capacity, RING_ROW), np.int32),
            np.zeros((ndev, 8), np.int32),
            np.zeros((ndev, rk.T, 8), np.int32),
        ]
    args.append(abort_words(None, ndev))
    compiled = rk._build(256, 1 << 14, None).lower(
        *_shapes(args, NamedSharding(mesh, P("q")))
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    # the name a profiler trace gives the kernel (benchmarks/metrics/
    # mesh_round_us.json finds it by this name)
    assert "resident_mesh" in text


def test_smem_footprint_check_names_the_capacity_that_fits():
    """The SMEM check that replaces XLA's RESOURCE_EXHAUSTED: against the
    v5e row of the per-device_kind table it refuses the default
    capacity=4096 with the largest capacity that fits, accepts the 768
    the benches use, and a compiled build runs it at construction."""
    from hclib_tpu.device.megakernel import (
        DEVICE_TABLE, device_row, smem_bytes,
    )
    from hclib_tpu.device.workloads import make_fib_megakernel

    v5e = device_row("TPU v5 lite")
    assert v5e is DEVICE_TABLE["TPU v5 lite"] and v5e["smem_bytes"] == 1 << 20
    with pytest.raises(ValueError, match="no row"):
        device_row("TPU v9 imaginary")
    # a [capacity, 16] row pads to 128 lanes; 1-D arrays to 128..1024 words
    assert smem_bytes((768, 16)) == 768 * 512
    assert smem_bytes((8,)) == 512 and smem_bytes((4016,)) == 16384

    make_fib_megakernel(768, interpret=True).check_smem(v5e)
    big = make_fib_megakernel(4096, interpret=True)  # interpreter: no check
    with pytest.raises(ValueError, match=r"largest capacity that fits.* 848;"):
        big.check_smem(v5e)
    with pytest.raises(ValueError, match="capacity=4096"):
        make_fib_megakernel(4096, interpret=False)
    # 968 is where the v5e compiler itself stops accepting this kernel.
    make_fib_megakernel(968, interpret=True).check_smem(v5e)
    with pytest.raises(ValueError, match="fits.* 968;"):
        make_fib_megakernel(969, interpret=True).check_smem(v5e)
