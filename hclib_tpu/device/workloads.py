"""Device task kernels for the benchmark workloads (scalar + tile kernels).

These run inside the megakernel's ``lax.switch`` table. fib demonstrates
dynamic on-device spawning with continuation passing; arrayadd demonstrates
tile tasks that DMA HBM data through VMEM and use the VPU.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .descriptor import TaskGraphBuilder
from .megakernel import (
    VBLOCK,
    BatchContext,
    BatchSpec,
    KernelContext,
    Megakernel,
    fault_mix,
)

__all__ = [
    "device_fib",
    "device_arrayadd",
    "make_fib_megakernel",
    "make_vfib_megakernel",
    "device_vfib",
    "make_uts_megakernel",
    "device_uts_mk",
    "UTS_NODE",
    "batch_of",
    "rmat_edges",
    "stencil_loop",
    "stencil_body",
    "stencil_reference",
    "stencil_data",
    "map_loop",
    "map_body",
    "map_reference",
    "map_data",
]


# ------------------------------------------------------------------- fib

FIB = 0
SUM = 1


def _fib_kernel(ctx: KernelContext) -> None:
    n = ctx.arg(0)

    @pl.when(n < 2)
    def _():
        ctx.set_out(n)

    @pl.when(n >= 2)
    def _():
        # This task becomes its own continuation: the row is re-armed as
        # the SUM that waits for the two children, and keeps its
        # successors and its out slot where they lie (ctx.become; no second
        # row, no link copy, and complete() leaves the row alone). The
        # children write into the value block OWNED BY THIS ROW - no
        # allocator call, and the block recycles with the row when the SUM
        # completes (by which point its result is already in the parent's
        # block).
        # nargs declares each spawn's true arity: the scalar tier's cost IS
        # its instruction count, so dead arg-zeroing writes are skipped.
        ctx.become(SUM, 2)
        base = ctx.row_values(ctx.idx)
        ctx.set_arg(ctx.idx, 0, base)
        ctx.set_arg(ctx.idx, 1, base + 1)

        # The two children by a two-trip loop, not two spawns written out:
        # the v5e compiler turns every branch-free region it finds small
        # enough into predicated straight-line code, and a fork of two
        # written-out spawns is small enough since it lost its second row,
        # so that every LEAF walked through the fork's 80 bundles with
        # their stores switched off (131.3 ns a task; 119.6 with the loop,
        # 128.4 before ctx.become: my chip runs, PR 41). A loop is not
        # predicated, so the fork stays a branch that a leaf jumps.
        def child(i, _):
            ctx.spawn(FIB, [n - 1 - i], succ0=ctx.idx, out=base + i, nargs=1)
            return 0

        jax.lax.fori_loop(0, 2, child, 0)


def _sum_kernel(ctx: KernelContext) -> None:
    ctx.set_out(ctx.value(ctx.arg(0)) + ctx.value(ctx.arg(1)))


def batch_of(scalar_kernel, width: int = 8) -> BatchSpec:
    """Batched same-kind spelling of a scalar task kernel: one batch round
    pops up to ``width`` same-kind descriptors and runs ``scalar_kernel``
    once per live slot through ``BatchContext.slot_ctx`` - bit-identical to
    scalar dispatch (the per-slot context shares every ref), but the per-
    descriptor ring pop + lax.switch overhead is paid once per ROUND
    instead of once per task. This is how spawn-heavy scalar families
    (fib/UTS nodes) ride the batch tier; tile families with a genuinely
    fused body (SW waves, Cholesky updrow) write their own BatchSpec."""

    def body(ctx: BatchContext) -> None:
        for s in range(ctx.width):
            @pl.when(ctx.live(s))
            def _(s=s):
                scalar_kernel(ctx.slot_ctx(s))

    return BatchSpec(body, width=width)


def make_fib_megakernel(
    capacity: int = 768,  # SMEM windows pad scalars ~32B/word: ~800-row max
    interpret: Optional[bool] = None,
    num_values: Optional[int] = None,
    trace=None,
    batch_width: Optional[int] = None,
    checkpoint: Optional[bool] = None,
) -> Megakernel:
    # Descriptor rows recycle, and value blocks are row-owned (SUM reads
    # its children's results out of its own row's block), so both live
    # sets are ~ the spawn-tree depth and a small table runs arbitrarily
    # deep fibs. The value buffer must cover every row's block plus the
    # host slots.
    need = VBLOCK * capacity + 16  # 16 host slots for presets/outputs
    if num_values is None:
        num_values = need
    elif num_values < need:
        raise ValueError(
            f"fib uses row-owned value blocks: num_values must be >= "
            f"VBLOCK*capacity+16 = {need}, got {num_values}"
        )
    # batch_width routes the FIB kind through the batched same-kind tier
    # (one batch round runs up to batch_width fib bodies per-slot through
    # slot_ctx - bit-identical to scalar dispatch); SUM stays scalar: join
    # tasks become ready one at a time as their children complete, so a
    # SUM lane would fire near-empty batches for pure routing overhead.
    route = (
        {"fib": batch_of(_fib_kernel, width=batch_width)}
        if batch_width else None
    )
    return Megakernel(
        kernels=[("fib", _fib_kernel), ("sum", _sum_kernel)],
        capacity=capacity,
        num_values=num_values,
        succ_capacity=64,
        interpret=interpret,
        uses_row_values=True,
        trace=trace,
        route=route,
        checkpoint=checkpoint,
        # hclint reshard-class: fib is DELIBERATELY claimed migratable
        # on the mesh runners (forest seeds are link-free rows; the
        # exchanges' row filter keeps the spawned continuation chains
        # home) - annotate the intent so the audit shows the finding as
        # suppressed instead of flagging every forest run.
        verify_suppress=("reshard-class:fib",),
    )


def device_fib(
    n: int,
    capacity: int = 768,
    interpret: Optional[bool] = None,
    num_values: Optional[int] = None,
) -> Tuple[int, dict]:
    """Compute fib(n) entirely on-device via dynamic task spawning."""
    mk = make_fib_megakernel(capacity, interpret, num_values=num_values)
    b = TaskGraphBuilder()
    b.add(FIB, args=[n], out=0)
    ivalues, _, info = mk.run(b)
    return int(ivalues[0]), info


# ------------------------------------------------------- fib, vector tier

VFIB = 0


def make_vfib_megakernel(
    max_n: int = 32,
    lanes: Tuple[int, int] = (8, 128),
    interpret: Optional[bool] = None,
    capacity: int = 64,
) -> Megakernel:
    """fib on the megakernel's batch-dispatch tier: one seed descriptor in
    the scalar table; the subtree runs wide over VPU lanes
    (device/vector_engine.py). Far larger fibs fit than on the scalar tier
    (the tree lives in per-lane VMEM stacks, not SMEM descriptor rows)."""
    from .vector_engine import fib_spec

    return Megakernel(
        kernels=[("vfib", fib_spec(max_n=max_n, lanes=lanes))],
        capacity=capacity,
        num_values=16,
        succ_capacity=8,
        interpret=interpret,
    )


def device_vfib(
    n: int,
    lanes: Tuple[int, int] = (8, 128),
    interpret: Optional[bool] = None,
) -> Tuple[int, dict]:
    """Compute fib(n) via batched vector dispatch; info['executed'] counts
    the full recursion tree (2*fib(n+1) - 1 tasks)."""
    mk = make_vfib_megakernel(max_n=n + 2, lanes=lanes, interpret=interpret)
    b = TaskGraphBuilder()
    b.add(VFIB, args=[n], out=0)
    ivalues, _, info = mk.run(b)
    return int(ivalues[0]), info


# ------------------------------------------------------ UTS, scalar tier

UTS_NODE = 0


def make_uts_megakernel(
    seed: int = 19,
    q_millis: int = 440,
    m_children: int = 4,
    max_depth: int = 12,
    capacity: int = 1024,
    interpret: Optional[bool] = None,
    trace=None,
    checkpoint: Optional[bool] = None,
    quiesce_stride: Optional[int] = None,
    batch_width: Optional[int] = None,
) -> Megakernel:
    """Seeded unbalanced-tree search on the scalar megakernel tier: the
    dynamic-spawn UTS-style workload (the reference's north-star tree,
    models/uts.py, reduced to the descriptor ABI) used by the checkpoint
    tests/bench to quiesce a traversal mid-tree.

    Every node task counts itself into value slot 0 and spawns child c
    (c < ``m_children``) iff ``fault_mix(seed, c, node_id, 0, depth) <
    q_millis`` - the same in-kernel integer mixer the DeviceFaultPlan
    decision tables use, so the whole tree is a pure function of the
    seed (deterministic, reproducible, unbalanced by construction). The
    root (depth 0) spawns all ``m_children`` (the b0 root factor of UTS);
    ``max_depth`` bounds the traversal. Spawned rows are link-free
    (count-accumulate only), so they are migratable on every multi-device
    runner AND re-homeable across mesh sizes by
    ``CheckpointBundle.reshard``."""

    def node(ctx: KernelContext) -> None:
        ctx.set_value(0, ctx.value(0) + 1)
        node_id = ctx.arg(0)
        depth = ctx.arg(1)

        @pl.when(depth < max_depth)
        def _():
            for c in range(m_children):
                h = fault_mix(seed, c, node_id, 0, depth)
                exists = (depth == 0) | (h < q_millis)

                @pl.when(exists)
                def _(c=c):
                    ctx.spawn(
                        UTS_NODE,
                        [node_id * 31 + jnp.int32(7 * c + 1) + depth,
                         depth + 1],
                        nargs=2,
                    )

    # batch_width: run node expansion through the batched same-kind tier
    # (the whole tree is one kind, so every round past the root fires a
    # near-full batch); rows stay link-free, so batched UTS remains
    # migratable AND reshardable - the lanes-active checkpoint workload.
    route = (
        {"uts_node": batch_of(node, width=batch_width)}
        if batch_width else None
    )
    return Megakernel(
        kernels=[("uts_node", node)],
        capacity=capacity,
        num_values=16,
        succ_capacity=8,
        interpret=interpret,
        trace=trace,
        checkpoint=checkpoint,
        quiesce_stride=quiesce_stride,
        route=route,
    )


def device_uts_mk(
    seed: int = 19,
    interpret: Optional[bool] = None,
    mk: Optional[Megakernel] = None,
    **mk_kw,
) -> Tuple[int, dict]:
    """Run the seeded UTS tree to completion; returns (nodes, info)."""
    if mk is None:
        mk = make_uts_megakernel(seed=seed, interpret=interpret, **mk_kw)
    b = TaskGraphBuilder()
    b.add(UTS_NODE, args=[1, 0])
    ivalues, _, info = mk.run(b)
    return int(ivalues[0]), info


# --------------------------------------------------- n-queens, vector tier

VNQUEENS = 0


def device_nqueens(
    n: int,
    lanes: Tuple[int, int] = (8, 128),
    interpret: Optional[bool] = None,
) -> Tuple[int, dict]:
    """Count n-queens solutions via batched vector dispatch;
    info['executed'] counts safe partial placements (the search tree)."""
    from .vector_engine import nqueens_spec

    mk = Megakernel(
        kernels=[("vnqueens", nqueens_spec(n, lanes=lanes))],
        capacity=64,
        num_values=16,
        succ_capacity=8,
        interpret=interpret,
    )
    b = TaskGraphBuilder()
    b.add(VNQUEENS, args=[0], out=0)
    ivalues, _, info = mk.run(b)
    return int(ivalues[0]), info


# --------------------------------------- forasync tile loops (device tier)
#
# The two acceptance workloads of the forasync device tier
# (device/forasync_tier.py): a 2D Jacobi-style 5-point stencil and a
# map-style batched-apply loop. Both are int32 so "bit-identical across
# host forasync, scalar device dispatch, and the tile tier" is airtight
# (no float summation-order caveats); inputs are bounded so no arithmetic
# wraps. Each workload ships four spellings of the SAME computation:
# the TileKernel (device, both dispatch tiers derive from it), the
# per-index host-forasync body, a vectorized numpy reference, and a data
# factory - tests/bench/CI compare the spellings instead of trusting any
# one of them.

MAP_MUL = 3
MAP_ADD = 7


def stencil_loop(H: int, W: int, th: int = 8, tw: int = 128):
    """2D Jacobi-style stencil over an (H, W) interior, ``gin`` ->
    ``gout``, both int32:

        gout[i, j] = gin[i+1, j+1] + gin[i, j+1] + gin[i+2, j+1]
                   + gin[i+1, j] + gin[i+1, j+2]

    ``gin`` carries the interior at ``[1:H+1, 1:W+1]`` inside a zero halo
    and ``gout`` is the bare (H, W) interior. Returns ``(tile_kernel,
    bounds, tile)`` for the forasync entry points.

    Every slab window is (8, 128)-tile aligned, which is what the TPU's
    DMA engine and Mosaic's ``memref_slice`` require of the last two
    dims (an unaligned (th+2, tw+2) halo window passes the interpreter
    and is refused by the compiler). So a tile LOADS the aligned
    (th+8, tw+128) superset that starts at its own corner and slices the
    halo neighbourhood out of the loaded VALUE, and ``gin`` is allocated
    (H+8, W+128) so the last tile's superset stays in bounds; the store
    is the tile itself at its own aligned offset."""
    from .forasync_tier import Slab, TileKernel

    if th % 8 or tw % 128:
        raise ValueError(
            f"stencil tiles must be whole (8, 128) tiles, got ({th}, {tw})"
        )
    gin = jax.ShapeDtypeStruct((H + 8, W + 128), jnp.int32)
    gout = jax.ShapeDtypeStruct((H, W), jnp.int32)

    def compute(ins):
        v = ins["vin"]  # rows [lo0, lo0+th+8) x cols [lo1, lo1+tw+128)
        return {
            "vout": (
                v[1:th + 1, 1:tw + 1] + v[:th, 1:tw + 1]
                + v[2:th + 2, 1:tw + 1] + v[1:th + 1, :tw]
                + v[1:th + 1, 2:tw + 2]
            )
        }

    def corner(a):
        # Tile corners are multiples of the tile by construction; the
        # hint lets the compiler prove the window is tile-aligned.
        return pl.multiple_of(a[1], 8), pl.multiple_of(a[2], 128)

    tk = TileKernel(
        loads=[Slab(
            "vin", "gin",
            lambda a: (pl.ds(corner(a)[0], th + 8),
                       pl.ds(corner(a)[1], tw + 128)),
            (th + 8, tw + 128),
        )],
        stores=[Slab(
            "vout", "gout",
            lambda a: (pl.ds(corner(a)[0], th), pl.ds(corner(a)[1], tw)),
            (th, tw),
        )],
        compute=compute,
        data_specs={"gin": gin, "gout": gout},
        name="fa_stencil",
    )
    return tk, [H, W], [th, tw]


def stencil_body(gin: np.ndarray, gout: np.ndarray):
    """Per-index host-forasync body over the numpy grids (the host arm
    of the three-way bit-identity acceptance)."""

    def body(i, j):
        gout[i, j] = (
            gin[i + 1, j + 1] + gin[i, j + 1] + gin[i + 2, j + 1]
            + gin[i + 1, j] + gin[i + 1, j + 2]
        )

    return body


def stencil_reference(gin: np.ndarray) -> np.ndarray:
    """Vectorized numpy oracle (``stencil_data``'s gin -> the (H, W)
    interior)."""
    H, W = gin.shape[0] - 8, gin.shape[1] - 128
    c = gin[1:H + 1, 1:W + 1]
    return (
        c + gin[:H, 1:W + 1] + gin[2:H + 2, 1:W + 1]
        + gin[1:H + 1, :W] + gin[1:H + 1, 2:W + 2]
    )


def stencil_data(H: int, W: int, seed: int = 0):
    """(gin, gout) int32 grids in ``stencil_loop``'s layout; values
    bounded so the 5-point sum never wraps."""
    rng = np.random.default_rng(seed)
    gin = np.zeros((H + 8, W + 128), np.int32)
    gin[1:H + 1, 1:W + 1] = rng.integers(
        0, 1 << 20, size=(H, W), dtype=np.int32
    )
    return gin, np.zeros((H, W), np.int32)


# Halo of the time-stepped layout: a whole (8, 128) vector tile a side.
JAC_HR, JAC_HC = 8, 128
# What a tile of a step awaits of the step before: itself and the four
# tiles it shares an edge with, as offsets in tile units.
JAC_AWAITS = ((0, 0), (-1, 0), (1, 0), (0, -1), (0, 1))


def jacobi_loop(H: int, W: int, th: int = 8, tw: int = 128, steps: int = 1,
                awaits=JAC_AWAITS):
    """``steps`` time steps of ``stencil_loop``'s 5-point sum (int32,
    wrapping) in ONE layout, read and written in place: the buffer
    ``grid`` is ``(2, H + 16, W + 256)`` int32, two planes that each carry
    the (H, W) interior at ``[8:H+8, 128:W+128]`` inside a zero halo of one
    (8, 128) vector tile a side. Step s reads plane ``s & 1`` and writes
    the interior of plane ``(s + 1) & 1``; the halo is never stored to, so
    it stays zero. The caller hands step 0's grid in plane 0 and finds the
    grid after the last step in plane ``steps & 1``
    (``jacobi_result``). Returns ``(tile_kernel, bounds, tile)``.

    A tile loads FIVE aligned pieces into one VMEM staging buffer of
    ``(th + 16, tw + 256)``: itself, an 8-row strip above and below, a
    128-column strip left and right (31 % over the tile at (256, 1024)),
    and slices its neighbourhood out of the staged value. No corners: the
    aligned superset's corners lie in the diagonal neighbours' tiles, which
    a 5-point tile does not await, so loading them would read what a tile
    of the next step may be overwriting (``check_tile_windows`` refuses
    it), and awaiting nine tiles instead of five to load 2 % more that
    ``compute`` never reads would tie the front tighter for nothing.

    ``awaits`` is the declaration ``TileKernel`` takes; the default is what
    the five pieces need. Tests hand in a smaller set to see a missed
    dependence caught."""
    from .forasync_tier import Slab, TileKernel

    if th % 8 or tw % 128:
        raise ValueError(
            f"stencil tiles must be whole (8, 128) tiles, got ({th}, {tw})"
        )
    R, C = JAC_HR, JAC_HC
    grid = jax.ShapeDtypeStruct((2, H + 2 * R, W + 2 * C), jnp.int32)

    def compute(ins):
        v = ins["v"]  # rows [lo0 - 8, lo0 + th + 8) x cols [lo1 - 128, ...)
        return {
            "vout": (
                v[R:R + th, C:C + tw] + v[R - 1:R + th - 1, C:C + tw]
                + v[R + 1:R + th + 1, C:C + tw]
                + v[R:R + th, C - 1:C + tw - 1]
                + v[R:R + th, C + 1:C + tw + 1]
            )
        }

    def step(a):
        # A loop of one step carries no step word: its tiles are step 0's.
        return a[4] if len(a) > 4 else 0

    def piece(name, r0, nr, c0, nc):
        # The piece of the tile's padded neighbourhood that starts (r0, c0)
        # from the neighbourhood's corner, which in buffer coordinates is
        # the tile's own loop corner (the halo shifts both by one tile).
        def index(a):
            row = pl.multiple_of(a[1], 8) + r0
            col = pl.multiple_of(a[2], 128) + c0
            return step(a) & 1, pl.ds(row, nr), pl.ds(col, nc)

        return Slab(name, "grid", index, (nr, nc), into="v",
                    at=(pl.ds(r0, nr), pl.ds(c0, nc)))

    def out_index(a):
        row = pl.multiple_of(a[1], 8) + R
        col = pl.multiple_of(a[2], 128) + C
        return (step(a) + 1) & 1, pl.ds(row, th), pl.ds(col, tw)

    tk = TileKernel(
        loads=[
            piece("c", R, th, C, tw),
            piece("n", 0, R, C, tw),
            piece("s", R + th, R, C, tw),
            piece("w", R, th, 0, C),
            piece("e", R, th, C + tw, C),
        ],
        stores=[Slab("vout", "grid", out_index, (th, tw))],
        compute=compute,
        data_specs={"grid": grid},
        staging={"v": (th + 2 * R, tw + 2 * C)},
        name="fa_jacobi",
        steps=steps,
        awaits=awaits if steps > 1 else (),
    )
    return tk, [H, W], [th, tw]


def jacobi_data(H: int, W: int, seed: int = 0) -> np.ndarray:
    """``jacobi_loop``'s ``grid``: plane 0's interior uniform in
    [0, 2^20) from the seed (``stencil_data``'s values), plane 1's full of
    -1 (no value a step leaves behind by accident), both halos zero."""
    R, C = JAC_HR, JAC_HC
    rng = np.random.default_rng(seed)
    g = np.zeros((2, H + 2 * R, W + 2 * C), np.int32)
    g[0, R:R + H, C:C + W] = rng.integers(
        0, 1 << 20, size=(H, W), dtype=np.int32
    )
    g[1, R:R + H, C:C + W] = -1
    return g


def jacobi_result(grid, steps: int):
    """The (H, W) interior after the last step, out of ``grid`` as the
    loop left it."""
    R, C = JAC_HR, JAC_HC
    return grid[steps & 1, R:grid.shape[1] - R, C:grid.shape[2] - C]


def jacobi_reference(interior: np.ndarray, steps: int) -> np.ndarray:
    """Numpy oracle: the 5-point sum with a zero halo applied ``steps``
    times to an (H, W) int32 interior, wrapping as the device does."""
    cur = np.asarray(interior, np.int32)
    for _ in range(steps):
        p = np.pad(cur, 1)
        cur = (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
               + p[1:-1, :-2] + p[1:-1, 2:])
    return cur


def map_loop(T: int, th: int = 8, tw: int = 128):
    """Map-style batched-apply loop (the batched-inference shape): block
    t of the (T, th, tw) int32 input maps elementwise through
    ``x * MAP_MUL + MAP_ADD`` into the output block. The 1D loop runs
    over all T*th*tw elements with one (th*tw)-element tile per block,
    so the flat tile index IS the block index."""
    from .forasync_tier import Slab, TileKernel

    spec = jax.ShapeDtypeStruct((T, th, tw), jnp.int32)

    def compute(ins):
        return {"vout": ins["vin"] * MAP_MUL + MAP_ADD}

    tk = TileKernel(
        loads=[Slab("vin", "vin", lambda a: (a[0],), (th, tw))],
        stores=[Slab("vout", "vout", lambda a: (a[0],), (th, tw))],
        compute=compute,
        data_specs={"vin": spec, "vout": spec},
        name="fa_map",
    )
    return tk, [T * th * tw], [th * tw]


def map_body(vin: np.ndarray, vout: np.ndarray):
    """Per-index host-forasync body over flat views of the block arrays."""
    fin = vin.reshape(-1)
    fout = vout.reshape(-1)

    def body(i):
        fout[i] = fin[i] * MAP_MUL + MAP_ADD

    return body


def map_reference(vin: np.ndarray) -> np.ndarray:
    return (vin * MAP_MUL + MAP_ADD).astype(np.int32)


def map_data(T: int, th: int = 8, tw: int = 128, seed: int = 0):
    rng = np.random.default_rng(seed)
    vin = rng.integers(0, 1 << 20, size=(T, th, tw), dtype=np.int32)
    return vin, np.zeros_like(vin)


# ------------------------------------------------- R-MAT graph generator
#
# Seeded edge factory for the graph-analytics frontier tier
# (device/frontier.py): the skewed, power-law-ish degree distribution of
# the Graph500 R-MAT recursion is exactly the load shape ROADMAP
# direction 5 wants - hub vertices whose expansion floods the ready ring
# with same-kind EXPAND descriptors while the long tail trickles.


def rmat_edges(
    scale: int,
    efactor: int = 8,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    max_weight: int = 16,
):
    """Seeded R-MAT-style edge list over ``N = 2**scale`` vertices with
    ``efactor * N`` samples (self-loops dropped, duplicates merged, so
    the returned edge count is a bit lower). Returns ``(n, src, dst,
    weights)`` int32 arrays - weights uniform in [1, max_weight], for
    the SSSP arm. Pure function of the arguments (one seeded
    Generator), so every bench/test arm rebuilds the identical graph."""
    if scale < 1:
        raise ValueError(f"rmat scale must be >= 1, got {scale}")
    n = 1 << scale
    ne = int(efactor) * n
    rng = np.random.default_rng(seed)
    src = np.zeros(ne, np.int64)
    dst = np.zeros(ne, np.int64)
    d = 1.0 - a - b - c
    if d <= 0:
        raise ValueError(f"rmat quadrants must leave d > 0, got {d}")
    for _ in range(scale):
        sb = rng.random(ne) >= (a + b)  # src bit: lower half vs upper
        pd = np.where(sb, d / (c + d), b / (a + b))
        db = rng.random(ne) < pd
        src = (src << 1) | sb
        dst = (dst << 1) | db
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    src = (key // n).astype(np.int32)
    dst = (key % n).astype(np.int32)
    w = rng.integers(1, max_weight + 1, size=len(src)).astype(np.int32)
    return n, src, dst, w


# --------------------------------------------------------------- arrayadd

ADD_TILE = 0
_TILE = (8, 128)  # f32 min tile


def _addtile_kernel(ctx: KernelContext) -> None:
    t = ctx.arg(0)
    a, b_, c = ctx.data["a"], ctx.data["b"], ctx.data["c"]
    va, vb = ctx.scratch["va"], ctx.scratch["vb"]
    sems = ctx.scratch["sems"]
    in_a = pltpu.make_async_copy(a.at[t], va, sems.at[0])
    in_b = pltpu.make_async_copy(b_.at[t], vb, sems.at[1])
    in_a.start()
    in_b.start()
    in_a.wait()
    in_b.wait()
    va[:] = va[:] + vb[:]
    out = pltpu.make_async_copy(va, c.at[t], sems.at[2])
    out.start()
    out.wait()


def device_arrayadd(ntiles: int = 16, interpret: Optional[bool] = None):
    """c = a + b over (ntiles, 8, 128) f32 blocks, one tile task per block."""
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
        capacity=max(64, ntiles),
        num_values=8,
        succ_capacity=8,
        interpret=interpret,
    )
    b = TaskGraphBuilder()
    for t in range(ntiles):
        b.add(ADD_TILE, args=[t])
    rng = np.random.default_rng(0)
    a = rng.standard_normal(shape).astype(np.float32)
    bb = rng.standard_normal(shape).astype(np.float32)
    c = np.zeros(shape, dtype=np.float32)
    _, data, info = mk.run(b, data={"a": a, "b": bb, "c": c})
    return a, bb, np.asarray(data["c"]), info
