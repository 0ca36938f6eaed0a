"""Fully-fused Pallas UTS: the entire tree traversal in ONE resident kernel.

The XLA engine (uts_vec.py) emits the DFS step as ~1.3k separate VPU ops;
unfused intermediates round-trip HBM, putting the measured per-step wall
(~85us at 8192 lanes) ~8x above the raw op cost. Splitting only the
expansion loop into a Pallas phase kernel got ~16us/step but left ~1ms of
XLA glue per refill round (gathers + layout conversions around the custom
call) - at 60-250 rounds per run that glue dominated. This engine therefore
runs EVERYTHING on-core in one kernel launch:

- the DFS traversal (uts_vec.make_traversal - the exact driver and step
  shared with the XLA engine) with all lane state (~2 MB at (64,128)
  lanes) living in VMEM/registers;
- the shared-root-queue refill, re-expressed in Mosaic-supported primitives:
  * flat cumsum over the starved mask -> two triangular MXU matmuls (exact:
    counts <= nlanes << 2^24 in f32);
  * the root-window DMA -> a 1024-aligned dynamic row-block copy from HBM
    (the seeding lays the roots out (rows, 128); the residual offset folds
    into the gather indices);
  * the monotone claim gather -> same-shape ``take_along_axis`` passes
    (Mosaic's only gather form): claim ranks are a prefix sum, so each
    output row's indices span <= 127 and touch <= 2 window rows - select
    those two rows (clipped row-gathers), roll each by the row's start
    offset, stitch, then one in-row gather finishes the job.

The reference's work-stealing scheduler loop (src/hclib-runtime.c:705-724)
maps to the megakernel (device/megakernel.py) for task graphs; this is the
same persistent-kernel idea specialized to the data-parallel engine - the
core never returns to XLA until the tree is fully counted.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.uts import BIN, UTSParams
from ..runtime.spans import span
from .megakernel import resolve_interpret
from .uts_vec import (
    FRAME_WORDS,
    LANES,
    _call_bin,
    _engine_shape,
    _geo_only,
    _launch_once,
    _seeded,
    apply_claim,
    inrow_threshold_table,
    make_bin_traversal,
    make_traversal,
    padded_threshold_table,
    pool_of,
)

__all__ = ["uts_pallas"]

ALIGN = 1024  # dynamic DMA offsets must be 1024-aligned (Mosaic tiling)


def _mm_cumsum(mask, lanes):
    """Inclusive prefix sum of a 0/1 mask over flat lane order via two
    triangular MXU matmuls (exact in f32 for counts < 2^24)."""
    rows, cols = lanes
    m = mask.astype(jnp.float32)
    Uc = (
        jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 1)
    ).astype(jnp.float32)
    P = jnp.dot(m, Uc, preferred_element_type=jnp.float32)
    t = P[:, cols - 1 : cols]  # (rows, 1) row totals
    Ur = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    ).astype(jnp.float32)
    carry = jnp.dot(t.T, Ur, preferred_element_type=jnp.float32)  # (1, rows)
    return (P + carry.T).astype(jnp.int32)


def _row_select(win2d, a, lanes, winrows):
    """A[i, :] = win2d[a[i], :] for a (rows,)-vector of window-row indices.

    Mosaic's axis-0 dynamic gather is single-vreg-only, so this is a
    one-hot MXU matmul instead: onehot(a) (rows, winrows) @ win2d
    (winrows, cols). The MXU multiplies f32 inputs at bf16 precision
    (8-bit mantissa), so full 32-bit words are split into four BYTES -
    integers <= 255 are exact in bf16, the one-hot rows are 0/1, and each
    output sums exactly one product."""
    rows, cols = lanes
    oh = (
        a[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (rows, winrows), 1)
    ).astype(jnp.float32)
    out = jnp.zeros(lanes, jnp.int32)
    for b in range(4):
        byte = ((win2d >> (8 * b)) & 0xFF).astype(jnp.float32)
        got = jnp.dot(oh, byte, preferred_element_type=jnp.float32).astype(
            jnp.int32
        )
        out = out | (got << (8 * b))
    return out


def _monotone_gather(win2d, idx, lanes, winrows):
    """out[i,j] = win2d.flat[idx[i,j]] for flat-monotone idx (a prefix-sum
    rank + offset): each output row spans <= cols indices, touching <= 2
    window rows - so two row-selects + per-row rolls + one in-row gather."""
    rows, cols = lanes
    start = idx[:, 0]  # (rows,) monotone
    a = start // cols
    c = start % cols
    A = _row_select(win2d, a, lanes, winrows)
    B = _row_select(win2d, jnp.minimum(a + 1, winrows - 1), lanes, winrows)
    j = jax.lax.broadcasted_iota(jnp.int32, lanes, 1)
    roll = (j + c[:, None]) % cols
    Ar = jnp.take_along_axis(A, roll, axis=1)
    Br = jnp.take_along_axis(B, roll, axis=1)
    W = jnp.where(c[:, None] + j < cols, Ar, Br)  # W[i,j] = flat[start_i+j]
    o = jnp.clip(idx - start[:, None], 0, cols - 1)
    return jnp.take_along_axis(W, o, axis=1)


def _dfs_kernel(
    S: int,
    lanes: tuple,
    thresholds,
    min_idle: int,
    max_steps: int,
    winrows: int,
    # refs
    roots_state_ref,  # ANY (5, Rrows, 128) i32 (u32 bits)
    roots_count_ref,  # ANY (Rrows, 128) i32
    scal_ref,  # SMEM (3,): R (real root count), d0, gen_mx
    tab_ref,  # VMEM (K, 128): in-row threshold table ((1,128) dummy when
    # the shape is depth-independent - kernels cannot capture constants)
    nodes_ref, leaves_ref, maxd_ref,  # VMEM lanes, outputs
    ctl_ref,  # SMEM (3,): steps, unfinished, refill rounds
    wstate, wcount, sems,  # scratch: (5, winrows, 128), (winrows, 128), DMA
) -> None:
    rows, cols = lanes
    nlanes = rows * cols
    R = scal_ref[0]
    d0 = scal_ref[1]
    gen_mx = scal_ref[2]

    def refill(sp, next_root, st0, ch0, cn0, dp0):
        starved = sp < 0
        cum = _mm_cumsum(starved, lanes)
        avail = R - next_root
        claim = starved & (cum <= avail)
        aligned = (next_root // ALIGN) * ALIGN
        rowstart = aligned // cols  # divisible by ALIGN/cols = 8
        cps = [
            pltpu.make_async_copy(
                roots_state_ref.at[i, pl.ds(rowstart, winrows)],
                wstate.at[i],
                sems.at[i],
            )
            for i in range(5)
        ]
        cpc = pltpu.make_async_copy(
            roots_count_ref.at[pl.ds(rowstart, winrows)], wcount, sems.at[5]
        )
        for cp in cps:
            cp.start()
        cpc.start()
        for cp in cps:
            cp.wait()
        cpc.wait()
        idx = jnp.clip(cum - 1, 0, nlanes - 1) + (next_root - aligned)
        rst = [
            _monotone_gather(
                wstate[i], idx, lanes, winrows
            ).astype(jnp.uint32)
            for i in range(5)
        ]
        rcn = _monotone_gather(wcount[...], idx, lanes, winrows)
        sp, st0, ch0, cn0, dp0 = apply_claim(
            claim, rst, rcn, d0, sp, st0, ch0, cn0, dp0
        )
        next_root = next_root + jnp.minimum(
            jnp.sum(starved.astype(jnp.int32)), avail
        )
        return sp, next_root, st0, ch0, cn0, dp0

    run = make_traversal(
        S, lanes, thresholds, gen_mx, min_idle, max_steps, refill, R,
        inrow_table=tab_ref[...] if thresholds is None else None,
    )
    sp, next_root, nodes, leaves, maxd, steps, refills = run()
    nodes_ref[...] = nodes
    leaves_ref[...] = leaves
    maxd_ref[...] = maxd
    ctl_ref[0] = steps
    ctl_ref[1] = (jnp.any(sp >= 0) | (next_root < R)).astype(jnp.int32)
    ctl_ref[2] = refills


@functools.partial(
    jax.jit,
    static_argnames=(
        "stack_size", "thresholds", "max_steps", "lanes",
        "min_idle_div", "interpret", "vmem_limit_bytes",
    ),
)
def _uts_dfs_pallas(
    roots_state,  # (5, Rrows, 128) i32 (u32 bits), padded + aligned
    roots_count,  # (Rrows, 128) i32
    scal,  # (3,) i32 - [R (real root count), d0, gen_mx]
    tab,  # (K, 128) i32 in-row threshold table ((1, 128) dummy for FIXED)
    stack_size: int,
    thresholds,  # static ints (FIXED fast path) or None (runtime table)
    max_steps: int,
    lanes: tuple,
    min_idle_div: int = 8,
    interpret: bool = False,
    vmem_limit_bytes: int = 100 * 2**20,
):
    S = stack_size
    rows, cols = lanes
    nlanes = rows * cols
    min_idle = max(64, nlanes // min_idle_div)
    winrows = nlanes // cols + ALIGN // cols  # window covers slack + claims
    i32 = jnp.int32
    kernel = pl.pallas_call(
        functools.partial(
            _dfs_kernel, S, lanes, thresholds, min_idle,
            max_steps, winrows,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(lanes, i32),  # nodes
            jax.ShapeDtypeStruct(lanes, i32),  # leaves
            jax.ShapeDtypeStruct(lanes, i32),  # maxd
            jax.ShapeDtypeStruct((3,), i32),   # steps, unfinished, refills
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(
            [pl.BlockSpec(memory_space=pltpu.VMEM)] * 3
            + [pl.BlockSpec(memory_space=pltpu.SMEM)]
        ),
        scratch_shapes=[
            pltpu.VMEM((5, winrows, cols), i32),
            pltpu.VMEM((winrows, cols), i32),
            pltpu.SemaphoreType.DMA((6,)),
        ],
        name="uts_dfs",  # the kernel's name in a profiler trace
        interpret=interpret,  # bool: the fast XLA-backed interpreter
        # (InterpretParams would select the slow Mosaic one - only
        # remote-DMA/semaphore kernels need that; see megakernel.py)
        # Lane state + refill windows + a (K,128) threshold table overflow
        # the compiler's default 16 MiB scoped-vmem budget at (64,128)
        # lanes; real VMEM is 128 MiB on v5e.
        compiler_params=(
            None
            if interpret
            else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
        ),
    )
    nodes, leaves, maxd, ctl = kernel(roots_state, roots_count, scal, tab)
    return (
        # Per-lane planes, not totals: totals are summed on the host in
        # int64 so trees beyond 2^31 total nodes (T1XXL's 4.23B) count
        # correctly while per-lane counters stay comfortably in int32.
        nodes,
        leaves,
        maxd,
        ctl[0],
        ctl[1] != 0,
        ctl[2],
    )


def _bin_kernel(
    S: int,
    lanes: tuple,
    every: int,
    slabs0: int,
    pool_slabs: int,
    unroll: bool,
    # refs
    scal_ref,  # SMEM (4,): R (roots), below, m, max_steps
    pool_in_ref,  # ANY (pool_slabs, FRAME_WORDS, rows, 128) i32: the pool,
    # its first slabs0 slabs the roots (aliased to pool_ref)
    nodes_ref, leaves_ref, maxd_ref, spmax_ref,  # VMEM lanes, outputs
    ctl_ref,  # SMEM (10,): steps, unfinished, rounds, then the pool's seven
    pool_ref,  # ANY: the pool, in place
    ebuf, sem,  # scratch: one slab in VMEM (FRAME_WORDS, rows, 128), DMA
) -> None:
    """The binomial traversal, whole, in one resident kernel: the step,
    the balance round and the driver are uts_vec's (``make_bin_traversal``,
    its steps written out if ``unroll``); what is the kernel's own is the
    pool's far end, whole slabs between the exchange buffer and HBM by one
    DMA each."""
    del pool_in_ref  # the same buffer as pool_ref

    def spill(_, do, k, planes):
        @pl.when(do)
        def _():
            for w in range(FRAME_WORDS):
                ebuf[w] = planes[w]
            cp = pltpu.make_async_copy(ebuf, pool_ref.at[k], sem.at[0])
            cp.start()
            cp.wait()

    def fetch(_, do, k):
        @pl.when(do)
        def _():
            cp = pltpu.make_async_copy(
                pool_ref.at[jnp.maximum(k, 0)], ebuf, sem.at[0])
            cp.start()
            cp.wait()

        return tuple(ebuf[w] for w in range(FRAME_WORDS))

    run = make_bin_traversal(
        S, lanes, scal_ref[3], scal_ref[0], below=scal_ref[1],
        m=scal_ref[2], every=every, slabs0=jnp.int32(slabs0),
        pool_slabs=pool_slabs, pstate=None, spill=spill, fetch=fetch,
        roll_rows=lambda x: pltpu.roll(x, 1, 0), unroll=unroll,
    )
    nodes, leaves, maxd, spmax, steps, unfinished, rounds, counters = run()
    nodes_ref[...] = nodes
    leaves_ref[...] = leaves
    maxd_ref[...] = maxd
    spmax_ref[...] = spmax
    ctl_ref[0] = steps
    ctl_ref[1] = unfinished.astype(jnp.int32)
    ctl_ref[2] = rounds
    for i, c in enumerate(counters):
        ctl_ref[3 + i] = c


@functools.partial(
    jax.jit,
    static_argnames=(
        "stack_size", "lanes", "every", "pool_slabs", "interpret",
        "unroll", "vmem_limit_bytes",
    ),
)
def _uts_bin_pallas(
    slabs,  # (n0, FRAME_WORDS, rows, 128) i32 - the roots, as slabs
    scal,  # (4,) i32 - [R, below, m, max_steps]
    stack_size: int,
    lanes: tuple,
    every: int,
    pool_slabs: int,
    interpret: bool = False,
    unroll: Optional[bool] = None,
    vmem_limit_bytes: int = 100 * 2**20,
):
    # The compiled kernel writes its steps out; the interpreter keeps them
    # in a loop (written out, XLA's CPU backend compiles a ring of 2 in
    # 14 s for 5 and a ring of 8 in 53) unless a test asks for the driver
    # the chip runs.
    unroll = not interpret if unroll is None else unroll
    pool = pool_of(slabs, pool_slabs)
    i32 = jnp.int32
    plane = jax.ShapeDtypeStruct(lanes, i32)
    kernel = pl.pallas_call(
        functools.partial(
            _bin_kernel, stack_size, lanes, every, slabs.shape[0],
            pool.shape[0], unroll,
        ),
        out_shape=(
            plane, plane, plane, plane,  # nodes, leaves, maxd, spmax
            jax.ShapeDtypeStruct((10,), i32),
            jax.ShapeDtypeStruct(pool.shape, i32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=tuple(
            [pl.BlockSpec(memory_space=pltpu.VMEM)] * 4
            + [pl.BlockSpec(memory_space=pltpu.SMEM),
               pl.BlockSpec(memory_space=pl.ANY)]
        ),
        scratch_shapes=[
            pltpu.VMEM(slabs.shape[1:], i32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        input_output_aliases={1: 5},
        # a name that starts uts_dfs: the trace patterns of the UTS
        # metrics find both kernels
        name="uts_dfs_bin",
        interpret=interpret,
        compiler_params=(
            None
            if interpret
            else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
        ),
    )
    nodes, leaves, maxd, spmax, ctl, _ = kernel(scal, pool)
    return (nodes, leaves, maxd, ctl[0], ctl[1] != 0, ctl[2], spmax, ctl[3:])


def uts_pallas(
    params: UTSParams,
    target_roots: Optional[int] = None,
    max_steps: Optional[int] = None,
    device=None,
    lanes: Tuple[int, int] = LANES,
    min_idle_div: int = 8,
    interpret: Optional[bool] = None,
    depth_bound: Optional[int] = None,
    vmem_limit_bytes: int = 100 * 2**20,
    stack_pad: Optional[int] = None,
    table_cols: Optional[int] = None,
    stack_size: Optional[int] = None,
) -> dict:
    """uts_vec with the whole traversal fused into one Pallas kernel; same
    exact counts, same seeding (``uts_vec._seeded``: the tree's top on the
    host, its larger levels and the roots' sort and layout on the device),
    same result dict.

    All GEO shapes run fused: FIXED on the depth-independent threshold
    fast path; LINEAR/CYCLIC (canonical T5/T2) and EXPDEC via the same
    exact per-depth threshold tables as uts_vec, realized on-core as
    same-shape ``take_along_axis`` in-row lookups (the one gather form
    Mosaic supports); the table's depth cap must fit a 128-lane row.
    EXPDEC's cap comes from ``depth_bound`` (default 8*gen_mx) and the
    run fails loudly if the tree actually reaches it. The scoped-vmem
    budget defaults to 100 MiB (sized for v5e's 128 MiB physical VMEM);
    pass a smaller ``vmem_limit_bytes`` on TPU generations with less
    (mirrors Megakernel.vmem_limit_bytes).

    One call is one traversal: one seeding, one launch of the kernel, one
    readback. ``device_seconds`` is that launch, and the first launch of a
    shape compiles: a caller that wants a rate calls twice.

    A BINOMIAL tree runs as ``uts_vec`` says (its keyword ``stack_size``),
    fused likewise:
    the lanes' rings and the exchange buffer in VMEM, the pool's slabs in
    HBM, moved by one DMA each, and no return to the host before the tree
    is counted."""
    if lanes[1] != 128:
        raise ValueError("uts_pallas lanes must be (rows, 128)")
    interpret = resolve_interpret(interpret)
    if max_steps is None:
        max_steps = (1 << 31) - 1
    rows, cols = lanes
    nlanes = rows * cols
    if params.tree == BIN:
        return _call_bin(
            "uts_pallas", _uts_bin_pallas, params, lanes, device, max_steps,
            stack_size,
            dict(target_roots=target_roots, depth_bound=depth_bound,
                 stack_pad=stack_pad, table_cols=table_cols),
            interpret=interpret, vmem_limit_bytes=vmem_limit_bytes,
        )
    _geo_only(stack_size)
    if target_roots is None:
        target_roots = 16 * LANES[0] * LANES[1]
    # Padded so any aligned window [align_down(next_root), +nlanes+ALIGN)
    # is in bounds (next_root <= R), laid out as (Rrows, 128) for row-block
    # DMA. PAD_QUANTUM (a multiple of ALIGN) keeps trees with different
    # root counts on one padded shape, sharing one compiled kernel (R is a
    # runtime scalar; only the padded shape is static).
    seed, roots, result = _seeded(
        params, target_roots, device, nlanes + ALIGN, True
    )
    if roots is None:
        return result
    d0 = seed[2]
    with span("uts.stage"):
        thr, stack_size, cap, bounded = _engine_shape(
            params, d0, depth_bound, stack_pad
        )
        if thr is not None:
            tabnp = np.zeros((1, cols), np.int32)  # unused dummy input
        else:
            # The padded in-row table is a kernel INPUT. max_rows =
            # cols - 1: the in-row gather clips depth to column cols - 1
            # and needs that column to stay -1 padding, so the row
            # quantization must not round past it (restores depth caps up
            # to cols - 2 = 126 that the plain 16-row round-up would
            # reject). table_cols (like stack_pad) opts into a shared
            # width class so different trees reuse one compiled engine.
            tabnp = inrow_threshold_table(
                padded_threshold_table(
                    params, cap, max_rows=cols - 1, min_cols=table_cols
                ),
                cols,
            )
        args = roots + jax.block_until_ready(jax.device_put(
            (np.array([result["roots"], d0, params.gen_mx], np.int32),
             tabnp),
            device,
        ))  # the upload belongs to this span
        kw = dict(
            stack_size=stack_size,
            thresholds=thr,
            max_steps=max_steps,
            lanes=tuple(lanes),
            min_idle_div=min_idle_div,
            interpret=interpret,
            vmem_limit_bytes=vmem_limit_bytes,
        )
    return _launch_once(
        "uts_pallas", lambda: _uts_dfs_pallas(*args, **kw), result, seed,
        nlanes, max_steps, cap if bounded else None, interpret,
    )


if __name__ == "__main__":  # pragma: no cover
    import sys

    from ..models.uts import T1, T1L, T3, T3L, T_TINY

    name = sys.argv[1] if len(sys.argv) > 1 else "T_TINY"
    params = {"T1": T1, "T1L": T1L, "T3": T3, "T3L": T3L,
              "T_TINY": T_TINY}[name]
    print(uts_pallas(params))
