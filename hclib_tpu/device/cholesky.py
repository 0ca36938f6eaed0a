"""Tiled Cholesky inside the megakernel: MXU tile tasks on a DDF DAG.

Same dependency structure as the host model (models/cholesky.py; reference
test/cholesky/cholesky.cpp), with the tile kernels designed for the TPU
compute units rather than translated from LAPACK:

- POTRF (VPU + MXU): ``factor_and_inv`` - serial math confined to 8x8
  diagonal micro-blocks; panels, trailing updates, and the inverse are MXU
  block algebra (ops/tiles.py). Writes L_kk (f32) and inv(L_kk) PRE-SPLIT
  to bf16 hi/lo.
- TRSM (MXU): with inv(L_kk) available, the triangular solve is one
  3-pass matmul: A_ik <- A_ik inv(L_kk)^T. The default graph runs it as a
  COLUMN STREAM (one task per step k): inv's split stays resident while
  the A_ik tiles double-buffer through, and each result is stored twice -
  f32 (the factor output) and bf16 hi/lo (the ``lsp`` operand cache).
- UPDROW (MXU, row-fused trailing update): one task per (row i, step k)
  performs A_ij -= L_ik L_jk^T for all j in (k, i] (the SYRK j = i case
  included). Both L operands stream from ``lsp`` ALREADY SPLIT, so the
  hot loop is exactly the three MXU passes plus one subtract - no VPU
  split work (splitting both operands per iteration measured ~15% of the
  stream's wall clock). L_ik stays resident for the row; (A_ij, L_jk)
  pairs double-buffer so the next pair's DMA rides under the current
  GEMM.

Why 3 passes: f32 data, MXU matmuls at ~f32 accuracy via the bf16 hi/lo
split (ops/tiles.mm_nt_split). This sets the physics of the benchmark: a
3-pass f32-accurate GEMM can never exceed 1/3 of the chip's bf16 matmul
clock, so the meaningful utilization number is (achieved f32-effective
FLOP/s) / (peak/3) - the cell ``cholesky-8192`` reports the share of
the whole bf16 peak as ``chol_roofline``, whose ceiling is so 33 %.
"""

from __future__ import annotations

import contextlib
import functools as _ft
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.tiles import (
    dma_copy as _dma,
    factor_and_inv,
    mm_nt_rsplit as _mm_nt_rsplit,
    mm_nt_split as _mm_nt_split,
    split_bf16 as _split,
)
from ..runtime.spans import span
from .descriptor import TaskGraphBuilder
from .megakernel import KernelContext, Megakernel

__all__ = ["device_cholesky", "build_cholesky_graph", "make_cholesky_megakernel"]

T = 128  # default tile edge (MXU-native); 256+ amortizes scheduling

POTRF = 0
TRSM = 1
UPDROW = 2
TRSMCOL = 3


def _load_all(pairs, sems) -> None:
    """Start every (src, dst) copy, then wait - loads ride the DMA engines
    concurrently instead of serializing start/wait per tile."""
    cps = [
        pltpu.make_async_copy(src, dst, sems.at[i])
        for i, (src, dst) in enumerate(pairs)
    ]
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()


def _potrf_kernel(ctx: KernelContext, ts: int = T, fbase: int = 128) -> None:
    k = ctx.arg(0)
    tiles, linvsp = ctx.data["tiles"], ctx.data["linvsp"]
    va = ctx.scratch["va"]
    rvh, rvl = ctx.scratch["rvh"], ctx.scratch["rvl"]
    sem = ctx.scratch["sems"]
    _dma(tiles.at[k, k], va, sem.at[0])
    l, inv = factor_and_inv(va[:], ts, base=fbase)
    va[:] = l
    ih, il = _split(inv)
    rvh[:] = ih
    rvl[:] = il
    _load_all(
        [(va, tiles.at[k, k]), (rvh, linvsp.at[k, 0]), (rvl, linvsp.at[k, 1])],
        sem,
    )


def _trsm_kernel(ctx: KernelContext, ts: int = T) -> None:
    """Tile-at-a-time TRSM (the unfused graph's form): one 3-pass matmul
    against the resident inverse split, stored f32 + split."""
    i, k = ctx.arg(0), ctx.arg(1)
    tiles, linvsp, lsp = ctx.data["tiles"], ctx.data["linvsp"], ctx.data["lsp"]
    f32a, f32b = ctx.scratch["f32a"], ctx.scratch["f32b"]
    bfh, bfl = ctx.scratch["bfh"], ctx.scratch["bfl"]
    rvh, rvl = ctx.scratch["rvh"], ctx.scratch["rvl"]
    sem = ctx.scratch["sems"]
    _load_all(
        [(tiles.at[i, k], f32a.at[0]), (linvsp.at[k, 0], rvh),
         (linvsp.at[k, 1], rvl)],
        sem,
    )
    s = _mm_nt_rsplit(f32a[0], rvh[:], rvl[:])  # A_ik inv(L_kk)^T
    f32b[0] = s
    sh, sl = _split(s)
    bfh[0] = sh
    bfl[0] = sl
    _load_all(
        [(f32b.at[0], tiles.at[i, k]), (bfh.at[0], lsp.at[i, k, 0]),
         (bfl.at[0], lsp.at[i, k, 1])],
        sem,
    )


def _trsmcol_kernel(ctx: KernelContext, ts: int = T, nt: int = 0) -> None:
    """Column-fused TRSM stream (one task per step k): inv(L_kk)'s split
    stays resident; the A_ik tiles (i = k+1 .. nt-1) double-buffer
    through, each result stored back f32 AND bf16 hi/lo (the ``lsp``
    operand cache the trailing updates stream from). On a single core the
    DAG's TRSM tiles run back-to-back anyway; fusing them removes
    per-tile dispatch and lets every load/store ride under a neighbor's
    matmul."""
    k = ctx.arg(0)
    tiles, linvsp, lsp = ctx.data["tiles"], ctx.data["linvsp"], ctx.data["lsp"]
    f32a, f32b = ctx.scratch["f32a"], ctx.scratch["f32b"]
    bfh, bfl = ctx.scratch["bfh"], ctx.scratch["bfl"]
    rvh, rvl = ctx.scratch["rvh"], ctx.scratch["rvl"]
    sem = ctx.scratch["sems"]
    sl = ctx.scratch["sload"]  # (2, 3) load sems (only [:, 0] used here)
    ss = ctx.scratch["sstore"]  # (2, 3): per-slot {f32, hi, lo} store sems
    _load_all([(linvsp.at[k, 0], rvh), (linvsp.at[k, 1], rvl)], sem)
    nj = nt - 1 - k  # i walks k+1 .. nt-1

    def start_load(slot, i) -> None:
        pltpu.make_async_copy(
            tiles.at[i, k], f32a.at[slot], sl.at[slot, 0]
        ).start()

    def start_stores(slot, i) -> None:
        pltpu.make_async_copy(
            f32b.at[slot], tiles.at[i, k], ss.at[slot, 0]
        ).start()
        pltpu.make_async_copy(
            bfh.at[slot], lsp.at[i, k, 0], ss.at[slot, 1]
        ).start()
        pltpu.make_async_copy(
            bfl.at[slot], lsp.at[i, k, 1], ss.at[slot, 2]
        ).start()

    def wait_stores(slot, i) -> None:
        pltpu.make_async_copy(
            f32b.at[slot], tiles.at[i, k], ss.at[slot, 0]
        ).wait()
        pltpu.make_async_copy(
            bfh.at[slot], lsp.at[i, k, 0], ss.at[slot, 1]
        ).wait()
        pltpu.make_async_copy(
            bfl.at[slot], lsp.at[i, k, 1], ss.at[slot, 2]
        ).wait()

    start_load(0, k + 1)

    def body(t, _):
        i = k + 1 + t
        cur = t % 2
        nxt = 1 - cur

        @pl.when(t + 1 < nj)
        def _():
            # f32a[nxt] was an INPUT at t-1 (read synchronously by that
            # iteration's matmul), so prefetching over it is safe.
            start_load(nxt, i + 1)

        pltpu.make_async_copy(tiles.at[i, k], f32a.at[cur], sl.at[cur, 0]).wait()
        s = _mm_nt_rsplit(f32a[cur], rvh[:], rvl[:])
        # Slot cur's OUTPUT buffers last stored at t-2 (dst row i-2);
        # those transfers must land before this compute overwrites them.
        @pl.when(t >= 2)
        def _():
            wait_stores(cur, i - 2)

        f32b[cur] = s
        sh, slo = _split(s)
        bfh[cur] = sh
        bfl[cur] = slo
        start_stores(cur, i)
        return 0

    jax.lax.fori_loop(0, nj, body, 0)
    last = (nj - 1) % 2

    @pl.when(nj >= 2)
    def _():
        wait_stores(1 - last, k + nj - 1)

    wait_stores(last, k + nj)


def _updrow_stream(ctx, i, k, lh, ll) -> None:
    """The row-fused trailing-update stream for row ``i`` at step ``k``
    with the resident L_ik split already loaded (``lh``/``ll`` values):
    A_ij -= L_ik L_jk^T for j in (k, i].

    The (A_ij, L_jk-split) streams double-buffer through two slots -
    iteration t starts the DMAs for t+1 before computing t, and
    store-backs ride their own semaphores so a slot is only reused once
    its previous store completed. The SYRK j = i case needs no special
    path: lsp[j, k] at j = i IS the resident L_ik (same bits). Every
    started DMA is waited exactly once (the epilogue drains the last two
    stores), so the scalar kernel and the batched body can both run this
    back to back. ``ctx`` may be a KernelContext or a BatchContext (only
    ``data``/``scratch`` are touched)."""
    tiles, lsp = ctx.data["tiles"], ctx.data["lsp"]
    f32a = ctx.scratch["f32a"]
    bfh, bfl = ctx.scratch["bfh"], ctx.scratch["bfl"]
    sl = ctx.scratch["sload"]  # (2, 3): per-slot {A, L-hi, L-lo}
    ss = ctx.scratch["sstore"]  # (2, 3): [slot, 0] = A store-back
    nj = i - k  # j walks k+1 .. i

    def start_loads(slot, j) -> None:
        pltpu.make_async_copy(tiles.at[i, j], f32a.at[slot], sl.at[slot, 0]).start()
        pltpu.make_async_copy(lsp.at[j, k, 0], bfh.at[slot], sl.at[slot, 1]).start()
        pltpu.make_async_copy(lsp.at[j, k, 1], bfl.at[slot], sl.at[slot, 2]).start()

    start_loads(0, k + 1)

    def body(t, _):
        j = k + 1 + t
        cur = t % 2
        nxt = 1 - cur

        @pl.when(t + 1 < nj)
        def _():
            # Slot nxt last stored at t-1 (dst tiles[i, j-1]); that store
            # must land before the prefetch overwrites the buffer.
            @pl.when(t >= 1)
            def _():
                pltpu.make_async_copy(
                    f32a.at[nxt], tiles.at[i, j - 1], ss.at[nxt, 0]
                ).wait()

            start_loads(nxt, j + 1)

        pltpu.make_async_copy(tiles.at[i, j], f32a.at[cur], sl.at[cur, 0]).wait()
        pltpu.make_async_copy(lsp.at[j, k, 0], bfh.at[cur], sl.at[cur, 1]).wait()
        pltpu.make_async_copy(lsp.at[j, k, 1], bfl.at[cur], sl.at[cur, 2]).wait()
        f32a[cur] = f32a[cur] - _mm_nt_split(lh, ll, bfh[cur], bfl[cur])
        pltpu.make_async_copy(f32a.at[cur], tiles.at[i, j], ss.at[cur, 0]).start()
        return 0

    jax.lax.fori_loop(0, nj, body, 0)
    # Drain the last two stores: slot `last` stored tiles[i, i] (j = i at
    # t = nj-1), slot `1-last` stored tiles[i, i-1] (t = nj-2).
    last = (nj - 1) % 2

    @pl.when(nj >= 2)
    def _():
        pltpu.make_async_copy(
            f32a.at[1 - last], tiles.at[i, i - 1], ss.at[1 - last, 0]
        ).wait()

    pltpu.make_async_copy(f32a.at[last], tiles.at[i, i], ss.at[last, 0]).wait()


def _updrow_kernel(ctx: KernelContext, ts: int = T) -> None:
    """Scalar-dispatch trailing update: load L_ik's split resident, then
    run the shared row stream."""
    i, k = ctx.arg(0), ctx.arg(1)
    lsp = ctx.data["lsp"]
    rvh, rvl = ctx.scratch["rvh"], ctx.scratch["rvl"]
    sem = ctx.scratch["sems"]
    _load_all([(lsp.at[i, k, 0], rvh), (lsp.at[i, k, 1], rvl)], sem)
    _updrow_stream(ctx, i, k, rvh[:], rvl[:])


UPD_B = 4  # row tasks per batched trailing-update round


def _updrow_batch_kernel(ctx, ts: int = T) -> None:
    """Batched trailing updates: up to ``ctx.width`` ready row tasks (all
    rows of one step k, in practice - a TRSMCOL completion readies them
    together) through one body. The per-row GEMM stream is byte-identical
    to the scalar kernel's; what the batch buys is the resident-operand
    pipeline: slot b+1's L_ik split streams into the other half of a
    double-buffered pair DURING slot b's row stream, so the MXU never
    stalls on the per-task resident load, and the per-task ``lax.switch``
    dispatch disappears."""
    lsp = ctx.data["lsp"]
    brvh, brvl = ctx.scratch["brvh"], ctx.scratch["brvl"]  # (2, ts, ts)
    bsem = ctx.scratch["bsem"]  # (2, 2): per-half {hi, lo}

    def res_copies(half, b):
        i, k = ctx.arg(b, 0), ctx.arg(b, 1)
        return (
            pltpu.make_async_copy(lsp.at[i, k, 0], brvh.at[half], bsem.at[half, 0]),
            pltpu.make_async_copy(lsp.at[i, k, 1], brvl.at[half], bsem.at[half, 1]),
        )

    for cp in res_copies(0, 0):  # slot 0 is always live (take >= 1)
        cp.start()
    for b in range(ctx.width):
        half = b % 2

        @pl.when(ctx.live(b))
        def _(b=b, half=half):
            if b + 1 < ctx.width:
                @pl.when(ctx.live(b + 1))
                def _():
                    for cp in res_copies(1 - half, b + 1):
                        cp.start()

            for cp in res_copies(half, b):
                cp.wait()
            i, k = ctx.arg(b, 0), ctx.arg(b, 1)
            _updrow_stream(ctx, i, k, brvh[half], brvl[half])


def build_cholesky_graph(nt: int, fused_trsm: bool = True) -> TaskGraphBuilder:
    """Static DAG: POTRF / TRSM tile tasks + row-fused trailing updates.

    Dependency shape (R = UPDROW row task, C = TRSMCOL column stream):
      POTRF(k)  <- R(k, k-1)              (its diagonal tile's last writer)
      C(k)      <- POTRF(k), R(i, k-1) for all i > k   (fused default:
                   the stream reads every tile (i, k), whose last writers
                   are the step-(k-1) row updates)
      R(i, k)   <- C(k)                   (the L operands; C(k) carries
                                           R(i, k-1) transitively)
    or, with ``fused_trsm=False`` (tile-level TRSM, the reference's
    granularity, test/cholesky/cholesky.cpp):
      TRSM(i,k) <- POTRF(k), R(i, k-1)
      R(i, k)   <- TRSM(j,k) for k<j<=i

    The fused graph keeps the full cross-row parallelism of the trailing
    updates (the FLOPs); it serializes only the column solves, which a
    single core runs back-to-back in either form.
    """
    b = TaskGraphBuilder()
    P = {}
    S = {}
    R = {}  # (i, k) -> row-update task for row i at step k

    def dep(*ids):
        return [t for t in ids if t is not None]

    for k in range(nt):
        P[k] = b.add(POTRF, args=[k], deps=dep(R.get((k, k - 1))))
        if fused_trsm:
            if k + 1 < nt:
                prev = [R[(i, k - 1)] for i in range(k + 1, nt)] if k else []
                col = b.add(TRSMCOL, args=[k], deps=[P[k]] + prev)
                for i in range(k + 1, nt):
                    R[(i, k)] = b.add(UPDROW, args=[i, k], deps=[col])
        else:
            for i in range(k + 1, nt):
                S[(i, k)] = b.add(
                    TRSM, args=[i, k], deps=dep(P[k], R.get((i, k - 1)))
                )
            for i in range(k + 1, nt):
                R[(i, k)] = b.add(
                    UPDROW,
                    args=[i, k],
                    deps=[S[(j, k)] for j in range(k + 1, i + 1)],
                )
    return b


def make_cholesky_megakernel(
    nt: int,
    interpret: Optional[bool] = None,
    tile: int = T,
    factor_base: Optional[int] = None,
    fused_only: bool = False,
    batch_updrow: bool = True,
    checkpoint: Optional[bool] = None,
) -> Megakernel:
    """``batch_updrow`` routes the trailing-update row tasks through the
    megakernel's batched same-kind dispatch tier (UPD_B rows per round,
    resident L-split pipelined across slots); results are bit-identical
    to the scalar dispatch, which ``batch_updrow=False`` restores."""
    if factor_base is None:
        # In-kernel A/B at n=8192 (fast windows, interleaved): base 128
        # = 7.36 ms vs base 256 = 7.92-8.02 ms, every trial - the deeper
        # recursion's extra block algebra is cheaper than factor_tile +
        # Newton-Schulz on 256-wide planes. (A plain-jit microbench had
        # suggested the opposite; it was clock-window noise.)
        factor_base = min(tile, 128)
    tile_spec = jax.ShapeDtypeStruct((nt, nt, tile, tile), jnp.float32)
    linvsp_spec = jax.ShapeDtypeStruct((nt, 2, tile, tile), jnp.bfloat16)
    lsp_spec = jax.ShapeDtypeStruct((nt, nt, 2, tile, tile), jnp.bfloat16)
    # POTRF + TRSM tile tasks (or column streams) + one row-update task
    # per (row, step): capacity covers the larger (unfused) form unless
    # ``fused_only`` - SMEM windows pad task-table scalars to ~32 B/word,
    # so large-nt kernels (nt >= 32) only fit the 1 MB SMEM budget with
    # the fused graph's smaller table.
    if fused_only:
        ntasks = nt + (nt - 1) + nt * (nt - 1) // 2
    else:
        ntasks = nt + 2 * (nt * (nt - 1) // 2)
    capacity = max(64, ntasks)
    scratch = {
        "va": pltpu.VMEM((tile, tile), jnp.float32),
        "f32a": pltpu.VMEM((2, tile, tile), jnp.float32),
        "f32b": pltpu.VMEM((2, tile, tile), jnp.float32),
        "bfh": pltpu.VMEM((2, tile, tile), jnp.bfloat16),
        "bfl": pltpu.VMEM((2, tile, tile), jnp.bfloat16),
        "rvh": pltpu.VMEM((tile, tile), jnp.bfloat16),
        "rvl": pltpu.VMEM((tile, tile), jnp.bfloat16),
        "sems": pltpu.SemaphoreType.DMA((3,)),
        "sload": pltpu.SemaphoreType.DMA((2, 3)),
        "sstore": pltpu.SemaphoreType.DMA((2, 3)),
    }
    route = {}
    if batch_updrow:
        from .megakernel import BatchSpec

        scratch["brvh"] = pltpu.VMEM((2, tile, tile), jnp.bfloat16)
        scratch["brvl"] = pltpu.VMEM((2, tile, tile), jnp.bfloat16)
        scratch["bsem"] = pltpu.SemaphoreType.DMA((2, 2))
        route["updrow"] = BatchSpec(
            _ft.partial(_updrow_batch_kernel, ts=tile), width=UPD_B
        )
    return Megakernel(
        kernels=[
            ("potrf", _ft.partial(_potrf_kernel, ts=tile, fbase=factor_base)),
            ("trsm", _ft.partial(_trsm_kernel, ts=tile)),
            ("updrow", _ft.partial(_updrow_kernel, ts=tile)),
            ("trsmcol", _ft.partial(_trsmcol_kernel, ts=tile, nt=nt)),
        ],
        route=route,
        data_specs={
            "tiles": tile_spec, "linvsp": linvsp_spec, "lsp": lsp_spec,
        },
        scratch_specs=scratch,
        capacity=capacity,
        num_values=8,
        succ_capacity=max(
            64,
            4 * ntasks + (nt * nt if fused_only else nt * nt * nt // 2),
        ),
        interpret=interpret,
        checkpoint=checkpoint,
        # 8 f32-equivalent tile buffers + compiler stack temporaries
        # (factor_and_inv block values, bf16 split operands) + the batched
        # tier's resident double-buffer pair: past the 16 MiB scoped
        # default once tile >= 512.
        vmem_limit_bytes=max(
            (26 if batch_updrow else 24) * tile * tile * 4,
            16 * 1024 * 1024,
        ),
    )


def _to_tiles(a: np.ndarray, nt: int, ts: int = T) -> np.ndarray:
    return (
        a.reshape(nt, ts, nt, ts).swapaxes(1, 2).astype(np.float32).copy()
    )


def _from_tiles(tiles: np.ndarray, nt: int, ts: int = T) -> np.ndarray:
    return np.asarray(tiles).swapaxes(1, 2).reshape(nt * ts, nt * ts)


def cholesky_buffers(a: np.ndarray, nt: int, tile: int = T) -> dict:
    """The three data buffers a Cholesky run needs: f32 tiles plus the
    bf16 split caches (inverse + subdiagonal L operands)."""
    return {
        "tiles": _to_tiles(a, nt, tile),
        "linvsp": jnp.zeros((nt, 2, tile, tile), jnp.bfloat16),
        "lsp": jnp.zeros((nt, nt, 2, tile, tile), jnp.bfloat16),
    }


@_ft.partial(jax.jit, static_argnums=1)
def _tiles_on_device(x, ts: int):
    """``_to_tiles``' layout, made where the matrix already is:
    (n, n) -> (nt, nt, ts, ts)."""
    nt = x.shape[0] // ts
    return x.reshape(nt, ts, nt, ts).swapaxes(1, 2)


@jax.jit
def _tril_on_device(tiles):
    """``np.tril(_from_tiles(tiles))`` before the download:
    (nt, nt, ts, ts) -> (n, n), +0.0 above the diagonal."""
    nt, _, ts, _ = tiles.shape
    return jnp.tril(tiles.swapaxes(1, 2).reshape(nt * ts, nt * ts))


def _layout_device(mk: Megakernel):
    """Where the two layout functions run: ``Megakernel._execute``'s rule,
    so an interpreter run on a machine with a chip stays on the host CPU."""
    if mk.interpret:
        return jax.default_device(jax.devices("cpu")[0])
    return contextlib.nullcontext()


def device_cholesky(
    a: np.ndarray,
    interpret: Optional[bool] = None,
    mk: Optional[Megakernel] = None,
    tile: int = T,
    fused_trsm: bool = True,
    batch_updrow: bool = True,
) -> Tuple[np.ndarray, dict]:
    """Factor SPD ``a`` ((nt*tile)^2) on-device; returns (L, info).

    What a call costs: one contiguous upload of ``a`` (as it is when it is
    C-contiguous float32; anything else pays one host pass,
    ``np.ascontiguousarray(a, np.float32)``, first), one kernel, and one
    contiguous download of ``L``. The tile layout going in and the
    un-tiling and ``tril`` coming out are two small jitted functions that
    run on the device around the kernel. ``L`` is C-contiguous float32
    with exactly 0.0 above the diagonal, and it is what ``np.asarray`` of
    a device array gives: possibly READ-ONLY, so copy it before writing
    into it. ``a`` is only read.

    Three profiler spans split the call for a traced run, ``chol.upload``
    (to the tiled buffer ready on the device), ``chol.run``
    (``Megakernel.run``, whose ``mk.*`` spans nest inside) and
    ``chol.download``: ``runtime/spans.py:STAGES`` has the table."""
    n = a.shape[0]
    if n % tile != 0:
        raise ValueError(f"matrix size must be a multiple of {tile}")
    nt = n // tile
    if mk is None:
        mk = make_cholesky_megakernel(
            nt, interpret, tile=tile, batch_updrow=batch_updrow
        )
    b = build_cholesky_graph(nt, fused_trsm=fused_trsm)
    # No pass and no copy for a C-contiguous float32 matrix.
    a = np.ascontiguousarray(a, dtype=np.float32)
    t0 = time.perf_counter()
    with span("chol.upload"), _layout_device(mk):
        # Nothing is donated: on the CPU backend device_put may alias the
        # caller's memory, and the (n, n) buffer is freed as soon as the
        # tiling has run anyway, since nothing else refers to it.
        data = {
            "tiles": _tiles_on_device(jax.device_put(a), tile),
            "linvsp": jnp.zeros((nt, 2, tile, tile), jnp.bfloat16),
            "lsp": jnp.zeros((nt, nt, 2, tile, tile), jnp.bfloat16),
        }
        data["tiles"].block_until_ready()
    with span("chol.run"):
        _, data, info = mk.run(b, data=data)
    dt = time.perf_counter() - t0
    with span("chol.download"), _layout_device(mk):
        L = np.asarray(_tril_on_device(data["tiles"]))
    info = dict(info)
    info["seconds"] = dt
    info["gflops"] = (n**3 / 3.0) / dt / 1e9
    return L, info
