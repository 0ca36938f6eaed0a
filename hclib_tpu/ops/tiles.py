"""MXU tile kernels shared by device task kernels.

Designed for the TPU compute units rather than translated from LAPACK
(used by hclib_tpu/device/cholesky.py; reference workload
test/cholesky/cholesky.cpp):

- ``factor_tile`` (VPU): lower-Cholesky of a symmetric tile as masked
  rank-1 updates - row j equals column j by symmetry, so both outer-product
  factors come from cheap masked reductions; no transposes, no dynamic lane
  indexing.
- ``tri_inverse`` (MXU): inv(L) via Newton-Schulz X <- X(2I - LX), *exact*
  for triangular matrices after ceil(log2 T) steps - matmuls instead of a
  scalar substitution sweep.
- ``factor_and_inv``: (L, inv(L)) for any tile size - the serial sweep
  runs only on 128x128 diagonal base blocks; larger tiles recurse by 2x2
  blocking with panels/updates/inverse as MXU block algebra.
- ``mm_nt`` (MXU): A @ B^T as a dot_general contraction on the second axis
  of both operands (no materialized transpose), at ~f32 accuracy via a
  3-pass bf16 hi/lo split (2x the throughput of HIGHEST's 6 passes).
- ``dma_copy``: start+wait of a Pallas async copy (HBM<->VMEM staging in
  task kernels).
- ``lu_tile`` / ``lu_and_inv``: LU WITHOUT pivoting of a tile (L unit
  lower, U upper) and both inverses, for device/sparselu.py's ``lu0``: the
  same panel blocking as ``factor_tile`` with the symmetry gone - the tile
  and its transpose are swept together, so the L columns of a panel are
  rows of the transpose and nothing is sliced along lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "factor_tile", "tri_inverse", "factor_and_inv", "mm_nt", "dma_copy",
    "split_bf16", "mm_nt_split", "mm_nt_rsplit", "lu_tile", "lu_and_inv",
    "mm_nn_lsplit", "mm_nn_rsplit",
]


PANEL = 8  # factor-panel width: one sublane group


def _chol8_and_inv(d8):
    """Serial lower-Cholesky + inverse of an (8, 8) block, fully unrolled
    with static slices (the only truly sequential math in the tile
    factorization; everything around it is MXU block algebra). Returns
    (L8, inv(L8))."""
    rows8 = jax.lax.broadcasted_iota(jnp.int32, (PANEL, PANEL), 0)
    cols8 = jax.lax.broadcasted_iota(jnp.int32, (PANEL, PANEL), 1)
    s8 = d8
    lcols = []
    for q in range(PANEL):
        dq = jax.lax.slice(s8, (q, q), (q + 1, q + 1))
        colq = jax.lax.slice(s8, (0, q), (PANEL, q + 1))
        c = jnp.where(rows8[:, :1] >= q, colq * jax.lax.rsqrt(dq), 0.0)
        lcols.append(c)
        s8 = jnp.where(
            (rows8 > q) & (cols8 > q), s8 - c * jnp.transpose(c), s8
        )
    l8 = jnp.concatenate(lcols, axis=1)
    # Forward substitution, unrolled: row i of inv solves L X = I.
    # (A Newton-Schulz inverse on the (8, 8) block was measured: it
    # shortens the serial chain and buys ~2.6% end-to-end, but costs
    # accuracy the n=8192 residual gate cannot spare - 9.84e-7 vs this
    # form's 9.26e-7 against the 1e-6 bound.)
    xrows = []
    for i in range(PANEL):
        acc = (cols8[:1] == i).astype(d8.dtype)
        for j in range(i):
            lij = jax.lax.slice(l8, (i, j), (i + 1, j + 1))
            acc = acc - lij * xrows[j]
        dii = jax.lax.slice(l8, (i, i), (i + 1, i + 1))
        xrows.append(acc / dii)
    return l8, jnp.concatenate(xrows, axis=0)


def factor_tile(t, ts: int):
    """Panel-blocked lower-Cholesky of a symmetric (ts, ts) tile.

    Exploits symmetry: for the 8-row panel J, the rows s[J, :] ARE the
    columns s[:, J] transposed, so each panel factorization runs on one
    (8, ts) sublane block. The serial math is confined to the panel's
    8x8 diagonal block (_chol8_and_inv, static slices on (8, 8) arrays);
    the rest of the panel's U rows come from ONE (8, 8) @ (8, ts)
    triangular-solve matmul (U_panel = inv(L8) @ S_panel), and the
    trailing matrix takes one rank-8 MXU update per panel (3-pass bf16
    split, ~f32 exact). This replaces the earlier formulation's 8
    full-width masked rank-1 micro-iterations per panel - whose chained
    (8, ts) reductions, not FLOPs, dominated the POTRF tasks' wall clock
    (measured 138 us/task at tile 512, ~31% of the whole n=8192
    factorization).

    Builds U = L^T row-by-row and transposes once at the end.
    """
    assert ts % PANEL == 0, ts
    rows = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 1)
    lanep = jax.lax.broadcasted_iota(jnp.int32, (PANEL, ts), 1)
    s = t
    pans = []
    npanels = ts // PANEL
    for p in range(npanels):
        j0 = p * PANEL
        pan = jax.lax.slice(s, (j0, 0), (j0 + PANEL, ts))
        d8 = jax.lax.slice(pan, (0, j0), (PANEL, j0 + PANEL))
        l8, i8 = _chol8_and_inv(d8)
        # U rows of this panel: inv(L8) @ S[j0:j0+8, :], valid for
        # columns > the diagonal block; the block itself is exactly L8^T
        # (spliced in via static concatenate + mask - Mosaic lowers
        # neither dynamic_update_slice nor pad), columns left of the
        # panel are zeroed.
        u = mm_nn(i8, pan)
        parts = []
        if j0:
            parts.append(jnp.zeros((PANEL, j0), t.dtype))
        parts.append(jnp.transpose(l8))
        if ts - j0 - PANEL:
            parts.append(jnp.zeros((PANEL, ts - j0 - PANEL), t.dtype))
        l8w = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        u = jnp.where((lanep >= j0) & (lanep < j0 + PANEL), l8w, u)
        u = jnp.where(lanep >= j0, u, 0.0)
        pans.append(u)
        if p + 1 < npanels:
            # Rank-8 trailing update in one contraction over the panel:
            # s[m, n] -= sum_q L[m, j0+q] L[n, j0+q] = (u^T u)[m, n].
            upd8 = _mm_tn(u, u)
            edge = j0 + PANEL - 1
            s = jnp.where((rows > edge) & (cols > edge), s - upd8, s)
    return jnp.transpose(jnp.concatenate(pans, axis=0))


def tri_inverse(l, ts: int):
    """inv(L) for lower-triangular L via Newton-Schulz (exact in log2 ts).

    L is constant across the iterations, so its bf16 hi/lo split is
    hoisted out of the loop (each iteration then splits only the two
    fresh operands x and Lx)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 1)
    dg = jnp.sum(jnp.where(rows == cols, l, 0.0), axis=1, keepdims=True)
    x = jnp.where(rows == cols, 1.0 / dg, 0.0)
    steps = max(1, int(np.ceil(np.log2(ts))))
    lh, ll = split_bf16(l)
    for _ in range(steps):
        xh, xl = split_bf16(x)
        lx = _d_nn(lh, xh) + _d_nn(lh, xl) + _d_nn(ll, xh)
        lxh, lxl = split_bf16(lx)
        x = 2.0 * x - (
            _d_nn(xh, lxh) + _d_nn(xh, lxl) + _d_nn(xl, lxh)
        )
    return x


def factor_and_inv(t, ts: int, base: int = 128):
    """(L, inv(L)) for a symmetric (ts, ts) tile.

    The scalar rank-1 sweep (factor_tile) costs O(ts) serial iterations on
    O(ts^2) planes - ~100us at ts=256 - so tiles larger than ``base`` are
    factored recursively by 2x2 blocking, keeping the sweep on base-sized
    diagonal blocks and doing panels/updates/inverses as MXU block algebra:

        A = [[A00,  . ], [A10, A11]]
        L00, I00 = factor_and_inv(A00);  L10 = A10 I00^T
        L11, I11 = factor_and_inv(A11 - L10 L10^T)
        inv(L)   = [[I00, 0], [-I11 L10 I00, I11]]
    """
    if ts <= base:
        l = factor_tile(t, ts)
        return l, tri_inverse(l, ts)
    h = ts // 2
    a00 = jax.lax.slice(t, (0, 0), (h, h))
    a10 = jax.lax.slice(t, (h, 0), (ts, h))
    a11 = jax.lax.slice(t, (h, h), (ts, ts))
    l00, i00 = factor_and_inv(a00, h, base)
    l10 = mm_nt(a10, i00)
    l11, i11 = factor_and_inv(a11 - mm_nt(l10, l10), h, base)
    off = -mm_nn(mm_nn(i11, l10), i00)
    z = jnp.zeros((h, h), t.dtype)
    l = jnp.concatenate(
        [jnp.concatenate([l00, z], 1), jnp.concatenate([l10, l11], 1)], 0
    )
    inv = jnp.concatenate(
        [jnp.concatenate([i00, z], 1), jnp.concatenate([off, i11], 1)], 0
    )
    return l, inv


def split_bf16(x):
    """bf16 hi/lo decomposition of an f32 array: x ~= hi + lo with the
    lo term holding the next ~8 mantissa bits. The shared building block
    of every 3-pass ~f32 matmul here; task kernels also use it to STORE
    operands pre-split (hclib_tpu/device/cholesky.py keeps the L tiles in
    split form so the trailing-update hot loop runs zero VPU splits)."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _d_nt(x, y):
    return jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _d_nn(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32)


def mm_nt(a, b):
    """a @ b^T without materializing the transpose, at ~f32 accuracy via a
    hand-rolled 3-pass bf16 split (hi/lo decomposition of each operand;
    the lo x lo term is below f32 noise). Mosaic lowers only DEFAULT (one
    bf16 pass, ~3 decimal digits worse residuals) and HIGHEST (6 passes,
    2x slower than this with no measurable residual gain on Cholesky:
    7.7e-7 vs 8.8e-7 at n=1024)."""
    return _split3(_d_nt, a, b)


def mm_nt_split(ah, al, bh, bl):
    """a @ b^T with BOTH operands already bf16 hi/lo split: the three MXU
    passes and nothing else - the hot-loop form for kernels that stream
    pre-split operands (identical rounding to mm_nt on the unsplit
    values)."""
    return _d_nt(ah, bh) + _d_nt(ah, bl) + _d_nt(al, bh)


def mm_nt_rsplit(a, bh, bl):
    """a @ b^T with only the RIGHT operand pre-split (a is split here)."""
    ah, al = split_bf16(a)
    return _d_nt(ah, bh) + _d_nt(ah, bl) + _d_nt(al, bh)


def _split3(d, a, b):
    """The shared 3-pass bf16 hi/lo split: decompose both operands, sum the
    three passes whose products are above f32 noise (lo x lo is not).
    ``d`` supplies the contraction (NT / TN / NN variants below)."""
    ah, al = split_bf16(a)
    bh, bl = split_bf16(b)
    return d(ah, bh) + d(ah, bl) + d(al, bh)


def _mm_tn(a, b):
    """a^T @ b (contraction over axis 0 of both) via the 3-pass bf16
    hi/lo split - the rank-8 panel contraction of factor_tile."""
    dims = (((0,), (0,)), ((), ()))
    return _split3(
        lambda x, y: jax.lax.dot_general(
            x, y, dimension_numbers=dims,
            preferred_element_type=jnp.float32,
        ),
        a, b,
    )


def mm_nn(a, b):
    """a @ b at ~f32 accuracy via the same 3-pass bf16 hi/lo split as
    mm_nt (2x the throughput of Precision.HIGHEST's 6 passes)."""
    return _split3(
        lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32),
        a, b,
    )


def dma_copy(src, dst, sem):
    """Start + wait one async copy (task kernels stage HBM<->VMEM)."""
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def mm_nn_lsplit(ah, al, b):
    """a @ b with only the LEFT operand pre-split (b is split here): the
    three MXU passes of ``mm_nn``, for a kernel that keeps ``a`` split
    (device/sparselu.py: ``inv(L) @ A_kj``)."""
    bh, bl = split_bf16(b)
    return _d_nn(ah, bh) + _d_nn(ah, bl) + _d_nn(al, bh)


def mm_nn_rsplit(a, bh, bl):
    """a @ b with only the RIGHT operand pre-split (``A_ik @ inv(U)``)."""
    ah, al = split_bf16(a)
    return _d_nn(ah, bh) + _d_nn(ah, bl) + _d_nn(al, bh)


def _lu8_and_inv(d8):
    """Serial LU without pivoting of an (8, 8) block and both inverses,
    fully unrolled with static slices, as ``_chol8_and_inv``. Returns
    ``(L8, U8, inv(L8), inv(U8))``, L8 unit lower with its ones."""
    rows8 = jax.lax.broadcasted_iota(jnp.int32, (PANEL, PANEL), 0)
    cols8 = jax.lax.broadcasted_iota(jnp.int32, (PANEL, PANEL), 1)
    s8 = d8
    lcols, urows = [], []
    for q in range(PANEL):
        dq = jax.lax.slice(s8, (q, q), (q + 1, q + 1))
        colq = jax.lax.slice(s8, (0, q), (PANEL, q + 1))
        rowq = jax.lax.slice(s8, (q, 0), (q + 1, PANEL))
        c = jnp.where(rows8[:, :1] > q, colq / dq, 0.0)
        r = jnp.where(cols8[:1] >= q, rowq, 0.0)
        lcols.append(c + (rows8[:, :1] == q).astype(d8.dtype))
        urows.append(r)
        s8 = jnp.where((rows8 > q) & (cols8 > q), s8 - c * r, s8)
    l8 = jnp.concatenate(lcols, axis=1)
    u8 = jnp.concatenate(urows, axis=0)
    # inv(L8) by forward substitution (unit diagonal), inv(U8) by back
    # substitution from the last row up: row i of X solves T X = I.
    lrows = []
    for i in range(PANEL):
        acc = (cols8[:1] == i).astype(d8.dtype)
        for j in range(i):
            acc = acc - jax.lax.slice(l8, (i, j), (i + 1, j + 1)) * lrows[j]
        lrows.append(acc)
    xrows = [None] * PANEL
    for i in reversed(range(PANEL)):
        acc = (cols8[:1] == i).astype(d8.dtype)
        for j in range(i + 1, PANEL):
            acc = acc - jax.lax.slice(u8, (i, j), (i + 1, j + 1)) * xrows[j]
        xrows[i] = acc / jax.lax.slice(u8, (i, i), (i + 1, i + 1))
    return (l8, u8, jnp.concatenate(lrows, axis=0),
            jnp.concatenate(xrows, axis=0))


def lu_tile(t, ts: int):
    """Panel-blocked LU without pivoting of a (ts, ts) tile: ``(L, U)``, L
    unit lower with its ones, U upper, ``L U = t``.

    ``factor_tile`` reads a panel's columns off its rows by symmetry; here
    the tile ``s`` and its transpose ``st`` are carried together, so the
    panel's U rows come from rows of ``s`` (``inv(L8) @ s[J, :]``) and its
    L columns, transposed, from rows of ``st`` (``inv(U8)^T @ st[J, :]``):
    every slice is a sublane block. Each panel ends in one rank-8 MXU
    update of ``s`` and one of ``st`` (3-pass bf16 split, ~f32 exact); the
    serial math stays on the 8x8 diagonal block."""
    assert ts % PANEL == 0, ts
    rows = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 1)
    lanep = jax.lax.broadcasted_iota(jnp.int32, (PANEL, ts), 1)
    s, st = t, jnp.transpose(t)
    upans, lpans = [], []
    npanels = ts // PANEL

    def splice(x, blk8, j0):
        """``x`` with ``blk8`` in its diagonal-block window and zero left
        of it (static concatenate + mask, as ``factor_tile``)."""
        parts = []
        if j0:
            parts.append(jnp.zeros((PANEL, j0), t.dtype))
        parts.append(blk8)
        if ts - j0 - PANEL:
            parts.append(jnp.zeros((PANEL, ts - j0 - PANEL), t.dtype))
        w = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        x = jnp.where((lanep >= j0) & (lanep < j0 + PANEL), w, x)
        return jnp.where(lanep >= j0, x, 0.0)

    for p in range(npanels):
        j0 = p * PANEL
        pan = jax.lax.slice(s, (j0, 0), (j0 + PANEL, ts))
        pant = jax.lax.slice(st, (j0, 0), (j0 + PANEL, ts))
        d8 = jax.lax.slice(pan, (0, j0), (PANEL, j0 + PANEL))
        l8, u8, il8, iu8 = _lu8_and_inv(d8)
        u = splice(mm_nn(il8, pan), u8, j0)
        lt = splice(mm_nn(jnp.transpose(iu8), pant), jnp.transpose(l8), j0)
        upans.append(u)
        lpans.append(lt)
        if p + 1 < npanels:
            # s[a, b] -= sum_q L[a, j0+q] U[j0+q, b] = (lt^T u)[a, b], and
            # the same of the transpose.
            edge = j0 + PANEL - 1
            trail = (rows > edge) & (cols > edge)
            s = jnp.where(trail, s - _mm_tn(lt, u), s)
            st = jnp.where(trail, st - _mm_tn(u, lt), st)
    return (jnp.transpose(jnp.concatenate(lpans, axis=0)),
            jnp.concatenate(upans, axis=0))


def lu_and_inv(t, ts: int):
    """``(LU, inv(L), inv(U))`` of a (ts, ts) tile factored without
    pivoting: LU packed (L below the diagonal, its ones implied; U on and
    above it), the inverses by ``tri_inverse``'s Newton-Schulz, which is
    as exact for an upper triangle as for a lower one. With them a block's
    two triangular solves are one 3-pass product each, as Cholesky's TRSM
    is."""
    l, u = lu_tile(t, ts)
    rows = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ts, ts), 1)
    return (jnp.where(rows > cols, l, u), tri_inverse(l, ts),
            tri_inverse(u, ts))
