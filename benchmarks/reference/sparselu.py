"""Plain reference for the SparseLU deployment (BOTS ``sparselu`` with
KASTORS' task dependences): ``genmat``'s pattern and the symbolic counts,
the matrix made from the seed, BOTS' sequential ``sparselu_seq_call`` in
numpy float32 for the sizes tests use, the componentwise backward error
that decides ``correct`` at the cell's size, and a blocked factorisation
in plain ``jnp`` that the two controls run in the program's place (one
bf16 pass in the trailing products; one ``bmod`` left out). Imports
nothing of the program.

Storage is sparse: a slot a block of the FINAL pattern, the blocks present
before the factorisation first, row by row, then the fill blocks, row by
row (``slots``). LU without pivoting of a given matrix is unique, so the
structure and the residual together are the answer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ structure


def genmat_pattern(n: int) -> np.ndarray:
    """BOTS ``genmat``: block ``(ii, jj)`` is there before the call."""
    there = np.zeros((n, n), bool)
    for ii in range(n):
        for jj in range(n):
            null = (ii < jj and ii % 3 != 0) or (ii > jj and jj % 3 != 0)
            if ii % 2 == 1 or jj % 2 == 1:
                null = True
            if ii == jj or ii == jj - 1 or ii - 1 == jj:
                null = False
            there[ii, jj] = not null
    return there


def symbolic(present: np.ndarray) -> dict:
    """``sparselu``'s loop nest over booleans: the final pattern, the four
    task counts, and the blocks a ``bmod`` makes."""
    n = len(present)
    final = np.array(present, bool)
    counts = dict(lu0=0, fwd=0, bdiv=0, bmod=0)
    for kk in range(n):
        counts["lu0"] += 1
        right = [jj for jj in range(kk + 1, n) if final[kk, jj]]
        below = [ii for ii in range(kk + 1, n) if final[ii, kk]]
        counts["fwd"] += len(right)
        counts["bdiv"] += len(below)
        counts["bmod"] += len(right) * len(below)
        for ii in below:
            final[ii, right] = True
    return {"final": final, "counts": counts,
            "fill_blocks": int(final.sum() - np.sum(present)),
            "descriptors": sum(counts.values())}


def slots(present: np.ndarray, final: np.ndarray):
    """``(rows, cols)`` of every slot: present blocks, then fill blocks."""
    order = np.concatenate([np.flatnonzero(present.ravel()),
                            np.flatnonzero((final & ~present).ravel())])
    n = len(present)
    return (order // n).astype(np.int32), (order % n).astype(np.int32)


def flops(counts: dict, m: int) -> float:
    """``2 m^3`` a bmod, ``m^3`` a fwd or a bdiv, ``2/3 m^3`` a lu0: the
    operations of the algorithm in the precision it states, whatever
    implements it."""
    return m ** 3 * (2.0 * counts["bmod"] + counts["fwd"] + counts["bdiv"]
                     + 2.0 / 3.0 * counts["lu0"])


def bmods(present: np.ndarray) -> np.ndarray:
    """Every ``bmod`` in the sequential order, a row ``(kk, ii, jj,
    fills)``: ``fills`` is how many of its two operands ``A[ii][kk]`` and
    ``A[kk][jj]`` are fill blocks (0, 1 or 2). An operand ``A_ik
    inv(U_kk)`` is ``1 / diag_shift`` of its block, so every generation of
    fill is some 20 times smaller than the one before, and what the check
    reads with a ``bmod`` left out falls with ``fills`` (PERF.md section 2
    has the census of all 174,784)."""
    n = len(present)
    final = np.array(present, bool)
    out = []
    for kk in range(n):
        right = np.flatnonzero(final[kk, kk + 1:]) + kk + 1
        below = np.flatnonzero(final[kk + 1:, kk]) + kk + 1
        ii, jj = np.meshgrid(below, right, indexing="ij")
        fills = (~present[ii, kk]).astype(int) + ~present[kk, jj]
        out.append(np.stack([np.full(ii.shape, kk), ii, jj, fills], -1)
                   .reshape(-1, 4))
        final[np.ix_(below, right)] = True
    return np.concatenate(out)


def control_bmod(present: np.ndarray, seed: int):
    """The ``bmod`` the control leaves out, ``(kk, ii, jj)``: drawn from
    the seed out of all those whose two operands were present from the
    start (``fills`` 0: 31,312 of 174,784 at ``n`` 128, some in every
    step). PERF.md says what the check makes of the others."""
    drawn = bmods(present)
    drawn = drawn[drawn[:, 3] == 0]
    at = np.random.default_rng(seed).integers(len(drawn))
    return tuple(int(x) for x in drawn[at, :3])


# --------------------------------------------------------------- values


def diag_shift(present: np.ndarray, m: int) -> float:
    """What every diagonal entry is raised by: four times the root sum of
    squares of the longest row (entries uniform in [-2, 2) have variance
    4/3), which keeps the symmetric part positive definite and so LU
    without pivoting stable, and is small enough that one ``bmod`` left
    out stands far above float32 rounding of the diagonal."""
    return 4.0 * float(np.sqrt(np.sum(present, 1).max() * m * 4.0 / 3.0))


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31
    )


@functools.partial(jax.jit, static_argnums=(2, 3))
def _blocks(key, diagonal, count: int, m: int, shift):
    a = jax.random.uniform(key, (count, m, m), jnp.float32, -2.0, 2.0)
    return a.at[diagonal].add(shift * jnp.eye(m, dtype=jnp.float32))


def make_blocks(seed: int, present: np.ndarray, m: int):
    """The present blocks ``[n_present, m, m]`` float32 in slot order, made
    on the device in one jitted call: uniform in [-2, 2) from the seed, the
    diagonal blocks' diagonals raised by ``diag_shift``."""
    rows, cols = np.nonzero(present)
    return _blocks(seed_key(seed), jnp.asarray(np.flatnonzero(rows == cols)),
                   len(rows), m, jnp.float32(diag_shift(present, m)))


# ------------------------------------------- BOTS' sequential, in numpy


def _lu0(diag):
    m = len(diag)
    for k in range(m):
        for i in range(k + 1, m):
            diag[i, k] = diag[i, k] / diag[k, k]
            diag[i, k + 1:] -= diag[i, k] * diag[k, k + 1:]


def _bdiv(diag, row):
    m = len(diag)
    for i in range(m):
        for k in range(m):
            row[i, k] = row[i, k] / diag[k, k]
            row[i, k + 1:] -= row[i, k] * diag[k, k + 1:]


def _bmod(row, col, inner):
    m = len(row)
    for i in range(m):
        for k in range(m):  # BOTS' k is innermost: the same sum, k rising
            inner[i] -= row[i, k] * col[k]


def _fwd(diag, col):
    m = len(diag)
    for k in range(m):
        for i in range(k + 1, m):
            col[i] -= diag[i, k] * col[k]


def sparselu_seq(blocks: np.ndarray, present: np.ndarray) -> dict:
    """BOTS' ``sparselu_seq_call`` loop for loop in float32 (the innermost
    loop over a block's columns is one numpy row operation): ``blocks``
    holds the present blocks row by row; returns ``{(ii, jj): block}`` of
    the factor, the fill blocks ``allocate_clean_block``'s."""
    n, m = len(present), blocks.shape[-1]
    rows, cols = np.nonzero(present)
    A = {(int(i), int(j)): np.array(b, np.float32)
         for i, j, b in zip(rows, cols, np.asarray(blocks))}
    for kk in range(n):
        _lu0(A[kk, kk])
        for jj in range(kk + 1, n):
            if (kk, jj) in A:
                _fwd(A[kk, kk], A[kk, jj])
        for ii in range(kk + 1, n):
            if (ii, kk) in A:
                _bdiv(A[kk, kk], A[ii, kk])
        for ii in range(kk + 1, n):
            if (ii, kk) in A:
                for jj in range(kk + 1, n):
                    if (kk, jj) in A:
                        if (ii, jj) not in A:
                            A[ii, jj] = np.zeros((m, m), np.float32)
                        _bmod(A[ii, kk], A[kk, jj], A[ii, jj])
    return A


# ------------------------------------------- the cell's size, on device


@functools.partial(jax.jit, static_argnums=3)
def dense(blocks, rows, cols, n: int):
    """Slot storage as the dense ``(n m, n m)`` matrix, absent blocks 0."""
    m = blocks.shape[-1]
    d = jnp.zeros((n, n, m, m), blocks.dtype).at[rows, cols].set(blocks)
    return d.swapaxes(1, 2).reshape(n * m, n * m)


@functools.partial(jax.jit, static_argnums=2)
def _readings(lu, a, rows: int):
    """One program for the whole reading (a cold run compiles it once):
    the worst ``|L U - A| / (|L| |U|)`` a panel of ``rows`` rows at a
    time, both products at HIGHEST; all finite; the growth."""
    N = lu.shape[0]
    upper = jnp.triu(lu)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, N), 1)

    def panel(p, worst):
        r0 = p * rows
        lp = jax.lax.dynamic_slice(lu, (r0, 0), (rows, N))
        ap = jax.lax.dynamic_slice(a, (r0, 0), (rows, N))
        i = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, N), 0)
        low = jnp.where(j < i, lp, 0.0) + (i == j).astype(lu.dtype)
        err = jnp.abs(jnp.matmul(low, upper, precision=HIGHEST) - ap)
        scale = jnp.matmul(jnp.abs(low), jnp.abs(upper), precision=HIGHEST)
        # an entry with no term at all (outside the final pattern) must
        # be exactly A's zero
        off = jnp.where(err > 0, jnp.inf, 0.0)
        return jnp.maximum(worst, jnp.max(
            jnp.where(scale > 0, err / scale, off)))

    worst = jax.lax.fori_loop(0, N // rows, panel, jnp.float32(0))
    return (worst, jnp.all(jnp.isfinite(lu)),
            jnp.max(jnp.abs(upper)) / jnp.max(jnp.abs(a)))


def readings(factor, a_blocks, present: np.ndarray, final: np.ndarray,
             panel_rows: int = 2048) -> dict:
    """What the check compares: the componentwise backward error ``max |L U
    - A| / (|L| |U|)`` over the whole matrix, element by element (L unit
    lower and U upper as packed in the factor's blocks, absent blocks of
    ``A`` zero, absent blocks of the factor absent by the storage), both
    products at HIGHEST on the device, a panel of rows at a time. ``(|L|
    |U|)_ij`` is the sum of the absolute terms that make entry ``(i, j)``:
    rounding in any precision ``u`` leaves ``|L U - A| <= c u |L| |U|``
    entry by entry, so every entry is held to its own scale - the raised
    diagonal to its few hundred, a fill block to the ``bmod`` products
    that made it, however small - and one ``bmod`` left out stands in the
    residual of its block whole. Over ``max|A|``, or over 2, the fill
    blocks (6,552 of the factor's 8,320) would be held to a scale far
    above their own. Also whether all of the factor is finite, and the
    growth (``max|U| / max|A|``)."""
    n, m = len(present), a_blocks.shape[-1]
    prow, pcol = np.nonzero(present)
    frow, fcol = slots(present, final)
    a = dense(jnp.asarray(a_blocks), jnp.asarray(prow), jnp.asarray(pcol), n)
    lu = dense(jnp.asarray(factor), jnp.asarray(frow), jnp.asarray(fcol), n)
    N = n * m
    rows = min(panel_rows, N)
    while N % rows:
        rows -= m
    worst, finite, growth = _readings(lu, a, rows)
    finite = bool(finite)
    return {"residual": float(worst) if finite else float("inf"),
            "finite": finite, "growth": float(growth)}


def _lu_nopivot(d):
    m = d.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)

    def body(k, d):
        col = jnp.where(i[:, 0] > k, d[:, k] / d[k, k], 0.0)
        row = jnp.where(j[0] > k, d[k], 0.0)
        d = d - col[:, None] * row[None, :]
        return jnp.where((j == k) & (i > k), col[:, None], d)

    return jax.lax.fori_loop(0, m, body, d)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _blocked(a, m: int, precision: str, drop):
    """Right-looking blocked LU without pivoting of the dense matrix, a
    step a block column. The diagonal block and the two panels are
    factored and solved in float32; ``precision`` is that of the trailing
    update, where nearly all the operations are: 'float32' multiplies at
    HIGHEST, 'bfloat16' rounds both operands to bfloat16 (one MXU pass).
    ``drop = (kk, ii, jj)`` leaves that one block update out."""
    N = a.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (N,), 0)

    def mm(x, y):
        if precision == "bfloat16":
            return jnp.matmul(
                x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
        return jnp.matmul(x, y, precision=HIGHEST)

    def step(k, a):
        k0 = k * m
        lu = _lu_nopivot(jax.lax.dynamic_slice(a, (k0, k0), (m, m)))
        low = jnp.tril(lu, -1) + jnp.eye(m, dtype=a.dtype)
        with jax.default_matmul_precision("highest"):
            row = jax.scipy.linalg.solve_triangular(
                low, jax.lax.dynamic_slice(a, (k0, 0), (m, N)), lower=True,
                unit_diagonal=True)
            col = jax.scipy.linalg.solve_triangular(
                jnp.triu(lu).T, jax.lax.dynamic_slice(a, (0, k0), (N, m)).T,
                lower=True).T
        right, below = at >= k0 + m, at >= k0 + m
        row = jnp.where(right[None, :], row, 0.0)
        col = jnp.where(below[:, None], col, 0.0)
        a = a - mm(col, row)
        if drop is not None:
            kk, ii, jj = drop
            back = mm(jax.lax.dynamic_slice(col, (ii * m, 0), (m, m)),
                      jax.lax.dynamic_slice(row, (0, jj * m), (m, m)))
            blk = jax.lax.dynamic_slice(a, (ii * m, jj * m), (m, m))
            a = jax.lax.dynamic_update_slice(
                a, blk + jnp.where(k == kk, back, 0.0), (ii * m, jj * m))
        # the panels and the diagonal block, where the step's results lie
        keep_r = jax.lax.dynamic_slice(a, (k0, 0), (m, N))
        a = jax.lax.dynamic_update_slice(
            a, jnp.where(right[None, :], row, keep_r), (k0, 0))
        keep_c = jax.lax.dynamic_slice(a, (0, k0), (N, m))
        a = jax.lax.dynamic_update_slice(
            a, jnp.where(below[:, None], col, keep_c), (0, k0))
        return jax.lax.dynamic_update_slice(a, lu, (k0, k0))

    return jax.lax.fori_loop(0, N // m, step, a)


def blocked_lu(a_blocks, present: np.ndarray, final: np.ndarray,
               precision: str = "float32", drop=None):
    """The reference's own factorisation standing in the program's place:
    the factor's blocks in slot order, from the present blocks."""
    n, m = len(present), a_blocks.shape[-1]
    prow, pcol = np.nonzero(present)
    a = dense(jnp.asarray(a_blocks), jnp.asarray(prow), jnp.asarray(pcol), n)
    lu = _blocked(a, m, precision, None if drop is None else tuple(drop))
    frow, fcol = slots(present, final)
    return lu.reshape(n, m, n, m).swapaxes(1, 2)[
        jnp.asarray(frow), jnp.asarray(fcol)]
