"""SparseLU (BOTS ``sparselu``; KASTORS 1.1's task-dependence form) as a
promise/future dataflow DAG over a block-sparse matrix that fills in.

An ``n`` x ``n`` matrix of ``m`` x ``m`` blocks, a block absent by
``genmat``'s rule (``genmat_pattern``). For ``kk`` in ``0..n-1``:

- ``lu0(kk)``:        LU without pivoting of ``A[kk][kk]`` in place (L unit
                      lower, U upper, packed in the block);
- ``fwd(kk, jj)``:    ``A[kk][jj] <- L^-1 A[kk][jj]`` for every present
                      block right of the diagonal;
- ``bdiv(ii, kk)``:   ``A[ii][kk] <- A[ii][kk] U^-1`` for every present
                      block below it;
- ``bmod(ii, jj, kk)``: ``A[ii][jj] -= A[ii][kk] A[kk][jj]`` for every
                      present pair, ALLOCATING ``A[ii][jj]`` clean where it
                      was absent: the matrix fills in while it is factored.

BOTS puts a ``taskwait`` after the panel and after the update of each
``kk``; KASTORS replaces both with ``depend(in: diagonal / row / column
block) depend(inout: the block)``, which is the DAG here: a task awaits
the last writer of every block it touches, so a ``bmod`` of step ``kk+1``
may run beside one of step ``kk`` and the updates of one block run in
``kk`` order, one at a time.

``symbolic`` is the symbolic factorisation (the host's, at set-up, as any
direct solver's): the final pattern, the four task counts, the fill count,
and the slot a block of the final pattern has in sparse block storage. The
device variant is ``device/sparselu.py``; its release
(``device/block_release.py``) reads the final pattern as bit masks.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Tuple

import numpy as np

__all__ = [
    "genmat_pattern", "symbolic", "Symbolic", "make_blocks",
    "sparselu_tasks", "to_dense", "run",
]


def genmat_pattern(n: int) -> np.ndarray:
    """BOTS ``genmat``: which blocks of the ``n`` x ``n`` block matrix are
    present before the factorisation (bool ``[n, n]``)."""
    ii, jj = np.indices((n, n))
    null = ((ii < jj) & (ii % 3 != 0)) | ((ii > jj) & (jj % 3 != 0))
    null |= (ii % 2 == 1) | (jj % 2 == 1)
    null &= ~((ii == jj) | (ii == jj - 1) | (ii - 1 == jj))
    return ~null


@dataclasses.dataclass(frozen=True)
class Symbolic:
    """What the structure alone decides (``symbolic``)."""
    n: int
    present: np.ndarray      # bool [n, n]: blocks there before the call
    final: np.ndarray        # bool [n, n]: blocks there after it
    counts: Dict[str, int]   # lu0, fwd, bdiv, bmod
    fill_blocks: int         # blocks a bmod makes
    slot_of: np.ndarray      # int32 [n, n]: slot of a final block, else -1
    rows: np.ndarray         # int32 [slots]: block row of a slot
    cols: np.ndarray         # int32 [slots]: block column of a slot
    widest_step: int         # most bmods one kk has

    @property
    def tasks(self) -> int:
        return sum(self.counts.values())

    @property
    def slots(self) -> int:
        return len(self.rows)

    @property
    def n_present(self) -> int:
        return int(self.present.sum())

    def flops(self, m: int) -> float:
        """``2 m^3`` a bmod, ``m^3`` a fwd or a bdiv, ``2/3 m^3`` a lu0."""
        c = self.counts
        return m ** 3 * (2.0 * c["bmod"] + c["fwd"] + c["bdiv"]
                         + 2.0 / 3.0 * c["lu0"])


def symbolic(present: np.ndarray) -> Symbolic:
    """The symbolic factorisation of a pattern: ``sparselu``'s loop nest
    over booleans. Slots: the blocks present from the start first, row by
    row, then the fill blocks, row by row, so that the present blocks of
    the caller's array are slots ``0 .. n_present-1`` as they lie."""
    present = np.asarray(present, bool)
    n = present.shape[0]
    if present.shape != (n, n) or not present.diagonal().all():
        raise ValueError("a square pattern with every diagonal block")
    final = present.copy()
    counts = dict(lu0=n, fwd=0, bdiv=0, bmod=0)
    widest = 0
    for kk in range(n):
        right = np.flatnonzero(final[kk, kk + 1:]) + kk + 1
        below = np.flatnonzero(final[kk + 1:, kk]) + kk + 1
        counts["fwd"] += len(right)
        counts["bdiv"] += len(below)
        counts["bmod"] += len(right) * len(below)
        widest = max(widest, len(right) * len(below))
        final[np.ix_(below, right)] = True
    fill = final & ~present
    order = np.concatenate([np.flatnonzero(present.ravel()),
                            np.flatnonzero(fill.ravel())])
    slot_of = np.full(n * n, -1, np.int32)
    slot_of[order] = np.arange(len(order), dtype=np.int32)
    return Symbolic(
        n=n, present=present, final=final, counts=counts,
        fill_blocks=int(fill.sum()), slot_of=slot_of.reshape(n, n),
        rows=(order // n).astype(np.int32), cols=(order % n).astype(np.int32),
        widest_step=widest,
    )


def make_blocks(sym: Symbolic, m: int, seed: int = 0) -> np.ndarray:
    """The present blocks ``[n_present, m, m]`` float32 in slot order:
    uniform in [-2, 2), the diagonal raised by four times the longest
    row's root sum of squares, which keeps LU without pivoting stable."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(-2.0, 2.0, (sym.n_present, m, m)).astype(np.float32)
    shift = 4.0 * np.sqrt(sym.present.sum(1).max() * m * 4.0 / 3.0)
    d = np.flatnonzero(sym.rows[:sym.n_present] == sym.cols[:sym.n_present])
    a[d] += np.float32(shift) * np.eye(m, dtype=np.float32)
    return a


def to_dense(blocks: np.ndarray, rows, cols, n: int) -> np.ndarray:
    """Slot storage as the dense ``(n m, n m)`` matrix, absent blocks 0."""
    m = blocks.shape[-1]
    d = np.zeros((n, n, m, m), blocks.dtype)
    d[np.asarray(rows), np.asarray(cols)] = blocks
    return d.swapaxes(1, 2).reshape(n * m, n * m)


def _lu0(d: np.ndarray) -> None:
    for k in range(d.shape[0]):
        d[k + 1:, k] /= d[k, k]
        d[k + 1:, k + 1:] -= np.outer(d[k + 1:, k], d[k, k + 1:])


def sparselu_tasks(a: np.ndarray, sym: Symbolic, nworkers=None) -> np.ndarray:
    """Factor on the host runtime as the KASTORS DAG: one task a ``lu0``,
    ``fwd``, ``bdiv`` or ``bmod``, each awaiting the last writer of the
    blocks it reads and of the block it writes. ``a`` holds the present
    blocks in slot order; returns the factor's blocks ``[slots, m, m]``
    (fill blocks made by the first ``bmod`` that writes them)."""
    import hclib_tpu as hc

    m = a.shape[-1]
    out = np.full((sym.slots, m, m), np.nan, a.dtype)  # no block is assumed
    out[:sym.n_present] = a
    made = sym.present.copy()
    slot, final = sym.slot_of, sym.final

    def lu0(kk):
        _lu0(out[slot[kk, kk]])

    def fwd(kk, jj):
        d, b = out[slot[kk, kk]], out[slot[kk, jj]]
        b[:] = np.linalg.solve(np.tril(d, -1) + np.eye(m, dtype=d.dtype), b)

    def bdiv(ii, kk):
        d, b = out[slot[kk, kk]], out[slot[ii, kk]]
        b[:] = np.linalg.solve(np.triu(d).T, b.T).T

    def bmod(ii, jj, kk):
        c = out[slot[ii, jj]]
        if not made[ii, jj]:  # allocate_clean_block
            c[:] = 0
            made[ii, jj] = True
        c -= out[slot[ii, kk]] @ out[slot[kk, jj]]

    def main():
        last: Dict[Tuple[int, int], "hc.Future"] = {}

        def after(*blocks):
            return [last[b] for b in blocks if b in last]

        with hc.finish():
            for kk in range(sym.n):
                right = [j for j in range(kk + 1, sym.n) if final[kk, j]]
                below = [i for i in range(kk + 1, sym.n) if final[i, kk]]
                last[kk, kk] = hc.async_future(
                    lu0, kk, await_=after((kk, kk)), non_blocking=True)
                for jj in right:
                    last[kk, jj] = hc.async_future(
                        fwd, kk, jj, await_=after((kk, kk), (kk, jj)),
                        non_blocking=True)
                for ii in below:
                    last[ii, kk] = hc.async_future(
                        bdiv, ii, kk, await_=after((kk, kk), (ii, kk)),
                        non_blocking=True)
                for ii in below:
                    for jj in right:
                        last[ii, jj] = hc.async_future(
                            bmod, ii, jj, kk,
                            await_=after((ii, kk), (kk, jj), (ii, jj)),
                            non_blocking=True)

    hc.launch(main, nworkers=nworkers)
    return out


def run(n: int = 8, m: int = 16, nworkers=None) -> dict:
    sym = symbolic(genmat_pattern(n))
    a = make_blocks(sym, m).astype(np.float64)
    t0 = time.perf_counter()
    f = sparselu_tasks(a, sym, nworkers=nworkers)
    dt = time.perf_counter() - t0
    lu = to_dense(f, sym.rows, sym.cols, n)
    low = np.tril(lu, -1) + np.eye(n * m)
    dense = to_dense(a, sym.rows[:sym.n_present], sym.cols[:sym.n_present], n)
    err = float(np.max(np.abs(low @ np.triu(lu) - dense)))
    return {
        "n": n, "m": m, "max_error": err, "seconds": dt,
        "tasks": sym.tasks, "fill_blocks": sym.fill_blocks,
        "ok": bool(np.isfinite(f).all()) and err < 1e-9 * n * m,
    }


if __name__ == "__main__":  # pragma: no cover
    import sys

    print(run(*(int(x) for x in sys.argv[1:3])))
