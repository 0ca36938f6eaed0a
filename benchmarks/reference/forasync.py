"""Plain reference for the forasync deployments: one 5-point sweep of an
``H`` x ``W`` int32 grid with a zero halo,

    gout[i, j] = gin[i, j] + gin[i-1, j] + gin[i+1, j] + gin[i, j-1] + gin[i, j+1]

in numpy, a band of rows at a time so that a grid of gigabytes fits, with
the two digests a call of the deployment is held to and the counts of
its loop. Imports nothing of the program.

The grid arrives in the program's layout (``padded``): the interior at
``[1:H+1, 1:W+1]`` of a larger zero array, so the halo is the array's
own zeros. ``sweep_naive`` is the recurrence as written, cell by cell,
for ``self_check`` to hold ``sweep`` to on a corner.
"""

from __future__ import annotations

import math

import numpy as np

BAND = 1024  # rows of the interior a step of ``sweep`` holds at once
CORNER = 256  # ``self_check`` compares a CORNER x CORNER corner

# The position-weighted digest's weight of cell (i, j), in wrapping
# 32-bit arithmetic: two odd multipliers, so that swapping two cells, two
# rows or two tiles changes the sum.
W_ROW, W_COL = 40503, 30011


def sweep(padded: np.ndarray, H: int, W: int, band: int = BAND):
    """Yields ``(row0, block)``: the sweep's rows ``[row0, row0 + n)`` as
    an ``(n, W)`` int32 block, top to bottom. Every block is a view of
    ONE buffer, overwritten by the next step (a fresh gigabyte of pages a
    sweep is most of what a band-wise numpy pass costs): use it, or copy
    it, before asking for the next."""
    buf = np.empty((min(band, H), W), np.int32)
    for r in range(0, H, band):
        n = min(band, H - r)
        out = buf[:n]
        np.add(padded[r + 1:r + n + 1, 1:W + 1],
               padded[r:r + n, 1:W + 1], out=out)
        for nb in (padded[r + 2:r + n + 2, 1:W + 1],
                   padded[r + 1:r + n + 1, :W],
                   padded[r + 1:r + n + 1, 2:W + 2]):
            np.add(out, nb, out=out)
        yield r, out


def sweep_naive(padded: np.ndarray, H: int, W: int) -> np.ndarray:
    """The top-left ``H`` x ``W`` of the sweep, cell by cell as the
    recurrence is written. Quadratic in Python: for corners and tests."""
    out = np.zeros((H, W), np.int64)
    for i in range(H):
        for j in range(W):
            out[i, j] = (int(padded[i + 1, j + 1]) + int(padded[i, j + 1])
                         + int(padded[i + 2, j + 1]) + int(padded[i + 1, j])
                         + int(padded[i + 1, j + 2]))
    return out.astype(np.int32)


def _wrapped(total: int) -> int:
    """A whole number as the int32 its low 32 bits spell."""
    return int(np.uint32(total & 0xFFFFFFFF).view(np.int32))


def digests(blocks, W: int):
    """``(plain, weighted)`` of a grid given as ``sweep`` gives it, both
    wrapping int32: the sum of every cell, and the sum of every cell
    times its position's weight ``i * W_ROW + j * W_COL + 1``. The weight
    is a row's part plus a column's, so the weighted sum is the row sums
    against ``i * W_ROW`` plus the column sums against ``j * W_COL + 1``:
    no product a cell, and no array the size of a band."""
    col_w = np.arange(W, dtype=np.uint32) * np.uint32(W_COL) + np.uint32(1)
    plain = weighted = 0
    for row0, block in blocks:
        u = block.view(np.uint32)
        rows = u.sum(axis=1, dtype=np.uint32)
        cols = u.sum(axis=0, dtype=np.uint32)
        row_w = (np.arange(row0, row0 + len(u), dtype=np.uint32)
                 * np.uint32(W_ROW))
        plain += int(rows.sum(dtype=np.uint32))
        weighted += int((rows * row_w).sum(dtype=np.uint32))
        weighted += int((cols * col_w).sum(dtype=np.uint32))
    return _wrapped(plain), _wrapped(weighted)


def loop_counts(H: int, W: int, tile) -> dict:
    """What a RECURSIVE ``forasync2D`` over ``H`` x ``W`` in ``tile`` makes:
    tiles, splits (the inner nodes of a binary tree with a leaf a tile)
    and descriptors executed (both)."""
    tiles = math.prod(-(-n // t) for n, t in zip((H, W), tile))
    return {"tiles": tiles, "splits": tiles - 1, "executed": 2 * tiles - 1}


def self_check(padded: np.ndarray, H: int, W: int, tile, stated: dict):
    """The reference held to itself: ``sweep`` against ``sweep_naive`` on
    the grid's corner (cells that differ), and ``loop_counts`` against the
    configuration's ``stated`` counts (absolute differences)."""
    c = min(CORNER, H, W)
    quick = np.concatenate(
        [b[:, :c].copy() for _, b in sweep(padded[:c + 2], c, W, band=64)])
    errs = {"corner_differing": int(np.count_nonzero(
        quick != sweep_naive(padded, c, c)))}
    counts = loop_counts(H, W, tile)
    errs.update({k + "_abs_err": abs(counts[k] - stated[k]) for k in counts})
    return c, counts, errs
