"""Plain reference for the jacobi deployment: ``steps`` whole-grid sweeps
of an ``H`` x ``W`` int32 grid with a zero halo,

    next[i, j] = cur[i, j] + cur[i-1, j] + cur[i+1, j] + cur[i, j-1] + cur[i, j+1]

in numpy with wrapping int32, every step a whole sweep of the grid before
the next begins (no tiles, no dependences: what the tiled program must
equal). Imports nothing of the program.

A grid of gigabytes is swept in BANDS of rows with an apron of ``steps``
rows above and below: ``steps`` sweeps of a band's rows need no cell
further than ``steps`` rows away, so a band is a job of its own and the
bands run on several threads (numpy drops the interpreter's lock inside
an add). A step shrinks what is valid of the apron by a row a side; rows
outside the grid are the halo and are put back to zero after every step.

``sweeps_naive`` is the recurrence as written, cell by cell and step by
step in Python's own whole numbers, for ``self_check`` to hold the banded
sweeps to on a corner. The two digests are ``forasync-2d-hbm``'s
(``reference/forasync.py``).
"""

from __future__ import annotations

import collections
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .forasync import W_COL, W_ROW, digests  # noqa: F401  (re-exported)

BAND = 256  # rows of the grid a job advances ``steps`` steps
CORNER = 256  # ``self_check`` compares a CORNER x CORNER corner


def _band(interior: np.ndarray, H: int, W: int, steps: int, r0: int,
          n: int) -> np.ndarray:
    """Rows ``[r0, r0 + n)`` of the grid after ``steps`` steps."""
    top = r0 - steps - 1  # grid row of the work array's row 0
    rows = n + 2 * steps + 2
    cur = np.zeros((rows, W + 2), np.int32)
    lo, hi = max(0, top), min(H, top + rows)
    cur[lo - top:hi - top, 1:W + 1] = interior[lo:hi]
    new = np.zeros_like(cur)
    for _ in range(steps):
        out = new[1:-1, 1:W + 1]
        np.add(cur[1:-1, 1:W + 1], cur[:-2, 1:W + 1], out=out)
        for nb in (cur[2:, 1:W + 1], cur[1:-1, :W], cur[1:-1, 2:]):
            np.add(out, nb, out=out)
        new[:max(0, -top)] = 0  # rows above the grid are the halo
        new[max(0, H - top):] = 0  # and rows below it
        cur, new = new, cur
    return cur[steps + 1:steps + 1 + n, 1:W + 1]


def sweeps(interior: np.ndarray, H: int, W: int, steps: int,
           band: int = BAND, threads: int = 0):
    """Yields ``(row0, block)``: rows ``[row0, row0 + n)`` of the grid
    after ``steps`` steps as an ``(n, W)`` int32 block, top to bottom.
    ``interior`` is the ``(H, W)`` grid the first step reads (a view
    will do; it is only read). At most two bands a thread are in flight,
    so a consumer that keeps up holds a few hundred megabytes."""
    threads = threads or os.cpu_count() or 1
    jobs = [(r, min(band, H - r)) for r in range(0, H, band)]
    with ThreadPoolExecutor(threads) as pool:
        flight = collections.deque()
        for r, n in jobs:
            flight.append((r, pool.submit(
                _band, interior, H, W, steps, r, n)))
            if len(flight) >= 2 * threads:
                r0, f = flight.popleft()
                yield r0, f.result()
        while flight:
            r0, f = flight.popleft()
            yield r0, f.result()


def sweeps_naive(interior: np.ndarray, H: int, W: int, steps: int,
                 c: int) -> np.ndarray:
    """The top-left ``c`` x ``c`` of the grid after ``steps`` steps, cell
    by cell as the recurrence is written, in Python's whole numbers
    (wrapped once at the end: the recurrence is a sum). Computed on the
    corner widened by ``steps`` cells, beyond which nothing reaches it."""
    m = min(c + steps, H)
    k = min(c + steps, W)
    cur = [[int(v) for v in row[:k]] for row in interior[:m]]

    def at(g, i, j):  # the grid's own zero halo above and left; below
        # and right of the widened corner the value is never needed
        return g[i][j] if 0 <= i < m and 0 <= j < k else 0

    for _ in range(steps):
        cur = [[at(cur, i, j) + at(cur, i - 1, j) + at(cur, i + 1, j)
                + at(cur, i, j - 1) + at(cur, i, j + 1)
                for j in range(k)] for i in range(m)]
    out = np.array([[v & 0xFFFFFFFF for v in row[:c]] for row in cur[:c]],
                   np.uint64)
    return out.astype(np.uint32).view(np.int32)


def loop_counts(H: int, W: int, tile, steps: int) -> dict:
    """What ``steps`` time steps of a RECURSIVE ``forasync2D`` over ``H``
    x ``W`` in ``tile`` make, every tile awaiting itself and the tiles it
    shares an edge with in the step before: tiles (all steps), splits
    (step 0's binary tree), tiles released by a dependence (every tile of
    steps 1 and later), decrements (one for each awaited tile of each of
    those) and descriptors executed."""
    ny, nx = (-(-n // t) for n, t in zip((H, W), tile))
    one = ny * nx
    awaited = one + 2 * (ny - 1) * nx + 2 * ny * (nx - 1)
    return {"tiles": steps * one, "splits": one - 1,
            "released": (steps - 1) * one,
            "decrements": (steps - 1) * awaited,
            "executed": steps * one + one - 1}


def self_check(interior: np.ndarray, H: int, W: int, tile, steps: int,
               stated: dict):
    """The reference held to itself: the banded sweeps (in bands of 64
    rows, so that several meet inside the corner) against
    ``sweeps_naive`` on the grid's corner, cells that differ; and
    ``loop_counts`` against the configuration's ``stated`` counts."""
    c = min(CORNER, H, W)
    m = min(c + steps, H)
    quick = np.concatenate([
        b[:, :c].copy() for r, b in sweeps(interior[:m], m, W, steps,
                                           band=64) if r < c])[:c]
    errs = {"corner_differing": int(np.count_nonzero(
        quick != sweeps_naive(interior, H, W, steps, c)))}
    counts = loop_counts(H, W, tile, steps)
    errs.update({k + "_abs_err": abs(counts[k] - stated[k]) for k in counts})
    return c, counts, errs
