"""Plain reference for the Smith-Waterman deployments: the pair from the
seed, and the local-alignment matrix H of one pair by the recurrence

    H[i, j] = max(0, H[i-1, j-1] + s(a_i, b_j), H[i-1, j] - gap, H[i, j-1] - gap)

with ``s`` = ``match`` where the letters agree and ``mismatch`` where not,
H = 0 outside the matrix, all in int32 and exact. Imports nothing of the
program; the scoring constants are the caller's (the configuration's).

``sw_last`` goes row by row in numpy and keeps what a check of the whole
matrix needs without holding the matrix: the best score, H's last row and
H's last column (every cell of H reaches one of the two through the
recurrence, so a wrong tile anywhere shows in them unless a zero or a
larger neighbour absorbs it). Within a row only the gap chain from the left
is sequential. With ``c[j] = max(H[i-1, j-1] + s, H[i-1, j] - gap)`` and
``t[j] = max(c[j], 0)`` the row is ``h[j] = max(t[j], h[j-1] - gap)``,
which unrolls to ``h[j] = max over k <= j of (t[k] - (j - k) * gap)``
``= max over k <= j of (t[k] + k * gap) - j * gap``: a running maximum of
``t + ramp`` less the ramp. The boundary ``h[-1] = 0`` adds ``-gap * (j + 1)``,
below every ``t >= 0``, so it drops out. ``sw_naive`` is the double loop as
written, for ``check`` to hold ``sw_last`` to on a corner of the pair.
"""

from __future__ import annotations

import numpy as np


def make_pair(seed: int, n: int, m: int, alphabet: int = 4):
    """The pair of ``--seed``: ``a`` (n letters) then ``b`` (m letters),
    uniform over the alphabet. Every seed gives the same shapes."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, alphabet, n, dtype=np.int32)
    b = rng.integers(0, alphabet, m, dtype=np.int32)
    return a, b


def sw_last(a: np.ndarray, b: np.ndarray, match: int = 2,
            mismatch: int = -1, gap: int = 1) -> dict:
    """``{"score", "last_row", "last_col"}`` of H for ``a`` (rows) against
    ``b`` (columns): the maximum of H, ``H[n-1, :]`` and ``H[:, m-1]``."""
    n, m = len(a), len(b)
    b = np.asarray(b, np.int32)
    ramp = np.arange(m, dtype=np.int32) * np.int32(gap)
    prev = np.zeros(m, np.int32)
    diag = np.zeros(m, np.int32)
    last_col = np.zeros(n, np.int32)
    score = 0
    for i in range(n):
        s = np.where(b == a[i], np.int32(match), np.int32(mismatch))
        diag[1:] = prev[:-1]  # diag[0] stays H[i-1, -1] = 0
        t = np.maximum(np.maximum(diag + s, prev - np.int32(gap)), 0)
        prev = np.maximum.accumulate(t + ramp) - ramp
        last_col[i] = prev[-1]
        score = max(score, int(prev.max()))
    return {"score": score, "last_row": prev, "last_col": last_col}


def sw_naive(a, b, match: int = 2, mismatch: int = -1,
             gap: int = 1) -> np.ndarray:
    """H, ``(len(a), len(b))`` int32, cell by cell as the recurrence is
    written. Quadratic in Python: for corners and tests."""
    n, m = len(a), len(b)
    h = np.zeros((n + 1, m + 1), np.int64)
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            s = match if a[i - 1] == b[j - 1] else mismatch
            h[i, j] = max(0, h[i - 1, j - 1] + s, h[i - 1, j] - gap,
                          h[i, j - 1] - gap)
    return h[1:, 1:].astype(np.int32)


def wave_counts(nt_i: int, nt_j: int, chunk: int) -> dict:
    """What the wavefront of an ``nt_i`` x ``nt_j`` tile grid holds when
    each anti-diagonal is cut into descriptors of up to ``chunk`` tiles and
    every descriptor of a wave awaits every descriptor of the wave before:
    tiles, descriptors, waves, and the successor words past the two a
    descriptor carries inline (the CSR's)."""
    per_wave = [min(w + 1, nt_i, nt_j, nt_i + nt_j - 1 - w)
                for w in range(nt_i + nt_j - 1)]
    desc = [-(-t // chunk) for t in per_wave]
    return {"tiles": sum(per_wave), "descriptors": sum(desc),
            "waves": len(per_wave),
            "csr_words": sum(d * max(0, nxt - 2)
                             for d, nxt in zip(desc, desc[1:]))}
