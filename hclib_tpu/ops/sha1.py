"""FIPS-180-1 SHA-1 compression, vectorized over arrays of any shape.

The UTS splittable RNG (one hash per tree node). Generic over the array
module: ``xp=jnp`` hashes device planes inside the vectorized DFS and
whole BFS frontier levels in the seeding's device expansion
(hclib_tpu/device/uts_vec.py: uts_seed_expand); ``xp=numpy`` is the same
on the host, and ``sha1_children_np`` is that host hash without a
temporary, for the seeding's small levels at the root. A scalar
single-block variant lives in the native runtime
(hclib_tpu/native/src/sha1.hpp).
"""

from __future__ import annotations

from typing import List

import numpy as np

__all__ = ["sha1_block", "sha1_child", "sha1_children_np"]

_K = (0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6)
_H = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0)


def _rotl(x, s: int):
    # Plain-int shift amounts keep u32 dtype under both numpy (NEP 50 weak
    # scalars) and jnp weak typing.
    return (x << s) | (x >> (32 - s))


def sha1_block(w16: List, xp):
    """SHA-1 compression of one 16-word block."""
    K, H = _K, _H
    w = list(w16)
    a = xp.full_like(w[0], H[0])
    b = xp.full_like(w[0], H[1])
    c = xp.full_like(w[0], H[2])
    d = xp.full_like(w[0], H[3])
    e = xp.full_like(w[0], H[4])
    for i in range(80):
        if i >= 16:
            nw = _rotl(w[(i - 3) % 16] ^ w[(i - 8) % 16] ^ w[(i - 14) % 16]
                       ^ w[i % 16], 1)
            w[i % 16] = nw
        wi = w[i % 16]
        if i < 20:
            f = (b & c) | (~b & d)
            k = K[0]
        elif i < 40:
            f = b ^ c ^ d
            k = K[1]
        elif i < 60:
            f = (b & c) | (b & d) | (c & d)
            k = K[2]
        else:
            f = b ^ c ^ d
            k = K[3]
        tmp = _rotl(a, 5) + f + e + xp.uint32(k) + wi
        e, d, c, b, a = d, c, _rotl(b, 30), a, tmp
    return (
        a + xp.uint32(H[0]),
        b + xp.uint32(H[1]),
        c + xp.uint32(H[2]),
        d + xp.uint32(H[3]),
        e + xp.uint32(H[4]),
    )


def sha1_child(state5, child_idx, xp):
    """SHA1(parent_state(20B) || BE32(child)) for UTS's 24-byte messages."""
    zero = xp.zeros_like(state5[0])
    w16 = [
        state5[0], state5[1], state5[2], state5[3], state5[4],
        child_idx.astype(xp.uint32),
        xp.full_like(state5[0], 0x80000000),
        zero, zero, zero, zero, zero, zero, zero, zero,
        xp.full_like(state5[0], 24 * 8),
    ]
    return sha1_block(w16, xp)


def sha1_children_np(state: np.ndarray, parent: np.ndarray,
                     index: np.ndarray) -> np.ndarray:
    """``sha1_child`` for numpy, in place: the (5, n) states of
    SHA1(state[:, parent[j]] || BE32(index[j])), bit-identical to
    ``np.stack(sha1_child(list(state[:, parent]), index, np))``.

    Every operation writes into one of 23 rows allocated once (``out=``),
    where the generic form above allocates a fresh n-word temporary for
    each of its 1.4k operations. At the 300 k nodes of a seeding level (on
    the host until PR 30; levels that size now go to the device)
    those are 1.2 MB blocks that the allocator maps and unmaps one by one,
    and in a process with the TPU runtime's threads that cost differed
    between processes by half. A whole level at once, not L2-sized chunks:
    on the v5e's host chunks of 4096 and 8192 nodes were slower (numpy's
    call overhead) and no steadier between processes (PERF.md, PR 29)."""
    n = parent.shape[0]
    rows = np.empty((23, n), np.uint32)
    w, (a, b, c, d, e, t, f) = rows[:16], rows[16:]
    for i in range(5):
        np.take(state[i], parent, out=w[i])
    w[5] = index
    w[6] = 0x80000000
    w[7:15] = 0
    w[15] = 24 * 8
    for x, h in zip((a, b, c, d, e), _H):
        x[:] = h
    for r in range(80):
        x = w[r % 16]
        if r >= 16:
            x ^= w[(r - 3) % 16]
            x ^= w[(r - 8) % 16]
            x ^= w[(r - 14) % 16]
            np.left_shift(x, 1, out=t)  # x = rotl(x, 1)
            np.right_shift(x, 31, out=x)
            x |= t
        if r < 20:  # Ch, as d ^ (b & (c ^ d))
            np.bitwise_xor(c, d, out=f)
            f &= b
            f ^= d
        elif 40 <= r < 60:  # Maj, as (b & c) | (d & (b | c))
            np.bitwise_or(b, c, out=f)
            f &= d
            np.bitwise_and(b, c, out=t)
            f |= t
        else:  # Parity
            np.bitwise_xor(b, c, out=f)
            f ^= d
        # e becomes rotl(a, 5) + f + e + k + w; the halves of a rotate
        # share no bit, so they add
        e += f
        e += x
        e += np.uint32(_K[r // 20])
        np.left_shift(a, 5, out=t)
        e += t
        np.right_shift(a, 27, out=t)
        e += t
        np.left_shift(b, 30, out=t)  # b = rotl(b, 30)
        np.right_shift(b, 2, out=b)
        b |= t
        a, b, c, d, e = e, a, b, c, d
    out = np.empty((5, n), np.uint32)
    for i, (x, h) in enumerate(zip((a, b, c, d, e), _H)):
        np.add(x, np.uint32(h), out=out[i])
    return out
