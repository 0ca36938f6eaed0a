"""Plain reference for the UTS deployment (upstream HClib ``test/uts``):
counts a whole geometric tree level by level, from the published algorithm
alone. Imports nothing of the program.

The specification (``test/uts/uts.c:143-221``, ``rng/brg_sha1.c:49-93``):
a node's state is a SHA-1 digest; the root's is ``SHA1(16 zero bytes ||
BE32(root_seed))``, child ``i``'s is ``SHA1(parent || BE32(i))``; a node at
depth ``d`` has ``floor(log(1 - u) / log(1 - p))`` children, ``u`` the last
31 bits of its state over 2^31, ``p = 1 / (1 + b_d)``, at most 100.

Departures from ``uts.c``, each for speed of the reference only:

- breadth first over whole levels (``uts.c`` is depth first, one node at a
  time); the counts do not depend on the order;
- the SHA-1 compression is written out from FIPS 180-1 section 7 over
  uint32 arrays (``brg_sha1.c`` hashes one byte string at a time); both
  messages are shorter than one block, so one compression each;
- only the FIXED shape (``-a 3``: ``b_d = b0`` while ``d < gen_mx``, else
  0) is written; another shape raises;
- a level at depth ``gen_mx`` has no children whatever its states, so it
  is counted from its parents' child counts and its states are never
  computed (``uts.c`` hashes every node as it is spawned). ``hashed_nodes``
  is therefore the number of nodes at depths 1 to ``gen_mx - 1``: the
  hashes no traversal can avoid.
"""

from __future__ import annotations

import math

import numpy as np

MAX_CHILDREN = 100  # uts.h:31 MAXNUMCHILDREN
BLOCK = 1 << 20  # children hashed at once on a device


def _rotl(x, s: int):
    return (x << s) | (x >> (32 - s))


def sha1_compress(w16, xp):
    """FIPS 180-1 section 7: the digest (five uint32 arrays) of ONE padded
    512-bit block given as sixteen uint32 arrays, from the initial H."""
    h = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0)
    w = list(w16)
    for t in range(16, 80):
        w.append(_rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1))
    zero = xp.zeros_like(w[0])
    a, b, c, d, e = (zero + xp.uint32(x) for x in h)
    for t in range(80):
        if t < 20:
            f, k = (b & c) | (~b & d), 0x5A827999
        elif t < 40:
            f, k = b ^ c ^ d, 0x6ED9EBA1
        elif t < 60:
            f, k = (b & c) | (b & d) | (c & d), 0x8F1BBCDC
        else:
            f, k = b ^ c ^ d, 0xCA62C1D6
        temp = _rotl(a, 5) + f + e + w[t] + xp.uint32(k)
        a, b, c, d, e = temp, a, _rotl(b, 30), c, d
    return [x + xp.uint32(y) for x, y in zip((a, b, c, d, e), h)]


def _message(words, nbytes: int, xp):
    """FIPS 180-1 section 4 padding of a message of whole words shorter
    than 56 bytes: a one bit, zeros, the length in bits."""
    zero = xp.zeros_like(words[0])
    pad = [zero + xp.uint32(0x80000000)]
    pad += [zero] * (15 - len(words) - 1)
    return list(words) + pad + [zero + xp.uint32(8 * nbytes)]


def root_state(root_seed: int, xp=np):
    """rng_init: SHA1(16 zero bytes || BE32(seed)), as five (1,) arrays."""
    zero = xp.zeros(1, xp.uint32)
    seed = zero + xp.uint32(root_seed & 0xFFFFFFFF)
    return sha1_compress(_message([zero] * 4 + [seed], 20, xp), xp)


def child_state(parent5, index, xp=np):
    """rng_spawn: SHA1(parent (20 bytes) || BE32(index))."""
    return sha1_compress(
        _message(list(parent5) + [index.astype(xp.uint32)], 24, xp), xp
    )


def num_children(state4: np.ndarray, depth: int, tree: dict) -> np.ndarray:
    """uts_numChildren_geo in f64 on the host, for every node of a level:
    ``state4`` is the last word of their states."""
    if tree["shape"] != "FIXED":
        raise NotImplementedError(f"shape {tree['shape']!r}")
    b_d = float(tree["b0"]) if depth < tree["gen_mx"] else 0.0
    if b_d <= 0.0:
        return np.zeros(state4.shape, np.int64)
    p = 1.0 / (1.0 + b_d)
    u = (state4 & np.uint32(0x7FFFFFFF)).astype(np.float64) / 2147483648.0
    n = np.floor(np.log(1.0 - u) / math.log(1.0 - p))
    return np.minimum(n, MAX_CHILDREN).astype(np.int64)


def _hasher(xp):
    """The block hash in ``xp``: (6, n) uint32 messages (a parent's five
    words, the child's index) -> the (5, n) child states. numpy hashes a
    level whole; on a device the hash is jitted once and runs over blocks
    of one fixed size, the last one padded."""
    def hash_all(msg):
        return xp.stack(child_state(list(msg[:5]), msg[5], xp))

    if xp is np:
        return hash_all
    import jax

    hash_block = jax.jit(hash_all)

    def in_blocks(msg):
        total = msg.shape[1]
        out = np.empty((5, total), np.uint32)
        for s in range(0, total, BLOCK):
            m = min(BLOCK, total - s)
            block = np.zeros((6, BLOCK), np.uint32)
            block[:, :m] = msg[:, s:s + m]
            out[:, s:s + m] = np.asarray(hash_block(xp.asarray(block)))[:, :m]
        return out

    return in_blocks


def _messages(state: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """The (6, sum(counts)) messages of a level's children, in order."""
    parent = np.repeat(np.arange(counts.shape[0]), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    index = np.arange(parent.shape[0]) - first
    return np.concatenate([state[:, parent], index[None].astype(np.uint32)])


def count_tree(tree: dict, xp=np) -> dict:
    """Nodes, leaves, depth, the size of every level and the number of
    states that had to be hashed, for the whole tree ``tree`` (``shape``,
    ``gen_mx``, ``b0``, ``root_seed``). ``xp`` is the array module the
    block hash runs in: numpy, or ``jax.numpy`` on a device."""
    state = np.stack(root_state(tree["root_seed"]))
    hash_children = _hasher(xp)
    levels, leaves, hashed = [], 0, 0
    while True:
        depth = len(levels)
        levels.append(state.shape[1])
        counts = num_children(state[4], depth, tree)
        leaves += int((counts == 0).sum())
        total = int(counts.sum())
        if total == 0:
            break
        if depth + 1 >= tree["gen_mx"]:  # leaves by the specification
            levels.append(total)
            leaves += total
            break
        state = hash_children(_messages(state, counts))
        hashed += total
    return {"nodes": sum(levels), "leaves": leaves,
            "depth": len(levels) - 1, "levels": levels,
            "hashed_nodes": hashed}
