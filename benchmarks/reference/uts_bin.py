"""Plain reference for the binomial UTS deployment (upstream HClib
``test/uts``, tree type ``-t 0``): counts a whole binomial tree level by
level, from the published algorithm alone. Imports nothing of the program;
the SHA-1 block and the two UTS messages are ``reference/uts.py``'s.

The specification (``test/uts/uts.c``, ``uts_numChildren_bin``;
``rng/brg_sha1.c:49-93``): a node's state is a SHA-1 digest; the root's is
``SHA1(16 zero bytes || BE32(root_seed))``, child ``i``'s is ``SHA1(parent
|| BE32(i))``. The root has ``floor(b0)`` children (the cap of 100 does not
apply to a BIN root); every other node has ``m`` children if ``toProb(
rng_rand(state)) < q``, that is if the last 31 bits of its state over 2^31,
in float64, are below ``q``, and none otherwise.

Departures from ``uts.c``, each for speed of the reference only:

- breadth first over whole levels (``uts.c`` is depth first, one node at a
  time); the counts do not depend on the order;
- the SHA-1 compression is ``reference/uts.py``'s, written out from FIPS
  180-1 over uint32 arrays (``brg_sha1.c`` hashes one byte string at a
  time);
- the float64 compare ``r / 2^31 < q`` is made in integers: ``r`` is an
  integer below 2^31, so ``r / 2^31`` is exact in float64, and so is
  ``q * 2^31``; the compare is ``r < ceil(q * 2^31)`` exactly (the device
  has no float64);
- on a device the whole count is ONE jitted loop over levels: the level's
  non-leaf nodes sit compacted in an array of fixed capacity (``PARENTS``;
  a level that holds more raises) and are expanded ``CHUNK`` parents at a
  time, so a level costs what it holds and not the capacity. Under numpy a
  level is expanded whole;
- every node but the root is hashed to learn whether it is a leaf, so
  ``hashed_nodes`` is ``nodes - 1``: no traversal can avoid one of them.

Counters on the device are int32: a tree of 2^31 nodes and more raises.
"""

from __future__ import annotations

import math

import numpy as np

from .uts import child_state, root_state

PARENTS = 1 << 16  # non-leaf nodes of one level the device loop can hold
CHUNK = 2048  # parents expanded at once on a device


def nonleaf_below(q: float) -> int:
    """The integer ``T`` with ``r / 2^31 < q`` (float64) iff ``r < T``."""
    return math.ceil(q * 2147483648.0)


def _rand(state4, xp):
    return (state4 & xp.uint32(0x7FFFFFFF)).astype(xp.int32)


def _root_children(tree: dict) -> np.ndarray:
    """The states of the root's floor(b0) children, (5, b0) uint32."""
    root = [np.repeat(w, int(math.floor(tree["b0"])))
            for w in root_state(tree["root_seed"])]
    index = np.arange(root[0].shape[0], dtype=np.uint32)
    return np.stack(child_state(root, index))


def _count_numpy(tree: dict) -> dict:
    below, m = nonleaf_below(tree["q"]), int(tree["m"])
    level = _root_children(tree)
    nodes, leaves, depth, widest = 1, int(level.shape[1] == 0), 0, 1
    while level.shape[1]:
        n = level.shape[1]
        nodes, depth, widest = nodes + n, depth + 1, max(widest, n)
        parents = level[:, _rand(level[4], np) < below]
        leaves += n - parents.shape[1]
        index = np.tile(np.arange(m, dtype=np.uint32), parents.shape[1])
        level = np.stack(child_state(
            list(np.repeat(parents, m, axis=1)), index))
    return {"nodes": nodes, "leaves": leaves, "depth": depth,
            "widest_level": widest}


def _device_loop(m: int):
    """The jitted loop over levels: (frontier, five (PARENTS,) u32 arrays,
    its size, threshold) -> (nodes, leaves, levels, widest level,
    overflow), all below the frontier's own level. Five arrays and not one
    stacked: XLA's CPU backend fuses a stack with the hash above it and
    then computes every hash five times over."""
    import jax
    import jax.numpy as jnp

    slot = jnp.arange(CHUNK * m, dtype=jnp.int32)

    def expand_chunk(c, carry):
        parents, n, below, nxt, nn, over = carry
        blk = [jnp.repeat(jax.lax.dynamic_slice(w, (c * CHUNK,), (CHUNK,)),
                          m) for w in parents]
        live = (c * CHUNK + slot // m) < n
        child = child_state(blk, (slot % m).astype(jnp.uint32), jnp)
        keep = live & (_rand(child[4], jnp) < below)
        place = nn + jnp.cumsum(keep.astype(jnp.int32)) - 1
        place = jnp.where(keep, place, PARENTS)  # dropped
        nxt = tuple(a.at[place].set(w, mode="drop")
                    for a, w in zip(nxt, child))
        nn = nn + jnp.sum(keep, dtype=jnp.int32)
        return parents, n, below, nxt, nn, over | (nn > PARENTS)

    def level(carry):
        parents, n, below, nodes, leaves, depth, widest, over = carry
        chunks = (n + CHUNK - 1) // CHUNK
        _, _, _, nxt, nn, over = jax.lax.fori_loop(
            0, chunks, expand_chunk,
            (parents, n, below, tuple(jnp.zeros_like(w) for w in parents),
             jnp.int32(0), over))
        born = n * m
        return (nxt, nn, below, nodes + born, leaves + born - nn,
                depth + 1, jnp.maximum(widest, born),
                over | (nodes + born < 0))

    @jax.jit
    def run(parents, n, below):
        zero = jnp.int32(0)
        out = jax.lax.while_loop(
            lambda c: (c[1] > 0) & ~c[7], level,
            (parents, n, below, zero, zero, zero, zero, jnp.bool_(False)))
        return out[3:]

    return run


def _count_device(tree: dict) -> dict:
    import jax.numpy as jnp

    below, m = nonleaf_below(tree["q"]), int(tree["m"])
    top = _root_children(tree)
    parents = top[:, _rand(top[4], np) < below]
    n = parents.shape[1]
    if n > PARENTS:
        raise OverflowError(f"{n} non-leaf root children, room for {PARENTS}")
    frontier = np.zeros((5, PARENTS), np.uint32)
    frontier[:, :n] = parents
    nodes, leaves, levels, widest, over = (int(x) for x in _device_loop(m)(
        tuple(jnp.asarray(w) for w in frontier), jnp.int32(n),
        jnp.int32(below)))
    if over:
        raise OverflowError(
            f"a level held more than {PARENTS} non-leaf nodes, or the tree "
            "2^31 nodes: the reference's device loop cannot count it")
    b0 = top.shape[1]
    return {"nodes": 1 + b0 + nodes, "leaves": max(b0, 1) - n + leaves,
            "depth": (1 if b0 else 0) + levels,
            "widest_level": max(1, b0, widest)}


def count_tree(tree: dict, xp=np) -> dict:
    """Nodes, leaves, depth, the states that had to be hashed and the
    widest level of the whole binomial tree ``tree`` (``b0``, ``q``, ``m``,
    ``root_seed``). ``xp`` is the array module the hashes run in: numpy, or
    ``jax.numpy`` on a device."""
    out = _count_numpy(tree) if xp is np else _count_device(tree)
    return {**out, "hashed_nodes": out["nodes"] - 1}
