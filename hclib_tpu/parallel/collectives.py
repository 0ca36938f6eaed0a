"""Collective operations over mesh axes.

The reference's distributed layer is MPI collectives serviced by a NIC-locale
worker (modules/mpi/src/hclib_mpi.cpp:220-286: Allreduce/Bcast/Barrier as
finish{async_nb_at(nic)}). TPU-first these are XLA collectives compiled into
the program and riding ICI/DCN. Two tiers live here:

1. Primitive parity aliases (XLA has the op; the name maps the reference's
   vocabulary onto it):

    MPI_Allreduce(SUM)  -> psum(x, axis)
    MPI_Allgather       -> all_gather(x, axis)
    MPI_Reduce_scatter  -> reduce_scatter(x, axis)
    MPI_Alltoall        -> all_to_all(x, axis, ...)
    SHMEM put-to-right  -> ring_permute(x, axis, shift)

2. Composed collectives XLA does NOT expose as single primitives, built
   here from masks and permutes (all usable inside jit/shard_map):

    MPI_Bcast           -> bcast(x, axis, root)       (mask + psum)
    MPI_Reduce          -> reduce(x, axis, root)      (psum + root mask)
    MPI_Exscan          -> exscan(x, axis)            (log-step doubling)
    MPI_Barrier         -> barrier(axis)              (token psum)
    ring_allreduce(x, axis) - the bandwidth-optimal reduce-scatter +
    all-gather ring schedule written out in ppermute steps. XLA's psum
    normally picks this (or better) by itself; this explicit form is for
    pipelining reductions against compute under jax.remat boundaries and
    as the reference schedule the profiler compares psum against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = [
    "psum", "all_gather", "reduce_scatter", "all_to_all", "ring_permute",
    "bcast", "reduce", "exscan", "barrier", "ring_allreduce",
]


def psum(x, axis: str):
    return jax.lax.psum(x, axis)


def all_gather(x, axis: str, *, tiled: bool = False):
    return jax.lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_dimension: int = 0):
    return jax.lax.psum_scatter(
        x, axis, scatter_dimension=scatter_dimension, tiled=True
    )


def all_to_all(x, axis: str, *, split_axis: int = 0, concat_axis: int = 0):
    return jax.lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def ring_permute(x, axis: str, shift: int = 1):
    """Rotate shards around the mesh axis (one-sided neighbor exchange)."""
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def _check_root(root: int, axis: str) -> None:
    n = jax.lax.axis_size(axis)
    if not (0 <= root < n):
        raise ValueError(f"root {root} out of range for {n}-shard axis {axis!r}")


def bcast(x, axis: str, root: int = 0):
    """MPI_Bcast: every shard receives the root shard's value. Composed as
    mask-then-psum (zero everywhere but the root, sum across the axis) -
    one collective, no gather of the full axis."""
    _check_root(root, axis)
    me = jax.lax.axis_index(axis)
    masked = jnp.where(me == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


def reduce(x, axis: str, root: int = 0):
    """MPI_Reduce(SUM): the reduction lands on ``root``; other shards get
    zeros. (XLA computes the allreduce either way on TPU - the rooted
    form exists for API parity and so callers can elide the result's
    later use on non-roots, letting DCE drop it.)"""
    _check_root(root, axis)
    me = jax.lax.axis_index(axis)
    s = jax.lax.psum(x, axis)
    return jnp.where(me == root, s, jnp.zeros_like(s))


def exscan(x, axis: str):
    """MPI_Exscan(SUM): shard i receives sum of shards [0, i) - rank 0
    gets zeros. Hillis-Steele doubling in log2(n) ppermute steps; works
    for any axis size (shifts past the edge contribute zero)."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    acc = x
    total = jnp.zeros_like(x)
    shift = 1
    while shift < n:
        perm = [(i, i + shift) for i in range(n - shift)]
        moved = jax.lax.ppermute(acc, axis, perm)
        # Ranks < shift received nothing: their incoming slot is zeros
        # (ppermute leaves unnamed destinations zero-filled).
        total = total + jnp.where(me >= shift, moved, jnp.zeros_like(x))
        acc = acc + moved
        shift *= 2
    # ``total`` accumulated every prefix contribution except x itself.
    return total


def barrier(axis: str):
    """MPI_Barrier: a 1-element token allreduce; returns the token so the
    caller can thread a data dependency through it (inside jit, ordering
    IS data dependence - there is no side-effect fence to wait on)."""
    return jax.lax.psum(jnp.ones((), jnp.int32), axis)


def ring_allreduce(x, axis: str):
    """Bandwidth-optimal allreduce written as explicit ring steps:
    reduce-scatter (n-1 ppermutes, each shard ends owning one fully
    reduced chunk) then all-gather (n-1 more). Requires the leading dim
    divisible by the axis size. Matches psum numerically; exists as the
    reference schedule for profiling and for manual compute/comm
    pipelining (interleave chunk FLOPs between steps)."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    me = jax.lax.axis_index(axis)
    chunks = jnp.stack(jnp.split(x, n, axis=0))  # (n, ...) chunk view
    right = [(i, (i + 1) % n) for i in range(n)]

    # Reduce-scatter: at step s, send the partial for chunk (me - s),
    # receive and fold the partial for chunk (me - s - 1).
    send_idx = me
    partial = chunks[send_idx]
    for s in range(n - 1):
        moved = jax.lax.ppermute(partial, axis, right)
        send_idx = (send_idx - 1) % n
        partial = chunks[send_idx] + moved
    # Every shard now owns the fully reduced chunk (me + 1) % n.

    # All-gather: circulate the reduced chunks; scatter each into place.
    own_idx = (me + 1) % n
    out = jnp.zeros_like(chunks)
    cur, cur_idx = partial, own_idx
    out = out.at[cur_idx].set(cur)
    for s in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, right)
        cur_idx = (cur_idx - 1) % n
        out = out.at[cur_idx].set(cur)
    return out.reshape(x.shape)
