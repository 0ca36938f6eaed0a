"""Fused-Pallas Smith-Waterman: the batched row sweep resident on-core.

sw_vec.py expresses the sweep as a ``lax.scan`` whose (B, m) carry and
~20 plane ops per row round-trip HBM between XLA ops - the same unfused
overhead the UTS engine shed in uts_pallas.py. Here one kernel runs the
whole n-row sweep with the DP row, the running best, and both sequence
blocks VMEM-resident; a grid over batch blocks lets Pallas double-buffer
the next block's sequence data while the current block computes.

Layout is the transpose of sw_vec's: **batch on the lane axis, sequence on
sublanes** ((m, B) planes, sequences passed pre-transposed). That makes
the per-row query symbol an 8-aligned sublane slice + select (Mosaic can
neither vector-load a 1-wide lane slice nor prove unaligned sublane
offsets), the diagonal shift a static sublane concat, and the horizontal
chain a sublane-shifted max cascade - no transposes, no gathers, no MXU.

Same recurrences as sw_vec (shared constants; exact vs the sequential
reference DP models/smithwaterman.py):
- vertical/diagonal: t = max(diag + subst, prev - GAP, 0)
- in-row horizontal chain via the decay-cummax identity
  c[j] = cummax(t + j)[j] - j, computed as log2(m) shifted maxima
  (associative_scan does not lower in Mosaic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.smithwaterman import GAP, MATCH, MISMATCH
from .megakernel import resolve_interpret

__all__ = ["sw_scores_pallas"]

assert GAP == 1, "decay-cummax form assumes unit linear gap"

_NEG = -(1 << 30)  # plain int: a jnp scalar here would be captured as a
# traced constant, which pallas kernels reject


def _shifted_cummax0(c):
    """cummax along axis 0 (sublanes) as log2(m) static shifted maxima."""
    m = c.shape[0]
    sh = 1
    while sh < m:
        pad = jnp.full((sh, c.shape[1]), _NEG, c.dtype)
        c = jnp.maximum(c, jnp.concatenate([pad, c[:-sh, :]], axis=0))
        sh *= 2
    return c


def _kernel(n: int, a_ref, b_ref, out_ref):
    bs = b_ref[...]  # (m, Bb)
    m, Bb = bs.shape
    iidx = jax.lax.broadcasted_iota(jnp.int32, (m, Bb), 0)
    sel_iota = jax.lax.broadcasted_iota(jnp.int32, (8, Bb), 0)

    def row(i, carry):
        prev, best = carry
        # Query symbol i for every batch lane: 8-aligned sublane slice of
        # the (n, Bb) query block, then an in-register row select.
        base = (i // 8) * 8
        blk = a_ref[pl.ds(base, 8), :]  # (8, Bb)
        ai = jnp.sum(
            jnp.where(sel_iota == (i - base), blk, 0), axis=0, keepdims=True
        )  # (1, Bb)
        s = jnp.where(bs == ai, MATCH, MISMATCH).astype(jnp.int32)
        diag = jnp.concatenate(
            [jnp.zeros((1, Bb), jnp.int32), prev[:-1, :]], axis=0
        )
        t = jnp.maximum(jnp.maximum(diag + s, prev - GAP), 0)
        c = _shifted_cummax0(t + iidx) - iidx
        return c, jnp.maximum(best, c)

    zeros = jnp.zeros((m, Bb), jnp.int32)
    _, best = jax.lax.fori_loop(0, n, row, (zeros, zeros))
    out_ref[...] = jnp.max(best, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _sw_pallas(a_t, b_t, block_b: int = 256, interpret: bool = False):
    """a_t (n, B) and b_t (m, B) pre-transposed; returns (1, B) scores.
    B must be a whole number of batch blocks (sw_scores_pallas pads)."""
    n, B = a_t.shape
    m = b_t.shape[0]
    if B % block_b:
        raise ValueError(f"B={B} not a multiple of block_b={block_b}")
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_b), lambda g: (0, g),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((m, block_b), lambda g: (0, g),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda g: (0, g),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        interpret=interpret,  # bool: the fast XLA-backed interpreter
        # (InterpretParams would select the slow Mosaic one - only
        # remote-DMA/semaphore kernels need that; see megakernel.py)
    )(a_t, b_t)


def sw_scores_pallas(a_batch, b_batch, block_b: int = 256,
                     interpret=None) -> np.ndarray:
    """Scores for B pairs: a_batch (B, n) vs b_batch (B, m) -> (B,) i32.
    B is padded to a whole number of batch blocks and n to a multiple of 8
    (pad symbol -1 matches nothing, so scores are unchanged). The default
    block of 256 pairs is the bench's; 512 at m=1024 needs 16.66 MiB of
    scoped VMEM and the v5e compiler's default limit is 16 MiB."""
    interpret = resolve_interpret(interpret)
    a = np.asarray(a_batch, np.int32)
    b = np.asarray(b_batch, np.int32)
    B = a.shape[0]
    # Lane-axis blocks must be 128-multiples; tiny batches pad up to one
    # minimal block.
    block_b = max(128, (min(block_b, B) // 128) * 128)
    padb = (-B) % block_b
    if padb:
        a = np.concatenate([a, np.zeros((padb, a.shape[1]), np.int32)])
        b = np.concatenate([b, np.full((padb, b.shape[1]), -1, np.int32)])
    padn = (-a.shape[1]) % 8
    if padn:
        a = np.concatenate(
            [a, np.full((a.shape[0], padn), -1, np.int32)], axis=1
        )
    out = _sw_pallas(
        jnp.asarray(a.T), jnp.asarray(b.T), block_b=block_b,
        interpret=interpret,  # bool: the fast XLA-backed interpreter
        # (InterpretParams would select the slow Mosaic one - only
        # remote-DMA/semaphore kernels need that; see megakernel.py)
    )
    return np.asarray(out)[0, :B]
