"""Smith-Waterman wavefront inside the megakernel.

Tile tasks on the same 2D DDF grid as the host model (reference:
test/smithwaterman/smith_waterman.cpp:77-180), with the tile computation
re-designed for the VPU instead of translated from the scalar DP loop:

- Rows are processed top to bottom; the row recurrence's left-to-right
  dependency H[i,j] = max(0, cand[i,j], H[i,j-1] - G) is solved *exactly* as
  a max-plus prefix scan: H = max(0, cummax(cand + j*G) - j*G), where the
  0-truncation can be applied once at the end because a truncation point
  only ever contributes negative values downstream. cummax is a shift+max
  ladder over the 128 lanes whose windows overlap (``SCAN_STAGES``): a row
  waits on the ladder's STAGES, each a trip through the cross-lane unit,
  not on its rolls, so it has few stages of many rolls.
- Inter-tile boundaries travel through dedicated HBM buffers (bottom row,
  right column, corner per tile) instead of overlapping tile reads, keeping
  every DMA aligned. The right column and the per-row left boundary live in
  SMEM so the row loop can read/write per-row scalars without dynamic lane
  indexing in VMEM.

The global best score accumulates in ivalues[0].
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.smithwaterman import GAP, MATCH, MISMATCH
from ..runtime.spans import span
from .descriptor import TaskGraphBuilder
from .megakernel import KernelContext, Megakernel

__all__ = [
    "device_sw", "make_sw_megakernel", "device_sw_wave",
    "make_sw_wave_megakernel", "build_sw_wave_graph", "sw_wave_buffers",
    "device_sw_batched", "make_sw_batched_megakernel", "build_sw_tile_graph",
]

T = 128
TILE_FN = 0
NEG = -(1 << 30)  # plain int: a jnp constant here would be captured by the trace


# The shifts of _cummax_lanes, stage by stage. max is idempotent, so the
# windows of a stage may overlap: the rolls of one stage all read the
# stage's input and are pushed to the cross-lane unit back to back, and
# only the stages wait for each other. A stage of k rolls widens the
# window k + 1 times: 8, 64, 128 lanes. On the v5e a stage in series costs
# a row 76 ns and a roll more 2-4 ns (PERF.md section 6, PR 54), which is
# why radix 8 and not 4 (four stages of 3, 3, 3, 1: 9.7 us a round more)
# or 16 (two of 15 and 7: no faster by the wall clock, the 26 pushes a
# row more catch up with the trip saved).
SCAN_STAGES = (
    (1, 2, 3, 4, 5, 6, 7),
    (8, 16, 24, 32, 40, 48, 56),
    (64,),
)


def _max_tree(terms):
    """Maximum of the planes in ``terms`` as a balanced tree (the VALU
    chain stays log-deep)."""
    while len(terms) > 1:
        terms = [
            jnp.maximum(*terms[k : k + 2]) if k + 1 < len(terms)
            else terms[k]
            for k in range(0, len(terms), 2)
        ]
    return terms[0]


def _cummax_lanes(x, shifted: bool = False):
    """Inclusive running max along the 128 lanes of an (R, T) plane (each
    sublane row scans independently), exactly: ``SCAN_STAGES`` dependent
    trips through the cross-lane unit, not the seven of a radix-2 ladder.

    ``shifted``: also the EXCLUSIVE running max (lane j holds the maximum
    of lanes < j, lane 0 ``NEG``): the scan rolled one lane up, made by the
    last stage's own trip from rolls one lane longer, so a caller that
    wants its result's left neighbour pays no trip of its own for it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def rolled(sh):  # of the stage's input: x as it stands when called
        return jnp.where(lane >= sh, pltpu.roll(x, sh, axis=1), NEG)

    for k, shifts in enumerate(SCAN_STAGES):
        if shifted and k == len(SCAN_STAGES) - 1:
            left = _max_tree([rolled(sh + 1) for sh in (0,) + shifts])
        x = _max_tree([x] + [rolled(sh) for sh in shifts])
    return (x, left) if shifted else x


def _sw_tile_kernel(ctx: KernelContext, with_h: bool = True) -> None:
    ti, tj = ctx.arg(0), ctx.arg(1)
    aseq, bseq = ctx.data["aseq"], ctx.data["bseq"]
    bot, right = ctx.data["bot"], ctx.data["right"]
    htiles = ctx.data["htiles"] if with_h else None
    vh = ctx.scratch["vh"] if with_h else None  # (T, T) VMEM: this tile's H
    vtop = ctx.scratch["vtop"]  # (1, T) VMEM: incoming top boundary
    vb = ctx.scratch["vb"]  # (1, T) VMEM: b chars for this column tile
    a_sm = ctx.scratch["a_sm"]  # (1, T) SMEM: a chars (per-row scalars)
    left_sm = ctx.scratch["left_sm"]  # (1, T) SMEM: incoming left boundary
    rout_sm = ctx.scratch["rout_sm"]  # (1, T) SMEM: outgoing right column
    corner_sm = ctx.scratch["corner_sm"]  # (1, T) SMEM; corner at lane T-1
    sems = ctx.scratch["sems"]

    def dma(src, dst, s):
        cp = pltpu.make_async_copy(src, dst, s)
        cp.start()
        cp.wait()

    dma(aseq.at[ti], a_sm, sems.at[0])
    dma(bseq.at[tj], vb, sems.at[1])

    @pl.when(ti > 0)
    def _():
        dma(bot.at[ti - 1, tj], vtop, sems.at[0])

    @pl.when(ti == 0)
    def _():
        vtop[:] = jnp.zeros((1, T), jnp.int32)

    @pl.when(tj > 0)
    def _():
        dma(right.at[ti, tj - 1], left_sm, sems.at[1])

    @pl.when(tj == 0)
    def _():
        # SMEM only takes scalar stores - zero it with a scalar loop.
        def z(i, _):
            left_sm[0, i] = 0
            return 0

        jax.lax.fori_loop(0, T, z, 0)

    # The diagonal corner H[(ti-1,tj-1)][T-1,T-1] is lane T-1 of that
    # tile's right column - no separate (1,1) buffer (DMA lane alignment).
    @pl.when((ti > 0) & (tj > 0))
    def _():
        dma(right.at[ti - 1, tj - 1], corner_sm, sems.at[2])

    @pl.when((ti == 0) | (tj == 0))
    def _():
        corner_sm[0, T - 1] = 0

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    bvec = vb[:]

    def row(i, carry):
        hprev = carry[0]
        ai = a_sm[0, i]
        # H[i-1, j0-1]: the left boundary one row up (corner for row 0).
        im1 = jnp.maximum(i - 1, 0)
        prev_left = jnp.where(i == 0, corner_sm[0, T - 1], left_sm[0, im1])
        sub = jnp.where(bvec == ai, jnp.int32(MATCH), jnp.int32(MISMATCH))
        diag = pltpu.roll(hprev, 1, axis=1)
        diag = jnp.where(lane == 0, prev_left, diag)
        cand = jnp.maximum(diag + sub, hprev - GAP)
        # This row's left boundary enters as an extra candidate at lane 0.
        cand = jnp.maximum(
            cand, jnp.where(lane == 0, left_sm[0, i] - GAP, NEG)
        )
        scan = _cummax_lanes(cand + lane * GAP) - lane * GAP
        hrow = jnp.maximum(scan, 0)
        if with_h:
            vh[pl.ds(i, 1), :] = hrow
        rout_sm[0, i] = hrow[0, T - 1]
        return hrow, jnp.maximum(carry[1], hrow)

    hlast, hmax = jax.lax.fori_loop(
        0, T, lambda i, c: row(i, c), (vtop[:], jnp.zeros((1, T), jnp.int32))
    )

    # Publish boundaries + tile, update the global best score.
    vtop[:] = hlast
    dma(vtop, bot.at[ti, tj], sems.at[0])
    dma(rout_sm, right.at[ti, tj], sems.at[1])
    if with_h:
        dma(vh, htiles.at[ti, tj], sems.at[3])
    tile_max = jnp.max(hmax)
    best = ctx.value(0)
    ctx.set_value(0, jnp.maximum(best, tile_max))


WAVE_R = 8  # tile slots per wave-chunk descriptor (VPU sublanes)
WAVE_FN = 0
WAVE_B = 2  # chunk descriptors per batch round (16 stacked tile planes)


def _zero_slot(ctx, buf, slot) -> None:
    """Uniform zero planes for a dead tile slot (scores can't leak: vb of
    -1 never matches a real character)."""
    zrow = jnp.zeros((1, T), jnp.int32)
    va, vb = ctx.scratch["va"], ctx.scratch["vb"]
    ctx.scratch["vtop"][buf, pl.ds(slot, 1)] = zrow
    ctx.scratch["vleft"][buf, pl.ds(slot, 1)] = zrow
    ctx.scratch["vcorn"][buf, pl.ds(slot, 1)] = zrow
    va[buf, pl.ds(slot, 1)] = zrow
    vb[buf, pl.ds(slot, 1)] = zrow - 1


def _chunk_dma(ctx, buf, b, chunk: int, w, lo, cnt, wait: bool) -> None:
    """Start (``wait=False``) or retire (``wait=True``) the operand copies
    of chunk descriptor ``b`` - tiles (lo+s, w-lo-s) for s < cnt - into
    operand half ``buf``. Starts and waits are split so a round can put
    EVERY copy of every slot in flight before the first wait (the old
    wave kernel's serial start/wait per tile paid ~40 DMA latencies per
    chunk - the single biggest term in BENCH_r05's 1.2 GCUPS), and so the
    prefetch path can issue the identical starts one round early. One
    DMA semaphore per (half, slot) counts all five streams; every start
    is matched by exactly one wait under the same predicate."""
    aseq, bseq = ctx.data["aseq"], ctx.data["bseq"]
    bot, right = ctx.data["bot"], ctx.data["right"]
    va, vb = ctx.scratch["va"], ctx.scratch["vb"]
    vtop, vleft = ctx.scratch["vtop"], ctx.scratch["vleft"]
    vcorn = ctx.scratch["vcorn"]
    lsem = ctx.scratch["lsem"]
    zrow = jnp.zeros((1, T), jnp.int32)
    for s in range(chunk):
        slot = b * chunk + s
        ti = lo + s
        tj = w - ti
        sem = lsem.at[buf, slot]

        def go(src, dst):
            cp = pltpu.make_async_copy(src, dst, sem)
            (cp.wait if wait else cp.start)()

        @pl.when(jnp.int32(s) < cnt)
        def _(slot=slot, ti=ti, tj=tj, go=go):
            go(aseq.at[ti], va.at[buf, pl.ds(slot, 1)])
            go(bseq.at[tj], vb.at[buf, pl.ds(slot, 1)])

            @pl.when(ti > 0)
            def _():
                go(bot.at[ti - 1, tj], vtop.at[buf, pl.ds(slot, 1)])

            @pl.when(tj > 0)
            def _():
                go(right.at[ti, tj - 1], vleft.at[buf, pl.ds(slot, 1)])

            @pl.when((ti > 0) & (tj > 0))
            def _():
                go(
                    right.at[ti - 1, tj - 1],
                    vcorn.at[buf, pl.ds(slot, 1)],
                )

            if not wait:
                @pl.when(ti == 0)
                def _():
                    vtop[buf, pl.ds(slot, 1)] = zrow

                @pl.when(tj == 0)
                def _():
                    vleft[buf, pl.ds(slot, 1)] = zrow

                @pl.when((ti == 0) | (tj == 0))
                def _():
                    vcorn[buf, pl.ds(slot, 1)] = zrow

        if not wait:
            @pl.when(jnp.int32(s) >= cnt)
            def _(slot=slot):
                _zero_slot(ctx, buf, slot)


def _sw_wave_batch_kernel(ctx, chunk: int, with_h: bool = True) -> None:
    """Batched-tier SW wavefront body: up to ``ctx.width`` same-kind wave
    descriptors per round, each carrying up to ``chunk`` anti-diagonal
    tiles, swept together as (width*chunk, T) VPU planes - the tile
    kernel's (1, T) row recurrence runs width*chunk tiles per VPU step.
    Dependencies stay REAL: descriptors are DAG tasks whose dep counters
    encode the wavefront order (the reference's wavefront DAG,
    test/smithwaterman/smith_waterman.cpp:77-180, regrouped for the
    hardware); the scheduler's per-F_FN lane is what groups the
    simultaneously-ready ones.

    Operand motion is double-buffered across rounds via the tier's
    prefetch protocol: ``ctx.prefetched`` descriptors already have their
    boundaries in half ``ctx.buf`` (issued during the PREVIOUS round's
    compute), the rest start now; the next prospective batch's copies are
    put in flight into the other half before this round's waits, so they
    ride under this round's 128-row sweep. A lane entry's inputs are
    final before it enters the lane (its predecessors' stores drained
    before their completion), which is what makes the early issue safe.

    descriptor args: [w, lo, count] - tiles (ti, w - ti), ti in
    [lo, lo+count). A per-tile graph is the chunk=1 special case.
    """
    width = ctx.width
    S = width * chunk
    buf = ctx.buf
    vtop, vleft = ctx.scratch["vtop"], ctx.scratch["vleft"]
    vcorn = ctx.scratch["vcorn"]
    va, vb = ctx.scratch["va"], ctx.scratch["vb"]
    vh = ctx.scratch["vwh"] if with_h else None
    htiles = ctx.data["htiles"] if with_h else None
    bot, right = ctx.data["bot"], ctx.data["right"]
    ssem = ctx.scratch["ssem"]

    # Phase 1: start operand copies for live descriptors the prefetch
    # didn't cover; zero the dead ones.
    for b in range(width):
        @pl.when(ctx.live(b) & (jnp.int32(b) >= ctx.prefetched))
        def _(b=b):
            _chunk_dma(
                ctx, buf, b, chunk,
                ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2), wait=False,
            )

        @pl.when(jnp.logical_not(ctx.live(b)))
        def _(b=b):
            for s in range(chunk):
                _zero_slot(ctx, buf, b * chunk + s)

    # Phase 2: put the NEXT batch's copies in flight into the other half -
    # they land while this round computes, so the next round starts its
    # sweep without a single boundary-DMA stall.
    obuf = 1 - buf
    for b in range(width):
        @pl.when(jnp.int32(b) < ctx.prefetch_count)
        def _(b=b):
            _chunk_dma(
                ctx, obuf, b, chunk,
                ctx.next_arg(b, 0), ctx.next_arg(b, 1), ctx.next_arg(b, 2),
                wait=False,
            )

    # Phase 3: retire this round's loads (prefetched and fresh alike wait
    # the same (src, dst, sem) triples their starts used).
    for b in range(width):
        @pl.when(ctx.live(b))
        def _(b=b):
            _chunk_dma(
                ctx, buf, b, chunk,
                ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2), wait=True,
            )

    # Phase 4: the (S, T) wavefront sweep.
    lane = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    aplane = va[buf]
    bplane = vb[buf]
    leftp = vleft[buf]
    corner = vcorn[buf][:, T - 1 :]  # (S, 1)

    def col(plane, i):
        """Column i of an (S, T) plane on every lane of an (S, T) plane: a
        lane gather, one cross-lane push a vreg (Mosaic has no
        dynamic_slice on values; a masked lane sum of int32 is four)."""
        return jnp.take_along_axis(
            plane, jnp.full((S, T), i, jnp.int32), axis=1
        )

    def ahead(i):
        """What row ``i`` takes from the operands alone: its substitution
        scores and its left boundary H[i, j0-1] on every lane. No row
        computes either, so row ``i - 1`` makes them and hands them on in
        the carry: their gathers pop under that row's scan and row ``i``
        starts with them in registers."""
        sub = jnp.where(
            bplane == col(aplane, i), jnp.int32(MATCH), jnp.int32(MISMATCH)
        )
        return sub, col(leftp, i)

    def row(i, carry):
        # diag: H[i-1, j-1] on lanes j >= 1, the row before's own making.
        hprev, rout, _mpl, sub, prev_left, this_left, diag = carry
        # Row i-1's right column (lane T-1) into column i-1 of rout - pure
        # plane ops, and a row late, so its lane broadcast too pops under
        # this row's scan (row 0 finds no lane -1 and leaves rout alone).
        rout = jnp.where(lane == i - 1, hprev[:, T - 1 :], rout)
        sub_next, left_next = ahead(jnp.minimum(i + 1, T - 1))
        diag = jnp.where(lane == 0, prev_left, diag)
        cand = jnp.maximum(diag + sub, hprev - GAP)
        cand = jnp.maximum(cand, jnp.where(lane == 0, this_left - GAP, NEG))
        # The scan's last stage makes the next row's diagonal with it: the
        # row waits on the scan's trips alone, none for a roll of hrow.
        scan, left = _cummax_lanes(cand + lane * GAP, shifted=True)
        hrow = jnp.maximum(scan - lane * GAP, 0)
        diag = jnp.maximum(left - (lane - 1) * GAP, 0)
        if with_h:
            vh[:, pl.ds(i, 1), :] = hrow[:, None, :]
        mplane = jnp.maximum(_mpl, hrow)
        # This row's left boundary is the next row's H[i-1, j0-1].
        return hrow, rout, mplane, sub_next, this_left, left_next, diag

    zero_st = jnp.zeros((S, T), jnp.int32)
    sub0, left0 = ahead(0)
    hlast, rout, mplane, *_ = jax.lax.fori_loop(
        0, T, row,
        (vtop[buf], zero_st, zero_st, sub0,
         jnp.broadcast_to(corner, (S, T)), left0,
         pltpu.roll(vtop[buf], 1, axis=1)),
    )
    rout = jnp.where(lane == T - 1, hlast[:, T - 1 :], rout)
    # Reuse this half as store staging (the prefetch lives in the other
    # half, and these stores drain before this body returns).
    vtop[buf] = hlast
    vleft[buf] = rout
    vcorn[buf] = mplane
    mall = vcorn[buf]

    # Phase 5: publish boundaries (+ tiles), fold the running best score;
    # all stores start together, then all are waited - successors may be
    # dispatched the moment this body returns, so nothing may still be in
    # flight toward the boundary buffers they read.
    def stores(wait: bool):
        for b in range(width):
            @pl.when(ctx.live(b))
            def _(b=b):
                w, lo, cnt = ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2)
                for s in range(chunk):
                    slot = b * chunk + s
                    ti = lo + s
                    tj = w - ti

                    @pl.when(jnp.int32(s) < cnt)
                    def _(slot=slot, ti=ti, tj=tj):
                        def go(src, dst):
                            cp = pltpu.make_async_copy(
                                src, dst, ssem.at[slot]
                            )
                            (cp.wait if wait else cp.start)()

                        go(vtop.at[buf, pl.ds(slot, 1)], bot.at[ti, tj])
                        go(vleft.at[buf, pl.ds(slot, 1)], right.at[ti, tj])
                        if with_h:
                            go(vh.at[slot], htiles.at[ti, tj])

                if not wait:
                    # Each descriptor accounts for `cnt` tiles (itself +
                    # cnt-1 extra) so 'executed' counts tiles across
                    # tiers, as the vector tier does.
                    ctx.add_executed(cnt - 1)
                    for s in range(chunk):
                        @pl.when(jnp.int32(s) < cnt)
                        def _(s=s, b=b):
                            m = jnp.max(mall[b * chunk + s])
                            ctx.set_value(
                                0, jnp.maximum(ctx.value(0), m)
                            )

    stores(wait=False)
    stores(wait=True)


def _sw_wave_drain(ctx, chunk: int) -> None:
    """Retire an in-flight prefetch whose targets will be spilled instead
    of batched (scheduler exit with lane entries unrun): wait the same
    copies Phase 2 started, so no DMA outlives the kernel's round loop."""
    for b in range(ctx.width):
        @pl.when(jnp.int32(b) < ctx.prefetched)
        def _(b=b):
            _chunk_dma(
                ctx, ctx.buf, b, chunk,
                ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2), wait=True,
            )


def _sw_batch_megakernel(
    nt_i: int, nt_j: int, interpret: Optional[bool], with_h: bool,
    chunk: int, width: int, capacity: int, succ_capacity: int,
    checkpoint: Optional[bool] = None,
) -> Megakernel:
    import functools as _ft

    from .megakernel import BatchSpec, _batch_stub

    i32 = jnp.int32
    S = width * chunk
    data_specs = {
        "aseq": jax.ShapeDtypeStruct((nt_i, 1, T), i32),
        "bseq": jax.ShapeDtypeStruct((nt_j, 1, T), i32),
        "bot": jax.ShapeDtypeStruct((nt_i, nt_j, 1, T), i32),
        "right": jax.ShapeDtypeStruct((nt_i, nt_j, 1, T), i32),
    }
    scratch = {
        # Operand planes are double-buffered (leading 2): one half computes
        # while the tier's prefetch fills the other.
        "va": pltpu.VMEM((2, S, T), i32),
        "vb": pltpu.VMEM((2, S, T), i32),
        "vtop": pltpu.VMEM((2, S, T), i32),
        "vleft": pltpu.VMEM((2, S, T), i32),
        "vcorn": pltpu.VMEM((2, S, T), i32),
        "lsem": pltpu.SemaphoreType.DMA((2, S)),
        "ssem": pltpu.SemaphoreType.DMA((S,)),
    }
    if with_h:
        data_specs["htiles"] = jax.ShapeDtypeStruct((nt_i, nt_j, T, T), i32)
        scratch["vwh"] = pltpu.VMEM((S, T, T), i32)
    return Megakernel(
        kernels=[("sw_wave", _batch_stub)],
        route={
            "sw_wave": BatchSpec(
                _ft.partial(
                    _sw_wave_batch_kernel, chunk=chunk, with_h=with_h
                ),
                width=width,
                prefetch=True,
                drain=_ft.partial(_sw_wave_drain, chunk=chunk),
            )
        },
        data_specs=data_specs,
        scratch_specs=scratch,
        capacity=capacity,
        num_values=8,
        succ_capacity=succ_capacity,
        interpret=interpret,
        checkpoint=checkpoint,
    )


def make_sw_wave_megakernel(
    nt_i: int, nt_j: int, interpret: Optional[bool] = None,
    with_h: bool = True, chunk: int = WAVE_R, width: int = WAVE_B,
    checkpoint: Optional[bool] = None,
) -> Megakernel:
    nwaves = nt_i + nt_j - 1
    chunks = [
        -(-min(w + 1, nt_i, nt_j, nt_i + nt_j - 1 - w) // chunk)
        for w in range(nwaves)
    ]
    ntasks = sum(chunks)
    # Exact CSR demand: every wave-w chunk lists ALL wave-(w+1) chunks as
    # successors (2 ride inline, the rest spill to CSR) - quadratic in
    # chunks-per-diagonal, so a heuristic multiple of ntasks under-counts
    # on large grids.
    csr_words = sum(
        chunks[w] * max(0, chunks[w + 1] - 2) for w in range(nwaves - 1)
    )
    return _sw_batch_megakernel(
        nt_i, nt_j, interpret, with_h, chunk, width,
        capacity=max(64, ntasks), succ_capacity=max(64, csr_words),
        checkpoint=checkpoint,
    )


def build_sw_wave_graph(
    nt_i: int, nt_j: int, chunk: int = WAVE_R
) -> TaskGraphBuilder:
    """Wave-chunk task DAG: up to ``chunk`` tiles of one anti-diagonal per
    task, consecutive anti-diagonals chained by dependencies (shared by
    device_sw_wave and the bench so both stage the SAME graph)."""
    builder = TaskGraphBuilder()
    prev_wave: list = []
    for w in range(nt_i + nt_j - 1):
        lo = max(0, w - (nt_j - 1))
        hi = min(nt_i - 1, w)
        this_wave = []
        for base in range(lo, hi + 1, chunk):
            cnt = min(chunk, hi + 1 - base)
            this_wave.append(
                builder.add(WAVE_FN, args=[w, base, cnt], deps=prev_wave)
            )
        prev_wave = this_wave
    return builder


def build_sw_tile_graph(nt_i: int, nt_j: int) -> TaskGraphBuilder:
    """Per-TILE task DAG with the precise 3-neighbor dependencies (the
    reference's granularity): descriptors carry [w, lo, 1] so the batched
    wave body runs them as its chunk=1 special case. Which tiles execute
    together is decided by the SCHEDULER's same-kind lane, round by round
    - the dynamic-grouping shape the batched dispatch tier exists for."""
    builder = TaskGraphBuilder()
    ids: dict = {}
    for ti in range(nt_i):
        for tj in range(nt_j):
            deps = [
                ids[key]
                for key in ((ti - 1, tj), (ti, tj - 1), (ti - 1, tj - 1))
                if key in ids
            ]
            ids[(ti, tj)] = builder.add(
                WAVE_FN, args=[ti + tj, ti, 1], deps=deps
            )
    return builder


def make_sw_batched_megakernel(
    nt_i: int, nt_j: int, interpret: Optional[bool] = None,
    with_h: bool = True, width: int = WAVE_R,
) -> Megakernel:
    """Megakernel for the per-tile graph: ``width`` tile descriptors per
    batch round (the scheduler groups whatever subset of the wavefront is
    ready). SMEM note: the per-tile table is nt_i*nt_j rows - grids past
    ~32x32 tiles want the chunked graph (make_sw_wave_megakernel), whose
    descriptor count divides by the chunk size."""
    ntasks = nt_i * nt_j
    return _sw_batch_megakernel(
        nt_i, nt_j, interpret, with_h, chunk=1, width=width,
        capacity=max(64, ntasks), succ_capacity=max(64, 3 * ntasks),
    )


def sw_wave_buffers(a: np.ndarray, b: np.ndarray) -> dict:
    """Host data buffers for the wave engine (without the optional H
    matrix): sequences in row-tile layout + the boundary channels."""
    n, m = len(a), len(b)
    nt_i, nt_j = n // T, m // T
    i32 = np.int32
    return {
        "aseq": np.asarray(a, i32).reshape(nt_i, 1, T),
        "bseq": np.asarray(b, i32).reshape(nt_j, 1, T),
        "bot": np.zeros((nt_i, nt_j, 1, T), i32),
        "right": np.zeros((nt_i, nt_j, 1, T), i32),
    }


def _sw_result(n: int, m: int, ivalues, out: dict, info: dict, dt: float):
    """The tail the three engines share: ``(score, H or None, info)`` from
    what ``mk.run`` brought back. Besides the run's own keys ``info`` holds
    ``last_row`` (H[n-1, :], ``m`` values: the bottom rows the last tile
    row published to ``bot``) and ``last_col`` (H[:, m-1], ``n`` values:
    the right columns the last tile column published to ``right``): every
    tile feeds them through the recurrence, so with ``with_h=False`` a
    caller still gets more than one integer to check."""
    with span("sw.readback"):
        h = (
            np.asarray(out["htiles"]).swapaxes(1, 2).reshape(n, m)
            if "htiles" in out
            else None
        )
        info = dict(info)
        info["seconds"] = dt
        info["cells_per_sec"] = n * m / dt
        info["last_row"] = np.asarray(out["bot"])[n // T - 1].reshape(m)
        info["last_col"] = np.asarray(out["right"])[:, m // T - 1].reshape(n)
        return int(ivalues[0]), h, info


def device_sw_wave(
    a: np.ndarray,
    b: np.ndarray,
    interpret: Optional[bool] = None,
    mk: Optional[Megakernel] = None,
    with_h: bool = True,
) -> Tuple[int, Optional[np.ndarray], dict]:
    """Tiled SW where each task is a WAVE CHUNK (up to WAVE_R tiles of one
    anti-diagonal batched over VPU sublanes); dependencies chain
    anti-diagonals. Same results as device_sw, ~WAVE_R x the vector-unit
    utilization once diagonals are wide.

    Four profiler spans split the call for a traced run, ``sw.build`` (the
    graph), ``sw.stage`` (the host buffers), ``sw.run`` (``Megakernel.run``,
    whose ``mk.*`` spans nest inside) and ``sw.readback`` (``_sw_result``):
    ``runtime/spans.py:STAGES`` has the table."""
    n, m = len(a), len(b)
    if n % T or m % T:
        raise ValueError(f"sequence lengths must be multiples of {T}")
    nt_i, nt_j = n // T, m // T
    if mk is None:
        mk = make_sw_wave_megakernel(nt_i, nt_j, interpret, with_h=with_h)
    with span("sw.build"):
        builder = build_sw_wave_graph(nt_i, nt_j)
    with span("sw.stage"):
        data = sw_wave_buffers(a, b)
        if "htiles" in mk.data_specs:
            data["htiles"] = np.zeros((nt_i, nt_j, T, T), np.int32)
    t0 = time.perf_counter()
    with span("sw.run"):
        ivalues, out, info = mk.run(builder, data=data)
    dt = time.perf_counter() - t0
    return _sw_result(n, m, ivalues, out, info, dt)


def device_sw_batched(
    a: np.ndarray,
    b: np.ndarray,
    interpret: Optional[bool] = None,
    mk: Optional[Megakernel] = None,
    with_h: bool = True,
    width: int = WAVE_R,
) -> Tuple[int, Optional[np.ndarray], dict]:
    """Tiled SW where each task is ONE tile on the precise 3-neighbor DAG
    and the megakernel's batched same-kind dispatch tier groups whatever
    subset of the wavefront is ready - up to ``width`` tiles per round
    through one (width, T)-plane body. Same results as device_sw, with the
    grouping decided at run time by the scheduler instead of at graph
    build time; ``info['tiers']`` carries the lane/occupancy counters."""
    n, m = len(a), len(b)
    if n % T or m % T:
        raise ValueError(f"sequence lengths must be multiples of {T}")
    nt_i, nt_j = n // T, m // T
    if mk is None:
        mk = make_sw_batched_megakernel(
            nt_i, nt_j, interpret, with_h=with_h, width=width
        )
    builder = build_sw_tile_graph(nt_i, nt_j)
    data = sw_wave_buffers(a, b)
    if "htiles" in mk.data_specs:
        data["htiles"] = np.zeros((nt_i, nt_j, T, T), np.int32)
    t0 = time.perf_counter()
    ivalues, out, info = mk.run(builder, data=data)
    dt = time.perf_counter() - t0
    return _sw_result(n, m, ivalues, out, info, dt)


def make_sw_megakernel(
    nt_i: int, nt_j: int, interpret: Optional[bool] = None,
    with_h: bool = True,
) -> Megakernel:
    import functools as _ft

    i32 = jnp.int32
    data_specs = {
        "aseq": jax.ShapeDtypeStruct((nt_i, 1, T), i32),
        "bseq": jax.ShapeDtypeStruct((nt_j, 1, T), i32),
        "bot": jax.ShapeDtypeStruct((nt_i, nt_j, 1, T), i32),
        "right": jax.ShapeDtypeStruct((nt_i, nt_j, 1, T), i32),
    }
    scratch = {
        "vtop": pltpu.VMEM((1, T), i32),
        "vb": pltpu.VMEM((1, T), i32),
        "a_sm": pltpu.SMEM((1, T), i32),
        "left_sm": pltpu.SMEM((1, T), i32),
        "rout_sm": pltpu.SMEM((1, T), i32),
        "corner_sm": pltpu.SMEM((1, T), i32),
        "sems": pltpu.SemaphoreType.DMA((4,)),
    }
    if with_h:
        data_specs["htiles"] = jax.ShapeDtypeStruct((nt_i, nt_j, T, T), i32)
        scratch["vh"] = pltpu.VMEM((T, T), i32)
    return Megakernel(
        kernels=[("sw_tile", _ft.partial(_sw_tile_kernel, with_h=with_h))],
        data_specs=data_specs,
        scratch_specs=scratch,
        capacity=max(64, nt_i * nt_j),
        num_values=8,
        succ_capacity=max(64, 3 * nt_i * nt_j),
        interpret=interpret,
    )


def device_sw(
    a: np.ndarray,
    b: np.ndarray,
    interpret: Optional[bool] = None,
    mk: Optional[Megakernel] = None,
    with_h: bool = True,
) -> Tuple[int, Optional[np.ndarray], dict]:
    """Run tiled SW on-device; returns (best_score, H[1:, 1:], info).

    Sequence lengths must be multiples of the 128 tile edge.
    """
    n, m = len(a), len(b)
    if n % T or m % T:
        raise ValueError(f"sequence lengths must be multiples of {T}")
    nt_i, nt_j = n // T, m // T
    if mk is None:
        mk = make_sw_megakernel(nt_i, nt_j, interpret, with_h=with_h)
    builder = TaskGraphBuilder()
    ids = {}
    for ti in range(nt_i):
        for tj in range(nt_j):
            deps = [
                ids[key]
                for key in ((ti - 1, tj), (ti, tj - 1), (ti - 1, tj - 1))
                if key in ids
            ]
            ids[(ti, tj)] = builder.add(TILE_FN, args=[ti, tj], deps=deps)
    i32 = np.int32
    data = {
        "aseq": np.asarray(a, i32).reshape(nt_i, 1, T),
        "bseq": np.asarray(b, i32).reshape(nt_j, 1, T),
        "bot": np.zeros((nt_i, nt_j, 1, T), i32),
        "right": np.zeros((nt_i, nt_j, 1, T), i32),
    }
    if "htiles" in mk.data_specs:
        data["htiles"] = np.zeros((nt_i, nt_j, T, T), i32)
    t0 = time.perf_counter()
    ivalues, out, info = mk.run(builder, data=data)
    dt = time.perf_counter() - t0
    return _sw_result(n, m, ivalues, out, info, dt)
