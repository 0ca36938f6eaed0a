"""Vectorized UTS: data-parallel tree search on the VPU.

The TPU-first re-design of UTS (reference workload: test/uts): instead of
one task per node (scalar megakernel) or one pthread per worker (C++ core),
thousands of SIMD lanes each run an independent DFS, with every per-node
operation vectorized across (rows, 128) VPU planes:

- SHA-1 (the UTS splittable RNG) is computed for all lanes' current children
  simultaneously - ~1.3k u32 plane-ops per step hash one child per lane.
- Each lane's DFS stack is a set of (state, next-child, count, depth) planes
  indexed by a per-lane stack pointer; stack reads/writes are select loops
  over the (small, static) stack height - no gathers, no dynamic indexing.
  Tail-call scheduling (a frame expanding its last non-leaf child is
  *replaced* by that child; a last leaf child pops immediately) keeps every
  stack frame expandable, so every active step performs an expansion - the
  classic DFS pop-the-exhausted-frame steps, ~20% of all steps on canonical
  trees, are eliminated.
- **Dynamic load balancing via a shared root queue**: the seeding leaves a
  flat array of subtree roots (all at one BFS depth d0); every step, lanes
  whose stack emptied claim the next unclaimed roots with a prefix-sum over the
  done mask + a gather from the root arrays. Imbalance is therefore bounded
  by the size of a single subtree instead of the sum of a lane's static
  deal - this is the work-stealing idea of the reference scheduler
  (src/hclib-deque.c) recast as a data-parallel claim, and it is what makes
  lane efficiency scale.
- Child counts are *exact*: the host binary-searches (in f64, matching the
  scalar implementations bit-for-bit) the integer thresholds t_k = min{r :
  floor(log(1-r/2^31)/log(1-p)) >= k}, and the device counts children as
  #(r >= t_k) with pure int32 compares. Leaf children are counted without
  being pushed (80% of canonical-tree nodes are leaves).
- The BFS seeding is itself vectorized, a whole frontier level at a time:
  the small levels at the root in place on numpy arrays
  (ops.sha1.sha1_children_np), every level from SEED_CHIP_FROM nodes on as
  one jitted expansion on the device (uts_seed_expand), which also sorts,
  pads and lays out the roots (uts_seed_roots), so they never visit the
  host: T1L's 239,628 roots are ready 25 ms after the call (PERF.md).

Binomial trees (``-t 0``; canonical T3/T3L, 17,844 levels deep on levels
narrower than the lanes are many) take another road through the same entry
points, because nothing above holds for them: no level is wide enough to
seed from, so the seeding is the root's children and no more; no static
stack is tall enough, so a lane's stack is a ring of a few frames that
gives its BOTTOM frame away (the owner keeps the top, the oldest work
goes: src/hclib-deque.c's discipline); and no list of roots made in
advance can balance subtrees whose sizes no root betrays, so every lane
that holds two frames or more gives, and starved lanes take what was
given, through an exchange buffer beside the lanes whose overflow is a
pool in HBM, inside the one launch (make_balance). A frame there is a
node and the RANGE of its children still to hash, and it is split when it
changes hands: a lane keeps the next child of its top frame and gives the
others, and a starved lane is dealt one child, so the children of a node
on the tree's deepest path are hashed by as many lanes in one step, and
that path costs two steps a level where a frame that moved whole cost
three and a half (PERF.md, PR 57). A geometric tree's engine holds none of
this: the two have their own step and driver.

Supports every GEO shape: FIXED (canonical T1/T1L/T1XL) on the
depth-independent threshold fast path, LINEAR/CYCLIC (canonical T5/T2) and
EXPDEC via exact per-depth threshold tables (one row of integer thresholds
per depth from the f64 shape function, -1 padded; the device gathers its
row by depth and counts with pure int32 compares).

This is pure JAX (jnp + while_loop) - XLA maps it onto the VPU without a
hand-written kernel; it also runs on the CPU backend for tests.
"""

from __future__ import annotations

import functools
import hashlib
import math
import struct
import time
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.uts import (
    BIN, CYCLIC, FIXED, LINEAR, UTSParams, _branching, bin_threshold,
    root_state,
)
from ..ops.sha1 import sha1_child as _sha1_child, sha1_children_np
from ..runtime.spans import span
from .megakernel import ran_on

__all__ = [
    "uts_vec", "child_thresholds", "child_threshold_table", "depth_cap",
    "inrow_threshold_table", "padded_threshold_table", "MAX_CHILDREN",
    "PAD_QUANTUM",
    "LANES", "NLANES", "make_count_children", "make_dfs_step",
    "make_refill", "make_bin_step", "make_balance", "make_bin_traversal",
]

LANES = (8, 128)
NLANES = LANES[0] * LANES[1]
MAX_CHILDREN = 100
# Root arrays are padded to a multiple of this (shared by both engines):
# trees with different root counts land on one padded shape and so share
# one compiled engine (the real count travels as a runtime scalar). A
# multiple of uts_pallas.ALIGN (1024) so the pallas row-block DMA windows
# stay aligned.
PAD_QUANTUM = 4096


@functools.lru_cache(maxsize=None)
def _thresholds_for_b(b_i: float) -> Tuple[int, ...]:
    """Integer thresholds for the geometric child count at branching b_i:
    count(r) = #{k : r >= t_k}, ascending. Exact w.r.t. the f64 scalar
    formula; 3 k logarithms a row, so a row is computed once."""
    if b_i <= 0.0:
        return ()
    p = 1.0 / (1.0 + b_i)
    logq = math.log(1.0 - p)

    def count_of(r: int) -> int:
        u = r / 2147483648.0
        if u >= 1.0:
            return MAX_CHILDREN
        return min(MAX_CHILDREN, int(math.floor(math.log(1.0 - u) / logq)))

    ts: List[int] = []
    rmax = (1 << 31) - 1
    for k in range(1, MAX_CHILDREN + 1):
        if count_of(rmax) < k:
            break  # k unreachable for any r
        lo, hi = 0, rmax  # invariant: count(hi) >= k
        while lo < hi:
            mid = (lo + hi) // 2
            if count_of(mid) >= k:
                hi = mid
            else:
                lo = mid + 1
        ts.append(lo)
    return tuple(ts)


def child_thresholds(b0: float) -> np.ndarray:
    """Depth-independent thresholds (the GEO/FIXED fast path)."""
    return np.asarray(_thresholds_for_b(b0), dtype=np.int32)


def depth_cap(params: UTSParams) -> Optional[int]:
    """Smallest depth bound that covers every node the shape can produce
    (node depths strictly below the returned value), or None when the
    shape is unbounded (EXPDEC: b_i decays but never reaches 0, so a cap
    must be chosen by the caller and validated against the observed max
    depth)."""
    if params.shape == FIXED:
        return params.gen_mx + 1
    if params.shape == LINEAR:
        return params.gen_mx + 1  # b_i <= 0 at depth >= gen_mx
    if params.shape == CYCLIC:
        return 5 * params.gen_mx + 2  # b_i = 0 beyond 5*gen_mx
    return None


def child_threshold_table(params: UTSParams, max_depth: int) -> np.ndarray:
    """Per-depth threshold table for the depth-varying shapes
    (reference: the b_i shape functions, test/uts/uts.c:171-221): row d
    holds the thresholds for a node AT depth d, -1 padding marks child
    ordinals unreachable at that depth. Rows cover d in [0, max_depth]."""
    rows = [
        _thresholds_for_b(_branching(params, d))
        for d in range(max_depth + 1)
    ]
    K = max((len(r) for r in rows), default=0) or 1
    table = np.full((max_depth + 1, K), -1, dtype=np.int32)
    for d, r in enumerate(rows):
        table[d, : len(r)] = r
    return table


def _level_select(stack, sp):
    """Read a per-lane level from a tuple-of-planes stack via selects.

    The stack is a Python tuple (one plane per level), NOT a stacked array:
    functional updates then leave untouched levels as the same arrays, so
    XLA's while-loop carry aliasing avoids whole-stack copies (a stacked
    (S, ...) array with .at[].set() costs a full copy per write and made the
    DFS step ~300x slower than its op count).
    """
    out = jnp.zeros_like(stack[0])
    for L, plane in enumerate(stack):
        out = jnp.where(sp == L, plane, out)
    return out


def _level_store(stack, sp, value, mask):
    """Write value at per-lane level sp where mask; returns a new tuple."""
    return tuple(
        jnp.where(mask & (sp == L), value, plane)
        for L, plane in enumerate(stack)
    )


def inrow_threshold_table(thresholds: tuple, cols: int) -> np.ndarray:
    """Transpose a per-depth threshold table to the in-row-gather layout:
    one ``cols``-wide row per child ordinal, -1 padded, so a per-lane
    (depth -> threshold) lookup is a same-shape ``take_along_axis``. The
    fused Pallas engine passes this as a kernel input (Mosaic kernels
    cannot capture array constants)."""
    tab_np = np.asarray(thresholds, dtype=np.int32)  # (D+1, K)
    D = tab_np.shape[0] - 1
    if D + 1 >= cols:
        # STRICTLY below cols: count_children_inrow clips depth to
        # cols - 1 and relies on that column being -1 padding, so an
        # over-deep lane counts 0 children (a full table would put live
        # thresholds there and expand a phantom subtree to max_steps).
        raise NotImplementedError(
            f"in-row table gather needs depth cap + 1 < {cols} "
            f"lane columns, got {D + 1}"
        )
    padded = np.full((tab_np.shape[1], cols), -1, np.int32)
    padded[:, : D + 1] = tab_np.T
    return padded


def make_count_children(
    thresholds, gen_mx, lanes: tuple, inrow_table=None, table=None
):
    """Exact geometric child count. ``thresholds`` is either a flat tuple
    (depth-independent FIXED shape, guarded by the runtime ``gen_mx``
    scalar) or None: the per-depth threshold table then arrives as a
    RUNTIME array - ``table`` ((D+1, K), -1 padded; the count is a row
    gather by each lane's depth) or ``inrow_table`` ((K, cols) laid out by
    inrow_threshold_table): the Mosaic-compatible formulation for the
    fused Pallas engine, where the per-lane (depth -> threshold) lookup
    becomes a same-shape ``take_along_axis`` per child ordinal - the only
    gather form Mosaic supports. Same integer thresholds, bit-identical
    counts either way - and because the table VALUES are inputs, trees
    whose padded table SHAPES match share one compiled engine (the
    per-shape XLA/Mosaic compile is ~1 min; the suite pads all
    depth-varying trees to a common shape, see padded_threshold_table)."""
    if thresholds is None:
        if inrow_table is not None:
            K = inrow_table.shape[0]
            cols = lanes[1]

            def count_children_inrow(r, depth):
                # Depths beyond the real table rows hit the -1 column
                # padding (inrow tables are padded to the full row width),
                # so the count is exactly 0 there - no explicit guard.
                dclip = jnp.clip(depth, 0, cols - 1)
                cnt = jnp.zeros(lanes, jnp.int32)
                for k in range(K):
                    row = jnp.broadcast_to(inrow_table[k], lanes)
                    t = jnp.take_along_axis(row, dclip, axis=1)
                    cnt = cnt + ((t >= 0) & (r >= t)).astype(jnp.int32)
                return cnt

            return count_children_inrow
        D = table.shape[0] - 1

        def count_children_rows(r, depth):
            rows = jnp.take(table, jnp.clip(depth, 0, D), axis=0)
            cnt = jnp.sum(
                (rows >= 0) & (r[..., None] >= rows), axis=-1
            ).astype(jnp.int32)
            # Beyond the table the count is 0, NOT the last row's (which
            # may be supercritical when a depth_bound truncates a live
            # region): the traversal then terminates and the caller's
            # maxd >= cap validation fails loudly instead of the kernel
            # grinding a phantom infinite subtree to max_steps.
            return jnp.where(depth <= D, cnt, 0)

        return count_children_rows

    def count_children(r, depth):
        cnt = jnp.zeros(lanes, jnp.int32)
        for k in range(len(thresholds)):
            cnt = cnt + (r >= jnp.int32(thresholds[k])).astype(jnp.int32)
        return jnp.where(depth < gen_mx, cnt, 0)

    return count_children


def make_dfs_step(
    S: int, lanes: tuple, thresholds, gen_mx,
    inrow_table=None, table=None,
):
    """One vectorized DFS expansion step over all lanes (the hot loop body,
    shared by the XLA engine here and the fused Pallas engine in
    uts_pallas.py). Signature:
    (sp, nodes, leaves, maxd, st, ch, cn, dp) -> same tuple."""
    count_children = make_count_children(
        thresholds, gen_mx, lanes, inrow_table, table
    )

    def step(sp, nodes, leaves, maxd, st, ch, cn, dp):
        active = sp >= 0
        child = _level_select(ch, sp)
        count = _level_select(cn, sp)
        depth = _level_select(dp, sp)
        state = [
            _level_select(tuple(st[L][i] for L in range(S)), sp)
            for i in range(5)
        ]
        expand = active & (child < count)
        cstate = _sha1_child(state, child, jnp)
        r = (cstate[4] & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        cdepth = depth + 1
        ccount = count_children(r, cdepth)
        is_leaf = ccount == 0
        nodes = nodes + expand.astype(jnp.int32)
        leaves = leaves + (expand & is_leaf).astype(jnp.int32)
        maxd = jnp.maximum(maxd, jnp.where(expand, cdepth, 0))
        # Tail-call scheduling keeps every stack frame expandable (child <
        # count), so every active step performs an expansion - no steps are
        # wasted popping exhausted frames:
        #  - last+leaf child: frame is done, pop now.
        #  - last+non-leaf child: child *replaces* the parent frame (tail
        #    call) - exhausted parents are never buried on the stack.
        #  - otherwise: bump the cursor; push non-leaf children.
        last = expand & (child + 1 >= count)
        push = expand & ~is_leaf & ~last
        tail = expand & ~is_leaf & last
        pop = (expand & is_leaf & last) | (active & ~expand)
        ch = _level_store(ch, sp, child + 1, expand & ~last)
        # One store pass for both push (at sp+1) and tail-replace (at sp).
        spp = sp + 1
        lvl = jnp.where(push, spp, sp)
        newf = push | tail
        st = tuple(
            tuple(
                jnp.where(newf & (lvl == L), cstate[i], st[L][i])
                for i in range(5)
            )
            for L in range(S)
        )
        ch = _level_store(ch, lvl, jnp.zeros(lanes, jnp.int32), newf)
        cn = _level_store(cn, lvl, ccount, newf)
        dp = _level_store(dp, lvl, cdepth, newf)
        sp = jnp.where(push, spp, jnp.where(pop, sp - 1, sp))
        return sp, nodes, leaves, maxd, st, ch, cn, dp

    return step


# A frame's children word: the children still to hash are [lo, hi), packed
# as lo | hi << 16. A whole frame is [0, m); a frame that changed hands is
# a piece of one (make_balance). The widths of a row's frames are summed as
# one product on the MXU, whose one pass holds 8 bits of an operand, so a
# width, and with it a binomial tree's ``m``, is at most BIN_MAX_M.
BIN_MAX_M = 255


def _children(word):
    """A children word's (lo, hi)."""
    return word & 0xFFFF, word >> 16


def _children_word(lo, hi):
    return lo | (hi << 16)


def make_bin_step(S: int, lanes: tuple, below, m):
    """``make_dfs_step`` for a binomial tree, shared by both engines like
    it; ``below`` and ``m`` are runtime scalars. A binomial node's count is
    one compare (``m`` children iff r < below, whatever its depth), so a
    frame carries no count and no table is looked up: it is (state, the
    children ``[lo, hi)`` of that node still to hash, depth), and ``lo`` is
    the next one. A node pushed here is whole, ``[0, m)``; one that came
    from the balance round is one child, ``[c, c + 1)``, and what the round
    left of a frame it split is ``[lo, lo + 1)``. The stack is a RING of S
    planes (S a power of two) indexed by a per-lane ``top``, so the balance
    round can take the BOTTOM frame away without moving the others; and a
    push that finds the ring full stalls (the lane repeats the hash) until
    the next balance round has taken its bottom frame. Signature:
    (sp, top, nodes, leaves, maxd, spmax, st, ch, dp) -> same tuple."""
    assert S >= 2 and S & (S - 1) == 0, S

    def step(sp, top, nodes, leaves, maxd, spmax, st, ch, dp):
        active = sp >= 0
        word = _level_select(ch, top)
        child, hi = _children(word)
        depth = _level_select(dp, top)
        state = [
            _level_select(tuple(st[L][i] for L in range(S)), top)
            for i in range(5)
        ]
        cstate = _sha1_child(state, child, jnp)
        r = (cstate[4] & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        nonleaf = r < below
        last = child + 1 >= hi
        # Tail-call scheduling as in the geometric step: every frame on
        # the ring has a child left, so every active lane hashes one. A
        # push onto a full ring waits: nothing of the lane changes, and
        # the balance round takes its bottom frame (sp >= its threshold).
        expand = active & ~(nonleaf & ~last & (sp >= S - 1))
        cdepth = depth + 1
        nodes = nodes + expand.astype(jnp.int32)
        leaves = leaves + (expand & ~nonleaf).astype(jnp.int32)
        maxd = jnp.maximum(maxd, jnp.where(expand, cdepth, 0))
        push = expand & nonleaf & ~last
        tail = expand & nonleaf & last
        pop = expand & ~nonleaf & last
        ch = _level_store(ch, top, word + 1, expand & ~last)
        nxt = (top + 1) & (S - 1)
        lvl = jnp.where(push, nxt, top)
        newf = push | tail
        st = tuple(
            tuple(
                jnp.where(newf & (lvl == L), cstate[i], st[L][i])
                for i in range(5)
            )
            for L in range(S)
        )
        ch = _level_store(ch, lvl, _children_word(0, m), newf)
        dp = _level_store(dp, lvl, cdepth, newf)
        sp = jnp.where(push, sp + 1, jnp.where(pop, sp - 1, sp))
        top = jnp.where(push, nxt, jnp.where(pop, (top - 1) & (S - 1), top))
        return (sp, top, nodes, leaves, maxd, jnp.maximum(spmax, sp),
                st, ch, dp)

    return step


FRAME_WORDS = 7  # a pooled frame: five state words, children word, depth


def row_cumsum(x, lanes: tuple):
    """Inclusive prefix sum along each row of 128 lanes of a 0/1 mask, or
    of whole numbers up to BIN_MAX_M, as one product with a triangular
    matrix (exact: an operand fits the MXU's 8 bits, a sum f32's 24), and
    the rows' totals broadcast over their rows."""
    cols = lanes[1]
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 1)
    ).astype(jnp.float32)
    ranks = jnp.dot(
        x.astype(jnp.float32), upper, preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    return ranks, jnp.broadcast_to(ranks[:, cols - 1:cols], lanes)


def row_total(x, lanes: tuple):
    """The row sums of a 0/1 mask, or of a lane's 0 to 2 gifts, broadcast
    over their rows (a lane reduce; exact in f32: sums <= 256)."""
    total = jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)
    return jnp.broadcast_to(total.astype(jnp.int32), lanes)


def _as_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _as_u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def make_balance(S: int, lanes: tuple, pool_slabs: int, spill, fetch,
                 roll_rows):
    """The balance round of a binomial traversal: the work-stealing half of
    the reference (src/hclib-deque.c:75-106: the owner works at the top of
    its deque, what leaves it leaves from the BOTTOM), recast for lanes
    that cannot address each other, and with one thing the reference
    cannot do: **a frame that changes hands is split**. A node's children
    are ``SHA1(node || i)``: they depend on the node alone, so as many
    lanes as it has children left can hash them in the same step. A frame
    is (state, the children ``[lo, hi)`` still to hash, depth)
    (``make_bin_step``); what is given is such a range, and what a starved
    lane takes is ONE child of one.

    The pool's near end is an exchange buffer ``E`` of FRAME_WORDS planes
    beside the lanes: row i of it holds ``e[i]`` frames at its front. A
    round, in order:

    0. An empty ``E`` is filled with the pool's newest slab from HBM
       (``fetch``).
    1. *Who gives.* Every lane that holds two frames or more gives its
       BOTTOM frame, whole (three lanes in five are starved on a critical
       tree, so nobody waits to be asked, and a ring that gives a frame a
       round is never full for longer than a round). And every lane, in a
       row where some lane is starved, whose TOP frame has two children or
       more left keeps the next one, ``[lo, lo + 1)``, and gives the
       others, ``[lo + 1, hi)``: its only frame, or the one above the
       bottom frame it gives in the same round (a lane may so give twice:
       a node pushed in the step before the round is split in that round,
       not two rounds later). Where no lane of the row is starved nobody
       could take the children now, and a bushy tree gives as it did
       before frames were split. A giver appends its one or two ranges to
       row i by its rank among the gifts (a product with a triangular
       matrix), the inverse gather (a binary search of the ranks) and one
       in-row gather a word and gift. A lane whose gifts the row has no
       room for keeps them for a round, unless ``E`` is over half full all
       in all: then it first leaves for HBM, whole, as the pool's next
       slab (``spill``). (A row that a burst fills does not send the 63
       others away: what leaves comes back only when ``E`` is empty, and
       the subtrees in it fall that far behind; PERF.md, PR 57.)
    2. *Who takes.* The starved lanes (sp < 0) of lane row i are dealt the
       LAST children of ``E``'s row i, a child a lane, this round's gifts
       first: the frames' widths summed from the row's front (one more
       product), the same search finds the frame of a lane's child and its
       place in the sum the child, one in-row gather a word. A frame dealt
       out whole leaves ``E``, the one the dealing stopped in keeps its
       first children (its ``hi`` is cut). Gifts before claims: a frame on
       the tree's critical path changes hands every level, and each round
       it waits in ``E`` is ``every`` steps of the whole call (PERF.md, PR
       56 and PR 57).
    3. ``E`` turns by one row, so that what row i could not use feeds row
       i + 1 next round and every row in 64.

    Only in-row gathers, one-row turns and whole-slab copies: no
    compaction across rows, nothing Mosaic lacks.

    The books are in PIECES, a child a piece: ``donated`` grows by a gift's
    width, ``claimed`` by one a lane that was dealt a child, and the two
    sides count apart (the width as it left the lane, the lanes that took),
    so a child lost or dealt twice shows in ``donated + roots == claimed``
    to the unit; ``split_gifts`` counts the gifts that split a frame (its
    holder kept the next child). A slot of ``E`` is live iff its column is
    below ``e``. A full pool sets ``err`` and the traversal stops.

    ``spill(pstate, do, k, planes) -> pstate`` and ``fetch(pstate, do, k)
    -> planes`` are the engine's (a DMA in the kernel, a slice of a carried
    array in XLA), ``roll_rows`` its one-row turn. Returns
    ``balance(lane, pool) -> (lane, pool)``, lane = (sp, top, st, ch, dp),
    pool = (E, e, slabs, pstate, donated, claimed, moved_rounds, err,
    spills, split_gifts)."""
    rows, cols = lanes
    col = jax.lax.broadcasted_iota(jnp.int32, lanes, 1)
    search_steps = (cols - 1).bit_length()

    def first_at_least(ranks, total, target):
        """Per slot, the first column whose rank (non-decreasing along a
        row, ``total`` at its end) reaches ``target``, and that rank."""
        lo = jnp.zeros(lanes, jnp.int32)
        hi = jnp.full(lanes, cols - 1, jnp.int32)
        there = total
        for _ in range(search_steps):
            mid = (lo + hi) >> 1
            rank = jnp.take_along_axis(ranks, mid, axis=1)
            ge = rank >= target
            hi = jnp.where(ge, mid, hi)
            there = jnp.where(ge, rank, there)
            lo = jnp.where(ge, lo, mid + 1)
        return lo, there

    def balance(lane, pool):
        sp, top, st, ch, dp = lane
        (E, e, slabs, pstate, donated, claimed, moved, err, spills,
         splits) = pool
        # 0. an empty exchange takes the newest slab
        load = (jnp.sum(e) == 0) & (slabs > 0)
        slab = fetch(pstate, load, slabs - 1)
        E = tuple(jnp.where(load, s, x) for s, x in zip(slab, E))
        e = jnp.where(load, row_total(slab[6] > 0, lanes), e)
        slabs = slabs - load.astype(jnp.int32)
        # 1. givers append their ranges: a bottom frame whole, a top
        # frame's children but the next (no giver is starved, so the
        # starved lanes' ranks are the same before the gifts and after)
        starved = sp < 0
        want_rank, want = row_cumsum(starved, lanes)
        bottom = (top - sp) & (S - 1)
        bword, tword = _level_select(ch, bottom), _level_select(ch, top)
        (blo, bhi), (tlo, thi) = _children(bword), _children(tword)
        whole = sp >= 1
        split = (sp >= 0) & (thi - tlo >= 2) & (want > 0)
        gifts = whole.astype(jnp.int32) + split.astype(jnp.int32)
        rank, offered = row_cumsum(gifts, lanes)
        # (e is a plane: a row's count stands in each of its columns)
        flush = (jnp.any(e + offered > cols)
                 & (jnp.sum(e) > rows * cols * cols // 2))
        err = err | (flush & (slabs >= pool_slabs)).astype(jnp.int32)
        pstate = spill(
            pstate, flush, jnp.minimum(slabs, pool_slabs - 1),
            E[:6] + (jnp.where(col < e, E[6], 0),),
        )
        e = jnp.where(flush, 0, e)
        slabs = slabs + flush.astype(jnp.int32)
        fits = rank <= cols - e
        whole, split = whole & fits, split & fits
        given = row_total(jnp.where(fits, gifts, 0), lanes)

        def frame(level, word):
            return [
                _as_i32(_level_select(
                    tuple(st[L][i] for L in range(S)), level))
                for i in range(5)
            ] + [word, _level_select(dp, level)]

        # a lane's last gift is its top frame's tail if it gives one, and
        # the gift before that, if there is one, its bottom frame
        first = frame(bottom, bword)
        last = [jnp.where(split, t, b)
                for t, b in zip(frame(top, tword + 1), first)]
        slot = col - e + 1
        source, there = first_at_least(rank, offered, slot)
        put = (col >= e) & (col < e + given)
        E = tuple(
            jnp.where(put, jnp.where(
                there == slot, jnp.take_along_axis(t, source, axis=1),
                jnp.take_along_axis(b, source, axis=1)), x)
            for b, t, x in zip(first, last, E)
        )
        e = e + given
        sp = jnp.where(whole, sp - 1, sp)
        ch = _level_store(ch, top, _children_word(tlo, tlo + 1), split)
        # 2. starved lanes are dealt the row's last children, a child a
        # lane, this round's gifts first: a frame that changes hands waits
        # for no second round, and its children for no second step
        elo, ehi = _children(E[5])
        width = jnp.where(col < e, ehi - elo, 0)
        upto, avail = row_cumsum(width, lanes)  # children, from the front
        claim = starved & (want_rank <= avail)
        nth = avail - want_rank + 1
        at, there = first_at_least(upto, avail, nth)
        got = [jnp.take_along_axis(x, at, axis=1) for x in E]
        child = (got[5] >> 16) - 1 - (there - nth)
        st = (tuple(jnp.where(claim, _as_u32(got[i]), st[0][i])
                    for i in range(5)),) + st[1:]
        ch = (jnp.where(claim, _children_word(child, child + 1), ch[0]),
              ) + ch[1:]
        dp = (jnp.where(claim, got[6], dp[0]),) + dp[1:]
        sp = jnp.where(claim, 0, sp)
        top = jnp.where(claim, 0, top)
        # what is left: the frames that start below the children left, the
        # last of them cut where the dealing stopped
        left = avail - jnp.minimum(avail, want)
        before = upto - width
        keep = (col < e) & (before < left)
        E = E[:5] + (
            jnp.where(keep, _children_word(
                elo, jnp.minimum(ehi, elo + left - before)), E[5]),
            E[6],
        )
        e = row_total(keep, lanes)
        # 3. the exchange turns by one row
        E = tuple(roll_rows(x) for x in E)
        e = roll_rows(e)
        gave = jnp.sum(jnp.where(whole, bhi - blo, 0)
                       + jnp.where(split, thi - tlo - 1, 0))
        took = jnp.sum(claim.astype(jnp.int32))
        pool = (E, e, slabs, pstate, donated + gave, claimed + took,
                moved + (gave + took > 0).astype(jnp.int32), err,
                spills + flush.astype(jnp.int32),
                splits + jnp.sum(split.astype(jnp.int32)))
        return (sp, top, st, ch, dp), pool

    return balance


def apply_claim(claim, rst, rcn, d0, sp, st0, ch0, cn0, dp0):
    """Install gathered roots into level 0 of claiming lanes (the shared
    tail of every refill implementation)."""
    st0 = tuple(jnp.where(claim, rst[i], st0[i]) for i in range(5))
    ch0 = jnp.where(claim, 0, ch0)
    cn0 = jnp.where(claim, rcn, cn0)
    dp0 = jnp.where(claim, d0, dp0)
    sp = jnp.where(claim, 0, sp)
    return sp, st0, ch0, cn0, dp0


def make_refill(lanes: tuple, d0: int):
    """Shared-root-queue claim: starved lanes (sp < 0) take the next
    contiguous unclaimed roots via prefix-sum rank + windowed gather.
    Returns refill(roots_state, roots_count, R, sp, next_root, st0, ch0,
    cn0, dp0) -> (sp, next_root, st0, ch0, cn0, dp0)."""
    nlanes = lanes[0] * lanes[1]

    def refill(roots_state, roots_count, R, sp, next_root, st0, ch0, cn0,
               dp0):
        done = sp < 0
        rank = jnp.cumsum(done.reshape(-1).astype(jnp.int32)).reshape(lanes)
        avail = R - next_root
        claim = done & (rank <= avail)
        # Claims are contiguous [next_root, next_root + nclaim): slice an
        # nlanes-wide window once, then gather within it - a gather over a
        # small VMEM-resident window instead of the whole HBM root array.
        win = [
            jax.lax.dynamic_slice(roots_state[i], (next_root,), (nlanes,))
            for i in range(5)
        ]
        wcn = jax.lax.dynamic_slice(roots_count, (next_root,), (nlanes,))
        idx = jnp.clip(rank - 1, 0, nlanes - 1)
        rst = [jnp.take(win[i], idx, axis=0) for i in range(5)]
        rcn = jnp.take(wcn, idx, axis=0)
        sp, st0, ch0, cn0, dp0 = apply_claim(
            claim, rst, rcn, d0, sp, st0, ch0, cn0, dp0
        )
        next_root = next_root + jnp.minimum(
            jnp.sum(done.astype(jnp.int32)), avail
        )
        return sp, next_root, st0, ch0, cn0, dp0

    return refill


def make_traversal(
    S: int,
    lanes: tuple,
    thresholds,
    gen_mx,
    min_idle: int,
    max_steps: int,
    refill,
    R,
    inrow_table=None,
    table=None,
):
    """The complete traversal driver shared by both engines: outer loop =
    refill + refill-free inner expansion loop until `min_idle` lanes are
    starved (or nothing is left to claim). ``refill(sp, next_root, st0,
    ch0, cn0, dp0)`` is the only engine-specific part (XLA gather here vs
    in-kernel DMA + matmul gather in uts_pallas). Returns run() ->
    (sp, next_root, nodes, leaves, maxd, steps, refills); ``refills`` is
    the number of outer rounds, each of which claimed roots once."""
    step = make_dfs_step(S, lanes, thresholds, gen_mx, inrow_table, table)

    def inner_cond(carry):
        sp, nodes, leaves, maxd, st, ch, cn, dp, steps, avail = carry
        active = jnp.any(sp >= 0)
        ndone = jnp.sum((sp < 0).astype(jnp.int32))
        # Keep expanding while work remains and either too few lanes are
        # idle to justify a refill, or there is nothing left to claim.
        return (
            active
            & ((ndone < min_idle) | (avail <= 0))
            & (steps < max_steps)
        )

    def inner_body(carry):
        sp, nodes, leaves, maxd, st, ch, cn, dp, steps, avail = carry
        sp, nodes, leaves, maxd, st, ch, cn, dp = step(
            sp, nodes, leaves, maxd, st, ch, cn, dp
        )
        return sp, nodes, leaves, maxd, st, ch, cn, dp, steps + 1, avail

    def outer_cond(carry):
        sp, next_root, *_rest, steps, _refills = carry
        return (jnp.any(sp >= 0) | (next_root < R)) & (steps < max_steps)

    def outer_body(carry):
        (sp, next_root, nodes, leaves, maxd, st, ch, cn, dp, steps,
         refills) = carry
        sp, next_root, st0, ch0, cn0, dp0 = refill(
            sp, next_root, st[0], ch[0], cn[0], dp[0]
        )
        st = (st0,) + st[1:]
        ch = (ch0,) + ch[1:]
        cn = (cn0,) + cn[1:]
        dp = (dp0,) + dp[1:]
        inner = (
            sp, nodes, leaves, maxd, st, ch, cn, dp, steps, R - next_root,
        )
        (
            sp, nodes, leaves, maxd, st, ch, cn, dp, steps, _,
        ) = jax.lax.while_loop(inner_cond, inner_body, inner)
        return (sp, next_root, nodes, leaves, maxd, st, ch, cn, dp, steps,
                refills + 1)

    def run():
        zeros = jnp.zeros(lanes, jnp.int32)
        uzeros = jnp.zeros(lanes, jnp.uint32)
        st0 = tuple(tuple(uzeros for _ in range(5)) for _ in range(S))
        ch0 = tuple(zeros for _ in range(S))
        cn0 = tuple(zeros for _ in range(S))
        dp0 = tuple(zeros for _ in range(S))
        carry = (
            jnp.full(lanes, -1, jnp.int32), jnp.int32(0), zeros, zeros,
            zeros, st0, ch0, cn0, dp0, jnp.int32(0), jnp.int32(0),
        )
        (sp, next_root, nodes, leaves, maxd, *_rest, steps, refills) = (
            jax.lax.while_loop(outer_cond, outer_body, carry)
        )
        return sp, next_root, nodes, leaves, maxd, steps, refills

    return run


def make_bin_traversal(S, lanes, max_steps, R, *, below, m, every, slabs0,
                       pool_slabs, pstate, spill, fetch, roll_rows,
                       unroll=False):
    """``make_traversal`` for a binomial tree, shared by both engines like
    it: a balance round (``make_balance``) in the refill's place, then
    ``every`` steps, until no lane holds a frame and the pool none; the
    last steps of a traversal may find no lane active, and ``steps`` counts
    them too. ``unroll`` writes the steps out (the compiled kernel: a loop
    of their own costs a copy of every loop-carried plane a trip, a fifth
    of a step's bundles by the v5e compiler's listing, PERF.md, PR 56;
    XLA's CPU backend, which fuses one hash into the next and is three to
    ten times as long over the compile, keeps the loop). ``max_steps`` is a runtime scalar here.
    The pool starts as ``slabs0`` slabs holding the ``R`` roots (the root's
    non-leaf children, whole frames) and every lane starved; the books are
    in pieces (``make_balance``), and a root is dealt a child a lane like
    any frame, so ``donated`` starts at the ``R (m - 1)`` children the
    roots hold beyond one each. Returns run() -> (nodes, leaves, maxd,
    spmax, steps, unfinished, rounds, (donated, claimed, rounds in which a
    frame moved, the pool's high-water mark, pool full, slabs spilled to
    HBM, gifts that split a frame))."""
    step = make_bin_step(S, lanes, below, m)
    balance = make_balance(S, lanes, pool_slabs, spill, fetch, roll_rows)

    def pooled(pool):
        return R + pool[4] - pool[5]  # children neither in a lane nor done

    def work_left(sp, pool):
        # by what is there, not by the counters: a frame lost or doubled
        # must end the loop and fail the conservation check, not spin
        return jnp.any(sp >= 0) | (jnp.sum(pool[1]) > 0) | (pool[2] > 0)

    def outer_cond(carry):
        sp, pool, steps = carry[0], carry[-4], carry[-3]
        return (work_left(sp, pool) & (steps < max_steps) & (pool[7] == 0))

    def outer_body(carry):
        (sp, top, nodes, leaves, maxd, spmax, st, ch, dp, pool, steps,
         rounds, pool_max) = carry
        pool_max = jnp.maximum(pool_max, pooled(pool))
        (sp, top, st, ch, dp), pool = balance((sp, top, st, ch, dp), pool)
        lane = (sp, top, nodes, leaves, maxd, spmax, st, ch, dp)
        if unroll:
            for _ in range(every):
                lane = step(*lane)
        else:
            lane = jax.lax.fori_loop(
                0, every, lambda _, lane: step(*lane), lane)
        sp, top, nodes, leaves, maxd, spmax, st, ch, dp = lane
        steps = steps + every
        return (sp, top, nodes, leaves, maxd, spmax, st, ch, dp, pool, steps,
                rounds + 1, jnp.maximum(pool_max, pooled(pool)))

    def run():
        zeros = jnp.zeros(lanes, jnp.int32)
        uzeros = jnp.zeros(lanes, jnp.uint32)
        zero = jnp.int32(0)
        pool = (tuple(zeros for _ in range(FRAME_WORDS)), zeros, slabs0,
                pstate, R * (m - 1), zero, zero, zero, zero, zero)
        carry = (
            jnp.full(lanes, -1, jnp.int32), zeros, zeros, zeros, zeros,
            jnp.full(lanes, -1, jnp.int32),
            tuple(tuple(uzeros for _ in range(5)) for _ in range(S)),
            tuple(zeros for _ in range(S)), tuple(zeros for _ in range(S)),
            pool, zero, zero, zero,
        )
        (sp, _, nodes, leaves, maxd, spmax, _, _, _, pool, steps, rounds,
         pool_max) = jax.lax.while_loop(outer_cond, outer_body, carry)
        unfinished = work_left(sp, pool)
        return (nodes, leaves, maxd, spmax, steps, unfinished, rounds,
                (pool[4], pool[5], pool[6], pool_max, pool[7], pool[8],
                 pool[9]))

    return run


def _engine_shape(params: UTSParams, d0: int, depth_bound, stack_pad):
    """Tree shape -> (thresholds, stack height, depth cap, bounded), for
    both engines. ``thresholds`` is the static tuple of the FIXED fast
    path, or None: the per-depth table (rows up to ``cap``) is then a
    runtime INPUT, so all trees with one padded table shape + stack height
    share a compile. ``bounded`` says the cap was chosen, not derived from
    the shape, and the run must be validated against it."""
    derived = depth_cap(params)
    if derived is None:  # EXPDEC: caller-chosen bound
        cap = depth_bound if depth_bound is not None else 8 * params.gen_mx
        bounded = True
    elif depth_bound is not None and depth_bound < derived:
        # An explicit bound below the shape's own cap shrinks the stack
        # for known-shallow trees - and gets the same loud validation.
        cap = depth_bound
        bounded = True
    else:
        cap = derived
        bounded = False
    if params.shape == FIXED and not bounded:
        thr = tuple(int(t) for t in child_thresholds(params.b0))
        stack_size = max(1, params.gen_mx - d0)
    else:
        thr = None
        # Pushed frames hold non-leaf nodes only; for shapes whose cap is
        # exact the deepest non-leaf sits at cap-2, so the tight height is
        # cap-1-d0 (every extra level costs select/store work per step).
        stack_size = max(1, (cap - d0) if bounded else (cap - 1 - d0))
    if stack_pad is not None:
        # Opt-in: pad the stack so differently-shaped trees share one
        # compiled engine (taller stacks cost select/store work per step,
        # so the perf path keeps the tight height).
        stack_size = max(stack_size, int(stack_pad))
    return thr, stack_size, cap, bounded


def _seeded(params: UTSParams, target_roots: int, device, slack: int,
            planes: bool):
    """The whole seeding inside its span, which ends when the padded roots
    are ready on the device: ``(seed, roots, result)``. ``seed`` is the
    exact (nodes, leaves, d0) of levels 0 to d0, ``roots`` the engine's
    first two arguments (``_padded_roots``; None when the seeding consumed
    the whole tree, and the result dict is then complete). ``slack`` is
    what the engine's refill window may read beyond the last root."""
    with span("uts.seed"):
        t0 = time.perf_counter()
        nodes, leaves, d0, frontier, chip_levels, chip_nodes = _seed_top(
            params, target_roots, device
        )
        roots, R = None, 0
        if frontier is not None:
            R = frontier.n - frontier.leaves
            padn = -(-(R + slack) // PAD_QUANTUM) * PAD_QUANTUM
            with (span("uts.seed.host") if _on_host(frontier)
                  else span("uts.seed.chip")):
                roots = jax.block_until_ready(
                    _padded_roots(frontier, padn, planes, device)
                )
        seed_seconds = time.perf_counter() - t0
    result = {
        "host_seed_nodes": nodes,
        "roots": R,
        "seed_seconds": seed_seconds,
        "seed_levels_on_chip": chip_levels,
        "seed_nodes_on_chip": chip_nodes,
    }
    if roots is None:
        result.update(nodes=nodes, leaves=leaves, max_depth=d0, steps=0)
    return (nodes, leaves, d0), roots, result


def _launch_once(who, run, result, seed, nlanes, max_steps, cap, interpret):
    """One launch of the engine ``run`` and its outputs into the result
    dict. ``device_seconds`` is that launch, waited for; a caller that
    wants a rate warms first (the first launch of a shape compiles) and
    times a second call. Totals are summed here in int64 (see the engines'
    return comment); ``cap`` is the depth bound to validate, None where
    the shape's own cap is exact. A binomial traversal returns two outputs
    more, the lanes' deepest stack pointers and the pool's counters
    (``make_bin_traversal``), which are checked and reported here too."""
    with span("uts.run"):
        t0 = time.perf_counter()
        outs = jax.block_until_ready(run())
        dt = time.perf_counter() - t0
    host_nodes, host_leaves, d0 = seed
    nodes, leaves, maxd, steps, unfinished, refills, *pooled = outs
    on_device = nodes
    with span("uts.readback"):
        if pooled:
            # eight outputs in one transfer, not one each
            (nodes, leaves, maxd, steps, unfinished, refills, spmax,
             counters) = jax.device_get(outs)
            donated, claimed, moved, pool_max, full, spills, splits = (
                int(x) for x in np.asarray(counters)
            )
            if full:
                raise RuntimeError(
                    f"{who}: the pool is full ({result['pool_capacity']} "
                    "frames): nowhere to spill the exchange buffer - "
                    "uts_vec.BIN_POOL_SLABS is too small for this tree"
                )
        if bool(unfinished):
            raise RuntimeError(f"{who} ran out of steps ({max_steps})")
        if pooled:
            # every child that left a lane, or came with a root, was
            # taken once
            assert donated + result["roots"] == claimed, (
                donated, result["roots"], claimed)
            result.update(
                donated=donated, claimed=claimed, balance_rounds=moved,
                pool_max=pool_max, spills=spills, split_gifts=splits,
                stack_max=int(np.asarray(spmax).max()) + 1,
            )
        deepest = int(np.asarray(maxd).max())
        if cap is not None and deepest >= cap:
            raise RuntimeError(
                f"tree reached the depth bound ({cap}): counts beyond it "
                "are truncated - rerun with a larger depth_bound"
            )
        dev_nodes = int(np.asarray(nodes).sum(dtype=np.int64))
        steps = int(steps)
        result.update(
            nodes=host_nodes + dev_nodes,
            leaves=host_leaves + int(np.asarray(leaves).sum(dtype=np.int64)),
            max_depth=max(d0, deepest),
            steps=steps,
            refills=int(refills),
            device_nodes=dev_nodes,
            device_seconds=dt,
            nodes_per_sec=dev_nodes / dt if dt > 0 else float("inf"),
            lane_efficiency=dev_nodes / (steps * nlanes) if steps else 0.0,
            **ran_on(on_device, interpret),
        )
    return result


def padded_threshold_table(
    params: UTSParams,
    cap: int,
    max_rows: Optional[int] = None,
    min_cols: Optional[int] = None,
) -> np.ndarray:
    """child_threshold_table padded to a COMMON shape: rows (depths) up to
    a multiple of 16, columns (child ordinals) to the next multiple of 16
    (capped at MAX_CHILDREN), -1 filled. The table values are runtime
    inputs to both engines, so every depth-varying tree whose padded shape
    matches shares ONE compiled engine (per stack height) instead of
    paying the ~1 min XLA/Mosaic compile per tree - padding costs a few
    dead compares per step (the per-step table cost scales with the COLUMN
    count, so quantized widths keep small-ordinal trees cheap while trees
    in one width class still share a compile).

    ``max_rows`` (uts_pallas passes its lane-column limit) caps the row
    round-up when the quantized height would cross a consumer's bound but
    the real cap still fits - so a cap of, say, 120 under a 127-row bound
    rides at the bound (rows = max_rows, here 127) instead of failing at
    the quantized 128. ``min_cols`` widens the
    ordinal padding (capped at MAX_CHILDREN) so callers can opt INTO a
    shared width class across trees whose natural widths differ - the
    test suite pads every depth-varying tree to one (rows, cols) class
    and so pays ONE engine trace instead of one per tree; perf callers
    omit it and keep the tightest class."""
    t = child_threshold_table(params, cap)
    rows = -(-(cap + 1) // 16) * 16
    if max_rows is not None and rows > max_rows >= cap + 1:
        rows = max_rows
    cols = min(MAX_CHILDREN, -(-t.shape[1] // 16) * 16)
    if min_cols is not None:
        cols = min(MAX_CHILDREN, max(cols, int(min_cols)))
    out = np.full((rows, max(cols, t.shape[1])), -1, np.int32)
    out[: t.shape[0], : t.shape[1]] = t
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "stack_size", "thresholds", "max_steps", "lanes", "min_idle_div",
    ),
)
def _uts_dfs(
    roots_state,  # (5, P) u32 - subtree roots, all at BFS depth d0
    roots_count,  # (P,) i32 - exact child counts (all >= 1)
    tab,  # (D+1, K) i32 runtime threshold table ((1, 1) dummy for FIXED)
    gen_mx,  # () i32 - FIXED-shape depth guard (unused on the table path)
    d0,  # () i32 - BFS depth of the roots
    nroots,  # () i32 - REAL root count R (arrays are padded to a common
    # quantum P >= R + nlanes so different trees share one compile AND the
    # refill window dynamic_slice is always in bounds)
    stack_size: int,
    thresholds,  # static ints (FIXED fast path) or None (runtime table)
    max_steps: int,
    lanes: tuple,
    min_idle_div: int = 8,
):
    S = stack_size
    nlanes = lanes[0] * lanes[1]
    R = nroots

    # Refill threshold: the gather+cumsum claim is much more expensive than
    # one SHA-1 step, so the hot expansion loop runs refill-free (inner
    # while) until this many lanes are idle; the outer loop then claims
    # roots for all of them at once. Imbalance cost is bounded by
    # min_idle/nlanes per refill round; refill wall cost by R/min_idle
    # rounds - min_idle_div trades the two.
    refill_min_idle = max(64, nlanes // min_idle_div)

    refill_fn = make_refill(lanes, d0)

    def refill(sp, next_root, st0, ch0, cn0, dp0):
        return refill_fn(
            roots_state, roots_count, R, sp, next_root, st0, ch0, cn0, dp0
        )

    run = make_traversal(
        S, lanes, thresholds, gen_mx, refill_min_idle, max_steps, refill, R,
        table=tab if thresholds is None else None,
    )
    sp, next_root, nodes, leaves, maxd, steps, refills = run()
    return (
        # Per-lane planes, not totals: totals are summed on the host in
        # int64 so trees beyond 2^31 total nodes (T1XXL's 4.23B) count
        # correctly while per-lane counters stay comfortably in int32.
        nodes,
        leaves,
        maxd,
        steps,
        jnp.any(sp >= 0) | (next_root < R),
        refills,
    )


# The seeding: the tree's top, breadth first, a whole level at a time. A
# level of at least SEED_CHIP_FROM nodes is expanded on the device, and so
# is every level after it; the smaller ones above it in numpy on the host.
# The crossover is read from the input (the level's size), not set by a
# caller. On the v5e's host a numpy level costs 1.2 ms at 74 nodes and
# below (1.4 k numpy calls), 1.6 at 300, 2.2 at 1,179, 5.3 at 4,562; a
# device level 1.0-1.4 ms up to 25 k children (a launch and a read of three
# scalars), 2.0 at 75 k, 5.7 at 300 k. With the crossover at 256, 1,024 or
# 2,048 the whole T1L seeding takes 24.4-26.6 ms, from the root 25.6-27.4;
# each shape more costs a new process half a second (tracing 1.4 k
# operations of SHA-1, and the cache's load), so it is the largest of them
# (PERF.md, PR 30).
SEED_CHIP_FROM = 2048
# Static capacities of a device level's arrays: 2^k and 3 * 2^(k-1), from
# 2,048 to 12 M. A level takes the smallest rung that holds it, whole, so
# trees of like size share the compiled expansions (T1L: three of them and
# one hand-over) and a level pays for at most 1.5 times its size.
SEED_RUNGS = tuple(m << s for s in range(10, 23) for m in (2, 3))


class _Level(NamedTuple):
    """One breadth-first level. On the host ``state`` (5, n) u32 and
    ``counts`` (n,) i32 are numpy arrays; on the device ``state`` is five
    (C,) u32 arrays and ``counts`` (C,) i32, at a rung C >= n, with
    ``counts`` 0 beyond n. The host loop reads only the three exact
    scalars."""

    state: object
    counts: object
    n: int  # nodes
    leaves: int  # those of them with no child
    total: int  # their children in all


def _on_host(level: _Level) -> bool:
    return isinstance(level.counts, np.ndarray)


def _rung(n: int) -> int:
    for cap in SEED_RUNGS:
        if n <= cap:
            return cap
    raise ValueError(
        f"a level of {n} nodes is beyond the seeding's largest capacity "
        f"({SEED_RUNGS[-1]}): lower target_roots"
    )


def _level_thresholds(params: UTSParams, depth: int) -> Tuple[int, ...]:
    """The ascending thresholds of a node AT ``depth``: one code path
    covers FIXED and every depth-varying shape exactly."""
    return _thresholds_for_b(_branching(params, depth))


def _host_level(params: UTSParams, state: np.ndarray, depth: int) -> _Level:
    # The thresholds ascend, so #{k : r >= t_k} is r's insertion point.
    ts = np.asarray(_level_thresholds(params, depth), np.int32)
    r = (state[4] & np.uint32(0x7FFFFFFF)).astype(np.int32)
    counts = np.searchsorted(ts, r, side="right").astype(np.int32)
    n = state.shape[1]
    return _Level(
        state, counts, n, n - int(np.count_nonzero(counts)),
        int(counts.sum()),
    )


def _expand_host(level: _Level) -> np.ndarray:
    """The states of the level's children, (5, total). int32 indices: on
    the chip's host a freshly mapped page costs about 12 us (PERF.md, PR
    29)."""
    counts = level.counts
    parent = np.repeat(np.arange(level.n, dtype=np.int32), counts)
    first = (np.cumsum(counts) - counts).astype(np.int32)
    rank = np.arange(level.total, dtype=np.int32)
    rank -= first[parent]
    return sha1_children_np(level.state, parent, rank.view(np.uint32))


def _running_sum(x):
    """Inclusive running sum of a (C,) i32 array, C a multiple of 1,024:
    within rows of 1,024, then over the rows' totals. (A flat ``cumsum``
    over 2^19 words takes the TPU compiler 22 s; this form 0.3 s, and runs
    as fast: PERF.md, PR 30.)"""
    rows = x.reshape(-1, 1024)
    ends = jnp.cumsum(jnp.sum(rows, axis=1))
    before = jnp.concatenate([jnp.zeros(1, x.dtype), ends[:-1]])
    return (jnp.cumsum(rows, axis=1) + before[:, None]).reshape(-1)


@functools.partial(jax.jit, static_argnames=("cap",))
def uts_seed_expand(state, counts, thr, *, cap: int):
    """``_expand_host`` and the next ``_host_level`` on the device. In: a
    level's states, five (C_in,) u32, and child counts (C_in,) i32, 0
    beyond its real size, and the children's thresholds (MAX_CHILDREN,)
    i32, ascending and -1 padded. Out: the children's states, five (cap,)
    u32 (five arrays, not one stacked: XLA's CPU backend fuses a stack with
    the hash above it and then runs 2,048 hashes in 170 s), their own child
    counts (cap,) i32, 0 beyond the real size, and the scalars (children,
    leaves among them, grandchildren) i32."""
    cum = _running_sum(counts)
    first = cum - counts
    total = cum[-1]
    slot = jnp.arange(cap, dtype=jnp.int32)
    # The device form of np.repeat. Every parent marks the slot of its first
    # child, a childless one the slot its successor marks too, so the
    # running sum of the marks steps over it: the parent index is monotone.
    marks = jnp.zeros(cap, jnp.int32).at[first].add(
        1, indices_are_sorted=True, mode="drop"
    )
    parent = _running_sum(marks) - 1
    # One gather for all a child needs of its parent: on the v5e a gather
    # costs 4.5 ns an index whether it fetches one word or eight, and five
    # separate ones in a jit 23 ns (PERF.md, PR 30).
    of_parent = jnp.take(
        jnp.stack([*state, first.astype(jnp.uint32)]), parent, axis=1
    )
    rank = slot - of_parent[5].astype(jnp.int32)
    child = _sha1_child(list(of_parent[:5]), rank, jnp)
    live = slot < total
    r = (child[4] & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    ccounts = jnp.sum(
        (thr[:, None] >= 0) & (r[None, :] >= thr[:, None]), axis=0,
        dtype=jnp.int32,
    )
    ccounts = jnp.where(live, ccounts, 0)
    leaves = jnp.sum(live & (ccounts == 0), dtype=jnp.int32)
    scalars = jnp.stack([total, leaves, jnp.sum(ccounts)])
    return child, ccounts, scalars


@functools.partial(jax.jit, static_argnames=("padn", "planes"))
def uts_seed_roots(state, counts, *, padn: int, planes: bool):
    """The hand-over on the device: the level's non-leaf nodes in LPT order
    (descending child count, ties in frontier order, as ``np.argsort(-counts,
    kind="stable")`` gives), zero padded to ``padn``, in the engine's
    layout (``_padded_roots``). A counting sort, since a count is one of
    MAX_CHILDREN values: a node's place is where its count's class starts,
    plus the class's nodes in the rows of 1,024 before its own, plus those
    before it in its row. (XLA's own sort takes the TPU compiler 68 s at
    this size: PERF.md, PR 30.)"""
    row = counts.reshape(-1, 1024)
    i = jnp.arange(1024, dtype=jnp.int32)
    in_row = jnp.sum(
        (row[:, None, :] == row[:, :, None]) & (i[None, :] < i[:, None]),
        axis=2, dtype=jnp.int32,
    )

    def place_class(t, carry):
        dest, start = carry
        mine = row == MAX_CHILDREN - t  # the biggest counts first
        per_row = jnp.sum(mine, axis=1, dtype=jnp.int32)
        ends = jnp.cumsum(per_row)
        dest = jnp.where(mine, start + (ends - per_row)[:, None], dest)
        return dest, start + ends[-1]

    # A leaf matches no class: it gets a place of its own beyond padn, and
    # the scatter drops it. The scatter carries one word a node, its own
    # slot; one gather then brings states and counts (a scatter of the six
    # words themselves costs six times as much on the v5e, or 12 s of
    # compiling as one 2-D scatter).
    dest, R = jax.lax.fori_loop(
        0, MAX_CHILDREN, place_class, (jnp.zeros_like(row), jnp.int32(0))
    )
    slot = jnp.arange(counts.shape[0], dtype=jnp.int32)
    dest = jnp.where(counts > 0, (dest + in_row).reshape(-1), padn + slot)
    source = jnp.zeros(padn, jnp.int32).at[dest].set(
        slot, unique_indices=True, mode="drop"
    )
    out = jnp.take(
        jnp.stack([*state, counts.astype(jnp.uint32)]), source, axis=1
    )
    out = jnp.where(jnp.arange(padn, dtype=jnp.int32) < R, out, 0)
    pstate, pcount = out[:5], out[5].astype(jnp.int32)
    if planes:
        pstate = jax.lax.bitcast_convert_type(pstate, jnp.int32)
        return (pstate.reshape(5, -1, LANES[1]),
                pcount.reshape(-1, LANES[1]))
    return pstate, pcount


def _padded_roots(frontier: _Level, padn: int, planes: bool, device):
    """The engine's root arrays on the device, from a frontier on either
    side: the non-leaf nodes in LPT order - biggest child counts first, so
    the large subtrees are claimed (and balanced over lanes) early and the
    drain tail is short; totals are order-independent, only steps and lane
    efficiency change - zero padded to ``padn``, as (5, padn) u32 and
    (padn,) i32, or with ``planes`` as (5, padn/128, 128) i32 (u32 bits)
    and (padn/128, 128) i32 for the Pallas engine's row-block DMA."""
    state, counts = frontier.state, frontier.counts
    if not _on_host(frontier):
        return uts_seed_roots(state, counts, padn=padn, planes=planes)
    roots = np.flatnonzero(counts)
    roots = roots[np.argsort(-counts[roots], kind="stable")]
    pstate = np.zeros((5, padn), np.uint32)
    pstate[:, : roots.size] = state[:, roots]
    pcount = np.zeros(padn, np.int32)
    pcount[: roots.size] = counts[roots]
    if planes:
        pstate = pstate.view(np.int32).reshape(5, -1, LANES[1])
        pcount = pcount.reshape(-1, LANES[1])
    return jax.device_put((pstate, pcount), device)


def _expand_chip(params: UTSParams, level: _Level, depth: int, device,
                 thr_on_chip: dict) -> _Level:
    """The level's children, at ``depth``, as a device level: one launch of
    ``uts_seed_expand`` and one read of its three scalars. A level still on
    the host goes up first, at its rung. ``thr_on_chip`` keeps the
    threshold rows already uploaded (a FIXED tree has one)."""
    state, counts = level.state, level.counts
    if _on_host(level):
        pad = (0, _rung(level.n) - level.n)
        state, counts = jax.device_put(
            (tuple(np.pad(s, pad) for s in state), np.pad(counts, pad)),
            device,
        )
    ts = _level_thresholds(params, depth)
    if ts not in thr_on_chip:
        row = np.full(MAX_CHILDREN, -1, np.int32)
        row[: len(ts)] = ts
        thr_on_chip[ts] = jax.device_put(row, device)
    state, counts, scalars = uts_seed_expand(
        state, counts, thr_on_chip[ts], cap=_rung(level.total)
    )
    children, leaves, grandchildren = (int(x) for x in np.asarray(scalars))
    assert children == level.total, (children, level.total)
    return _Level(state, counts, children, leaves, grandchildren)


def _seed_top(params: UTSParams, target_roots: int, device):
    """Breadth-first expansion of the tree's top, a whole level at a time,
    to the first level of at least ``target_roots`` nodes.

    Returns (nodes, leaves, d0, frontier, chip_levels, chip_nodes): the
    exact counts of levels 0 to d0, all of them, wherever they were hashed;
    the frontier level at depth d0 (None when the tree ended there), whose
    non-leaf nodes are the engine's roots; how many levels the device
    expanded and how many nodes it hashed for them."""
    root = np.frombuffer(root_state(params.root_seed), ">u4")
    level = _host_level(params, root.astype(np.uint32)[:, None], 0)
    nodes = leaves = depth = chip_levels = chip_nodes = 0
    thr_on_chip: dict = {}
    while True:
        # Frontier leaves are counted here; the roots themselves as nodes.
        nodes += level.n
        leaves += level.leaves
        if level.total == 0:
            return nodes, leaves, depth, None, chip_levels, chip_nodes
        if level.n >= target_roots:
            return nodes, leaves, depth, level, chip_levels, chip_nodes
        depth += 1
        if _on_host(level) and level.n < SEED_CHIP_FROM:
            with span("uts.seed.host"):
                level = _host_level(params, _expand_host(level), depth)
            continue
        with span("uts.seed.chip"):
            level = _expand_chip(params, level, depth, device, thr_on_chip)
        chip_levels += 1
        chip_nodes += level.n


# A binomial traversal's shape. BIN_STACK is the default of the keyword
# ``stack_size`` (the ring's height); the other two are the engine's own:
# the steps between two balance rounds, and the pool's capacity in HBM in
# slabs of one frame a lane. PERF.md (PR 56) has the chip's readings of the
# alternatives (rings of 2-8, rounds every 1-4 steps) and of the pool: T3L
# whole on 8,192 lanes never held more than 3 slabs (the roots' one and 2
# spilled a call), so 8 is that and as much again and a bit; since PR 57
# the exchange leaves only when it is over half full, and T3L spills
# none. A tree that fills the slabs raises.
BIN_STACK = 2
BIN_EVERY = 2
BIN_POOL_SLABS = 8


def _geo_only(stack_size):
    if stack_size is not None:
        raise ValueError(
            "stack_size is a binomial tree's keyword (-t 0); this tree is "
            "geometric"
        )


def _seeded_bin(params: UTSParams, lanes: tuple):
    """A binomial tree's whole seeding inside its span: the root and its
    floor(b0) children, hashed on the host (hashlib: a call's host time
    is all of its spread between processes, PERF.md, PR 56), and the
    non-leaf ones laid out as the pool's first slabs, still on the host:
    ``(seed, slabs, result)`` as ``_seeded`` gives them. A slab is
    (FRAME_WORDS, rows, 128) int32 (state words as u32 bits, the children
    [0, m) as ``m << 16``, depth 1; depth 0 marks an empty slot), its
    frames dealt round-robin
    over the rows and each row filled from its front, which is how the
    balance round reads it."""
    rows, cols = lanes
    nlanes = rows * cols
    with span("uts.seed"):
        t0 = time.perf_counter()
        b0 = int(math.floor(params.b0))
        root = hashlib.sha1(root_state(params.root_seed))
        digests = []
        for i in range(b0):  # SHA1(root's state || BE32(i))
            child = root.copy()
            child.update(struct.pack(">i", i))
            digests.append(child.digest())
        kids = np.frombuffer(b"".join(digests), ">u4").reshape(
            b0, 5).T.astype(np.uint32)
        r = (kids[4] & np.uint32(0x7FFFFFFF)).astype(np.int64)
        keep = kids[:, r < bin_threshold(params.q)]
        R = keep.shape[1]
        slabs = None
        if R:
            n0 = -(-R // nlanes)
            flat = np.zeros((FRAME_WORDS, n0 * nlanes), np.uint32)
            flat[:5, :R] = keep
            flat[5, :R] = _children_word(0, params.m)
            flat[6, :R] = 1
            # frame f of a slab -> row f % rows, column f // rows
            slabs = np.ascontiguousarray(
                flat.reshape(FRAME_WORDS, n0, cols, rows)
                .transpose(1, 0, 3, 2)
            ).view(np.int32)
        seed_seconds = time.perf_counter() - t0
    result = {
        "host_seed_nodes": 1 + b0,
        "roots": R,
        "seed_seconds": seed_seconds,
        "seed_levels_on_chip": 0,
        "seed_nodes_on_chip": 0,
    }
    seed = (1 + b0, max(b0, 1) - R, 1 if b0 else 0)
    if slabs is None:
        result.update(nodes=seed[0], leaves=seed[1], max_depth=seed[2],
                      steps=0)
    return seed, slabs, result


def pool_of(slabs, pool_slabs: int):
    """The pool in HBM at the start of a launch: ``pool_slabs`` slabs (as
    many as the roots fill, if those are more), the first ones the roots',
    the others empty."""
    pool = jnp.zeros(
        (max(pool_slabs, slabs.shape[0]),) + slabs.shape[1:], jnp.int32)
    return jax.lax.dynamic_update_slice(pool, slabs, (0, 0, 0, 0))


@functools.partial(
    jax.jit,
    static_argnames=("stack_size", "lanes", "every", "pool_slabs"),
)
def _uts_bin(
    slabs,  # (n0, FRAME_WORDS, rows, 128) i32 - the roots, as slabs
    scal,  # (4,) i32 - [R, below, m, max_steps]
    stack_size: int,
    lanes: tuple,
    every: int,
    pool_slabs: int,
):
    pool = pool_of(slabs, pool_slabs)

    def spill(pool, do, k, planes):
        return jax.lax.cond(
            do,
            lambda p: jax.lax.dynamic_update_slice(
                p, jnp.stack(planes)[None], (k, 0, 0, 0)),
            lambda p: p,
            pool,
        )

    def fetch(pool, do, k):
        slab = jax.lax.dynamic_slice(
            pool, (k, 0, 0, 0), (1,) + pool.shape[1:])[0]
        return tuple(slab[w] for w in range(FRAME_WORDS))

    run = make_bin_traversal(
        stack_size, lanes, scal[3], scal[0], below=scal[1], m=scal[2],
        every=every, slabs0=jnp.int32(slabs.shape[0]),
        pool_slabs=pool.shape[0], pstate=pool, spill=spill, fetch=fetch,
        roll_rows=lambda x: jnp.roll(x, 1, 0),
    )
    nodes, leaves, maxd, spmax, steps, unfinished, rounds, counters = run()
    return (nodes, leaves, maxd, steps, unfinished, rounds, spmax,
            jnp.stack(counters))


def _call_bin(who, engine, params, lanes, device, max_steps, stack_size,
              geometric: dict, **engine_kw):
    """One binomial traversal through ``engine`` (``_uts_bin`` or
    uts_pallas's jitted kernel): the keywords checked, the seeding, the
    slabs and the runtime scalars sent up inside ``uts.stage``, one launch,
    one readback. ``geometric`` holds the call's keywords that mean nothing to
    a binomial tree (its seeding is the root's children and no more; its
    node's count does not depend on its depth, so no table and no depth
    cap) and raise if given; ``engine_kw`` goes to ``engine`` as it is."""
    for name, value in geometric.items():
        if value is not None:
            raise ValueError(
                f"{name} has no meaning for a binomial tree (-t 0): its "
                "seeding is the root's children, its stack a ring of "
                "stack_size frames that spills to the pool"
            )
    S = BIN_STACK if stack_size is None else int(stack_size)
    if S < 2 or S & (S - 1):
        raise ValueError(f"stack_size must be a power of two >= 2, got {S}")
    if params.m > BIN_MAX_M:
        raise ValueError(
            f"a binomial tree's m is at most {BIN_MAX_M} here (the balance "
            f"round sums a row's widths in one MXU pass), got {params.m}")
    seed, slabs, result = _seeded_bin(params, tuple(lanes))
    if slabs is None:
        return result
    with span("uts.stage"):
        result.update(
            stack_size=S,
            pool_capacity=max(BIN_POOL_SLABS, slabs.shape[0])
            * lanes[0] * lanes[1],
        )
        # One upload, not waited for: the launch waits for it, and every
        # wait of the host is a millisecond of a call that differs between
        # processes (PERF.md, PR 56).
        slabs, scal = jax.device_put(
            (slabs, np.array([result["roots"], bin_threshold(params.q),
                              params.m, max_steps], np.int32)),
            device,
        )
        kw = dict(stack_size=S, lanes=tuple(lanes), every=BIN_EVERY,
                  pool_slabs=BIN_POOL_SLABS, **engine_kw)
    return _launch_once(
        who, lambda: engine(slabs, scal, **kw), result, seed,
        lanes[0] * lanes[1], max_steps, None,
        engine_kw.get("interpret", False),
    )


def uts_vec(
    params: UTSParams,
    target_roots: Optional[int] = None,
    max_steps: Optional[int] = None,
    device=None,
    lanes: Tuple[int, int] = LANES,
    min_idle_div: int = 8,
    depth_bound: Optional[int] = None,
    stack_pad: Optional[int] = None,
    table_cols: Optional[int] = None,
    stack_size: Optional[int] = None,
) -> dict:
    """Run UTS with the vectorized DFS engine; returns counts + timing info.

    The seeding BFS-expands the tree top until >= target_roots frontier
    nodes (its small levels on the host, the others on the device, counting
    that part exactly), then the engine traverses the subtrees, lanes
    claiming roots from the shared queue as they drain.

    All GEO shapes are supported: FIXED uses the depth-independent
    threshold fast path; LINEAR/CYCLIC get exact per-depth threshold
    tables with a shape-derived depth cap; EXPDEC (whose branching decays
    but never reaches zero) uses ``depth_bound`` (default 8*gen_mx) and
    the run fails loudly if the tree actually reaches the bound.

    One call is one traversal: one seeding, one launch, one readback.
    ``device_seconds`` is that launch, and the first launch of a shape
    compiles (as the seeding's expansions do): a caller that wants a rate
    calls twice. ``host_seed_nodes`` counts levels 0 to d0 whole, wherever
    they were hashed; ``seed_levels_on_chip`` / ``seed_nodes_on_chip`` say
    how much of that the device did.

    A BINOMIAL tree (``params.tree == BIN``) takes the same road with other
    stations: the seeding is the root and its floor(b0) children, on the
    host, and the non-leaf ones are the pool's first frames
    (``target_roots`` and the depth keywords mean nothing and raise); a
    lane's stack is a ring of ``stack_size`` frames (default BIN_STACK)
    whose bottom frame, and the children of its top frame but the next,
    leave for the exchange buffer, and from there a child a starved lane,
    or for the pool in HBM, in the balance round that comes every
    BIN_EVERY steps (``make_balance``); the result dict gains ``donated``
    and ``claimed`` (children: a frame dealt to k lanes is k of each),
    ``split_gifts``, ``pool_max``, ``spills``, ``stack_max`` and
    ``balance_rounds``, and ``refills`` counts the balance rounds."""
    if max_steps is None:
        max_steps = (1 << 31) - 1
    nlanes = lanes[0] * lanes[1]
    if params.tree == BIN:
        return _call_bin(
            "uts_vec", _uts_bin, params, lanes, device, max_steps,
            stack_size,
            dict(target_roots=target_roots, depth_bound=depth_bound,
                 stack_pad=stack_pad, table_cols=table_cols),
        )
    _geo_only(stack_size)
    if target_roots is None:
        target_roots = 16 * NLANES
    # Padded to PAD_QUANTUM (>= R + nlanes): the refill window
    # dynamic_slice never runs off the end, and trees with different root
    # counts land on the SAME padded shape, sharing one compiled engine.
    seed, roots, result = _seeded(params, target_roots, device, nlanes, False)
    if roots is None:
        return result
    d0 = seed[2]
    with span("uts.stage"):
        thr, stack_size, cap, bounded = _engine_shape(
            params, d0, depth_bound, stack_pad
        )
        if thr is not None:
            tabnp = np.zeros((1, 1), np.int32)  # unused dummy input
        else:
            # table_cols (like stack_pad) opts into a shared width class.
            tabnp = padded_threshold_table(params, cap, min_cols=table_cols)
        args = roots + jax.block_until_ready(jax.device_put(
            (tabnp, np.int32(params.gen_mx), np.int32(d0),
             np.int32(result["roots"])),
            device,
        ))  # the upload belongs to this span
        kw = dict(
            stack_size=stack_size,
            thresholds=thr,
            max_steps=max_steps,
            lanes=tuple(lanes),
            min_idle_div=min_idle_div,
        )
    # uts_vec is plain XLA: it has no interpreter to fall back to.
    return _launch_once(
        "uts_vec", lambda: _uts_dfs(*args, **kw), result, seed, nlanes,
        max_steps, cap if bounded else None, False,
    )


if __name__ == "__main__":  # pragma: no cover
    import sys

    from ..models.uts import T1, T1L, T3, T3L, T_TINY

    name = sys.argv[1] if len(sys.argv) > 1 else "T_TINY"
    params = {"T1": T1, "T1L": T1L, "T3": T3, "T3L": T3L,
              "T_TINY": T_TINY}[name]
    print(uts_vec(params))
