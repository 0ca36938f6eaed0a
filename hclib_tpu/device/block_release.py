"""A dependence release keyed by (kind, block), with ``need`` read from
the fill pattern: the release of a right-looking block factorisation whose
matrix fills in (``device/sparselu.py``).

``forasync_tier.StepPlan`` counts down one kind whose awaiters are its
awaits mirrored. Here four kinds await each other over a block-sparse
matrix: a diagonal task (``lu0``) releases its row's and its column's
panel tasks; a finished panel block (``fwd`` of row ``kk``, ``bdiv`` of
column ``kk``) releases the updates (``bmod``) of its column, or row,
whose other operand is final; a finished update releases its block's NEXT
step - another update, or the block's own panel or diagonal task when no
step is left, which names a different kind. Every task after the root is
made on the device, by the task that completes its last input.

What it keeps, all in the kernel's value slots (SMEM), none of it a word a
task:

- the FINAL pattern as one bit mask a block row and one a block column
  (``2 n ceil(n / 32)`` words; the symbolic factorisation is the host's),
  and as many words again for the panel blocks that are final so far, a
  mask a step's row and a mask a step's column;
- one word a block of the ``n`` x ``n`` block grid: the step the block
  waits for (the lowest ``kk`` whose update it has not had, ``ST_DONE``
  when none is left), whether a task on it has been issued for that step
  (``B_BUSY``), whether it is final, whether its data lies in the output
  yet (``B_HAS``) or still in the caller's input (``B_FRESH``) - a fill
  block starts with neither and is made by the first update that writes
  it - and the block's slot in sparse block storage.

A block's next step is the lowest set bit above ``kk`` of ``rowmask[ii] &
colmask[jj]`` below ``min(ii, jj)``. The scheduler is serial, so a release
is race-free: of the three events that can be an update's last input (its
block reaching the step, the row operand final, the column operand final)
exactly one finds the other two true, and ``B_BUSY`` keeps a later look
from making the task again.

The widest step has ``(n / 2)^2`` updates ready at once and the table has
a few hundred rows, so a finished panel block does not make all it
releases in one go. It leaves a RANGE descriptor (the scan kinds, on the
scalar tier): popped, it walks a mask from a cursor (a finished ``fwd``
the step's column blocks that are final, a finished ``bdiv`` its row
blocks that are: of a pair of operands the later one finds the earlier
one's bit, so a pair is looked at once, by whoever came last), makes at
most ``CHUNK`` tasks, and puts itself back with the cursor where it
stopped.
The lanes fire at ``2 * width`` entries over a hot ring
(``BatchSpec.fire_at``), so a range is dealt out as its lane drains, and
the table holds the live front only: ``simulate`` replays the schedule on
the host, descriptor by descriptor, and sizes the table.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .forasync_tier import V_DECREMENTS, V_RELEASED, StepPlan

__all__ = [
    "BlockPlan", "K_DIAG", "K_PANEL", "K_UPDATE", "K_SCANP", "K_SCANU",
    # StepPlan's two counter words, which this release counts in too
    "V_RELEASED", "V_DECREMENTS",
]

# Kernel-table ids of a build: the diagonal task on the scalar tier, the
# panel and update kinds on a batch lane each (the lower id fires first),
# the two range kinds on the scalar tier.
K_DIAG, K_PANEL, K_UPDATE, K_SCANP, K_SCANU = 0, 1, 2, 3, 4

# Value slots: StepPlan's two counters (tasks the release made; tests of a
# block's readiness, those that made nothing among them), then this
# release's own, then the masks and the block words.
V_FILL, V_SCANS = 2, 3
V_DIAG, V_ROW, V_COL, V_UPD = 4, 5, 6, 7  # executed by kind
V_PANEL_ROUNDS, V_UPD_ROUNDS = 8, 9
# tasks of a lane whose loads were in flight before their round began
V_PANEL_PREFETCHED, V_UPD_PREFETCHED = 10, 11
BASE = 16

ST_MASK = 0xFF
ST_DONE = 0xFF       # no update left: the block's own final task is next
B_BUSY = 1 << 8      # a task for the step in ST_MASK has been made
B_FINAL = 1 << 9
B_HAS = 1 << 10      # the block's data lies in the output buffer
B_FRESH = 1 << 11    # ... or still in the caller's input, at the same slot
SLOT_SHIFT = 16

# Range modes: which mask a range walks and which block a set bit names.
M_ROW, M_COL = 0, 1  # (kk, t) off rowmask[kk]; (t, kk) off colmask[kk]

CHUNK = 16  # tasks a range makes before it puts itself back


def _i32(x: int) -> jnp.int32:
    return jnp.int32(np.uint32(x).view(np.int32))


def _lowest_bit(x):
    """Index of the lowest set bit of a nonzero int32, branch-free (the
    scalar core has no count-trailing-zeros this lowers to)."""
    lb = x & (-x)
    idx = jnp.int32(0)
    for mask, w in ((0xFFFF0000, 16), (0xFF00FF00, 8), (0xF0F0F0F0, 4),
                    (0xCCCCCCCC, 2), (0xAAAAAAAA, 1)):
        idx = idx + jnp.where((lb & _i32(mask)) != 0, w, 0)
    return idx


class BlockPlan:
    """The release of one block pattern: presets, the device side
    (``after_diag`` / ``after_panel`` / ``after_update`` / ``scan``) and
    the schedule replayed on the host (``simulate``)."""

    def __init__(self, present: np.ndarray, final: np.ndarray,
                 slot_of: np.ndarray) -> None:
        n = len(final)
        self.n = n
        self.nw = -(-n // 32)
        self.present = np.asarray(present, bool)
        self.final = np.asarray(final, bool)
        self.slot_of = np.asarray(slot_of)
        self.row_base = BASE
        self.col_base = BASE + n * self.nw
        # the row blocks (kk, jj) and the column blocks (ii, kk) of step kk
        # that are final so far
        self.frow_base = BASE + 2 * n * self.nw
        self.fcol_base = BASE + 3 * n * self.nw
        self.blk_base = BASE + 4 * n * self.nw
        self.num_values = self.blk_base + n * n
        w = 1 << np.arange(n, dtype=object)
        self.rowmask = [int((self.final[i] * w).sum()) for i in range(n)]
        self.colmask = [int((self.final[:, j] * w).sum()) for j in range(n)]
        self._presets: Optional[np.ndarray] = None

    # -- host side --

    def first_step(self, ii: int, jj: int, after: int = -1) -> int:
        """The lowest ``kk > after`` whose update block ``(ii, jj)`` takes,
        ``ST_DONE`` when there is none."""
        ii, jj, after = int(ii), int(jj), int(after)
        x = self.rowmask[ii] & self.colmask[jj] & ((1 << min(ii, jj)) - 1)
        x &= ~((1 << (after + 1)) - 1)
        return (x & -x).bit_length() - 1 if x else ST_DONE

    def root_word(self) -> int:
        """Block (0, 0)'s word as the root task carries it."""
        return int(self.presets()[self.blk_base]) | B_BUSY

    def presets(self) -> np.ndarray:
        """The value slots a launch starts from: counters zero, the masks,
        every block of the final pattern at its first step. One array,
        made once: a run copies it into its upload and never writes it."""
        if self._presets is None:
            n, nw = self.n, self.nw
            vals = np.zeros(self.num_values, np.int64)
            for base, masks in ((self.row_base, self.rowmask),
                                (self.col_base, self.colmask)):
                for i, mk in enumerate(masks):
                    for w in range(nw):
                        vals[base + i * nw + w] = (mk >> (32 * w)) & 0xFFFFFFFF
            for ii, jj in zip(*np.nonzero(self.final)):
                word = (int(self.slot_of[ii, jj]) << SLOT_SHIFT
                        | self.first_step(ii, jj)
                        | (B_FRESH if self.present[ii, jj] else 0))
                vals[self.blk_base + ii * n + jj] = word
            out = vals.astype(np.uint32).view(np.int32)
            out.setflags(write=False)
            self._presets = out
        return self._presets

    def simulate(self, panel_width: int, update_width: int,
                 log: Optional[list] = None) -> Dict[str, int]:
        """The launch replayed on the host, descriptor by descriptor, as
        the scheduler runs it: a lane fires (panel before update) at an
        empty ring or at twice its width; else the ring pops newest first;
        a task the release makes goes straight on its lane's tail (the
        diagonal task and the ranges on the ring) while the rows of the
        batch that made it are still live. A round announces the entries
        queued behind its batch, a batch at most, for the lane's next
        round to find prefetched (the scheduler's handshake: the count is
        taken before the round's own releases push). Returns the
        release's counters as ``info["sparselu"]`` reports them,
        ``live_rows_max`` among them: the table is sized from it, and the
        tests hold the kernel to all of them. ``log``, where given, takes
        the schedule: ``(lane, tasks, prefetched, announced)`` a batch
        round, the entry a pop of the ring."""
        n = self.n
        step: Dict[Tuple[int, int], int] = {}
        busy, final = set(), set()
        for ii, jj in zip(*np.nonzero(self.final)):
            step[int(ii), int(jj)] = self.first_step(ii, jj)
        ring: List[tuple] = [("d", 0)]
        lanes = {"p": [], "u": []}
        out = dict(released=0, decrements=0, scans=0, fill_blocks=0,
                   lu0=0, fwd=0, bdiv=0, bmod=0, panel_rounds=0,
                   bmod_rounds=0, panel_prefetched=0, bmod_prefetched=0,
                   live_rows_max=0)
        made = {(int(i), int(j)) for i, j in zip(*np.nonzero(self.present))}
        frow, fcol = [0] * n, [0] * n  # panel blocks final so far, a step
        live = hw = 1
        busy.add((0, 0))

        def spawn(where: str, entry: tuple) -> None:
            nonlocal live, hw
            live += 1
            hw = max(hw, live)
            (ring if where == "r" else lanes[where]).append(entry)

        def bits(mask: int, after: int):
            x = mask >> (after + 1)
            t = after + 1
            while x:
                if x & 1:
                    yield t
                x >>= 1
                t += 1

        def scan(kind: str, mode: int, kk: int, fx: int, cur: int) -> None:
            """``kind`` "p": the panel blocks of step kk (row or column);
            "u": the updates a panel block (kk, fx) or (fx, kk) feeds."""
            out["scans"] += 1
            if kind == "p":
                mask = self.rowmask[kk] if mode == M_ROW else self.colmask[kk]
            else:
                mask = frow[kk] if mode == M_ROW else fcol[kk]
            n_made = 0
            for t in bits(mask, cur):
                if n_made == CHUNK:
                    spawn("r", ("s", kind, mode, kk, fx, cur))
                    return
                cur = t
                if kind == "p":
                    blk = (kk, t) if mode == M_ROW else (t, kk)
                    want = ST_DONE
                else:
                    blk = (fx, t) if mode == M_ROW else (t, fx)
                    want = kk
                out["decrements"] += 1
                if (step[blk] == want and blk not in busy
                        and blk not in final):
                    busy.add(blk)
                    out["released"] += 1
                    n_made += 1
                    if kind == "p":
                        spawn("p", blk)
                    else:
                        spawn("u", (blk[0], blk[1], kk))

        def after_diag(kk: int) -> None:
            out["lu0"] += 1
            busy.discard((kk, kk))
            final.add((kk, kk))
            spawn("r", ("s", "p", M_ROW, kk, 0, kk))
            spawn("r", ("s", "p", M_COL, kk, 0, kk))

        def after_panel(ii: int, jj: int) -> None:
            out["fwd" if ii < jj else "bdiv"] += 1
            busy.discard((ii, jj))
            final.add((ii, jj))
            if ii < jj:  # fwd(kk=ii, jj): the updates of column jj
                frow[ii] |= 1 << jj
                spawn("r", ("s", "u", M_COL, ii, jj, ii))
            else:        # bdiv(ii, kk=jj): the updates of row ii
                fcol[jj] |= 1 << ii
                spawn("r", ("s", "u", M_ROW, jj, ii, jj))

        def after_update(ii: int, jj: int, kk: int) -> None:
            out["bmod"] += 1
            out["decrements"] += 1
            if (ii, jj) not in made:
                made.add((ii, jj))
                out["fill_blocks"] += 1
            nxt = self.first_step(ii, jj, kk)
            step[ii, jj] = nxt
            busy.discard((ii, jj))
            if nxt != ST_DONE:
                if (ii, nxt) in final and (nxt, jj) in final:
                    busy.add((ii, jj))
                    out["released"] += 1
                    spawn("u", (ii, jj, nxt))
            elif ii == jj:
                busy.add((ii, jj))
                out["released"] += 1
                spawn("r", ("d", ii))
            elif (min(ii, jj),) * 2 in final:
                busy.add((ii, jj))
                out["released"] += 1
                spawn("p", (ii, jj))

        fire = (("p", panel_width, after_panel, "panel"),
                ("u", update_width, after_update, "bmod"))
        announced = {"p": 0, "u": 0}
        while ring or lanes["p"] or lanes["u"]:
            for name, width, done, key in fire:
                lane = lanes[name]
                if lane and (not ring or len(lane) >= 2 * width):
                    take = lane[:width]
                    del lane[:width]
                    pre = min(announced[name], len(take))
                    announced[name] = min(len(lane), width)
                    if log is not None:
                        log.append((name, take, pre, announced[name]))
                    for t in take:
                        done(*t)
                    live -= len(take)
                    out[key + "_rounds"] += 1
                    out[key + "_prefetched"] += pre
                    break
            else:
                e = ring.pop()
                if log is not None:
                    log.append(e)
                if e[0] == "d":
                    after_diag(e[1])
                else:
                    scan(*e[1:])
                live -= 1
        out["live_rows_max"] = hw
        return out

    # -- device side --

    def next_bit(self, k, base_a, base_b, after):
        """The lowest bit above ``after`` set in the mask at ``base_a``
        (and in the one at ``base_b``, where given); ``n`` if none.
        Branch-free: every word masked, the lowest that keeps a bit
        selected, one bit search on it."""
        lo = after + 1
        word, at = jnp.int32(0), jnp.int32(0)
        for w in reversed(range(self.nw)):
            x = k.value(base_a + w)
            if base_b is not None:
                x = x & k.value(base_b + w)
            sh = jnp.clip(lo - 32 * w, 0, 32)  # bits below sh do not count
            x = x & jnp.where(sh >= 32, jnp.int32(0),
                              jnp.left_shift(jnp.int32(-1),
                                             jnp.minimum(sh, 31)))
            word = jnp.where(x != 0, x, word)
            at = jnp.where(x != 0, 32 * w, at)
        return jnp.where(word != 0, at + _lowest_bit(word), self.n)

    def word_at(self, ii, jj):
        return self.blk_base + ii * self.n + jj

    @staticmethod
    def slot(word):
        return word >> SLOT_SHIFT

    @staticmethod
    def _bump(k, slot, by=1) -> None:
        k.set_value(slot, k.value(slot) + by)

    def _range(self, k, kind: int, mode: int, kk, fx, cur) -> None:
        k.spawn(kind, [mode, kk, fx, cur], nargs=4)

    def after_diag(self, k, kk) -> None:
        """``lu0(kk)`` has stored: the block is final; leave the ranges of
        its row's and its column's panel blocks."""
        idx = self.word_at(kk, kk)
        w = k.value(idx)
        k.set_value(idx, (w & ~(B_BUSY | B_FRESH)) | B_FINAL | B_HAS)
        self._bump(k, V_DIAG)
        self._range(k, K_SCANP, M_ROW, kk, 0, kk)
        self._range(k, K_SCANP, M_COL, kk, 0, kk)

    def after_panel(self, k, ii, jj) -> None:
        """Panel block ``(ii, jj)`` has stored (``fwd`` where ``ii < jj``,
        else ``bdiv``): it is final; leave the range of the updates it
        feeds (its column's, or its row's)."""
        idx = self.word_at(ii, jj)
        w = k.value(idx)
        k.set_value(idx, (w & ~(B_BUSY | B_FRESH)) | B_FINAL | B_HAS)
        fwd = ii < jj
        self._bump(k, jnp.where(fwd, V_ROW, V_COL))
        kk, t = jnp.minimum(ii, jj), jnp.maximum(ii, jj)
        bit = (jnp.where(fwd, self.frow_base, self.fcol_base)
               + kk * self.nw + (t >> 5))
        k.set_value(bit, k.value(bit) | jnp.left_shift(jnp.int32(1), t & 31))
        self._range(k, K_SCANU, jnp.where(fwd, M_COL, M_ROW), kk, t, kk)

    def after_update(self, k, ii, jj, kk) -> None:
        """``bmod(ii, jj, kk)`` has stored: move the block to its next
        step and make that step's task if its inputs are final - another
        update, or, with no step left, the block's own ``lu0`` (on the
        diagonal) or panel task (if its diagonal block is final)."""
        n = self.n
        idx = self.word_at(ii, jj)
        w = k.value(idx)
        nxt = self.next_bit(k, self.row_base + ii * self.nw,
                            self.col_base + jj * self.nw, kk)
        d = jnp.minimum(ii, jj)
        more = nxt < d
        st = jnp.where(more, nxt, ST_DONE)
        base = (w & ~(ST_MASK | B_BUSY | B_FRESH)) | B_HAS | st
        # The two operands of the next update; with no step left, the
        # diagonal block the panel task reads (twice).
        fa = k.value(self.word_at(jnp.where(more, ii, d),
                                  jnp.where(more, nxt, d)))
        fb = k.value(self.word_at(jnp.where(more, nxt, d),
                                  jnp.where(more, jj, d)))
        both = ((fa & fb) & B_FINAL) != 0
        upd = more & both
        diag = jnp.logical_not(more) & (ii == jj)
        pan = jnp.logical_not(more) & (ii != jj) & both
        go = upd | diag | pan
        neww = jnp.where(go, base | B_BUSY, base)
        k.set_value(idx, neww)
        self._bump(k, V_UPD)
        self._bump(k, V_FILL,
                   ((w & (B_HAS | B_FRESH)) == 0).astype(jnp.int32))
        StepPlan.count(k, 1, go.astype(jnp.int32))

        @pl.when(upd)
        def _():
            k.spawn(K_UPDATE, [ii, jj, nxt, neww], nargs=4)

        @pl.when(pan)
        def _():
            k.spawn(K_PANEL, [ii, jj, neww], nargs=3)

        @pl.when(diag)
        def _():
            k.spawn(K_DIAG, [ii, neww], nargs=2)

    def scan(self, ctx, kind: int) -> None:
        """A range descriptor, popped: walk the mask from the cursor, make
        at most ``CHUNK`` tasks (``kind`` ``K_SCANP``: panel tasks of step
        ``kk`` whose updates are done; ``K_SCANU``: updates of step ``kk``
        whose block has reached it; the mask it walks holds the other
        operands that are final), and put the range back where tasks may
        be left."""
        n = self.n
        mode, kk, fx, cur0 = (ctx.arg(i) for i in range(4))
        by_row = mode == M_ROW
        panels = kind == K_SCANP
        # the pattern's masks for a step's panel blocks; for the updates a
        # panel block feeds, the other family's blocks that are final
        mbase = kk * self.nw + (
            jnp.where(by_row, self.row_base, self.col_base) if panels
            else jnp.where(by_row, self.frow_base, self.fcol_base))
        want = jnp.int32(ST_DONE) if panels else kk

        def cond(c):
            cur, made, _ = c
            return (cur < n) & (made < CHUNK)

        def body(c):
            cur, made, tests = c
            t = self.next_bit(ctx, mbase, None, cur)
            valid = t < n
            tc = jnp.minimum(t, n - 1)
            if panels:
                bi, bj = jnp.where(by_row, kk, tc), jnp.where(by_row, tc, kk)
            else:
                bi, bj = jnp.where(by_row, fx, tc), jnp.where(by_row, tc, fx)
            idx = self.word_at(bi, bj)
            w = ctx.value(idx)
            ok = valid & ((w & (ST_MASK | B_BUSY | B_FINAL)) == want)

            @pl.when(ok)
            def _():
                ctx.set_value(idx, w | B_BUSY)
                if panels:
                    ctx.spawn(K_PANEL, [bi, bj, w | B_BUSY], nargs=3)
                else:
                    ctx.spawn(K_UPDATE, [bi, bj, kk, w | B_BUSY], nargs=4)

            return (jnp.where(valid, t, n), made + ok.astype(jnp.int32),
                    tests + valid.astype(jnp.int32))

        cur, made, tests = jax.lax.while_loop(
            cond, body, (cur0, jnp.int32(0), jnp.int32(0)))
        StepPlan.count(ctx, tests, made)
        self._bump(ctx, V_SCANS)
        left = (cur < n) & (self.next_bit(ctx, mbase, None, cur) < n)

        @pl.when(left)
        def _():
            self._range(ctx, kind, mode, kk, fx, cur)
