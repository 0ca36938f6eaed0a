"""forasync device tier: tile loops lowered onto the batch-lane dispatcher.

The reference's flagship data-parallel construct - forasync1D/2D/3D with
flat tiling and dist-func locale placement (src/hclib.c:158-416,
inc/hclib-forasync.h) - rendered for the megakernel: every tile of the
iteration space becomes one task descriptor, and because all tiles of one
loop share one body, a tile IS a same-kind batch - the whole loop lowers
straight onto the PR 3 per-F_FN batch lanes. Each batch round pops up to
``width`` tile descriptors and runs ONE tiled Pallas body over the group,
with the cross-round double-buffered operand prefetch riding underneath
(a tile's operand slab is exactly the entries-behind-head pattern the
prefetch pipeline targets), so per-task scalar dispatch - the ring pop +
``lax.switch`` per descriptor that dominates map-style loops - is paid
once per ROUND instead of once per tile.

The lowering is organized around **slab pipelines**: a ``TileKernel``
declares its operand slabs (windows of named HBM data buffers addressed
by the tile's loop offsets), a pure compute function from loaded slab
values to output slab values, and its output slabs. From that one
declaration the tier derives BOTH dispatch spellings:

- the scalar-tier kernel (DMA in -> compute -> DMA out, one tile per
  ``lax.switch`` dispatch) - the bit-identity reference arm, and
- the batched body (all live slots' loads in flight before the first
  wait; the prospective next batch's slabs prefetched into the other
  VMEM half during this round's compute; one store wave) plus its
  ``drain`` callback, so the scheduler can retire in-flight prefetches
  when it exits with lane entries unrun (fuel, quiesce).

On a mesh, placement is DATA, not code: a dist-func or JSON placement
descriptor (runtime/locality.py ``MeshPlacement``, resolved against
``locality_graphs/*.json``) maps each flat tile to a device, seeding the
per-device ready rings of the sharded/resident runners; the machine
graph additionally orders the steal scan near-neighbors-first
(``steal_hop_order``), so a skewed or stale placement degrades into
recoverable work stealing instead of a wrong or wedged run.

Tiles of ONE step are successor-free descriptors whose kernels only
read their args and write disjoint output slabs, so they are migratable
by construction; on the mesh every device runs the loop over a
replicated input and writes its executed tiles into its own output copy,
and the host sums the per-device outputs (each tile executes exactly once
mesh-wide, and output buffers are required to start zero).

Two modes, the reference's two (src/hclib.c:158-416). FLAT stages one
descriptor a tile on the host, so the task table holds every tile.
RECURSIVE stages ONE range descriptor and makes the tiles on the device:
a split kind on the scalar tier halves the widest dimension that is
still more than one tile long, at a tile boundary (the one nearest the
midpoint, so every piece is whole tiles and a power-of-two tile count
splits exactly where the reference does), and spawns its two halves
until a half is one tile - the same ``[flat, lo0, lo1, lo2]`` descriptor
FLAT stages, through the same batch lane and the same body. The lane
fires as soon as it holds two batches (``BatchSpec.fire_at``), so the
splitter is paced by the tiles' consumer and the table holds the LIVE
set - two batches of tiles and a split a level of the recursion - however
many tiles the loop has (``info["forasync"]["live_rows_max"]``).

Time steps (PR 51). ``TileKernel(steps=, awaits=)`` makes the loop
``steps`` time steps in ONE launch, a tile of step t+1 awaiting the tiles
of step t at the offsets ``awaits`` names (its own among them): tasks
made on the device that wait for each other. Step 0 is the splitter's. A
tile of a later step does not exist until the last tile it awaits has
stored: a finishing tile counts down, in the kernel's value slots, every
tile of the next step that awaits it and ``spawn``s the one that reaches
zero, straight onto the lane (``StepPlan``; the descriptor carries its
step as a fifth word, by which a slab picks its plane). There is no
barrier between steps: the lane pops in the order tiles were released,
the splitter is held back while the lane holds two batches, and the
front runs skewed through several steps at once
(``info["forasync"]``: ``released``, ``decrements``, ``mixed_rounds``,
``step_skew_max``). The table is sized from the schedule replayed on the
host (``StepPlan.simulate``), and ``analysis.check_tile_windows`` proves
over the concrete tile space that every tile whose windows meet a store
of the next step is among the tiles that store's tile awaits.

Device-path constraints (explicit ``ValueError``\\ s):

- bounds must divide exactly by the tile (slab shapes are static; the
  reference's ragged last tile would need dynamic DMA sizes);
- a FLAT loop needs a table row a tile: one with more tiles than the
  table takes is refused, naming RECURSIVE;
- RECURSIVE runs on one device (a placement seeds per-device rings from
  flat tiles);
- a loop of several steps runs RECURSIVE on one device, its lane FIFO
  (``prefetch=True``); what it awaits is symmetric (with every offset its
  opposite) and includes its own tile, which is what lets two parities
  of countdowns serve any number of steps.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.env import env_int
from ..runtime.forasync import FLAT, RECURSIVE
from ..runtime.locality import MeshPlacement, resolve_placement
from ..runtime.spans import span
from .descriptor import TaskGraphBuilder
from .megakernel import BatchSpec, Megakernel, SmemError, _batch_stub

__all__ = [
    "Slab",
    "TileKernel",
    "tile_grid",
    "tile_args",
    "seed_tiles",
    "place_tiles",
    "make_forasync_megakernel",
    "run_forasync_device",
    "seed_root",
    "split_plan",
    "StepPlan",
    "FA_TILE",
    "FA_SPLIT",
]

# The tile kernel's table index: the tier builds one loop body per
# kernel, so the id is fixed. A RECURSIVE build adds the split kind.
FA_TILE = 0
FA_SPLIT = 1


# ------------------------------------------------------------- tiling math


def tile_grid(
    bounds: Sequence, tile: Sequence
) -> Tuple[List[Tuple[int, int]], List[int], List[int], int]:
    """Normalize (bounds, tile) into (dims, tile_dims, tile_counts,
    total) with the EXACT-DIVISION constraint the device tier needs:
    slab shapes are static per kernel, so a ragged last tile (which the
    host tier handles by clamping) is refused with a sizing hint."""
    dims: List[Tuple[int, int]] = []
    for b in bounds:
        if isinstance(b, int):
            dims.append((0, b))
        else:
            lo, hi = b
            dims.append((int(lo), int(hi)))
    if not 1 <= len(dims) <= 3:
        raise ValueError("forasync supports 1-3 dimensions")
    if isinstance(tile, int):
        tile_dims = [tile] * len(dims)
    else:
        tile_dims = [int(t) for t in tile]
    if len(tile_dims) != len(dims):
        raise ValueError("tile rank must match loop rank")
    counts = []
    for (lo, hi), t in zip(dims, tile_dims):
        n = hi - lo
        if t < 1 or n < 1:
            raise ValueError(f"empty dimension or tile: {(lo, hi)} / {t}")
        if n % t:
            raise ValueError(
                f"device forasync tiles must divide the bounds exactly "
                f"(dimension {(lo, hi)} is {n} long, tile {t}): pad the "
                "iteration space or pick a dividing tile"
            )
        counts.append(n // t)
    return dims, tile_dims, counts, math.prod(counts)


def tile_args(
    dims: Sequence[Tuple[int, int]],
    tile_dims: Sequence[int],
    counts: Sequence[int],
    flat: int,
) -> List[int]:
    """Descriptor args of flat tile ``flat``: ``[flat, lo0, lo1, lo2]``
    (trailing los zero below rank 3) - the lo corner in LOOP coordinates;
    slab index functions add their own data-layout offsets."""
    idx = []
    rem = flat
    for c in reversed(list(counts)):
        idx.append(rem % c)
        rem //= c
    idx.reverse()
    los = [lo + i * t for (lo, _), t, i in zip(dims, tile_dims, idx)]
    return [flat] + los + [0] * (3 - len(los))


def seed_tiles(
    builder: TaskGraphBuilder,
    bounds: Sequence,
    tile: Sequence,
    fn: int = FA_TILE,
) -> int:
    """Add one descriptor per flat tile (flat order); returns the total."""
    dims, tile_dims, counts, total = tile_grid(bounds, tile)
    for flat in range(total):
        builder.add(fn, args=tile_args(dims, tile_dims, counts, flat))
    return total


def place_tiles(
    builders: Sequence[TaskGraphBuilder],
    bounds: Sequence,
    tile: Sequence,
    placement,
    fn: int = FA_TILE,
) -> List[int]:
    """Seed per-device ready rings from a placement: every flat tile's
    descriptor lands in ``builders[device_of(flat)]``. ``placement`` is
    anything ``runtime.locality.resolve_placement`` accepts (descriptor
    object / dict / JSON path / dist-func). Returns the per-device tile
    counts - totals are conserved by construction (each flat index is
    placed exactly once), which the placement acceptance pins down."""
    ndev = len(builders)
    dims, tile_dims, counts, total = tile_grid(bounds, tile)
    p = resolve_placement(placement, ndev=ndev)
    dev_of = p.device_of if isinstance(p, MeshPlacement) else (
        lambda flat, tot: p(len(dims), flat, tot)
    )
    out = [0] * ndev
    for flat in range(total):
        d = int(dev_of(flat, total))
        if not 0 <= d < ndev:
            raise ValueError(
                f"placement sent tile {flat} to device {d} "
                f"(mesh has {ndev})"
            )
        builders[d].add(fn, args=tile_args(dims, tile_dims, counts, flat))
        out[d] += 1
    return out


# ------------------------------------------------------ recursive split


def _widest(extents: Sequence[int], tile_dims: Sequence[int]) -> int:
    """The dimension a piece of ``extents`` tiles splits: the longest (in
    loop indices) that is more than one tile, the first of equals, as
    runtime/forasync.py's ``_spawn_recursive`` picks it; -1 for a tile."""
    wdim, widest = -1, 0
    for d, (n, t) in enumerate(zip(extents, tile_dims)):
        if n > 1 and n * t > widest:
            wdim, widest = d, n * t
    return wdim


def split_plan(bounds: Sequence, tile: Sequence) -> Dict[str, int]:
    """What RECURSIVE mode does to ``(bounds, tile)``, on the host:
    ``tiles``, ``splits`` (a binary tree's inner nodes: tiles - 1) and
    ``depth`` (splits from the root to the deepest tile). The ring pops
    newest first, so the live set is one pending half a level of that
    depth, the piece in hand with its two halves, and what the lane
    holds."""
    _, tile_dims, counts, total = tile_grid(bounds, tile)

    def depth(ext) -> int:
        d = _widest(ext, tile_dims)
        if d < 0:
            return 0
        big = list(ext)
        big[d] = ext[d] - ext[d] // 2
        return 1 + depth(big)

    return {"tiles": total, "splits": total - 1, "depth": depth(counts)}


def _split_kernel(dims, tile_dims, counts) -> Callable:
    """The split kind of one tile space. A range descriptor's args are
    its piece in TILE units, ``[lo0, hi0, lo1, hi1, lo2, hi2]`` (unused
    dimensions ``[0, 1]``); each half goes back on the ring as a range,
    or - one tile long everywhere - as that tile's FLAT descriptor."""
    nd = len(dims)
    los = [lo for lo, _ in dims] + [0] * (3 - nd)
    tds = list(tile_dims) + [1] * (3 - nd)
    cts = list(counts) + [1] * (3 - nd)

    def kernel(ctx) -> None:
        lo = [ctx.arg(2 * d) for d in range(3)]
        hi = [ctx.arg(2 * d + 1) for d in range(3)]
        n = [h - l for l, h in zip(lo, hi)]
        # The widest over-tile dimension, the first of equals.
        wdim, widest = jnp.int32(0), jnp.int32(0)
        for d in range(nd):
            ext = jnp.where(n[d] > 1, n[d] * tds[d], 0)
            wdim = jnp.where(ext > widest, d, wdim)
            widest = jnp.maximum(ext, widest)
        mid = [
            jnp.where(wdim == d, lo[d] + n[d] // 2, hi[d])
            for d in range(3)
        ]
        # The upper half first: the ring pops newest first, so the lower
        # half splits next and tiles reach the lane in ascending order
        # along every dimension.
        for upper in (True, False):
            plo = [
                jnp.where((wdim == d) & upper, mid[d], lo[d])
                for d in range(3)
            ]
            phi = [
                jnp.where((wdim == d) & jnp.logical_not(upper), mid[d],
                          hi[d])
                for d in range(3)
            ]
            leaf = functools.reduce(
                jnp.logical_and, [h - l == 1 for l, h in zip(plo, phi)]
            )
            flat = (plo[0] * cts[1] + plo[1]) * cts[2] + plo[2]
            tile = [flat] + [
                los[d] + plo[d] * tds[d] if d < nd else 0
                for d in range(3)
            ]
            rng = [plo[0], phi[0], plo[1], phi[1], plo[2], phi[2]]
            ctx.spawn(
                jnp.where(leaf, FA_TILE, FA_SPLIT),
                [jnp.where(leaf, a, b)
                 for a, b in zip(tile + [0, 0], rng)],
            )

    return kernel


def seed_root(builder: TaskGraphBuilder, bounds: Sequence,
              tile: Sequence) -> int:
    """RECURSIVE's one host descriptor: the whole space as a range (or,
    for a loop of one tile, that tile). Returns the tile count."""
    dims, tile_dims, counts, total = tile_grid(bounds, tile)
    if total == 1:
        builder.add(FA_TILE, args=tile_args(dims, tile_dims, counts, 0))
    else:
        cts = list(counts) + [1] * (3 - len(counts))
        builder.add(FA_SPLIT, args=[0, cts[0], 0, cts[1], 0, cts[2]])
    return total


# ----------------------------------------------------------- time steps
#
# A loop of ``steps`` time steps (``TileKernel(steps=, awaits=)``) keeps a
# countdown a (step parity, tile) in the kernel's value slots. A tile that
# has stored decrements the countdown of every tile of the NEXT step that
# awaits it, and ``spawn``s the one whose countdown reaches zero, straight
# onto the lane; step 0 is the splitter's. Two parities are enough: tile y
# of step t+2 awaits tile x of step t+1 for every neighbour y of x, so
# nobody touches x's countdown for step t+3 before (t+1, x) was released
# and its countdown re-armed. A countdown word holds what it re-arms to
# above what is left: ``need << AW_SHIFT | left``.

AW_SHIFT = 8
AW_MASK = (1 << AW_SHIFT) - 1
# Value slots: the loop's counters, then the countdowns from AW_BASE.
V_RELEASED, V_DECREMENTS, V_MIXED, V_SKEW = 0, 1, 2, 3
AW_BASE = 8


class StepPlan:
    """What the steps of one tile space need on the device and on the
    host: the release a finished tile runs (``release`` / ``round`` /
    ``count``, traced into the tile kind's bodies), the countdowns'
    presets, and the schedule itself replayed on the host
    (``simulate``), which sizes the table."""

    def __init__(self, tk: "TileKernel", dims, tile_dims, counts) -> None:
        nd = len(dims)
        self.tk = tk
        self.nd = nd
        self.steps = tk.steps
        self.counts = list(counts)
        self.total = math.prod(counts)
        self.lo = [lo for lo, _ in dims]
        self.hi = [hi for _, hi in dims]
        self.td = list(tile_dims)
        stride = [math.prod(counts[d + 1:]) for d in range(nd)]
        if any(len(o) != nd for o in tk.awaits):
            raise ValueError(
                f"awaits {tk.awaits} are not offsets of a {nd}-D tile space"
            )
        # (offset, the same in flat tile indices), in the awaits' order.
        self.succ = [
            (o, sum(x * st for x, st in zip(o, stride))) for o in tk.awaits
        ]
        self.num_values = AW_BASE + 2 * self.total
        self._presets: Optional[np.ndarray] = None

    # -- host side --

    def near(self, idx: Sequence[int]) -> List[int]:
        """Flat indices of the tiles of the step before that tile ``idx``
        awaits, which by symmetry are also the tiles of the step after
        that await it: the offsets that stay inside the grid, in the
        awaits' order."""
        flat = 0
        for i, c in zip(idx, self.counts):
            flat = flat * c + i
        return [
            flat + dflat for o, dflat in self.succ
            if all(0 <= i + x < c for i, x, c in zip(idx, o, self.counts))
        ]

    def presets(self) -> np.ndarray:
        """The value slots a launch starts from: counters zero, every
        countdown of both parities armed. One array, made once: a run
        copies it into its upload and never writes it."""
        if self._presets is None:
            need = [len(self.near(idx))
                    for idx in np.ndindex(*self.counts)]
            vals = np.zeros(self.num_values, np.int32)
            vals[AW_BASE:] = np.tile(
                np.array(need, np.int32) * (AW_MASK + 2), 2)
            vals.setflags(write=False)
            self._presets = vals
        return self._presets

    def simulate(self, width: int) -> Dict[str, int]:
        """The launch replayed on the host, descriptor by descriptor, as
        the scheduler runs it: the ready ring popped newest first, the
        splitter's halves (upper first), a tile routed to its lane at the
        pop, the lane fired FIFO at an empty ring or at ``2 * width``
        entries, a released tile pushed on the lane's tail by the batch
        that released it while that batch's rows are still live
        (``width=0``: everything through the ring). Returns the loop's
        counters as ``info["forasync"]`` reports them, ``live_rows_max``
        among them: the table is sized from it, and the tests hold the
        kernel to all of them."""
        nd, counts, steps = self.nd, self.counts, self.steps
        coords = list(np.ndindex(*counts))
        need = [len(self.near(i)) for i in coords]
        left = [list(need), list(need)]
        ring: List[Any] = []  # ("s", extents lo/hi) or ("t", step, flat)
        lane: List[Tuple[int, int]] = []
        out = dict(released=0, decrements=0, mixed_rounds=0,
                   step_skew_max=0, live_rows_max=0, batch_rounds=0,
                   batch_tasks=0, splits=0)
        live = 1
        hw = 1
        if self.total == 1:
            ring.append(("t", 0, 0))
        else:
            ring.append(("s", [0] * nd, list(counts)))

        def spawn(entry, direct: bool) -> None:
            nonlocal live, hw
            live += 1
            hw = max(hw, live)
            (lane if direct else ring).append(
                entry[1:] if direct else entry
            )

        def finish(step: int, flat: int) -> None:
            if step + 1 >= steps:
                return
            cnt = left[(step + 1) & 1]
            for n in self.near(coords[flat]):
                out["decrements"] += 1
                cnt[n] -= 1
                if cnt[n] == 0:
                    cnt[n] = need[n]
                    out["released"] += 1
                    spawn(("t", step + 1, n), bool(width))

        def split(lo, hi) -> None:
            ext = [h - l for l, h in zip(lo, hi)]
            d = _widest(ext, self.td)
            mid = lo[d] + ext[d] // 2
            for upper in (True, False):
                plo, phi = list(lo), list(hi)
                if upper:
                    plo[d] = mid
                else:
                    phi[d] = mid
                if all(h - l == 1 for l, h in zip(plo, phi)):
                    flat = 0
                    for i, c in zip(plo, counts):
                        flat = flat * c + i
                    spawn(("t", 0, flat), False)
                else:
                    spawn(("s", plo, phi), False)
            out["splits"] += 1

        while ring or lane:
            if lane and (not ring or len(lane) >= 2 * width):
                take = lane[:width]
                del lane[:width]
                for step, flat in take:
                    finish(step, flat)
                live -= len(take)
                st = [s for s, _ in take]
                out["batch_rounds"] += 1
                out["batch_tasks"] += len(take)
                out["mixed_rounds"] += max(st) != min(st)
                out["step_skew_max"] = max(
                    out["step_skew_max"], max(st) - min(st))
                continue
            e = ring.pop()
            if e[0] == "s":
                split(e[1], e[2])
                live -= 1
            elif width:
                lane.append(e[1:])
            else:
                finish(e[1], e[2])
                live -= 1
        out["live_rows_max"] = hw
        return out

    # -- device side --

    def release(self, k, a, live):
        """Tile ``a`` (its five arg words) has stored: count down every
        tile of the next step that awaits it and spawn those that reach
        zero, through ``k`` (a ``KernelContext``). Nothing happens where
        ``live`` is false or ``a`` is of the last step. Returns the
        decrements and the releases made, traced."""
        nxt = a[4] + 1
        go = live & (nxt < self.steps)
        plane = AW_BASE + (nxt & 1) * self.total
        dec = rel = jnp.int32(0)
        for o, dflat in self.succ:
            nlo = [a[1 + d] + o[d] * self.td[d] for d in range(self.nd)]
            ok = go
            for d in range(self.nd):
                if o[d] < 0:
                    ok = ok & (nlo[d] >= self.lo[d])
                elif o[d] > 0:
                    ok = ok & (nlo[d] < self.hi[d])
            nflat = a[0] + dflat
            slot = plane + jnp.where(ok, nflat, 0)
            w = k.value(slot) - 1
            fire = ok & ((w & AW_MASK) == 0)

            @pl.when(ok)
            def _(slot=slot, w=w, fire=fire):
                k.set_value(
                    slot, jnp.where(fire, (w >> AW_SHIFT) * (AW_MASK + 2), w)
                )

            @pl.when(fire)
            def _(nflat=nflat, nlo=nlo):
                k.spawn(
                    FA_TILE,
                    [nflat] + nlo + [0] * (3 - self.nd) + [nxt],
                    nargs=5,
                )

            dec = dec + ok.astype(jnp.int32)
            rel = rel + fire.astype(jnp.int32)
        return dec, rel

    @staticmethod
    def count(ctx, dec, rel, mixed=None, skew=None) -> None:
        """Add a dispatch's releases to the loop's counters."""
        ctx.set_value(V_DECREMENTS, ctx.value(V_DECREMENTS) + dec)
        ctx.set_value(V_RELEASED, ctx.value(V_RELEASED) + rel)
        if mixed is not None:
            ctx.set_value(V_MIXED, ctx.value(V_MIXED) + mixed)
            ctx.set_value(V_SKEW, jnp.maximum(ctx.value(V_SKEW), skew))

    def round(self, ctx, args_of) -> None:
        """A batch round's releases, slot by slot in lane order, and what
        the round says of the front: whether its live slots held tiles of
        more than one step, and how many steps apart."""
        dec = rel = jnp.int32(0)
        lo = hi = args_of(0)[4]  # slot 0 is live in every fired round
        for b in range(ctx.width):
            a = args_of(b)
            d, r = self.release(ctx.slot_ctx(b), a, ctx.live(b))
            dec, rel = dec + d, rel + r
            lo = jnp.where(ctx.live(b), jnp.minimum(lo, a[4]), lo)
            hi = jnp.where(ctx.live(b), jnp.maximum(hi, a[4]), hi)
        self.count(ctx, dec, rel, (hi > lo).astype(jnp.int32), hi - lo)


# ---------------------------------------------------------- slab pipeline


class Slab:
    """One operand (or output) window of a named HBM data buffer.

    ``index(args)`` receives the tile's descriptor args ``(flat, lo0,
    lo1, lo2)`` as traced int32 and returns the indexer tuple applied as
    ``data_ref.at[...]`` - scalars for picked leading axes, ``pl.ds``
    for sliced ones. ``shape`` is the (static) slab shape the DMA moves;
    it must agree with what the indexer selects.

    On the chip a window's last two dims must be whole (8, 128) tiles at
    tile-aligned offsets (wrap a dynamic offset in ``pl.multiple_of`` so
    the compiler can prove it): Mosaic refuses an unaligned
    ``memref_slice``, which the interpreter never notices. A loop that
    needs an unaligned neighbourhood loads the aligned superset and
    slices the loaded value in ``compute`` (``workloads.stencil_loop``
    does).

    A load may land in a window of a STAGING buffer several loads share
    (``into`` names one of the ``TileKernel``'s ``staging`` buffers, ``at``
    the window inside it, static and aligned like any other): the pieces
    of a neighbourhood that is no box - a tile and four halo strips
    without the corners, ``workloads.jacobi_loop`` - arrive by a DMA each
    and ``compute`` reads them as one value under the buffer's name."""

    def __init__(
        self,
        name: str,
        data: str,
        index: Callable[[Sequence], Tuple],
        shape: Tuple[int, ...],
        into: Optional[str] = None,
        at: Optional[Tuple] = None,
    ) -> None:
        if (into is None) != (at is None):
            raise ValueError(
                f"slab {name!r}: into= and at= come together (a staging "
                "buffer and the window of it this slab fills)"
            )
        self.name = name
        self.data = data
        self.index = index
        self.shape = tuple(int(s) for s in shape)
        self.into = into
        self.at = None if at is None else tuple(at)


class TileKernel:
    """The device body of a forasync tile loop, as a slab pipeline:
    ``compute`` maps loaded input-slab VALUES (dict keyed by slab name, or
    by staging buffer for the loads that share one) to output-slab values
    - pure jnp, no refs - and the tier derives the scalar kernel, the
    batched body, and its prefetch drain from the slab declarations. One
    ``TileKernel`` therefore has ONE arithmetic trace, which is what makes
    the scalar-vs-tile-tier bit-identity of the acceptance runs hold by
    construction.

    ``data_specs`` declares every named buffer the slabs touch (the
    megakernel's ``data_specs``); output buffers must be disjointly
    written across tiles (the same contract batch bodies always carry).

    ``steps`` > 1 makes the loop ``steps`` time steps in one launch (module
    docstring, "Time steps"): ``awaits`` lists, as offsets in TILE units,
    the tiles of the step before that a tile waits for - its own (the zero
    offset) among them, and with every offset its opposite, so the tiles
    a finishing tile releases are the ones at the same offsets. A slab's
    ``index`` then receives a fifth word, the tile's step, and picks the
    plane it reads or writes by it.
    """

    def __init__(
        self,
        loads: Sequence[Slab],
        stores: Sequence[Slab],
        compute: Callable[[Dict[str, Any]], Dict[str, Any]],
        data_specs: Dict[str, jax.ShapeDtypeStruct],
        name: str = "fa_tile",
        staging: Optional[Dict[str, Tuple[int, ...]]] = None,
        steps: int = 1,
        awaits: Sequence[Sequence[int]] = (),
    ) -> None:
        names = [s.name for s in list(loads) + list(stores)]
        if len(set(names)) != len(names):
            raise ValueError(f"slab names must be unique: {names}")
        for s in list(loads) + list(stores):
            if s.data not in data_specs:
                raise ValueError(
                    f"slab {s.name!r} targets undeclared buffer {s.data!r}"
                )
        self.staging = {
            k: tuple(int(n) for n in v) for k, v in (staging or {}).items()
        }
        for s in loads:
            if s.into is not None and s.into not in self.staging:
                raise ValueError(
                    f"slab {s.name!r} lands in undeclared staging buffer "
                    f"{s.into!r}"
                )
        if any(s.into is not None for s in stores):
            raise ValueError("only loads land in a staging buffer")
        self.loads = list(loads)
        self.stores = list(stores)
        self.compute = compute
        self.data_specs = dict(data_specs)
        self.name = name
        self.steps = int(steps)
        self.awaits = [tuple(int(x) for x in o) for o in awaits]
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if self.steps > 1:
            offs = set(self.awaits)
            zero = {o for o in offs if not any(o)}
            if not zero or len(offs) != len(self.awaits) or any(
                tuple(-x for x in o) not in offs for o in offs
            ):
                raise ValueError(
                    "a loop of several steps awaits its own tile (the zero "
                    "offset) and, with every offset, its opposite, each "
                    f"once: got {self.awaits}"
                )
            if len(offs) > AW_MASK:
                raise ValueError(
                    f"at most {AW_MASK} awaited tiles, got {len(offs)}"
                )
        # Output buffers (store targets): the mesh path requires them to
        # start zero so per-device copies combine by sum.
        self.out_names = sorted({s.data for s in self.stores})
        # Arg words a tile descriptor carries: a stepped one its step too.
        self.nargs = 5 if self.steps > 1 else 4

    def _dtype(self, slab: Slab):
        return self.data_specs[slab.data].dtype

    def _load_bufs(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """VMEM buffers the loads fill, name -> (shape, dtype): a load's
        own, or the staging buffer it shares."""
        bufs: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
        for s in self.loads:
            if s.into is None:
                bufs[s.name] = (s.shape, self._dtype(s))
            else:
                bufs[s.into] = (self.staging[s.into], self._dtype(s))
        return bufs

    @staticmethod
    def _load_dst(scratch, s: Slab, lead: Tuple = ()):
        """Where load ``s`` lands: its buffer (or its window of a staging
        buffer) behind the leading (half, slot) index ``lead``."""
        ref = scratch[f"fa_{s.into or s.name}"]
        idx = tuple(lead) + (s.at or ())
        return ref.at[idx] if idx else ref

    # -- scalar-tier spelling --

    def scalar_scratch(self) -> Dict[str, Any]:
        sc: Dict[str, Any] = {}
        for n, (shape, dt) in self._load_bufs().items():
            sc[f"fa_{n}"] = pltpu.VMEM(shape, dt)
        for s in self.stores:
            sc[f"fa_{s.name}"] = pltpu.VMEM(s.shape, self._dtype(s))
        sc["fa_lsem"] = pltpu.SemaphoreType.DMA((1,))
        sc["fa_ssem"] = pltpu.SemaphoreType.DMA((1,))
        return sc

    def scalar_kernel(self, ctx, release=None) -> None:
        a = tuple(ctx.arg(i) for i in range(self.nargs))
        lsem = ctx.scratch["fa_lsem"]
        ssem = ctx.scratch["fa_ssem"]
        # All loads in flight before the first wait (one sem counts all
        # streams - every start is matched by exactly one wait).
        for wait in (False, True):
            for s in self.loads:
                cp = pltpu.make_async_copy(
                    ctx.data[s.data].at[s.index(a)],
                    self._load_dst(ctx.scratch, s),
                    lsem.at[0],
                )
                (cp.wait if wait else cp.start)()
        ins = {n: ctx.scratch[f"fa_{n}"][...] for n in self._load_bufs()}
        outs = self.compute(ins)
        for s in self.stores:
            ctx.scratch[f"fa_{s.name}"][...] = outs[s.name]
        for wait in (False, True):
            for s in self.stores:
                cp = pltpu.make_async_copy(
                    ctx.scratch[f"fa_{s.name}"],
                    ctx.data[s.data].at[s.index(a)],
                    ssem.at[0],
                )
                (cp.wait if wait else cp.start)()
        if release is not None:
            release.count(ctx, *release.release(ctx, a, jnp.bool_(True)))

    # -- batch-tier spelling --

    def batch_scratch(self, width: int) -> Dict[str, Any]:
        sc: Dict[str, Any] = {}
        for n, (shape, dt) in self._load_bufs().items():
            # Double-buffered (leading 2): one half computes while the
            # tier's cross-round prefetch fills the other.
            sc[f"fa_{n}"] = pltpu.VMEM((2, width) + shape, dt)
        for s in self.stores:
            sc[f"fa_{s.name}"] = pltpu.VMEM((width,) + s.shape, self._dtype(s))
        # One DMA semaphore per (half, slot) counting every load stream
        # of that slot; one per slot for the store wave.
        sc["fa_lsem"] = pltpu.SemaphoreType.DMA((2, width))
        sc["fa_ssem"] = pltpu.SemaphoreType.DMA((width,))
        return sc

    def _slot_loads(self, ctx, buf, slot: int, a, wait: bool) -> None:
        """Start (or retire) every load copy of batch slot ``slot`` into
        operand half ``buf``; args ``a`` name the tile whose slabs move.
        Starts and waits use the same (src, dst, sem) triples under the
        same predicates, so each start has exactly one matching wait."""
        sem = ctx.scratch["fa_lsem"].at[buf, slot]
        for s in self.loads:
            cp = pltpu.make_async_copy(
                ctx.data[s.data].at[s.index(a)],
                self._load_dst(ctx.scratch, s, (buf, slot)),
                sem,
            )
            (cp.wait if wait else cp.start)()

    def batch_body(self, ctx, release=None) -> None:
        width = ctx.width
        buf = ctx.buf
        nargs = self.nargs

        def args_of(s):
            return tuple(ctx.arg(s, i) for i in range(nargs))

        # Phase 1: start operand copies for live slots the prefetch
        # didn't already cover.
        for b in range(width):
            @pl.when(ctx.live(b) & (jnp.int32(b) >= ctx.prefetched))
            def _(b=b):
                self._slot_loads(ctx, buf, b, args_of(b), wait=False)

        # Phase 2: the prospective NEXT batch's slabs start into the
        # other half now - they land under this round's compute, so the
        # next round opens without a single operand-DMA stall.
        obuf = 1 - buf
        for b in range(width):
            @pl.when(jnp.int32(b) < ctx.prefetch_count)
            def _(b=b):
                nxt = tuple(ctx.next_arg(b, i) for i in range(nargs))
                self._slot_loads(ctx, obuf, b, nxt, wait=False)

        # Phase 3: retire this round's loads (prefetched slots wait the
        # copies LAST round's phase 2 started into this half).
        for b in range(width):
            @pl.when(ctx.live(b))
            def _(b=b):
                self._slot_loads(ctx, buf, b, args_of(b), wait=True)

        # Phase 4: per-slot compute into the store staging.
        for b in range(width):
            @pl.when(ctx.live(b))
            def _(b=b):
                ins = {
                    n: ctx.scratch[f"fa_{n}"][buf, b]
                    for n in self._load_bufs()
                }
                outs = self.compute(ins)
                for s in self.stores:
                    ctx.scratch[f"fa_{s.name}"][b] = outs[s.name]

        # Phase 5: one store wave - all starts, then all waits, so
        # nothing is still in flight toward the output buffers when the
        # batch's completions run.
        def store_wave(wait: bool) -> None:
            for b in range(width):
                @pl.when(ctx.live(b))
                def _(b=b):
                    a = args_of(b)
                    sem = ctx.scratch["fa_ssem"].at[b]
                    for s in self.stores:
                        cp = pltpu.make_async_copy(
                            ctx.scratch[f"fa_{s.name}"].at[b],
                            ctx.data[s.data].at[s.index(a)],
                            sem,
                        )
                        (cp.wait if wait else cp.start)()

        store_wave(False)
        if release is not None:
            # A stepped loop's dependence releases, under the store wave:
            # they write SMEM only (countdowns, new rows, the lane's
            # tail), and no tile they make starts a DMA before the next
            # round, which opens after the waits below.
            release.round(ctx, args_of)
        store_wave(True)

    def batch_drain(self, ctx) -> None:
        """Retire an in-flight prefetch whose target entries will be
        spilled instead of batched (scheduler exit - fuel, quiesce): wait
        the same copies phase 2 started, so no DMA outlives the round
        loop and checkpoint export sees only settled buffers."""
        for b in range(ctx.width):
            @pl.when(jnp.int32(b) < ctx.prefetched)
            def _(b=b):
                a = tuple(ctx.arg(b, i) for i in range(self.nargs))
                self._slot_loads(ctx, ctx.buf, b, a, wait=True)


# ------------------------------------------------------------ megakernel


# What the compiler grants a kernel's VMEM unasked on every supported
# chip (the v5e's scoped default); a loop's slabs may ask for more.
VMEM_DEFAULT_BYTES = 16 << 20


def _vmem_bytes(tk: TileKernel, scratch: Dict[str, Any]) -> int:
    """The kernel's VMEM limit, from its slabs: the scratch the tier
    declared, and room for ``compute``'s values of one tile (a load slab
    and the shifted views the body cuts from it, a store slab), which the
    compiler keeps in VMEM too. Never under the default."""
    held = sum(
        math.prod(sp.shape) * jnp.dtype(sp.dtype).itemsize
        for sp in scratch.values()
        if getattr(sp, "memory_space", None) == pltpu.VMEM
    )
    one = sum(
        math.prod(shape) * jnp.dtype(dt).itemsize
        for shape, dt in list(tk._load_bufs().values())
        + [(sl.shape, tk._dtype(sl)) for sl in tk.stores]
    )
    return max(VMEM_DEFAULT_BYTES, held + 4 * one + (4 << 20))


def make_forasync_megakernel(
    tk: TileKernel,
    *,
    width: int = 0,
    prefetch: bool = True,
    capacity: Optional[int] = None,
    interpret: Optional[bool] = None,
    trace=None,
    checkpoint: Optional[bool] = None,
    quiesce_stride: Optional[int] = None,
    verify: Optional[bool] = None,
    space: Optional[Tuple[Sequence, Sequence]] = None,
) -> Megakernel:
    """Build the loop's megakernel. ``width=0`` is the scalar-dispatch
    arm (one tile per ``lax.switch`` round - the bit-identity reference);
    ``width>0`` routes the tile kind through the batch lanes, with the
    double-buffered operand prefetch on by default. ``space=(bounds,
    tile)`` makes it a RECURSIVE build of that tile space: the split
    kind beside the tile kind, a lane that fires at two batches, and by
    default a table of the live set (``split_plan``: a pending half a
    level, the piece in hand with its halves, the lane), at least 64
    rows; a FLAT build's default is 256. A loop of several steps
    (``tk.steps`` > 1; RECURSIVE only) also gets its ``StepPlan``
    (``mk.fa_plan``): the countdowns in the value slots, and a table of
    the live set its replayed schedule reaches, with an eighth to spare."""
    plan = None
    if tk.steps > 1:
        if space is None:
            raise ValueError(
                f"a loop of {tk.steps} steps runs mode=RECURSIVE: step 0 is "
                "the splitter's, and the countdowns are sized by the tile "
                "space the build names (space=)"
            )
        if width and not prefetch:
            raise ValueError(
                "a loop of several steps runs its tiles in the order they "
                "were released: its lane pops FIFO (prefetch=True)"
            )
    if space is not None:
        dims, tile_dims, counts, _ = tile_grid(*space)
        if tk.steps > 1:
            plan = StepPlan(tk, dims, tile_dims, counts)
    if capacity is None:
        if plan is not None:
            live = plan.simulate(width)["live_rows_max"]
            capacity = max(64, live + max(8, live // 8))
        else:
            capacity = 256 if space is None else max(
                64, split_plan(*space)["depth"] + 2 * width + 8
            )
    body, scalar = tk.batch_body, tk.scalar_kernel
    if plan is not None:
        body = functools.partial(body, release=plan)
        scalar = functools.partial(scalar, release=plan)
    if width:
        spec = BatchSpec(
            body,
            width=width,
            prefetch=prefetch,
            drain=tk.batch_drain if prefetch else None,
            fire_at=2 * width if space is not None else None,
        )
        kernels = [(tk.name, _batch_stub)]
        route = {tk.name: spec}
        scratch = tk.batch_scratch(width)
    else:
        kernels = [(tk.name, scalar)]
        route = None
        scratch = tk.scalar_scratch()
    if space is not None:
        kernels.append(("fa_split", _split_kernel(dims, tile_dims, counts)))
    mk = Megakernel(
        kernels=kernels,
        route=route,
        data_specs=tk.data_specs,
        scratch_specs=scratch,
        capacity=capacity,
        num_values=16 if plan is None else plan.num_values,
        succ_capacity=8,
        interpret=interpret,
        vmem_limit_bytes=_vmem_bytes(tk, scratch),
        trace=trace,
        checkpoint=checkpoint,
        quiesce_stride=quiesce_stride,
        verify=verify,
        read_only=[k for k in tk.data_specs if k not in tk.out_names],
    )
    # The tile space a RECURSIVE build splits, as tile_grid normalises
    # it; None for a FLAT build, which takes any.
    mk.fa_space = None if space is None else (dims, tile_dims)
    mk.fa_plan = plan
    # Schedule-independence claim: tiles write disjoint slabs, so any
    # pop order yields one output state. The tile SPACE isn't known
    # until a run names (bounds, tile) - run_forasync_device completes
    # the claim then; analysis/model.py certifies it lazily (K permuted
    # orders over the concrete space) for describe()/hclint.
    mk.si_claim = ("tile", tk, None, None)
    return mk


def _verify_default() -> bool:
    from ..analysis.findings import verify_default

    return verify_default()


def _default_width() -> int:
    """Batch width when the caller leaves it unset: 8, overridable
    process-wide with HCLIB_TPU_FORASYNC_WIDTH (>= 1; malformed values
    raise - a typo must not silently change the dispatch tier)."""
    w = env_int("HCLIB_TPU_FORASYNC_WIDTH", 8)
    if w < 1:
        raise ValueError(
            f"HCLIB_TPU_FORASYNC_WIDTH must be >= 1, got {w!r}"
        )
    return w


def run_forasync_device(
    tk: TileKernel,
    bounds: Sequence,
    tile: Sequence,
    data: Dict[str, np.ndarray],
    *,
    width: Optional[int] = None,
    prefetch: bool = True,
    placement=None,
    mesh=None,
    steal: bool = True,
    quantum: int = 64,
    window: int = 16,
    hop_order: Optional[Sequence[int]] = None,
    capacity: Optional[int] = None,
    interpret: Optional[bool] = None,
    trace=None,
    fuel: int = 1 << 22,
    mk: Optional[Megakernel] = None,
    mode: str = FLAT,
) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Run one forasync tile loop on the device tier to completion;
    returns ``(data_out, info)``.

    ``mode=FLAT`` stages a descriptor a tile; ``mode=RECURSIVE`` stages
    the one range and lets the device split it (module docstring), so the
    default table is sized to the live set, not to the tile count.
    ``info["forasync"]`` (and the kernel's ``stats_dict()``) says which:
    ``mode``, ``tiles``, ``splits``, ``capacity`` and ``live_rows_max``,
    the table's high-water mark by the kernel's own ``allocated``.

    A ``data`` buffer may be a numpy array (uploaded; the caller's array
    is untouched) or a ``jax.Array`` that stays on the chip: one the
    ``TileKernel`` only loads is read where it lies and is still the
    caller's, one it stores to (``tk.out_names``) is consumed and comes
    back in ``data_out`` (``Megakernel.run``'s ownership rule).

    Single device when ``placement`` is None. With a placement (and an
    optional ``mesh``; defaults to a CPU mesh sized by the placement),
    tiles seed the per-device ready rings through ``place_tiles``, inputs
    replicate, tile descriptors migrate through the bulk-synchronous
    steal exchange (scan ordered by the placement's machine graph unless
    ``hop_order`` overrides), and per-device output copies combine by
    sum - which requires every output buffer to start zero (checked).
    ``info['placement_counts']`` carries the seeded per-device counts."""
    w = _default_width() if width is None else int(width)
    dims, tile_dims, tcounts, total = tile_grid(bounds, tile)
    if set(data) != set(tk.data_specs):
        raise ValueError(
            f"data buffers {sorted(data)} != declared {sorted(tk.data_specs)}"
        )
    recursive = mode == RECURSIVE
    if recursive and placement is not None:
        raise ValueError(
            "mode=RECURSIVE runs on one device: a placement seeds the "
            "per-device rings from flat tiles (use mode=FLAT on a mesh)"
        )
    if tk.steps > 1 and not recursive:
        raise ValueError(
            f"a loop of {tk.steps} steps runs mode=RECURSIVE on one device: "
            "its tiles of step 1 and later are made on the device, behind "
            "the splitter's step 0"
        )
    # A RECURSIVE table is sized by the build, to the live set.
    cap = capacity or (None if recursive else max(64, total + 8))
    if mk is not None and getattr(mk, "verify", False) or (
        mk is None and _verify_default()
    ):
        # Whole-loop store-window race detection (hclib_tpu.analysis):
        # the slab index callables are pure Python, so the bounds known
        # HERE let the verifier prove pairwise disjointness over the
        # CONCRETE tile space - the strong form of the construction-time
        # synthetic check (witness: the two colliding tile coords).
        from ..analysis import check_tile_windows

        check_tile_windows(
            tk, bounds, tile,
            suppress=getattr(mk, "verify_suppress", ()) if mk else (),
        ).raise_errors()
    if mk is not None:
        # A prebuilt kernel OWNS the dispatch configuration: verify the
        # caller's width agrees, so a benchmark arm can never believe it
        # measured the batch tier while running a scalar build (or vice
        # versa) - the results would still be bit-identical, hiding the
        # swap.
        mk_width = (
            mk.batch_specs[0][1].width if mk.batch_specs else 0
        )
        if width is not None and int(width) != mk_width:
            raise ValueError(
                f"width={width} disagrees with the prebuilt megakernel "
                f"(its tile kind is "
                f"{f'batch-routed at width {mk_width}' if mk_width else 'scalar-dispatched'})"
            )
        want = (dims, tile_dims) if recursive else None
        if getattr(mk, "fa_space", None) != want:
            raise ValueError(
                f"mode={mode!r} over {want} disagrees with the prebuilt "
                f"megakernel, built for {getattr(mk, 'fa_space', None)} "
                "(make_forasync_megakernel's space=; None is a FLAT build)"
            )
        plan = getattr(mk, "fa_plan", None)
        if (plan.tk if plan else None) is not (tk if tk.steps > 1 else None):
            raise ValueError(
                f"the prebuilt megakernel was built for another loop's "
                f"steps than this TileKernel's ({tk.steps})"
            )
        kernel = mk
    else:
        try:
            kernel = make_forasync_megakernel(
                tk, width=w, prefetch=prefetch, capacity=cap,
                interpret=interpret, trace=trace,
                space=(bounds, tile) if recursive else None,
            )
        except SmemError as e:
            if recursive:
                raise
            raise ValueError(
                f"mode=FLAT stages one descriptor a tile and this loop has "
                f"{total}: {e} mode=RECURSIVE makes the tiles on the device "
                "and needs a table of its live set only."
            ) from e
    if placement is None and not recursive and total > kernel.capacity:
        raise ValueError(
            f"mode=FLAT stages one descriptor a tile: {total} tiles do not "
            f"fit a table of {kernel.capacity} rows. mode=RECURSIVE makes "
            "the tiles on the device and needs a table of its live set only."
        )
    # Complete the schedule-independence claim with the concrete tile
    # space this run names (make_forasync_megakernel stamps it
    # unbound). Re-stamped on EVERY run: a later call over a different
    # (bounds, tile) space must invalidate the previous certificate -
    # an index fn can alias at one size and not another - and the
    # model.py cache keys on the space, so describe() re-certifies.
    claim = getattr(kernel, "si_claim", None)
    if claim is not None and claim[0] == "tile":
        nb = tuple(
            tuple(b) if not isinstance(b, int) else b for b in bounds
        )
        nt = tuple(tile) if not isinstance(tile, int) else (tile,)
        if (claim[2], claim[3]) != (nb, nt):
            kernel.si_claim = ("tile", claim[1], nb, nt)
    if placement is None:
        with span("fa.seed"):
            b = TaskGraphBuilder()
            (seed_root if recursive else seed_tiles)(b, bounds, tile)
        plan = kernel.fa_plan
        with span("fa.run"):
            vals, data_out, info = kernel.run(
                b, data=dict(data), fuel=fuel,
                ivalues=None if plan is None else plan.presets(),
            )
        # info is the dict stats_dict() copies: the loop's own counters
        # ride both.
        info["forasync"] = {
            "mode": mode,
            "tiles": total * tk.steps,
            "splits": total - 1 if recursive else 0,
            "capacity": kernel.capacity,
            "live_rows_max": info["allocated"],
        }
        if plan is not None:
            # Tiles a dependence made, the decrements that led there,
            # batch rounds that held tiles of several steps and the most
            # steps one held apart: the kernel's own words (StepPlan).
            info["forasync"].update(
                steps=tk.steps,
                released=int(vals[V_RELEASED]),
                decrements=int(vals[V_DECREMENTS]),
                mixed_rounds=int(vals[V_MIXED]),
                step_skew_max=int(vals[V_SKEW]),
            )
        return data_out, info

    p = resolve_placement(placement)
    from ..parallel.mesh import cpu_mesh
    from .sharded import ShardedMegakernel

    if mesh is None:
        if not isinstance(p, MeshPlacement):
            raise ValueError(
                "a dist-func placement needs an explicit mesh= (a "
                "MeshPlacement knows its own device count)"
            )
        mesh = cpu_mesh(p.ndev, axis_name="q")
    ndev = int(np.prod(mesh.devices.shape))
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    pcounts = place_tiles(builders, bounds, tile, p)
    if hop_order is None and isinstance(p, MeshPlacement):
        hop_order = p.hop_order()
    for name in tk.out_names:
        if np.asarray(data[name]).any():
            raise ValueError(
                f"mesh forasync output buffer {name!r} must start zero: "
                "per-device copies combine by sum"
            )
    stacked = {
        k: np.broadcast_to(
            np.asarray(v), (ndev,) + np.asarray(v).shape
        ).copy()
        for k, v in data.items()
    }
    smk = ShardedMegakernel(kernel, mesh, migratable_fns=[FA_TILE])
    _, data_o, info = smk.run(
        builders, data=stacked, steal=steal, quantum=quantum,
        window=window, hop_order=hop_order,
    )
    out: Dict[str, np.ndarray] = {}
    for k, v in data_o.items():
        arr = np.asarray(v)
        out[k] = (
            arr.sum(axis=0, dtype=arr.dtype)
            if k in tk.out_names
            else arr[0]
        )
    info["placement_counts"] = pcounts
    info["hop_order"] = list(hop_order) if hop_order else None
    return out, info
