"""Parallel loops: forasync 1D/2D/3D in flat and recursive modes.

Mirrors the reference semantics (src/hclib.c:158-473, inc/hclib-forasync.h):

- FLAT mode tiles the iteration space and spawns one task per tile; each tile
  task runs the body over its indices (src/hclib.c:316-416).
- RECURSIVE mode binary-splits the largest dimension until every piece is at
  most one tile, spawning a task per split (src/hclib.c:158-314).
- Auto-tile picks ``ceil(N / nworkers)`` per dimension (src/hclib.c:452-464).
- ``forasync_future`` wraps the loop in a non-blocking finish and returns its
  completion future (src/hclib.c:466-473).
- A registered *distribution function* maps each flat tile to a locale
  (hclib_register_dist_func / loop_dist_func, src/hclib.c:19-30,
  inc/hclib-forasync.h:349-380); the default places tiles at the central
  locale. RECURSIVE mode sees the SAME flat-tile -> locale mapping: a leaf
  piece is keyed by the flat index of the tile holding its low corner, so a
  flat-index dist func places both modes identically whenever the recursion
  lands on the flat tile grid (power-of-two tile counts) and consistently
  otherwise.

``place="device"`` lowers the loop onto the TPU megakernel's batched
same-kind dispatch lanes instead of spawning host tasks
(device/forasync_tier.py): the body is then a ``TileKernel`` slab pipeline,
``dist_func`` doubles as the mesh placement (dist-func callable or JSON
placement descriptor resolved against ``locality_graphs/``), and the call
returns ``(data_out, info)``. Both modes run there: FLAT stages one
descriptor a tile from the host; RECURSIVE stages one range descriptor and
a split kind on the device halves it, at tile boundaries, down to the same
tiles, so a loop of more tiles than the task table has rows still runs.
A ``TileKernel`` that declares ``steps=`` and ``awaits=`` runs that many
time steps in the one call (RECURSIVE only): a tile of a later step is
made on the device when the last tile it awaits of the step before has
stored, and there is no barrier between steps. The device tier requires
tiles that divide the bounds exactly (slab shapes are static).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence, Tuple

from .promise import Future
from .scheduler import (
    async_,
    current_runtime,
    end_finish_nonblocking,
    finish,
    start_finish,
)

__all__ = ["forasync", "forasync_future", "FLAT", "RECURSIVE", "register_dist_func"]

FLAT = "flat"
RECURSIVE = "recursive"

_dist_funcs: dict = {}


def register_dist_func(name: str, fn: Callable[..., Any]) -> None:
    """Register a tile->locale distribution function by name."""
    _dist_funcs[name] = fn


def lookup_dist_func(name: str) -> Callable[..., Any]:
    return _dist_funcs[name]


def _normalize(bounds: Sequence, tile: Optional[Sequence], nworkers: int):
    dims = []
    for b in bounds:
        if isinstance(b, int):
            dims.append((0, b))
        else:
            lo, hi = b
            dims.append((int(lo), int(hi)))
    if tile is None:
        tile_dims = [max(1, math.ceil((hi - lo) / nworkers)) for lo, hi in dims]
    elif isinstance(tile, int):
        tile_dims = [tile] * len(dims)
    else:
        tile_dims = [int(t) for t in tile]
    if len(tile_dims) != len(dims):
        raise ValueError("tile rank must match loop rank")
    return dims, tile_dims


def _run_tile(fn: Callable, ranges: Tuple[Tuple[int, int], ...]) -> None:
    ndim = len(ranges)
    if ndim == 1:
        (lo0, hi0), = ranges
        for i in range(lo0, hi0):
            fn(i)
    elif ndim == 2:
        (lo0, hi0), (lo1, hi1) = ranges
        for i in range(lo0, hi0):
            for j in range(lo1, hi1):
                fn(i, j)
    else:
        (lo0, hi0), (lo1, hi1), (lo2, hi2) = ranges
        for i in range(lo0, hi0):
            for j in range(lo1, hi1):
                for k in range(lo2, hi2):
                    fn(i, j, k)


def _tile_counts(dims, tile_dims):
    return [math.ceil((hi - lo) / t) for (lo, hi), t in zip(dims, tile_dims)]


def _flat_of_ranges(ranges, dims, tile_dims, tile_counts) -> int:
    """Flat tile index of the piece whose low corner is ``ranges``'s -
    the key RECURSIVE leaves use so a flat-index dist func sees the same
    tile -> locale mapping as FLAT mode. When the recursion lands exactly
    on the flat tile grid (power-of-two tile counts) the piece IS that
    flat tile; otherwise the low corner picks the covering tile."""
    flat = 0
    for (plo, _), (lo, _), t, c in zip(ranges, dims, tile_dims, tile_counts):
        idx = min((plo - lo) // t, c - 1)
        flat = flat * c + idx
    return flat


def _spawn_flat(fn, dims, tile_dims, dist_func) -> None:
    ndim = len(dims)
    if isinstance(dist_func, str):
        dist_func = lookup_dist_func(dist_func)
    if dist_func is None:
        # Reference default: flat tiles are routed to the central place
        # (hclib's default loop_dist_func, src/hclib-runtime.c:231-239).
        central = current_runtime().graph.central_locale()
        dist_func = lambda ndim_, tile_, total_: central  # noqa: E731
    tile_counts = _tile_counts(dims, tile_dims)
    total = math.prod(tile_counts)
    for flat in range(total):
        idx = []
        rem = flat
        for c in reversed(tile_counts):
            idx.append(rem % c)
            rem //= c
        idx.reverse()
        ranges = tuple(
            (lo + i * t, min(hi, lo + (i + 1) * t))
            for (lo, hi), t, i in zip(dims, tile_dims, idx)
        )
        async_(_run_tile, fn, ranges, at=dist_func(ndim, flat, total))


def _spawn_recursive(fn, ranges, tile_dims, dims=None, dist_func=None) -> None:
    # Split the largest over-tile dimension in half; recurse via new tasks
    # (reference: src/hclib.c:158-314). ``dims``/``dist_func`` thread the
    # flat-tile placement context down to the leaves: a leaf piece spawns
    # at ``dist_func(ndim, flat-of-low-corner, total)``, the SAME mapping
    # FLAT mode applies, so placement policy is mode-independent. With no
    # dist func, leaves run inline/at the spawner's locale as before.
    widest, wdim = -1, -1
    for d, ((lo, hi), t) in enumerate(zip(ranges, tile_dims)):
        if hi - lo > t and hi - lo > widest:
            widest, wdim = hi - lo, d
    if wdim < 0:
        if dist_func is not None:
            tile_counts = _tile_counts(dims, tile_dims)
            flat = _flat_of_ranges(ranges, dims, tile_dims, tile_counts)
            total = math.prod(tile_counts)
            async_(
                _run_tile, fn, tuple(ranges),
                at=dist_func(len(dims), flat, total),
            )
        else:
            _run_tile(fn, tuple(ranges))
        return
    lo, hi = ranges[wdim]
    mid = (lo + hi) // 2
    left = list(ranges)
    right = list(ranges)
    left[wdim] = (lo, mid)
    right[wdim] = (mid, hi)
    async_(_spawn_recursive, fn, left, tile_dims, dims, dist_func)
    _spawn_recursive(fn, right, tile_dims, dims, dist_func)


def _spawn_all(fn, dims, tile_dims, mode, dist_func) -> None:
    if mode == FLAT:
        _spawn_flat(fn, dims, tile_dims, dist_func)
    else:
        if isinstance(dist_func, str):
            dist_func = lookup_dist_func(dist_func)
        _spawn_recursive(fn, dims, tile_dims, dims, dist_func)


def forasync(
    fn: Callable[..., Any],
    bounds: Sequence,
    tile: Optional[Sequence] = None,
    mode: str = FLAT,
    dist_func: Optional[Callable[[int, int, int], Any]] = None,
    blocking: bool = True,
    place: Optional[str] = None,
    **device_kw,
):
    """Parallel loop over a 1-3D iteration space.

    ``bounds`` is a sequence of ``int`` (upper bound, from 0) or ``(lo, hi)``
    pairs, one per dimension. ``fn`` receives one index per dimension.

    ``place="device"`` runs the loop on the TPU megakernel's batch-lane
    tier instead (see module docstring): ``fn`` must be a
    ``device.forasync_tier.TileKernel``, ``tile`` is required,
    ``dist_func`` doubles as the mesh placement, ``mode=RECURSIVE`` makes
    the tiles on the device from one range descriptor, and extra keywords
    (``data=``, ``width=``, ``mesh=``, ...) forward to
    ``run_forasync_device``, whose ``(data_out, info)`` is returned. The
    number of time steps and what a tile awaits are the ``TileKernel``'s
    own (``steps=``, ``awaits=``): several steps need ``mode=RECURSIVE``.
    """
    if mode not in (FLAT, RECURSIVE):
        raise ValueError(f"unknown forasync mode {mode!r}")
    if place not in (None, "host", "device"):
        raise ValueError(f"unknown forasync place {place!r}")
    if place == "device":
        if tile is None:
            raise ValueError(
                "place='device' needs an explicit tile= (auto-tile is a "
                "host-worker-count policy; device tiles size the slabs)"
            )
        if getattr(fn, "steps", 1) > 1 and mode != RECURSIVE:
            raise ValueError(
                f"a TileKernel of {fn.steps} steps needs mode=RECURSIVE: "
                "step 0 is the splitter's, later steps are made on the "
                "device behind it"
            )
        if not blocking:
            raise ValueError(
                "place='device' is synchronous (the megakernel runs the "
                "loop to completion and returns its results): "
                "blocking=False has no device spelling"
            )
        from ..device.forasync_tier import run_forasync_device

        return run_forasync_device(
            fn, bounds, tile, placement=dist_func, mode=mode, **device_kw
        )
    if device_kw:
        raise TypeError(
            f"unexpected arguments {sorted(device_kw)} (device-tier "
            "options need place='device')"
        )
    if not 1 <= len(bounds) <= 3:
        raise ValueError("forasync supports 1-3 dimensions")
    rt = current_runtime()
    dims, tile_dims = _normalize(bounds, tile, rt.nworkers)

    if blocking:
        with finish():
            _spawn_all(fn, dims, tile_dims, mode, dist_func)
    else:
        _spawn_all(fn, dims, tile_dims, mode, dist_func)


def forasync_future(
    fn: Callable[..., Any],
    bounds: Sequence,
    tile: Optional[Sequence] = None,
    mode: str = FLAT,
    dist_func: Optional[Callable[[int, int, int], Any]] = None,
) -> Future:
    """Non-blocking forasync; returns a future satisfied when every tile has
    completed (hclib_forasync_future: src/hclib.c:466-473)."""
    if mode not in (FLAT, RECURSIVE):
        raise ValueError(f"unknown forasync mode {mode!r}")
    rt = current_runtime()
    dims, tile_dims = _normalize(bounds, tile, rt.nworkers)
    fin = start_finish()
    _spawn_all(fn, dims, tile_dims, mode, dist_func)
    return end_finish_nonblocking(fin)
