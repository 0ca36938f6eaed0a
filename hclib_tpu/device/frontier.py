"""Graph-analytics frontier tier: BFS / SSSP / PageRank on the batch lanes.

ROADMAP direction 5: UTS and fib prove dynamic trees, but nothing in the
bench family exercised skewed frontier expansion over a large in-HBM
structure. This module is that workload family - an adjacency kept in
HBM is traversed by EXPAND task descriptors, and because every EXPAND of
one traversal is the same kind, each round's frontier dynamically groups
onto ONE per-F_FN batch lane (the PR 3 tier) and fires ``width`` at a
time through one tiled body, with the cross-round double-buffered
prefetch streaming the next batch's edge slabs under the current batch's
relax loop.

**Blocked CSR.** The adjacency is CSR with every vertex's edge run
padded out to ``EBLOCK``-edge blocks (``Graph``): ``indices`` (and
``weights``) are ``(nblocks, EBLOCK)`` int32 arrays in HBM, and a
vertex's edges occupy blocks ``[blk_start[v], blk_start[v] +
blk_count[v])``. Block alignment is what makes the edge slab a STATIC
DMA shape - each EXPAND names one block, so a hub vertex is simply many
same-kind descriptors (the R-MAT skew becomes batch occupancy instead
of a ragged-transfer problem), and the slab address is a legal dynamic
offset on real hardware (Mosaic wants coarse alignment).

**Descriptor ABI.** ``EXPAND(v, blk, carry, cnt)``: expand block ``blk``
(``cnt`` live edges) of vertex ``v``, propagating ``carry`` - the
tentative distance of ``v`` (BFS/SSSP) or the residual mass delivered to
``v`` (PageRank). Everything a task needs rides its own descriptor plus
per-vertex state in SMEM value slots, and EXPANDs are spawned link-free,
so they are migratable on every multi-device runner by construction.

**Relaxation model.** BFS and SSSP are label-correcting: an edge
``v -> u`` relaxes ``dist[u] = min(dist[u], carry + w)`` and an
IMPROVING relax re-spawns u's blocks with the new distance. The final
distance array is the exact shortest-path fixpoint - independent of
execution order, batch grouping, and migration - which is what makes
"bit-identical across scalar dispatch, batched frontier, and the
4-device mesh" hold without any ordering machinery: per-device distance
arrays are local caches combined by elementwise min (a suppressed spawn
on one device means an equal-or-better carry was already propagated
there; propagation is transitive). Level-synchronous BFS order is the
special case the lane LIFO/FIFO approximates; with
``priority_buckets=B`` (ISSUE 15) SSSP runs TRUE delta-stepping -
EXPANDs route into bucket ring ``dist // delta`` and the scheduler
retires the lowest non-empty bucket first, so most relaxations happen
at final distances and the re-relaxation work of label correction
largely disappears (executed-EXPAND count and TEPS are the headline;
the fixpoint is the same either way). PageRank is push-style with integer
fixed-point mass: a delivery of ``q`` to ``u`` retains
``q - deg(u) * q_child`` into rank[u] and forwards ``q_child =
(alpha * q) / deg(u)`` along every out-edge, folding entirely into
rank[u] once ``q`` drops under ``reps`` - mass is conserved exactly,
every delivery's children depend only on its own descriptor, so the
result is deterministic across schedules and mesh runs (per-device
ranks combine by sum), and it approximates the float PageRank series
``(1-alpha) * sum_k alpha^k P^k`` to the fixed-point tolerance.

**Firing policy.** Frontier expansion is exactly the chained-spawner
shape the lane-policy watch item predicted: every batch deposits a
fan-out of same-kind children. With the prefetch on (the default) the
EXPAND lane pops FIFO off one ring, and a child that ``spawn`` makes on
the device - by a relax inside a batch slot, by the search kind's maker -
is pushed STRAIGHT onto that lane (``KernelContext.spawn``'s spawn-time
routing, ISSUE 50; ``info['tiers']['direct']``): the ready ring never
holds it, no scheduler round is spent routing it, and the lane fires at
the next round. What still reaches the lane through the ring, a routing
round each (``tiers['routed']``): the host's seeds, rows stolen or
restored, lane entries an exit spilled, and every child of a
``prefetch=False`` or ``priority_buckets`` build (a LIFO round rewrites
its lane's tail, a bucket is chosen at the routing pop). For those the
lane sits starved for the whole routing drain under pure ring-drain-first
firing, so the frontier megakernels default the ISSUE 10 age trigger ON
(``lane_max_age = 4 * width``): a lane that has held entries for that
many rounds jumps the ring and fires - full batches mid-drain once
>= width entries accumulated - keeping ``lane_partial_age`` and the
device-side ``max_starved_age`` gauge bounded (the frontier-batch perf
guard pins both).

**Search with the vertices in HBM.** The three traversals above read a
word of per-vertex state once an EDGE, so that word (and the vertex table)
is staged into SMEM value slots, which bounds them to small graphs.
Breadth-first search to a parent array (Graph500's kernel 2) needs one
BIT a vertex to refuse an edge: the search kind (``search_kernel``,
``GraphSearch``; "the search kind" below) keeps only that filter on the
scalar core and puts the vertex table, the frontier and the answer in HBM,
with EXPAND descriptors made on the device from the frontier as the task
table has room. The filter has a LEAD row of all ones in front of vertex
0's word, where vertex -1's bit lies: a block's padding reads as reached
by the same test as a vertex, so the loop over a block's entries compares
nothing with ``cnt``.

**TEPS.** Every EXPAND counts its ``cnt`` live edges into value slot
``V_EDGES``; traversed-edges/s = edges / wall over a run - the headline
the graph bench reports beside UTS nodes/s. Improving relaxations (or
PageRank deliveries) count into ``V_RELAX``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.locality import MeshPlacement, resolve_placement
from ..runtime.spans import span
from .descriptor import TaskGraphBuilder
from .megakernel import BK_MAX, BatchSpec, Megakernel, _batch_stub

__all__ = [
    "EBLOCK",
    "INF",
    "FR_EXPAND",
    "Graph",
    "FrontierKernel",
    "bfs_kernel",
    "sssp_kernel",
    "pagerank_kernel",
    "make_frontier_megakernel",
    "run_frontier",
    "GraphSearch",
    "SearchKernel",
    "search_kernel",
    "seed_frontier",
    "host_bfs",
    "host_sssp",
    "host_pagerank_push",
    "host_pagerank",
    "priority_bucket",
    "default_delta",
    "PR_NUM",
    "PR_DEN",
]

# Edge-block width: one 128-lane row of int32, and the blocked-CSR
# alignment unit (every vertex's edge run starts on a block boundary).
EBLOCK = 128
EBLOCK_SHIFT = 7  # log2(EBLOCK)

# Unreached distance sentinel (fits int32 with relax headroom: INF + any
# edge weight stays positive and still compares greater than any real
# path length).
INF = 0x3FFFFFFF

# The EXPAND kernel's table index: frontier megakernels are single-kind
# (one traversal family per build), so the id is fixed - which is also
# what puts every frontier descriptor on ONE batch lane.
FR_EXPAND = 0

# PageRank damping as an exact int32 rational: alpha = 13/16 = 0.8125
# (exactly representable in the float host reference too, so the only
# device-vs-float divergence is fixed-point truncation).
PR_NUM = 13
PR_DEN = 16

# Value-slot layout of the SMEM-staged kinds (bfs / sssp / pagerank): two
# counters, then the vertex table (3 words per vertex: block start / block
# count / out-degree), then per-vertex state (distance or rank). All
# host-preset, so the whole layout stages into SMEM and the device reads
# it with plain dynamic indexing. The search kind has its own (S_* below).
V_EDGES = 0   # traversed edges (the TEPS numerator; combines by sum)
V_RELAX = 1   # improving relaxations / PR deliveries (combines by sum)
VT_BASE = 8


_CHUNK = 1 << 22  # directed entries a thread of ``undirected`` takes at once


def _threaded(fn: Callable[[int, int], None], cuts: np.ndarray) -> None:
    """``fn(cuts[i], cuts[i + 1])`` for every i, on a few threads."""
    import concurrent.futures
    import os

    spans = [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:]) if b > a]
    with concurrent.futures.ThreadPoolExecutor(
        min(16, os.cpu_count() or 1)
    ) as pool:
        list(pool.map(lambda ab: fn(*ab), spans))


class Graph:
    """Host-side blocked-CSR adjacency (module docstring): dense int32
    arrays shaped for the device tier. Construction (Graph500's kernel 1)
    is array code throughout: one 64-bit sort that keeps the input order
    inside a vertex, one prefix sum, one scatter into the blocks. The edge
    list may hold self-loops and duplicates; they stay. The per-vertex
    python lists of the host reference arms (``adj``, ``adj_w``) are made
    from the blocks on first use."""

    def __init__(
        self,
        n: int,
        src: np.ndarray,
        dst: np.ndarray,
        weights: Optional[np.ndarray] = None,
    ) -> None:
        src = np.asarray(src)
        dst = np.asarray(dst)
        if src.shape != dst.shape or src.ndim != 1:
            raise ValueError("src/dst must be the same length")
        if len(src) and (
            src.min() < 0 or src.max() >= n or dst.min() < 0 or dst.max() >= n
        ):
            raise ValueError(f"edge endpoints out of range [0, {n})")
        self.n = int(n)
        self.m = int(len(src))
        if weights is not None:
            weights = np.asarray(weights)
            if weights.shape != src.shape:
                raise ValueError("weights must match the edge count")
            if len(weights) and weights.min() < 0:
                raise ValueError("weights must be >= 0")
        # Stable order by source in one sort of (src << 32 | position).
        key = (src.astype(np.uint64) << np.uint64(32)) | np.arange(
            self.m, dtype=np.uint64
        )
        key.sort()
        order = (key & np.uint64(0xFFFFFFFF)).astype(np.int64)
        key >>= np.uint64(32)  # the sources, ascending
        splits = np.searchsorted(key, np.arange(n + 1, dtype=np.uint64))
        del key
        self._layout(splits)
        # Edge k of the sorted list lands at its vertex's first block
        # plus its rank in the vertex.
        flat = np.arange(self.m, dtype=np.int64) + np.repeat(
            self.blk_start.astype(np.int64) * EBLOCK - splits[:-1], self.deg
        )
        self.indices = np.full((self.nblocks, EBLOCK), -1, np.int32)
        self.indices.reshape(-1)[flat] = dst[order]
        # The weights' blocks wait for their first reader (all ones where
        # the caller gave none): an unweighted search never pays for them.
        self._weights: Optional[np.ndarray] = None
        self._weights_src = (
            flat, None if weights is None else weights[order], self.nblocks
        )

    @classmethod
    def undirected(cls, n: int, u: np.ndarray, v: np.ndarray) -> "Graph":
        """The graph of the undirected edge tuples ``(u[i], v[i])``, each
        as both its directed entries, self-loops and duplicates as given;
        a vertex's targets ascend. The same construction as ``__init__``
        (sort, prefix sum, scatter) with nothing held a directed entry but
        its 64-bit sort key, and the key's fill and the blocks' scatter
        spread over a few threads a range of vertices each: at 2^27
        entries what a fresh host page costs decides the time, and
        threads take those faults side by side."""
        u, v = np.asarray(u), np.asarray(v)
        if u.shape != v.shape or u.ndim != 1:
            raise ValueError("u/v must be the same length")
        m = len(u)
        if m and (min(u.min(), v.min()) < 0 or max(u.max(), v.max()) >= n):
            raise ValueError(f"edge endpoints out of range [0, {n})")
        self = cls.__new__(cls)
        self.n, self.m = int(n), 2 * m
        with span("g500.kernel1"):
            self._build_undirected(u, v)
        return self

    def _build_undirected(self, u: np.ndarray, v: np.ndarray) -> None:
        n, m = self.n, len(u)
        key = np.empty(2 * m, np.uint64)

        def fill(lo, hi):
            a = u[lo:hi].astype(np.uint64)
            b = v[lo:hi].astype(np.uint64)
            key[lo:hi] = (a << np.uint64(32)) | b
            key[m + lo : m + hi] = (b << np.uint64(32)) | a

        _threaded(fill, np.arange(0, m + _CHUNK, _CHUNK).clip(max=m))
        key.sort()
        splits = np.searchsorted(
            key, np.arange(n + 1, dtype=np.uint64) << np.uint64(32)
        )
        self._layout(splits)
        self.indices = np.empty((self.nblocks, EBLOCK), np.int32)
        ends = np.append(self.blk_start, self.nblocks).astype(np.int64)

        def scatter(v0, v1):
            e0, b0 = splits[v0], ends[v0]
            out = self.indices[b0 : ends[v1]].reshape(-1)
            out[:] = -1
            at = np.arange(splits[v1] - e0) + np.repeat(
                (ends[v0:v1] - b0) * EBLOCK - (splits[v0:v1] - e0),
                self.deg[v0:v1],
            )
            out[at] = key[e0 : splits[v1]].astype(np.uint32)  # low: target

        # Ranges of vertices with about _CHUNK entries each.
        cuts = np.searchsorted(splits, np.arange(0, 2 * m, _CHUNK))
        _threaded(scatter, np.unique(np.append(cuts, n)))
        if not self.m:
            self.indices[:] = -1
        self._weights = self._weights_src = None

    def _layout(self, splits: np.ndarray) -> None:
        """``deg`` / ``blk_count`` / ``blk_start`` / ``nblocks`` from the
        sorted list's per-vertex boundaries."""
        self.deg = np.diff(splits).astype(np.int32)
        self.blk_count = ((self.deg + EBLOCK - 1) // EBLOCK).astype(np.int32)
        self.blk_start = np.zeros(self.n, np.int32)
        if self.n > 1:
            self.blk_start[1:] = np.cumsum(self.blk_count)[:-1].astype(
                np.int32
            )
        self.nblocks = max(1, int(self.blk_count.sum(dtype=np.int64)))

    @property
    def weights(self) -> np.ndarray:
        if self._weights is None:
            if self._weights_src is None:  # undirected(): all ones
                self._weights = (self.indices >= 0).astype(np.int32)
            else:
                flat, w, nblocks = self._weights_src
                self._weights = np.zeros((nblocks, EBLOCK), np.int32)
                self._weights.reshape(-1)[flat] = 1 if w is None else w
                self._weights_src = None
        return self._weights

    @weights.setter
    def weights(self, value: np.ndarray) -> None:
        self._weights = value

    def _per_vertex(self, blocks: np.ndarray) -> List[np.ndarray]:
        return [
            blocks[b0 : b0 + bc].reshape(-1)[:d]
            for b0, bc, d in zip(
                self.blk_start.tolist(), self.blk_count.tolist(),
                self.deg.tolist(),
            )
        ]

    @functools.cached_property
    def adj(self) -> List[np.ndarray]:
        """Per-vertex targets, for the host reference arms."""
        return self._per_vertex(self.indices)

    @functools.cached_property
    def adj_w(self) -> List[np.ndarray]:
        return self._per_vertex(self.weights)

    def vtab(self) -> np.ndarray:
        """The vertex table as the search kind reads it from HBM: ``(first
        block, degree)`` pairs, 64 vertices a 128-word row."""
        rows = -(-self.n // 64)
        t = np.zeros((rows * 64, 2), np.int32)
        t[: self.n, 0] = self.blk_start
        t[: self.n, 1] = self.deg
        return t.reshape(rows, EBLOCK)

    def block_cnt(self, v: int, i: int) -> int:
        """Live edges in block ``i`` of vertex ``v`` (the descriptor's
        ``cnt`` arg): full blocks then the ragged tail."""
        return int(min(EBLOCK, int(self.deg[v]) - i * EBLOCK))

    # -- value-slot layout --

    @property
    def st_base(self) -> int:
        return VT_BASE + 3 * self.n

    @property
    def num_value_slots(self) -> int:
        """Host-preset slots: counters + vertex table + per-vertex state."""
        return self.st_base + self.n

    def preset_values(self, num_values: int, state0: int) -> np.ndarray:
        """The host ivalues row: vertex table filled, per-vertex state
        initialized to ``state0`` (INF for distances, 0 for ranks)."""
        if num_values < self.num_value_slots:
            raise ValueError(
                f"graph wants num_values >= {self.num_value_slots}, "
                f"got {num_values}"
            )
        iv = np.zeros(num_values, np.int32)
        vt = np.stack(
            [self.blk_start, self.blk_count, self.deg], axis=1
        ).reshape(-1)
        iv[VT_BASE : VT_BASE + 3 * self.n] = vt
        iv[self.st_base : self.st_base + self.n] = state0
        return iv


# ----------------------------------------------------------- device tier


def _spawn_blocks(kctx, u, carry) -> None:
    """Spawn one EXPAND per adjacency block of vertex ``u`` (the device
    side of frontier growth; the host seeding mirrors it exactly)."""
    vt = VT_BASE + 3 * u
    bs = kctx.ivalues[vt]
    bc = kctx.ivalues[vt + 1]
    deg = kctx.ivalues[vt + 2]

    def sp(i, _):
        cnt = jnp.clip(deg - i * EBLOCK, 0, EBLOCK)
        kctx.spawn(FR_EXPAND, [u, bs + i, carry, cnt], nargs=4)
        return 0

    jax.lax.fori_loop(0, bc, sp, 0)


class FrontierKernel:
    """One traversal family as an edge-slab pipeline: a per-edge scalar
    ``relax(fk, kctx, u, w, carry)`` plus the slab declarations, from
    which BOTH dispatch spellings derive (the TileKernel pattern): the
    scalar-tier kernel (DMA one block in, relax its edges - the
    bit-identity reference arm) and the batched body (all live slots'
    slabs in flight before the first wait, the prospective next batch's
    slabs prefetched into the other scratch half during this round's relax
    loop, the PR 3 double-buffer protocol) with its ``drain``. One relax
    trace means scalar-vs-batched identity holds by construction - and
    for these kernels the RESULT is additionally schedule-independent
    (module docstring), which is what extends the identity across the
    mesh.

    ``relax`` receives the kernel itself first so it can read the
    graph-layout base ``fk.st_base`` at TRACE time -
    ``make_frontier_megakernel`` stamps it before the megakernel's lazy
    first trace."""

    def __init__(
        self,
        name: str,
        relax: Callable,
        weighted: bool,
        state0: int,
    ) -> None:
        self.name = name
        self._relax = relax
        self.weighted = bool(weighted)
        self.state0 = int(state0)
        # Per-vertex state region base in the value slots; stamped by
        # make_frontier_megakernel from the graph layout (trace-time
        # read, so the kernel must be bound to ONE graph layout).
        self.st_base: Optional[int] = None

    def relax(self, kctx, u, w, carry) -> None:
        if self.st_base is None:
            raise ValueError(
                "FrontierKernel has no graph layout bound: build it "
                "through make_frontier_megakernel (which stamps st_base)"
            )
        self._relax(self, kctx, u, w, carry)

    def data_specs(self, graph: Graph) -> Dict[str, jax.ShapeDtypeStruct]:
        specs = {
            "indices": jax.ShapeDtypeStruct(
                (graph.nblocks, EBLOCK), jnp.int32
            )
        }
        if self.weighted:
            specs["weights"] = jax.ShapeDtypeStruct(
                (graph.nblocks, EBLOCK), jnp.int32
            )
        return specs

    def data(self, graph: Graph) -> Dict[str, np.ndarray]:
        d = {"indices": graph.indices}
        if self.weighted:
            d["weights"] = graph.weights
        return d

    def _eff_cnt(self, kctx, v, blk, cnt):
        """Live-edge count of block ``blk`` as THIS device sees it -
        the static tier trusts the descriptor (``cnt`` unchanged, so
        static builds trace zero extra words); the dynamic-graph
        subclass clamps to the local vertex table so an EXPAND spawned
        after a splice the local replica has not applied yet never
        reads past the locally-live edges (dyngraph.py)."""
        return cnt

    def _relax_block(self, kctx, eslab, wslab, carry, cnt) -> None:
        """The shared relax loop over one loaded edge slab: the single
        arithmetic trace both dispatch spellings run. ``eslab``/``wslab``
        are zero-arg SMEM readers ``f(e) -> scalar``: the relax is a
        scalar loop that reads edge ``e`` at a dynamic lane, which the
        TPU allows of SMEM and refuses of VMEM ("cannot statically
        prove that index ... is a multiple of 128"), so the edge slabs
        are DMA'd HBM -> SMEM."""
        kctx.ivalues[V_EDGES] = kctx.ivalues[V_EDGES] + cnt

        def e_body(e, _):
            u = eslab(e)
            w = wslab(e) if self.weighted else jnp.int32(0)
            self.relax(kctx, u, w, carry)
            return 0

        jax.lax.fori_loop(0, cnt, e_body, 0)

    def _slab(self, ref, *row) -> Callable:
        """The reader ``f(e) -> scalar`` of the slab row ``ref[*row]``
        that ``_relax_block`` is handed."""
        return lambda e: ref[(*row, e)]

    # -- scalar-tier spelling --

    def scalar_scratch(self) -> Dict[str, Any]:
        sc: Dict[str, Any] = {
            "fr_idx": pltpu.SMEM((EBLOCK,), jnp.int32),
            "fr_lsem": pltpu.SemaphoreType.DMA((1,)),
        }
        if self.weighted:
            sc["fr_wgt"] = pltpu.SMEM((EBLOCK,), jnp.int32)
        return sc

    def scalar_kernel(self, ctx) -> None:
        v, blk, carry, cnt = (ctx.arg(i) for i in range(4))
        sem = ctx.scratch["fr_lsem"].at[0]
        copies = [
            pltpu.make_async_copy(
                ctx.data["indices"].at[blk], ctx.scratch["fr_idx"], sem
            )
        ]
        if self.weighted:
            copies.append(
                pltpu.make_async_copy(
                    ctx.data["weights"].at[blk], ctx.scratch["fr_wgt"], sem
                )
            )
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        cnt = self._eff_cnt(ctx, v, blk, cnt)
        self._relax_block(
            ctx,
            self._slab(ctx.scratch["fr_idx"]),
            self._slab(ctx.scratch["fr_wgt"]) if self.weighted else None,
            carry,
            cnt,
        )

    # -- batch-tier spelling --

    def batch_scratch(self, width: int) -> Dict[str, Any]:
        sc: Dict[str, Any] = {
            # Double-buffered (leading 2): one half relaxes while the
            # tier's cross-round prefetch streams the next batch's edge
            # slabs into the other.
            "fr_idx": pltpu.SMEM((2, width, EBLOCK), jnp.int32),
            "fr_lsem": pltpu.SemaphoreType.DMA((2, width)),
        }
        if self.weighted:
            sc["fr_wgt"] = pltpu.SMEM((2, width, EBLOCK), jnp.int32)
        return sc

    def _slot_loads(self, ctx, buf, slot: int, blk, wait: bool) -> None:
        """Start (or retire) the edge-slab copies of batch slot ``slot``
        into half ``buf`` - one semaphore per (half, slot) counting every
        stream, each start matched by exactly one wait."""
        sem = ctx.scratch["fr_lsem"].at[buf, slot]
        cp = pltpu.make_async_copy(
            ctx.data["indices"].at[blk],
            ctx.scratch["fr_idx"].at[buf, slot],
            sem,
        )
        (cp.wait if wait else cp.start)()
        if self.weighted:
            cp = pltpu.make_async_copy(
                ctx.data["weights"].at[blk],
                ctx.scratch["fr_wgt"].at[buf, slot],
                sem,
            )
            (cp.wait if wait else cp.start)()

    def batch_body(self, ctx) -> None:
        width = ctx.width
        buf = ctx.buf

        # Phase 1: start edge-slab copies for live slots the prefetch
        # didn't already cover.
        for b in range(width):
            @pl.when(ctx.live(b) & (jnp.int32(b) >= ctx.prefetched))
            def _(b=b):
                self._slot_loads(ctx, buf, b, ctx.arg(b, 1), wait=False)

        # Phase 2: the prospective NEXT batch's slabs start into the
        # other half now, landing under this round's relax loops.
        obuf = 1 - buf
        for b in range(width):
            @pl.when(jnp.int32(b) < ctx.prefetch_count)
            def _(b=b):
                self._slot_loads(ctx, obuf, b, ctx.next_arg(b, 1),
                                 wait=False)

        # Phase 3: retire this round's loads (prefetched slots wait the
        # copies LAST round's phase 2 started into this half).
        for b in range(width):
            @pl.when(ctx.live(b))
            def _(b=b):
                self._slot_loads(ctx, buf, b, ctx.arg(b, 1), wait=True)

        # Phase 4: per-slot relax loops, in slot order - each slot's
        # relaxes see the SMEM state earlier slots of the same batch
        # wrote, exactly as scalar dispatch of the same rows would.
        for b in range(width):
            @pl.when(ctx.live(b))
            def _(b=b):
                kctx = ctx.slot_ctx(b)
                self._relax_block(
                    kctx,
                    self._slab(ctx.scratch["fr_idx"], buf, b),
                    self._slab(ctx.scratch["fr_wgt"], buf, b)
                    if self.weighted
                    else None,
                    ctx.arg(b, 2),
                    self._eff_cnt(
                        kctx, ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 3)
                    ),
                )

    def batch_drain(self, ctx) -> None:
        """Retire an in-flight prefetch whose target entries will spill
        instead of batching (scheduler exit: fuel, quiesce) - no DMA
        outlives the round loop."""
        for b in range(ctx.width):
            @pl.when(jnp.int32(b) < ctx.prefetched)
            def _(b=b):
                self._slot_loads(ctx, ctx.buf, b, ctx.arg(b, 1), wait=True)


# ----------------------------------------------------- the three kernels


def bfs_kernel(spawn: Callable = _spawn_blocks) -> FrontierKernel:
    """Level-style BFS as monotone label correction: carry is dist[v] at
    spawn; an improving hop re-spawns the target's blocks. ``spawn``
    is the block spawner (dyngraph.py substitutes the two-range spare-
    aware spelling; the default traces byte-identically to PR 10)."""

    def relax(fk, kctx, u, w, carry) -> None:
        nd = carry + 1
        st = fk.st_base + u
        better = nd < kctx.ivalues[st]

        @pl.when(better)
        def _():
            kctx.ivalues[st] = nd
            kctx.ivalues[V_RELAX] = kctx.ivalues[V_RELAX] + 1
            spawn(kctx, u, nd)

    return FrontierKernel("fr_bfs", relax, weighted=False, state0=INF)


def sssp_kernel(spawn: Callable = _spawn_blocks) -> FrontierKernel:
    """SSSP (nonnegative int weights): the same monotone relaxation
    with ``carry + w``. Unordered, the lane's pop order stands in for
    the bucket discipline and re-expansions are the correction; with
    ``priority_buckets`` the build runs TRUE delta-stepping (bucket =
    dist // delta, lowest first) and the re-expansions mostly vanish -
    exactness depends on neither (the relax body is identical)."""

    def relax(fk, kctx, u, w, carry) -> None:
        nd = carry + w
        st = fk.st_base + u
        better = nd < kctx.ivalues[st]

        @pl.when(better)
        def _():
            kctx.ivalues[st] = nd
            kctx.ivalues[V_RELAX] = kctx.ivalues[V_RELAX] + 1
            spawn(kctx, u, nd)

    return FrontierKernel("fr_sssp", relax, weighted=True, state0=INF)


# PageRank residual-magnitude bands grow by this factor per bucket:
# bucket k holds deliveries with q in [reps*2^k, reps*2^(k+1)) - small
# residuals (which FOLD, freeing rows) land in bucket 0 and fire first,
# so the push collapses each subtree before the next large delivery
# splits (depth-first by magnitude = the bounded-frontier fix). Factor
# 2 resolves one alpha-split step (a delivery's children are ~q/deg:
# always a lower band), which the live-set model showed is what holds
# the peak flat as m0 grows; coarser bands leak whole generations into
# one bucket and the breadth returns.
PR_BAND = 2


def priority_bucket(kind: str, carry: int, *, delta: int = 1,
                    reps: int = 64) -> int:
    """HOST-int spelling of the priority-bucket functions the device
    routing runs (``_bucket_fn`` below is the traced twin - keep the two
    in lockstep; analysis/model.py certifies the bucketed pop order
    through THIS spelling). ``carry`` is the descriptor's carry word:
    the tentative distance (bfs/sssp - bucket = dist // delta, the
    delta-stepping discipline) or the delivered residual mass
    (pagerank - ascending magnitude bands). The scheduler clips into
    [0, priority_buckets)."""
    if kind in ("bfs", "sssp", "fr_bfs", "fr_sssp"):
        return int(carry) // max(1, int(delta))
    b = 0
    for k in range(1, BK_MAX):
        b += int(carry) >= int(reps) * (PR_BAND ** k)
    return b


def _bucket_fn(name: str, delta: int, reps: int):
    """Device (traced int32) twin of ``priority_bucket`` - the
    ``BatchSpec.priority`` callable for one frontier kind. Reads ONLY
    the descriptor's own arg words (carry is arg 2), which is what
    makes spilled/stolen/resharded residue re-bucket on its next
    routing pop."""
    if name in ("fr_bfs", "fr_sssp"):
        d = max(1, int(delta))
        return lambda arg: arg(2) // jnp.int32(d)

    def pr(arg):
        q = arg(2)
        b = jnp.int32(0)
        for k in range(1, BK_MAX):
            b = b + (q >= jnp.int32(int(reps) * (PR_BAND ** k))).astype(
                jnp.int32
            )
        return b

    return pr


def default_delta(graph: Graph) -> int:
    """Default delta-stepping bucket width for a graph: max edge weight
    over the bucket-ring count (>= 1), so the static ring set resolves
    roughly one relaxation step where the frontier lives. Measured on
    seeded weighted R-MAT this FINE delta beats the classic coarse
    ~max-weight choice even though far distances clip into the top
    ring (executed-EXPAND 0.68-0.77x FIFO at delta = w_max/8 vs
    0.85-0.87x at w_max/2): the early frontier is where re-relaxation
    happens, so that is where resolution pays. Override via
    ``make_frontier_megakernel(delta=)``."""
    w = int(graph.weights.max()) if graph.m else 1
    return max(1, w // BK_MAX)


def _pr_split(q, deg):
    """Child mass of a PageRank delivery ``q`` at a vertex of out-degree
    ``deg`` (int fixed point) - the ONE place the split arithmetic
    lives, shared by the device relax (traced int32), host seeding, and
    the exact host twin (python ints, so the twin is bit-exact)."""
    if isinstance(q, (int, np.integer)):
        return (int(q) * PR_NUM // PR_DEN) // max(int(deg), 1)
    return (q * PR_NUM // PR_DEN) // jnp.maximum(deg, 1)


def pagerank_kernel(reps: int = 64,
                    spawn: Callable = _spawn_blocks) -> FrontierKernel:
    """Push-style PageRank on integer fixed-point mass: a delivery of
    ``q`` retains ``q - deg*q_child`` into rank[u] and forwards
    ``q_child`` per out-edge; ``q < reps`` (or a zero child, or a
    dangling target) folds the whole delivery into rank[u]. Mass
    conserves exactly, so the result is deterministic across schedules
    and sums across mesh devices."""

    reps = int(reps)
    if reps < 1:
        raise ValueError(f"pagerank reps must be >= 1, got {reps}")

    def relax(fk, kctx, u, w, q) -> None:
        vt = VT_BASE + 3 * u
        deg = kctx.ivalues[vt + 2]
        qc = _pr_split(q, deg)
        expand = (q >= jnp.int32(reps)) & (qc > 0) & (deg > 0)
        retained = jnp.where(expand, q - deg * qc, q)
        st = fk.st_base + u
        kctx.ivalues[st] = kctx.ivalues[st] + retained
        kctx.ivalues[V_RELAX] = kctx.ivalues[V_RELAX] + 1

        @pl.when(expand)
        def _():
            spawn(kctx, u, qc)

    fk = FrontierKernel("fr_pagerank", relax, weighted=False, state0=0)
    fk.reps = reps
    return fk


# ------------------------------------------------- the search kind (HBM)
#
# Graph500's kernel 2: breadth-first search from one key, the answer a
# parent array. Nothing a vertex owns is staged into SMEM: the vertex
# table (``vtab``: first block and degree, 64 vertices a 128-word row),
# the frontier and the answer live in HBM. What stays on the scalar core
# is a FILTER of one bit a vertex (``sr_bits``), so that an edge whose far
# end is reached costs no HBM access at all, and HBM is touched once a
# NEWLY reached vertex: its ``(vertex, parent)`` pair is appended to the
# QUEUE (``queue``: 64 pairs a row, a row DMA'd out when it fills), and
# its table words are read when the queue's reader gets to it.
#
# Nearly every entry a search examines names a reached vertex, so what a
# search costs is the straight-line length of the test that finds nothing
# (ISSUE 49 read it off the compiler's listing: ``tools/kernel_listing.py
# --kernel search``). Vertex ``u``'s bit is bit ``u & 31`` of word
# ``SR_LEAD + (u >> 5)``: the ``SR_LEAD`` words in front are all ones, and
# a block's padding, -1, is bit 31 of the last of them (``-1 >> 5`` is -1,
# ``-1 & 31`` is 31), so padding reads as REACHED and needs neither a
# compare with ``cnt`` nor a clamp to vertex 0. The maker's first call
# sets the lead and clears the rest.
#
# The queue IS the frontier, in discovery order, so the task table holds
# no frontier at all: a MAKE task (scalar tier) reads the queue, gathers
# ``SR_GROUP`` vertices' table rows at a time, and makes EXPAND
# descriptors while the table has room (``capacity - SR_SPARE`` of them),
# each naming the maker as its successor and pushed straight onto the
# EXPAND lane, which runs them in the order they were made (so the tree is
# the queue-order tree: a vertex's parent is the first of the queue to
# name it). A trip of its loop is a VERTEX: the trip that takes a vertex
# makes its blocks in an inner loop whose body is the ``spawn`` and nothing
# else, the loop's state rides in registers and is stored once a call, and
# the gather and the level logic stand behind one branch a group takes
# once (ISSUE 52 read the loop off the compiler's listing: a trip a thing,
# with everything predicated into it, was 98 bundles an EXPAND and 98 a
# vertex; this is 38 and 43). Then it re-arms itself
# (``ctx.become``) on as many predecessors as it made, and runs again
# when the last of them has retired. It never crosses the end of a level
# with an EXPAND of that level outstanding, so the order is exactly
# level-synchronous: the first pair the queue holds for a vertex carries
# its true level, nothing is relaxed twice (``edges`` is the component's
# directed entries exactly), and the queue's level boundaries
# (``S_LSTART``) are the levels. The host reads the queue back and
# unrolls it into the parent array with one numpy scatter.

SR_MAKE = 1  # the maker's table index, beside FR_EXPAND

SR_GROUP = 16  # vertices whose table rows one gather fetches
SR_SPARE = 8   # table rows a maker leaves free (itself, and slack)
SR_LEVELS = 64  # level boundaries the value slots keep
SR_TEST_SHIFT = 4
SR_TEST = 1 << SR_TEST_SHIFT  # entries whose filter bits one branch tests
SR_SUB = 4  # of them a sub-group, which a hit walks through relax or jumps
SR_LEAD = EBLOCK  # filter words in front of vertex 0's (a row), all ones

# A search build's value slots: V_EDGES, then the maker's state.
S_EXPANDS = 1   # EXPAND descriptors made
S_QHEAD = 2     # queue position the reader is at
S_QTAIL = 3     # queue length: the vertices reached
S_LEVEL_END = 4  # queue position at which the level in hand ends
S_LEVEL = 5     # its number
S_FRONT_MAX = 6  # the longest level
S_GRP_N = 7     # vertices in the gathered group
S_GRP_K = 8     # those of them the maker is done with
S_CUR_DONE = 9  # blocks of the next one already given to EXPANDs
S_RD_ROW = 12   # queue row held in sr_qrd, + 1 (0: none)
S_HBM_RD = 13   # state words read from HBM (table rows, queue rows)
S_HBM_WR = 14   # state words written to HBM (queue rows)
S_HITS = 17     # sub-groups of entries that went into relax
S_TRIPS = 18    # trips of the maker's loop: a vertex each
S_LSTART = 24   # + level: the queue position its level starts at
S_WORDS = S_LSTART + SR_LEVELS


def _bits_rows(n: int) -> int:
    return -(-n // (32 * EBLOCK))


class SearchKernel(FrontierKernel):
    """The EXPAND of a search: the frontier tier's edge-slab pipeline
    (same descriptor, same batched body, same prefetch) with a relax that
    asks the bit filter and appends to the queue."""

    def __init__(self) -> None:
        super().__init__("fr_search", None, weighted=False, state0=0)
        self.st_base = 0  # no per-vertex value slots

    def data_specs(self, graph: Graph) -> Dict[str, jax.ShapeDtypeStruct]:
        specs = super().data_specs(graph)
        rows = -(-graph.n // 64)
        specs["vtab"] = jax.ShapeDtypeStruct((rows, EBLOCK), jnp.int32)
        specs["queue"] = jax.ShapeDtypeStruct((rows, EBLOCK), jnp.int32)
        return specs

    def search_scratch(self, graph: Graph) -> Dict[str, Any]:
        return {
            "sr_bits": pltpu.SMEM(
                (SR_LEAD + _bits_rows(graph.n) * EBLOCK,), jnp.int32
            ),
            "sr_qst": pltpu.SMEM((EBLOCK,), jnp.int32),
            "sr_qrd": pltpu.SMEM((EBLOCK,), jnp.int32),
            "sr_vt": pltpu.SMEM((SR_GROUP, EBLOCK), jnp.int32),
            "sr_grp": pltpu.SMEM((4 * SR_GROUP,), jnp.int32),
            "sr_sem": pltpu.SemaphoreType.DMA((SR_GROUP + 1,)),
        }

    def _slab(self, ref, *row) -> Callable:
        """``f(e, k=0)``: entry ``e + k`` of the row, ``k`` static, read
        through a view of the row taken here, once a slot, so an entry is
        one add away. (Of ``ref[buf, b, e]`` the compiler, which is not
        told ``e < 128``, makes ``((buf * width + b) + (e >> 7)) * 128 +
        (e & 127)``, five operations an entry.)"""
        view = ref.at[row] if row else ref
        return lambda e, k=0: view[e + k]

    def relax(self, kctx, u, w, carry) -> None:
        bits = kctx.scratch["sr_bits"]
        at = SR_LEAD + (u >> 5)
        word = bits[at]
        bit = jnp.int32(1) << (u & 31)

        @pl.when((word & bit) == 0)
        def _():
            bits[at] = word | bit
            _queue_append(kctx, u, carry)

    def _relax_block(self, kctx, eslab, wslab, carry, cnt) -> None:
        """The shared loop's spelling for a filter: nearly every entry's
        far end is reached already, so the entries are TESTED ``SR_TEST``
        at a time as straight-line integer code (a slab word, the filter
        word it names, a shift, an AND into its sub-group's word) and one
        branch is taken only by a group that holds an unreached one. Such
        a group walks, in block order, those of its sub-groups of
        ``SR_SUB`` whose own word says so through the per-entry relax
        (which tests again: two entries of a group may name one vertex).
        Padding needs no test of its own (``SR_LEAD``), so a group may
        reach past ``cnt``."""
        iv = kctx.ivalues
        iv[V_EDGES] = iv[V_EDGES] + cnt
        bits = kctx.scratch["sr_bits"]
        nsubs = SR_TEST // SR_SUB

        def reached(e0, k):
            u = eslab(e0, k)
            return bits[SR_LEAD + (u >> 5)] >> (u & 31)  # bit 0 answers

        def group(g, _):
            e0 = g * SR_TEST
            subs = [
                functools.reduce(
                    jnp.bitwise_and,
                    [reached(e0, k) for k in range(k0, k0 + SR_SUB)],
                )
                for k0 in range(0, SR_TEST, SR_SUB)
            ]

            @pl.when((functools.reduce(jnp.bitwise_and, subs) & 1) == 0)
            def _():
                # Bit i of ``done``: sub-group i holds nothing unreached,
                # or has been walked. The lowest clear bit is walked and
                # set till none is clear, so the relax is traced once an
                # entry of a sub-group, not once an entry of a group.
                done = functools.reduce(
                    jnp.bitwise_or,
                    [(sub & 1) << i for i, sub in enumerate(subs)],
                )

                def walk(done):
                    low = i = done & 1  # i: the trailing ones of ``done``
                    for j in range(1, nsubs - 1):
                        low = low & (done >> j)
                        i = i + low
                    iv[S_HITS] = iv[S_HITS] + 1
                    for k in range(SR_SUB):
                        self.relax(
                            kctx, eslab(e0 + i * SR_SUB, k), None, carry
                        )
                    return done | (jnp.int32(1) << i)

                jax.lax.while_loop(
                    lambda done: done != (1 << nsubs) - 1, walk, done
                )

            return 0

        jax.lax.fori_loop(
            0, (cnt + SR_TEST - 1) >> SR_TEST_SHIFT, group, 0
        )


def _queue_row_copy(kctx, row, out: bool):
    """The DMA between queue row ``row`` and its SMEM row: the staging
    row out, or the reader's row in."""
    q = kctx.data["queue"].at[row]
    sem = kctx.scratch["sr_sem"].at[SR_GROUP]
    if out:
        return pltpu.make_async_copy(kctx.scratch["sr_qst"], q, sem)
    return pltpu.make_async_copy(q, kctx.scratch["sr_qrd"], sem)


def _queue_append(kctx, u, parent) -> None:
    iv = kctx.ivalues
    qt = iv[S_QTAIL]
    lane = (qt & 63) * 2
    kctx.scratch["sr_qst"][lane] = u
    kctx.scratch["sr_qst"][lane + 1] = parent
    iv[S_QTAIL] = qt + 1

    @pl.when((qt & 63) == 63)
    def _():
        cp = _queue_row_copy(kctx, qt >> 6, out=True)
        cp.start()
        cp.wait()
        iv[S_HBM_WR] = iv[S_HBM_WR] + EBLOCK


def _queue_vertex(kctx, p):
    """The vertex at queue position ``p`` (< S_QTAIL): from the staging
    row while its row has not been written out, else from the reader's
    row, fetched when ``p`` enters a new one."""
    iv = kctx.ivalues
    row = p >> 6
    staged = row == (iv[S_QTAIL] >> 6)

    @pl.when(jnp.logical_not(staged) & (iv[S_RD_ROW] != row + 1))
    def _():
        cp = _queue_row_copy(kctx, row, out=False)
        cp.start()
        cp.wait()
        iv[S_RD_ROW] = row + 1
        iv[S_HBM_RD] = iv[S_HBM_RD] + EBLOCK

    lane = (p & 63) * 2
    return jnp.where(
        staged, kctx.scratch["sr_qst"][lane], kctx.scratch["sr_qrd"][lane]
    )


def _make_kernel(graph_n: int, budget: int) -> Callable:
    """The maker of one graph size (module comment above). Its descriptor:
    ``[first, key]``; ``first`` is 1 on the host's seed, which clears the
    filter and enqueues the key, and 0 ever after."""
    nwords = SR_LEAD + _bits_rows(graph_n) * EBLOCK

    def gather(ctx, qh, g) -> None:
        """The next ``g`` queue vertices and their table words into
        ``sr_grp``: every row DMA in flight before the first wait."""
        iv, grp = ctx.ivalues, ctx.scratch["sr_grp"]
        vt, sems = ctx.scratch["sr_vt"], ctx.scratch["sr_sem"]

        def copy(i):
            return pltpu.make_async_copy(
                ctx.data["vtab"].at[grp[4 * i] >> 6], vt.at[i], sems.at[i]
            )

        for i in range(SR_GROUP):
            @pl.when(i < g)
            def _(i=i):
                grp[4 * i] = _queue_vertex(ctx, qh + i)
                copy(i).start()

        for i in range(SR_GROUP):
            @pl.when(i < g)
            def _(i=i):
                copy(i).wait()
                lane = (grp[4 * i] & 63) * 2
                grp[4 * i + 1] = vt[i, lane]
                grp[4 * i + 2] = vt[i, lane + 1]

        iv[S_HBM_RD] = iv[S_HBM_RD] + g * EBLOCK
        iv[S_QHEAD] = qh + g

    def kernel(ctx) -> None:
        iv = ctx.ivalues
        bits = ctx.scratch["sr_bits"]
        grp = ctx.scratch["sr_grp"]

        @pl.when(ctx.arg(0) != 0)
        def _():
            def clear(i, _):
                bits[i] = jnp.where(i < SR_LEAD, -1, 0)  # the lead stays set
                return 0

            jax.lax.fori_loop(0, nwords, clear, 0)
            key = ctx.arg(1)
            bits[SR_LEAD + (key >> 5)] = jnp.int32(1) << (key & 31)
            ctx.scratch["sr_qst"][0] = key
            ctx.scratch["sr_qst"][1] = key
            iv[S_QTAIL] = 1
            iv[S_LEVEL_END] = 1
            iv[S_FRONT_MAX] = 1
            ctx.set_arg(ctx.idx, 0, 0)

        def refill(made):
            """The group is read out: ``(k, n, over)`` of the next one,
            gathered, or of none and the call over. Where the level's
            queue is read out too and EXPANDs of it are still to run, the
            next level's end is not known: the call is over. With none the
            level is whole: open the next, or end the search."""
            qh, lend, qt = iv[S_QHEAD], iv[S_LEVEL_END], iv[S_QTAIL]

            @pl.when((qh == lend) & (made == 0) & (qt > lend))
            def _():
                lvl = iv[S_LEVEL] + 1
                iv[S_LEVEL] = lvl
                iv[S_LSTART + jnp.minimum(lvl, SR_LEVELS - 1)] = lend
                iv[S_LEVEL_END] = qt
                iv[S_FRONT_MAX] = jnp.maximum(iv[S_FRONT_MAX], qt - lend)

            g = jnp.minimum(iv[S_LEVEL_END] - qh, SR_GROUP)

            @pl.when(g > 0)
            def _():
                gather(ctx, qh, g)

            return jnp.int32(0), g, g == 0

        def trip(s):
            """One vertex, the group's ``k``-th: its blocks from the
            ``done``-th on become EXPANDs, in block order, while the table
            has room. A vertex cut short stays the ``k``-th."""
            k, n, done, made, trips, _ = s
            k, n, over = jax.lax.cond(
                k >= n, lambda: refill(made),
                lambda: (k, n, jnp.bool_(False)),
            )
            v, first, deg = grp[4 * k], grp[4 * k + 1], grp[4 * k + 2]
            # A shift, not a divide: the scalar core has no cheap one.
            end = first + ((deg + EBLOCK - 1) >> EBLOCK_SHIFT)
            blk = first + done
            upto = jnp.where(
                over, blk, jnp.minimum(end, blk + (budget - made))
            )

            def expand(c):
                b, left = c
                cnt = jnp.minimum(left, EBLOCK)
                ctx.spawn(FR_EXPAND, [v, b, v, cnt], succ0=ctx.idx, nargs=4)
                return b + 1, left - cnt

            jax.lax.while_loop(
                lambda c: c[0] < upto, expand,
                (blk, deg - (done << EBLOCK_SHIFT)),
            )
            made = made + (upto - blk)
            whole = (upto == end) & jnp.logical_not(over)
            return (
                k + whole.astype(jnp.int32), n,
                jnp.where(whole, 0, upto - first), made, trips + 1,
                (made < budget) & jnp.logical_not(over),
            )

        # The loop's state rides in its carry: loaded here and stored
        # below, once a call, so a call that ``budget`` cuts in the middle
        # of a vertex goes on there.
        slots = (S_GRP_K, S_GRP_N, S_CUR_DONE)
        *hand, made, trips, _ = jax.lax.while_loop(
            lambda s: s[-1], trip,
            (*(iv[x] for x in slots), jnp.int32(0), iv[S_TRIPS],
             jnp.bool_(True)),
        )
        for slot, x in zip(slots, hand):
            iv[slot] = x
        iv[S_TRIPS] = trips
        iv[S_EXPANDS] = iv[S_EXPANDS] + made

        @pl.when(made > 0)
        def _():
            ctx.become(SR_MAKE, made)

        @pl.when((made == 0) & ((iv[S_QTAIL] & 63) != 0))
        def _():  # the search is over: the queue's last, part-filled row
            cp = _queue_row_copy(ctx, iv[S_QTAIL] >> 6, out=True)
            cp.start()
            cp.wait()
            iv[S_HBM_WR] = iv[S_HBM_WR] + EBLOCK

    return kernel


def search_kernel() -> SearchKernel:
    """Breadth-first search to a parent array, per-vertex state in HBM
    (``GraphSearch`` runs it)."""
    return SearchKernel()


# ------------------------------------------------------------ host side

_KINDS: Dict[str, Callable[..., FrontierKernel]] = {
    "bfs": bfs_kernel,
    "sssp": sssp_kernel,
    "pagerank": pagerank_kernel,
}


def seed_frontier(
    builder: TaskGraphBuilder,
    graph: Graph,
    kind: str,
    src: int = 0,
    m0: int = 1 << 14,
    reps: int = 64,
) -> List[Tuple[int, ...]]:
    """Host seeding (mirrors the device relax exactly). BFS/SSSP: dist
    preset 0 at ``src`` (the caller's preset row carries it) and one
    EXPAND per block of ``src``. PageRank: every vertex receives the
    initial mass ``m0`` host-side - retained rank goes into the preset
    row, survivors seed their blocks. Returns the seeded arg tuples (the
    placement path deals them across devices)."""
    seeds: List[Tuple[int, ...]] = []
    if kind in ("bfs", "sssp"):
        v = int(src)
        if not 0 <= v < graph.n:
            raise ValueError(f"source {v} out of range [0, {graph.n})")
        for i in range(int(graph.blk_count[v])):
            seeds.append(
                (v, int(graph.blk_start[v]) + i, 0, graph.block_cnt(v, i))
            )
    elif kind == "pagerank":
        for v in range(graph.n):
            deg = int(graph.deg[v])
            qc = _pr_split(m0, deg)
            if m0 >= reps and qc > 0 and deg > 0:
                for i in range(int(graph.blk_count[v])):
                    seeds.append(
                        (
                            v,
                            int(graph.blk_start[v]) + i,
                            qc,
                            graph.block_cnt(v, i),
                        )
                    )
    else:
        raise ValueError(f"unknown frontier kind {kind!r} (bfs|sssp|pagerank)")
    if builder is not None:
        for args in seeds:
            builder.add(FR_EXPAND, args=list(args))
    return seeds


def _pr_seed_rank(graph: Graph, m0: int, reps: int) -> np.ndarray:
    """Rank retained by the host-side seed deliveries (the preset the
    device run starts from; mirrors seed_frontier's split decisions)."""
    rank = np.zeros(graph.n, np.int64)
    for v in range(graph.n):
        deg = int(graph.deg[v])
        qc = _pr_split(m0, deg)
        if m0 >= reps and qc > 0 and deg > 0:
            rank[v] = m0 - deg * qc
        else:
            rank[v] = m0
    return rank


def host_bfs(graph: Graph, src: int = 0) -> np.ndarray:
    """Exact hop distances (frontier BFS; INF where unreached)."""
    dist = np.full(graph.n, INF, np.int64)
    dist[src] = 0
    frontier = [int(src)]
    while frontier:
        nxt: List[int] = []
        for v in frontier:
            nd = dist[v] + 1
            for u in graph.adj[v]:
                if nd < dist[u]:
                    dist[u] = nd
                    nxt.append(int(u))
        frontier = nxt
    return dist.astype(np.int32)


def host_sssp(graph: Graph, src: int = 0) -> np.ndarray:
    """Exact shortest paths (Dijkstra; nonnegative int weights)."""
    import heapq

    dist = np.full(graph.n, INF, np.int64)
    dist[src] = 0
    heap: List[Tuple[int, int]] = [(0, int(src))]
    while heap:
        d, v = heapq.heappop(heap)
        if d > dist[v]:
            continue
        for u, w in zip(graph.adj[v], graph.adj_w[v]):
            nd = d + int(w)
            if nd < dist[u]:
                dist[u] = nd
                heapq.heappush(heap, (nd, int(u)))
    return dist.astype(np.int32)


def host_pagerank_push(
    graph: Graph, m0: int = 1 << 14, reps: int = 64
) -> Tuple[np.ndarray, int]:
    """Exact integer twin of the device push (same split, same fold
    rule, any processing order - the bit-identity reference arm).
    Returns (rank, deliveries)."""
    rank = _pr_seed_rank(graph, m0, reps)
    queue: List[Tuple[int, int]] = []
    # Seed deliveries: every surviving seed vertex pushes qc along each
    # out-edge (the queue order is irrelevant - the push is
    # schedule-independent, which is the property under test).
    for v in range(graph.n):
        deg = int(graph.deg[v])
        qc = _pr_split(m0, deg)
        if m0 >= reps and qc > 0 and deg > 0:
            for u in graph.adj[v]:
                queue.append((int(u), qc))
    deliveries = 0
    while queue:
        u, q = queue.pop()
        deliveries += 1
        deg = int(graph.deg[u])
        qc = _pr_split(q, deg)
        if q >= reps and qc > 0 and deg > 0:
            rank[u] += q - deg * qc
            for t in graph.adj[u]:
                queue.append((int(t), qc))
        else:
            rank[u] += q
    return rank.astype(np.int64), deliveries


def host_pagerank(
    graph: Graph,
    alpha: float = PR_NUM / PR_DEN,
    iters: int = 80,
    m0: float = 1.0,
) -> np.ndarray:
    """Float PageRank series the push approximates: rank = sum_k of the
    mass retained at step k, with dangling vertices absorbing fully
    (the push's fold rule). Normalized to ``m0`` seed mass per vertex."""
    m = np.full(graph.n, float(m0))
    rank = np.zeros(graph.n)
    deg = graph.deg.astype(np.float64)
    for _ in range(iters):
        keep = np.where(deg > 0, (1.0 - alpha) * m, m)
        rank += keep
        push = np.where(deg > 0, alpha * m / np.maximum(deg, 1), 0.0)
        m2 = np.zeros(graph.n)
        for v in range(graph.n):
            if push[v] > 0:
                np.add.at(m2, graph.adj[v], push[v])
        m = m2
    return rank + m  # fold the residual tail


# ------------------------------------------------------------ megakernel


def _default_lane_max_age(width: int) -> int:
    """Frontier builds default the ISSUE 10 age trigger ON at 4x the
    lane width (module docstring); HCLIB_TPU_LANE_MAX_AGE (handled by
    Megakernel itself) still overrides process-wide."""
    from ..runtime.env import env_set

    if env_set("HCLIB_TPU_LANE_MAX_AGE"):
        return None  # type: ignore[return-value]  # env wins
    return 4 * width


def make_frontier_megakernel(
    fk: FrontierKernel,
    graph: Graph,
    *,
    width: int = 8,
    prefetch: bool = True,
    capacity: int = 512,
    num_values: Optional[int] = None,
    interpret: Optional[bool] = None,
    trace=None,
    checkpoint: Optional[bool] = None,
    lane_max_age: Optional[int] = None,
    priority_buckets: Optional[int] = None,
    delta: Optional[int] = None,
) -> Megakernel:
    """Build a traversal's megakernel. ``width=0`` is the scalar-
    dispatch arm (the bit-identity reference); ``width>0`` routes EXPAND
    through the batch lanes with the double-buffered edge-slab prefetch,
    and arms the age-triggered firing policy (``lane_max_age``; default
    4*width, 0 disables).

    ``priority_buckets=B`` (batched builds only) arms the ISSUE 15
    priority tier: EXPANDs route into B bucket rings popped lowest-
    nonempty-first - bucket = dist // ``delta`` for BFS/SSSP (TRUE
    delta-stepping: ordered relaxation replaces label-correction
    re-relaxation, so the executed-EXPAND count drops - the raw-speed
    story) or the residual-magnitude band for PageRank (small deliveries
    fold first, bounding the live frontier). Exactness never depends on
    it: the result is schedule-independent (certified via ``si_claim``)
    and bit-identical to the unordered arm."""
    search = isinstance(fk, SearchKernel)
    if num_values is None:
        num_values = S_WORDS + 8 if search else graph.num_value_slots + 8
    if priority_buckets is None:
        # The process-wide spelling reaches the builder too (the
        # builder must know: it disables the cross-round prefetch and
        # rescales the age default for bucketed builds).
        from ..runtime.env import env_int

        priority_buckets = env_int("HCLIB_TPU_PRIORITY_BUCKETS", None)
    priority_buckets = int(priority_buckets or 0)
    if priority_buckets and not width:
        raise ValueError(
            "priority_buckets needs the batched arm (width > 0): the "
            "bucket rings layer over the per-kind batch lanes"
        )
    if delta is None and not search:
        delta = default_delta(graph)
    if width:
        # Bucketed builds genuinely run WITHOUT the cross-round
        # prefetch (the next firing ring is chosen at fire time, so
        # there is no prospective next batch; the scheduler would never
        # announce one anyway) - the spec says so too, so describe()
        # and the prefetch-protocol analysis see the build that
        # actually runs. Bucket rings still pop FIFO (the scheduler's
        # bucket-ring discipline, independent of spec.prefetch).
        prefetch = bool(prefetch) and not priority_buckets
        spec = BatchSpec(
            fk.batch_body,
            width=width,
            prefetch=prefetch,
            drain=fk.batch_drain if prefetch else None,
            # The priority callable is carried unconditionally (it is
            # only consulted when priority_buckets arms the tier, so an
            # unbucketed build stays byte-identical - asserted in
            # tests/test_priority.py).
            priority=_bucket_fn(fk.name, delta, getattr(fk, "reps", 64)),
        )
        kernels = [(fk.name, _batch_stub)]
        route = {fk.name: spec}
        scratch = fk.batch_scratch(width)
        if lane_max_age is None:
            if priority_buckets:
                # Bucketed builds arm the SAME age-fire guard but at
                # the DRAIN-PERIOD scale (2x capacity - a routing
                # drain can hold a ring unfired for at most ~capacity
                # rounds, the ring size), not PR 10's 4*width latency
                # tune: at 4*width the guard fires constantly during
                # long routing drains and every forced fire jumps the
                # bucket order (measured: executed-EXPAND ratio decays
                # 0.63x -> 0.86x and the PageRank live-set fix washes
                # out 0.26x -> 0.92x). At 2x capacity it is a pure
                # starvation backstop: zero fires in steady state,
                # high buckets still provably bounded against a
                # pathological low-bucket refill.
                from ..runtime.env import env_set

                lane_max_age = (
                    None  # env wins, Megakernel resolves it
                    if env_set("HCLIB_TPU_LANE_MAX_AGE")
                    else 2 * capacity
                )
            else:
                lane_max_age = _default_lane_max_age(width)
    else:
        kernels = [(fk.name, fk.scalar_kernel)]
        route = None
        scratch = fk.scalar_scratch()
        lane_max_age = 0 if lane_max_age is None else lane_max_age
    if search:
        if priority_buckets:
            raise ValueError(
                "a search is level-synchronous by its maker: it takes no "
                "priority_buckets"
            )
        if capacity <= SR_SPARE:
            raise ValueError(f"a search needs capacity > {SR_SPARE}")
        kernels.append(
            ("sr_make", _make_kernel(graph.n, capacity - SR_SPARE))
        )
        scratch.update(fk.search_scratch(graph))
    if not search and fk.st_base is not None and fk.st_base != graph.st_base:
        raise ValueError(
            "FrontierKernel is already bound to a different graph layout "
            f"(st_base {fk.st_base} vs {graph.st_base}): build a fresh "
            "kernel per graph - megakernels trace lazily, so rebinding "
            "would silently retarget an earlier build's state region"
        )
    if not search:
        fk.st_base = graph.st_base
    mk = Megakernel(
        kernels=kernels,
        route=route,
        data_specs=fk.data_specs(graph),
        scratch_specs=scratch,
        capacity=capacity,
        num_values=num_values,
        succ_capacity=8,
        interpret=interpret,
        trace=trace,
        checkpoint=checkpoint,
        lane_max_age=lane_max_age,
        priority_buckets=priority_buckets,
        # A search writes its queue alone; the adjacency and the vertex
        # table are read where they lie.
        read_only=["indices", "vtab"] if search else (),
    )
    # Stamp the graph layout the traced kernel is bound to: the relax
    # closures bake st_base (and the data specs bake nblocks) into the
    # trace, so running this build over a DIFFERENT graph layout would
    # silently read the wrong state region - run_frontier refuses it.
    mk._frontier_layout = (
        fk.name, graph.n, graph.nblocks, 0 if search else graph.st_base
    )
    # Schedule-independence claim (the exactness model this module's
    # docstring promises): certified lazily by analysis/model.py - K
    # permuted pop orders to the fixpoint - and surfaced in describe()
    # beside the reshard classification.
    kind = {"fr_bfs": "bfs", "fr_sssp": "sssp",
            "fr_pagerank": "pagerank"}.get(fk.name)
    if kind is not None:
        # Bucketed builds extend the claim with (buckets, delta) so
        # certify_claim includes the BUCKETED pop order among the K
        # permutations it proves reach the same fixpoint - the priority
        # tier's exactness gate (the 3-tuple spelling stays for
        # unbucketed builds; certify_claim parses both).
        mk.si_claim = (
            ("frontier", kind, getattr(fk, "reps", None),
             priority_buckets, delta)
            if priority_buckets
            else ("frontier", kind, getattr(fk, "reps", None))
        )
    return mk


# ---------------------------------------------------------------- runner


def run_frontier(
    kind: str,
    graph: Graph,
    src: int = 0,
    *,
    width: int = 8,
    prefetch: bool = True,
    m0: int = 1 << 14,
    reps: int = 64,
    capacity: int = 512,
    interpret: Optional[bool] = None,
    trace=None,
    fuel: Optional[int] = None,
    lane_max_age: Optional[int] = None,
    priority_buckets: Optional[int] = None,
    delta: Optional[int] = None,
    mk: Optional[Megakernel] = None,
    placement=None,
    mesh=None,
    runner: str = "sharded",
    quantum: int = 64,
    window: int = 16,
    hop_order=None,
) -> Tuple[np.ndarray, Dict]:
    """Run one traversal to completion; returns ``(result, info)`` where
    ``result`` is the distance array (bfs/sssp, int32, INF = unreached)
    or the fixed-point rank array (pagerank, int64, ``m0`` mass units
    per vertex seeded). ``info`` gains ``edges`` (TEPS numerator) and
    ``relaxations``.

    Single device when ``placement`` is None. With a placement the seed
    descriptors deal across the per-device ready rings through
    ``runtime.locality.resolve_placement`` (the forasync placement
    discipline - data, not code), EXPANDs migrate through the chosen
    runner's steal exchange (``runner='sharded'`` fast-interpret, or
    ``'resident'`` - Mosaic interpret - whose XOR-hop exchange takes the
    graph-ordered ``hop_order``), per-device distance caches combine by
    elementwise min and ranks/counters by sum."""
    if kind not in _KINDS:
        raise ValueError(f"unknown frontier kind {kind!r} (bfs|sssp|pagerank)")
    fk = _KINDS[kind](reps=reps) if kind == "pagerank" else _KINDS[kind]()
    if mk is None:
        mk = make_frontier_megakernel(
            fk, graph, width=width, prefetch=prefetch, capacity=capacity,
            interpret=interpret, trace=trace, lane_max_age=lane_max_age,
            priority_buckets=priority_buckets, delta=delta,
        )
    else:
        # A prebuilt megakernel owns its own (already-bound) kernel; it
        # must have been built for THIS graph's layout (the trace bakes
        # st_base and the slab shapes in - a mismatch would silently
        # relax the wrong value slots). The local fk only supplies the
        # layout helpers below.
        expect = (fk.name, graph.n, graph.nblocks, graph.st_base)
        bound = getattr(mk, "_frontier_layout", None)
        if bound != expect:
            raise ValueError(
                f"prebuilt megakernel is bound to frontier layout "
                f"{bound}, but this run wants {expect} "
                "(kind, n, nblocks, st_base): build one megakernel per "
                "(kind, graph) via make_frontier_megakernel"
            )
        fk.st_base = graph.st_base
    st = graph.st_base
    iv = graph.preset_values(mk.num_values, fk.state0)
    if kind in ("bfs", "sssp"):
        iv[st + int(src)] = 0
    else:
        iv[st : st + graph.n] = _pr_seed_rank(graph, m0, reps).astype(
            np.int32
        )
    seeds = seed_frontier(None, graph, kind, src=src, m0=m0, reps=reps)
    data = fk.data(graph)

    def finish(iv_rows: np.ndarray, info: Dict) -> Tuple[np.ndarray, Dict]:
        rows = np.asarray(iv_rows, np.int64)
        if rows.ndim == 1:
            rows = rows[None]
        states = rows[:, st : st + graph.n]
        if kind in ("bfs", "sssp"):
            result = states.min(axis=0).astype(np.int32)
        else:
            result = states.sum(axis=0) - (
                (rows.shape[0] - 1) * iv[st : st + graph.n].astype(np.int64)
            )  # presets replicate per device; count the seed rank once
        info["edges"] = int(rows[:, V_EDGES].sum())
        info["relaxations"] = int(rows[:, V_RELAX].sum())
        return result, info

    if placement is None:
        b = TaskGraphBuilder()
        b.reserve_values(graph.num_value_slots)
        for args in seeds:
            b.add(FR_EXPAND, args=list(args))
        iv_o, _, info = mk.run(
            b, data=dict(data), ivalues=iv,
            fuel=1 << 22 if fuel is None else fuel,
        )
        return finish(iv_o, info)

    if fuel is not None:
        # The mesh runners budget by quantum/rounds, not fuel; silently
        # dropping a caller's bound would turn "bounded traversal" into
        # "unbounded run".
        raise ValueError(
            "fuel= applies to the single-device path only; bound a mesh "
            "run with quantum= (per-round budget) instead"
        )
    p = resolve_placement(placement)
    from ..parallel.mesh import cpu_mesh

    if mesh is None:
        if not isinstance(p, MeshPlacement):
            raise ValueError(
                "a dist-func placement needs an explicit mesh= (a "
                "MeshPlacement knows its own device count)"
            )
        mesh = cpu_mesh(p.ndev, axis_name="q")
    ndev = int(np.prod(mesh.devices.shape))
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].reserve_values(graph.num_value_slots)
    dev_of = p.device_of if isinstance(p, MeshPlacement) else (
        lambda i, tot: p(1, i, tot)
    )
    pcounts = [0] * ndev
    for i, args in enumerate(seeds):
        d = int(dev_of(i, max(1, len(seeds))))
        if not 0 <= d < ndev:
            raise ValueError(
                f"placement sent seed {i} to device {d} (mesh has {ndev})"
            )
        builders[d].add(FR_EXPAND, args=list(args))
        pcounts[d] += 1
    stacked_iv = np.broadcast_to(iv, (ndev,) + iv.shape).copy()
    stacked = {
        k: np.broadcast_to(v, (ndev,) + v.shape).copy()
        for k, v in data.items()
    }
    if runner == "sharded":
        from .sharded import ShardedMegakernel

        if hop_order is None and isinstance(p, MeshPlacement):
            hop_order = p.hop_order()
        smk = ShardedMegakernel(mk, mesh, migratable_fns=[FR_EXPAND])
        iv_o, _, info = smk.run(
            builders, data=stacked, ivalues=stacked_iv, steal=True,
            quantum=quantum, window=window, hop_order=hop_order,
        )
    elif runner == "resident":
        from .resident import ResidentKernel

        if hop_order is None and isinstance(p, MeshPlacement):
            hop_order = p.xor_hop_order()
        rk = ResidentKernel(
            mk, mesh, migratable_fns=[FR_EXPAND], window=window,
            homed=False,
        )
        iv_o, _, info = rk.run(
            builders, data=stacked, ivalues=stacked_iv, quantum=quantum,
            hop_order=hop_order,
        )
    else:
        raise ValueError(
            f"unknown frontier runner {runner!r} (sharded|resident)"
        )
    info["placement_counts"] = pcounts
    info["hop_order"] = list(hop_order) if hop_order else None
    result, info = finish(iv_o, info)
    return result, info


# ---------------------------------------------------------------- search


class GraphSearch:
    """Breadth-first searches over one resident graph (Graph500's kernel
    2): built once a graph - the ``Megakernel``, and the adjacency and
    vertex table uploaded to HBM, where they stay - and called once a
    search key.

    ``fuel`` bounds the tasks (EXPANDs and maker calls) of one search; a
    search that needs more stalls (``StallError``)."""

    def __init__(
        self,
        graph: Graph,
        *,
        width: int = 8,
        capacity: int = 128,
        interpret: Optional[bool] = None,
        fuel: int = 1 << 30,
    ) -> None:
        self.graph = graph
        self.fuel = int(fuel)
        self.mk = make_frontier_megakernel(
            search_kernel(), graph, width=width, capacity=capacity,
            interpret=interpret,
        )
        qspec = self.mk.data_specs["queue"]
        with self._device():
            self._data = {
                "indices": jnp.asarray(graph.indices),
                "vtab": jnp.asarray(graph.vtab()),
                "queue": jnp.zeros(qspec.shape, qspec.dtype),
            }

    def _device(self):
        """Where the graph lives: the default device, or - an interpreter
        build - the host CPU, as ``Megakernel`` pins its own program."""
        import contextlib

        if self.mk.interpret:
            return jax.default_device(jax.devices("cpu")[0])
        return contextlib.nullcontext()

    def bfs(self, key: int) -> Tuple[np.ndarray, Dict]:
        """One search from ``key``: ``(parent, info)``. ``parent`` is
        int32, -1 where the search did not reach, ``parent[key] == key``.
        ``info`` is ``Megakernel.run``'s with the search's own counters
        under ``info["search"]``."""
        key = int(key)
        if not 0 <= key < self.graph.n:
            raise ValueError(f"key {key} out of range [0, {self.graph.n})")
        with span("g500.seed"):
            b = TaskGraphBuilder()
            b.reserve_values(S_WORDS)
            b.add(SR_MAKE, args=[1, key])
            iv = np.zeros(self.mk.num_values, np.int32)
        with span("g500.search"):
            iv, out, info = self.mk.run(
                b, data=self._data, ivalues=iv, fuel=self.fuel
            )
        self._data = out  # the queue was consumed and comes back
        with span("g500.readback"):
            # The queue unrolled: its first S_QTAIL pairs, each vertex once.
            pairs = np.asarray(out["queue"]).reshape(-1, 2)[: iv[S_QTAIL]]
            parent = np.full(self.graph.n, -1, np.int32)
            parent[pairs[:, 0]] = pairs[:, 1]
        levels = int(iv[S_LEVEL]) + 1
        tiers = info.get("tiers", {})
        info["search"] = {
            "edges": int(iv[V_EDGES]),
            "reached": int(iv[S_QTAIL]),
            "levels": levels,
            "level_starts": [
                int(x) for x in iv[S_LSTART : S_LSTART + min(levels,
                                                             SR_LEVELS)]
            ],
            "expands": int(iv[S_EXPANDS]),
            "maker_trips": int(iv[S_TRIPS]),
            "frontier_max": int(iv[S_FRONT_MAX]),
            "live_rows_max": info["allocated"],
            "capacity": self.mk.capacity,
            "batch_rounds": tiers.get("batch_rounds", 0),
            "batch_slots": tiers.get("batch_tasks", 0),
            "hbm_words_read": int(iv[S_HBM_RD]),
            "hbm_words_written": int(iv[S_HBM_WR]),
            "hit_groups": int(iv[S_HITS]),
        }
        return parent, info

    def blocks_of(self, parent: np.ndarray) -> int:
        """Adjacency blocks of the vertices ``parent`` reaches: what
        ``expands`` is when every reached vertex is expanded once."""
        return int(
            self.graph.blk_count[np.asarray(parent) >= 0].sum(dtype=np.int64)
        )
