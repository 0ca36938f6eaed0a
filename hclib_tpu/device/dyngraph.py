"""Dynamic graph service: mutable blocked-CSR adjacency + incremental
recompute, served multi-tenant (ISSUE 20).

The frontier tier (frontier.py) traverses a STATIC blocked-CSR
adjacency; a production graph service takes edge inserts while queries
run. The substrate already fits: an edge insert is just one more task
descriptor kind. This module adds

**The mutable adjacency.** ``DynGraph`` pre-allocates ``spare`` edge
blocks per vertex in HBM behind the static rows: vertex ``v``'s spares
occupy rows ``[spare_base + v*spare, spare_base + (v+1)*spare)`` of the
same ``indices``/``weights`` arrays the static tier DMAs. The layout is
a PURE FUNCTION of (v, ordinal) - no link table, no allocation order -
so a block id means the same thing on every mesh replica and a migrated
EXPAND stays physically meaningful wherever it lands. The per-vertex
append cursor is the vertex's own live block count (``vt[1]``) in the
SMEM vertex table: all blocks are full except the tail, so the splice
target and position derive from ``(deg, blk_count)`` alone.

**The UPDATE kind.** ``UPDATE(u, v, w, uid)`` splices edge ``u -> v``
into u's chain in-kernel: DMA the tail block row into VMEM, set the
next lane, DMA it back (read-modify-write), or - when the tail is full -
blind-write a freshly-built row into the next spare block (the append
cursor owns fresh rows uniquely, hclint's documented blind-overwrite
exemption). No CAS anywhere: updates to one vertex serialize through
the batch body's slot order and the monotone SMEM folds, and the
``uid``-indexed applied flag makes every splice idempotent - which is
what lets the mesh path BROADCAST the full update stream to every
device (UPDATE is non-migratable; only EXPANDs steal) and lets reshard
re-deliver residue safely. After the splice the body relaxes the new
edge with u's CURRENT label and spawns v's blocks only if it improved -
incremental recompute touches exactly the rows whose labels can move.

**Exactness.** BFS/SSSP labels are monotone min-folds, so the
incremental fixpoint is bit-identical to a from-scratch run on the
mutated graph - per-device label arrays are local caches combined by
elementwise min, and a replica that has not yet applied a splice reads
a CLAMPED live-edge count (``_eff_cnt``) so it never relaxes a
half-visible edge; its own eventual splice-relax covers the edge with
whatever label u has by then, and transitivity does the rest. PageRank
splices are mass-neutral (degree changes only steer FUTURE splits), so
total mass conserves exactly while the result is schedule-dependent -
the certificate claims conservation, not identity. The
``("dyngraph", kind, reps, buckets, updates)`` claim is certified by
analysis/model.py against permuted update/expand interleavings.

**Serving.** Queries are their own kind (``QUERY(v)`` publishes the
label through the descriptor's out slot, so egress mailboxes resolve
query futures at retirement), and on priority-bucketed builds updates
and queries route to DISTINCT priority classes: the ``update_priority``
knob (HCLIB_TPU_DYNGRAPH_UPDATE_PRIORITY) pins the UPDATE lane's
bucket while queries default to the lowest class.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.locality import MeshPlacement, resolve_placement
from .descriptor import TaskGraphBuilder
from .frontier import (
    EBLOCK,
    FR_EXPAND,
    INF,
    V_EDGES,
    V_RELAX,
    VT_BASE,
    FrontierKernel,
    Graph,
    _bucket_fn,
    _pr_seed_rank,
    bfs_kernel,
    default_delta,
    host_bfs,
    host_pagerank_push,
    host_sssp,
    pagerank_kernel,
    seed_frontier,
    sssp_kernel,
)
from .megakernel import BatchSpec, Megakernel, _batch_stub

__all__ = [
    "DG_UPDATE",
    "DG_QUERY",
    "V_UPDATES",
    "V_FREE",
    "V_DROPPED",
    "V_QUERIES",
    "DynGraph",
    "DynFrontierKernel",
    "SpliceKernel",
    "QueryKernel",
    "make_dyngraph_megakernel",
    "run_dyngraph",
    "reshard_dyngraph",
    "serve_dyngraph",
    "host_dyngraph",
    "host_incremental",
    "host_incremental_pagerank",
]

# Kernel-table ids: EXPAND keeps the frontier tier's fixed id 0 (so
# ``_spawn_blocks``-shaped spawns and ``migratable_fns=[FR_EXPAND]``
# carry over unchanged); the service kinds follow.
DG_UPDATE = 1
DG_QUERY = 2

# Value-slot counters beyond the frontier tier's pair (V_EDGES=0,
# V_RELAX=1): all combine across devices by sum except V_FREE, which is
# per-replica spare-block occupancy (identical on every replica once
# the same update set applied).
V_UPDATES = 2  # splices applied (idempotent: counted once per uid)
V_FREE = 3     # spare blocks in use (the global free-cursor ledger)
V_DROPPED = 4  # splices dropped on spare exhaustion (overflow-flagged)
V_QUERIES = 5  # QUERY descriptors served


def _env_spare_blocks() -> int:
    from ..runtime.env import env_int

    s = env_int("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", 2)
    if s < 1:
        raise ValueError(
            f"HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS={s} must be >= 1"
        )
    return int(s)


def _env_update_priority() -> int:
    from ..runtime.env import env_int

    return int(env_int("HCLIB_TPU_DYNGRAPH_UPDATE_PRIORITY", 0))


class DynGraph(Graph):
    """Blocked-CSR adjacency with per-vertex spare blocks and a
    registered update stream. The STATIC arrays (``deg``/``blk_count``/
    ``adj``/block prefixes) stay immutable host-side - updates ride as
    descriptors and mutate the DEVICE copy in-kernel; the host mirror
    (``updates``) feeds the twin, the certifier, and reshard's
    canonical-rebuild path."""

    def __init__(
        self,
        n: int,
        src: np.ndarray,
        dst: np.ndarray,
        weights: Optional[np.ndarray] = None,
        *,
        spare_blocks: Optional[int] = None,
        upd_cap: int = 256,
    ) -> None:
        super().__init__(n, src, dst, weights)
        spare = (
            _env_spare_blocks() if spare_blocks is None
            else int(spare_blocks)
        )
        if spare < 0:
            # 0 is a legal DEGENERATE config (every need-new splice
            # drops, overflow-flagged) - the drop-path test spelling;
            # the env knob keeps its >= 1 floor for real builds.
            raise ValueError(f"spare_blocks must be >= 0, got {spare}")
        self.spare = spare
        self.spare_base = self.nblocks  # static rows end here
        self.static_nblocks = self.nblocks
        self.nblocks = self.spare_base + self.n * spare
        self.indices = np.concatenate(
            [self.indices, np.full((self.n * spare, EBLOCK), -1, np.int32)]
        )
        self.weights = np.concatenate(
            [self.weights, np.zeros((self.n * spare, EBLOCK), np.int32)]
        )
        self.upd_cap = int(upd_cap)
        if self.upd_cap < 1:
            raise ValueError(f"upd_cap must be >= 1, got {upd_cap}")
        self.updates: List[Tuple[int, int, int]] = []

    # -- value-slot layout (counters | vt | static-counts | flags | state) --

    @property
    def bcs_base(self) -> int:
        """Immutable static block counts, one word per vertex: the
        boundary between static rows and spare ordinals that both the
        dyn spawner and the clamp read back after vt[1] mutates."""
        return VT_BASE + 3 * self.n

    @property
    def flag_base(self) -> int:
        """Applied-update flags, one word per uid (idempotence)."""
        return self.bcs_base + self.n

    @property
    def st_base(self) -> int:
        return self.flag_base + self.upd_cap

    def preset_values(self, num_values: int, state0: int) -> np.ndarray:
        iv = super().preset_values(num_values, state0)
        iv[self.bcs_base : self.bcs_base + self.n] = self.blk_count
        return iv

    # -- the update stream --

    def add_update(self, u: int, v: int, w: int = 1) -> int:
        """Register edge insert ``u -> v`` (weight ``w``); returns its
        uid (the applied-flag index every replica keys idempotence on)."""
        u, v, w = int(u), int(v), int(w)
        if not (0 <= u < self.n and 0 <= v < self.n):
            raise ValueError(
                f"update endpoints ({u}, {v}) out of range [0, {self.n})"
            )
        if w < 0:
            raise ValueError(f"update weight must be >= 0, got {w}")
        uid = len(self.updates)
        if uid >= self.upd_cap:
            raise ValueError(
                f"update stream exceeds upd_cap={self.upd_cap}: size the "
                "applied-flag region up (DynGraph(upd_cap=))"
            )
        self.updates.append((u, v, w))
        return uid

    def spare_needed(self) -> int:
        """Spare blocks the registered stream consumes (host mirror of
        the device free-cursor ledger; drops excluded)."""
        deg = self.deg.astype(np.int64).copy()
        bc = self.blk_count.astype(np.int64).copy()
        used = 0
        for u, _v, _w in self.updates:
            if deg[u] == bc[u] * EBLOCK:
                if bc[u] - int(self.blk_count[u]) >= self.spare:
                    continue  # dropped on-device, consumes nothing
                bc[u] += 1
                used += 1
            deg[u] += 1
        return used

    def mutated(self, count: Optional[int] = None) -> Graph:
        """The host twin's graph: static edges + the first ``count``
        updates (all by default), as a plain static ``Graph`` - the
        from-scratch reference arm the incremental fixpoint must match
        bit-for-bit (bfs/sssp) or conserve mass against (pagerank).
        Updates the device would DROP (spare exhaustion) are excluded,
        mirroring the in-kernel bounds check exactly."""
        ups = self.updates if count is None else self.updates[:count]
        deg = self.deg.astype(np.int64).copy()
        bc = self.blk_count.astype(np.int64).copy()
        kept: List[Tuple[int, int, int]] = []
        for u, v, w in ups:
            if deg[u] == bc[u] * EBLOCK:  # tail full: needs a new block
                if bc[u] - int(self.blk_count[u]) >= self.spare:
                    continue  # device drops it (overflow-flagged)
                bc[u] += 1
            deg[u] += 1
            kept.append((u, v, w))
        src0 = np.repeat(np.arange(self.n), self.deg)
        dst0 = (
            np.concatenate(self.adj) if self.m else np.zeros(0, np.int64)
        )
        w0 = (
            np.concatenate(self.adj_w) if self.m else np.zeros(0, np.int64)
        )
        src = np.concatenate([src0, np.asarray([u for u, _, _ in kept])])
        dst = np.concatenate([dst0, np.asarray([v for _, v, _ in kept])])
        ww = np.concatenate([w0, np.asarray([w for _, _, w in kept])])
        return Graph(self.n, src.astype(np.int64), dst.astype(np.int64),
                     ww.astype(np.int64))


# ---------------------------------------------------------- device tier


class DynFrontierKernel(FrontierKernel):
    """A frontier kernel bound to a mutable adjacency: EXPANDs clamp
    their live-edge count to the LOCAL vertex table (a replica that has
    not applied a splice yet must not read past its own live edges),
    and improving relaxes spawn through the two-range spare-aware
    spawner the factory injected."""

    def __init__(self, name, relax, weighted, state0,
                 graph: DynGraph) -> None:
        super().__init__(name, relax, weighted, state0)
        self.graph = graph

    def _eff_cnt(self, kctx, v, blk, cnt):
        g = self.graph
        vt = VT_BASE + 3 * v
        bs = kctx.ivalues[vt]
        deg = kctx.ivalues[vt + 2]
        bcs = kctx.ivalues[g.bcs_base + v]
        ordinal = jnp.where(
            blk >= jnp.int32(g.spare_base),
            bcs + (blk - jnp.int32(g.spare_base) - v * jnp.int32(g.spare)),
            blk - bs,
        )
        live = jnp.clip(deg - ordinal * EBLOCK, 0, EBLOCK)
        return jnp.minimum(cnt, live)


def _dyn_spawn(graph: DynGraph) -> Callable:
    """The spare-aware block spawner: static rows ``[bs, bs+min(bc,
    bcs))`` then spare ordinals ``[0, bc - min(bc, bcs))`` - two
    contiguous ranges, each block's live count derived from ``deg``
    exactly as the static spawner derives it."""
    spare_base, spare, bcs_base = (
        graph.spare_base, graph.spare, graph.bcs_base,
    )

    def spawn(kctx, u, carry) -> None:
        vt = VT_BASE + 3 * u
        bs = kctx.ivalues[vt]
        bc = kctx.ivalues[vt + 1]
        deg = kctx.ivalues[vt + 2]
        bcs = kctx.ivalues[bcs_base + u]
        ns = jnp.minimum(bc, bcs)

        def sp_static(i, _):
            cnt = jnp.clip(deg - i * EBLOCK, 0, EBLOCK)
            kctx.spawn(FR_EXPAND, [u, bs + i, carry, cnt], nargs=4)
            return 0

        jax.lax.fori_loop(0, ns, sp_static, 0)

        def sp_spare(j, _):
            i = bcs + j
            cnt = jnp.clip(deg - i * EBLOCK, 0, EBLOCK)
            kctx.spawn(
                FR_EXPAND,
                [u, jnp.int32(spare_base) + u * jnp.int32(spare) + j,
                 carry, cnt],
                nargs=4,
            )
            return 0

        jax.lax.fori_loop(0, bc - ns, sp_spare, 0)

    return spawn


def _dyn_frontier_kernel(kind: str, graph: DynGraph,
                         reps: int = 64) -> DynFrontierKernel:
    """The traversal family over a mutable adjacency: the SAME relax
    closures as the static tier (one relax trace = scalar/batched/mesh
    identity by construction), with the spare-aware spawner injected."""
    spawn = _dyn_spawn(graph)
    if kind == "bfs":
        base = bfs_kernel(spawn=spawn)
    elif kind == "sssp":
        base = sssp_kernel(spawn=spawn)
    elif kind == "pagerank":
        base = pagerank_kernel(reps=reps, spawn=spawn)
    else:
        raise ValueError(
            f"unknown dyngraph kind {kind!r} (bfs|sssp|pagerank)"
        )
    fk = DynFrontierKernel(
        base.name, base._relax, base.weighted, base.state0, graph
    )
    if kind == "pagerank":
        fk.reps = int(reps)
    return fk


class SpliceKernel:
    """The UPDATE kind: splice + incremental relax, both dispatch
    spellings off ONE ``_splice`` trace (the FrontierKernel pattern).

    Splice protocol (checked by hclint's ``check_splice``):
    - the tail append is a read-modify-write of the whole block row
      (HBM -> VMEM, set one lane, VMEM -> HBM), strictly ordered inside
      the slot so same-vertex updates in one batch serialize;
    - a FULL tail allocates the next spare ordinal and blind-writes a
      freshly built row - legal ONLY because the append cursor
      (``vt[1]``) owns fresh spare rows uniquely (the blind-overwrite
      exemption, rows >= spare_base);
    - no lane of a dyngraph build runs the cross-round prefetch (a
      prefetched slab could race the write-back of the same row).
    """

    def __init__(self, fk: DynFrontierKernel) -> None:
        self.fk = fk
        self.graph = fk.graph

    def scratch(self, slots: int) -> Dict[str, Any]:
        sc: Dict[str, Any] = {
            "dg_idx": pltpu.VMEM((slots, EBLOCK), jnp.int32),
            "dg_lsem": pltpu.SemaphoreType.DMA((slots,)),
        }
        if self.fk.weighted:
            sc["dg_wgt"] = pltpu.VMEM((slots, EBLOCK), jnp.int32)
        return sc

    def _splice(self, kctx, s: int, u, v, w, uid) -> None:
        g = self.graph
        vt = VT_BASE + 3 * u
        bs = kctx.ivalues[vt]
        bc = kctx.ivalues[vt + 1]
        deg = kctx.ivalues[vt + 2]
        bcs = kctx.ivalues[g.bcs_base + u]
        applied = kctx.ivalues[g.flag_base + uid]
        need_new = deg == bc * EBLOCK  # tail full (or no blocks yet)
        used = bc - bcs                # spare ordinals in use
        overflow = need_new & (used >= jnp.int32(g.spare))
        fresh = applied == 0
        kctx.flag_overflow(fresh & overflow)
        kctx.ivalues[V_DROPPED] = kctx.ivalues[V_DROPPED] + jnp.where(
            fresh & overflow, 1, 0
        )
        do = fresh & jnp.logical_not(overflow)
        nb = jnp.int32(g.spare_base) + u * jnp.int32(g.spare) + used
        # Tail row of the CURRENT chain (only read when ~need_new, where
        # bc >= 1): static row while the static tail has slack, else the
        # newest spare ordinal.
        tb_tail = jnp.where(bc <= bcs, bs + bc - 1, nb - 1)
        pos = deg - jnp.maximum(bc - 1, 0) * EBLOCK  # live edges in tail
        sem = kctx.scratch["dg_lsem"].at[s]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, EBLOCK), 1)

        @pl.when(do & need_new)
        def _():
            # Blind-write the fresh spare row: build it whole in VMEM
            # (new edge in lane 0, the static fill elsewhere) and DMA it
            # out - no read, the append cursor owns row ``nb`` uniquely.
            kctx.scratch["dg_idx"][s : s + 1, :] = jnp.where(
                lane == 0, v, jnp.int32(-1)
            )
            cp = pltpu.make_async_copy(
                kctx.scratch["dg_idx"].at[s], kctx.data["indices"].at[nb],
                sem,
            )
            cp.start()
            if self.fk.weighted:
                kctx.scratch["dg_wgt"][s : s + 1, :] = jnp.where(
                    lane == 0, w, jnp.int32(0)
                )
                cpw = pltpu.make_async_copy(
                    kctx.scratch["dg_wgt"].at[s],
                    kctx.data["weights"].at[nb], sem,
                )
                cpw.start()
                cpw.wait()
            cp.wait()

        @pl.when(do & jnp.logical_not(need_new))
        def _():
            # Read-modify-write the tail row: the only writer of lanes
            # >= pos is this slot (earlier same-vertex slots already
            # folded their bumps into deg/bc before this read).
            cp = pltpu.make_async_copy(
                kctx.data["indices"].at[tb_tail],
                kctx.scratch["dg_idx"].at[s], sem,
            )
            cp.start()
            cp.wait()
            row = kctx.scratch["dg_idx"][s : s + 1, :]
            kctx.scratch["dg_idx"][s : s + 1, :] = jnp.where(
                lane == pos, v, row
            )
            cpo = pltpu.make_async_copy(
                kctx.scratch["dg_idx"].at[s],
                kctx.data["indices"].at[tb_tail], sem,
            )
            cpo.start()
            cpo.wait()
            if self.fk.weighted:
                cpw = pltpu.make_async_copy(
                    kctx.data["weights"].at[tb_tail],
                    kctx.scratch["dg_wgt"].at[s], sem,
                )
                cpw.start()
                cpw.wait()
                wrow = kctx.scratch["dg_wgt"][s : s + 1, :]
                kctx.scratch["dg_wgt"][s : s + 1, :] = jnp.where(
                    lane == pos, w, wrow
                )
                cpwo = pltpu.make_async_copy(
                    kctx.scratch["dg_wgt"].at[s],
                    kctx.data["weights"].at[tb_tail], sem,
                )
                cpwo.start()
                cpwo.wait()

        @pl.when(do)
        def _():
            # Fold the ledger bumps AFTER the block write retires, so a
            # concurrent reader that sees the new deg also sees the
            # edge (the monotone-fold ordering the protocol relies on).
            kctx.ivalues[vt + 1] = jnp.where(need_new, bc + 1, bc)
            kctx.ivalues[vt + 2] = deg + 1
            kctx.ivalues[g.flag_base + uid] = 1
            kctx.ivalues[V_FREE] = kctx.ivalues[V_FREE] + jnp.where(
                need_new, 1, 0
            )
            kctx.ivalues[V_UPDATES] = kctx.ivalues[V_UPDATES] + 1
            if self.fk.name != "fr_pagerank":
                # Incremental recompute: relax the ONE new edge with u's
                # current label - the same relax trace EXPAND runs, so
                # an improvement re-spawns v's blocks and nothing else.
                du = kctx.ivalues[self.fk.st_base + u]
                self.fk.relax(kctx, v, w, du)

    def scalar_kernel(self, ctx) -> None:
        u, v, w, uid = (ctx.arg(i) for i in range(4))
        self._splice(ctx, 0, u, v, w, uid)

    def batch_body(self, ctx) -> None:
        for b in range(ctx.width):
            @pl.when(ctx.live(b))
            def _(b=b):
                kctx = ctx.slot_ctx(b)
                self._splice(
                    kctx, b, ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2),
                    ctx.arg(b, 3),
                )


class QueryKernel:
    """The QUERY kind: publish vertex ``v``'s current label through the
    descriptor's out slot (egress mailboxes turn that into the query
    future's value at retirement). Mid-run queries read the TENTATIVE
    label - the serving semantic; post-drain queries read the exact
    fixpoint (what the bit-identity tests assert)."""

    def __init__(self, fk: DynFrontierKernel) -> None:
        self.fk = fk

    def _query(self, kctx, set_out) -> None:
        v = kctx.arg(0)
        kctx.ivalues[V_QUERIES] = kctx.ivalues[V_QUERIES] + 1
        set_out(kctx.ivalues[self.fk.st_base + v])

    def scalar_kernel(self, ctx) -> None:
        self._query(ctx, ctx.set_out)

    def batch_body(self, ctx) -> None:
        for b in range(ctx.width):
            @pl.when(ctx.live(b))
            def _(b=b):
                kctx = ctx.slot_ctx(b)
                self._query(kctx, kctx.set_out)


# ------------------------------------------------------------ megakernel


def make_dyngraph_megakernel(
    kind: str,
    graph: DynGraph,
    *,
    width: int = 8,
    capacity: int = 512,
    num_values: Optional[int] = None,
    interpret: Optional[bool] = None,
    trace=None,
    checkpoint: Optional[bool] = None,
    lane_max_age: Optional[int] = None,
    priority_buckets: Optional[int] = None,
    delta: Optional[int] = None,
    update_priority: Optional[int] = None,
    reps: int = 64,
) -> Megakernel:
    """Build the dynamic-graph service megakernel: the traversal's
    EXPAND lane plus the UPDATE (splice) and QUERY kinds. ``width=0``
    is the all-scalar bit-identity arm; ``width>0`` routes every kind
    through its own batch lane - all with the cross-round prefetch OFF
    (the splice protocol: a prefetched slab must never race a block
    write-back). ``priority_buckets=B`` maps updates and queries to
    distinct priority classes: UPDATEs pin to bucket
    ``update_priority`` (default 0 - inserts beat queries), QUERYs to
    the lowest class, EXPANDs keep the traversal's own bucket function."""
    if kind not in ("bfs", "sssp", "pagerank"):
        raise ValueError(
            f"unknown dyngraph kind {kind!r} (bfs|sssp|pagerank)"
        )
    if not isinstance(graph, DynGraph):
        raise TypeError(
            "make_dyngraph_megakernel needs a DynGraph (the static "
            "Graph has no spare rows to splice into)"
        )
    fk = _dyn_frontier_kernel(kind, graph, reps=reps)
    upd = SpliceKernel(fk)
    qk = QueryKernel(fk)
    if num_values is None:
        num_values = graph.num_value_slots + 16
    if priority_buckets is None:
        from ..runtime.env import env_int

        priority_buckets = env_int("HCLIB_TPU_PRIORITY_BUCKETS", None)
    priority_buckets = int(priority_buckets or 0)
    if priority_buckets and not width:
        raise ValueError(
            "priority_buckets needs the batched arm (width > 0): the "
            "bucket rings layer over the per-kind batch lanes"
        )
    if update_priority is None:
        update_priority = _env_update_priority()
    update_priority = int(update_priority)
    if priority_buckets:
        update_priority = max(0, min(update_priority,
                                     priority_buckets - 1))
    query_priority = max(0, priority_buckets - 1)
    if delta is None:
        delta = default_delta(graph)
    if width:
        kernels = [
            (fk.name, _batch_stub),
            ("dg_update", _batch_stub),
            ("dg_query", _batch_stub),
        ]
        up, qp = int(update_priority), int(query_priority)
        route = {
            fk.name: BatchSpec(
                fk.batch_body, width=width, prefetch=False,
                priority=_bucket_fn(fk.name, delta,
                                    getattr(fk, "reps", 64)),
            ),
            "dg_update": BatchSpec(
                upd.batch_body, width=width, prefetch=False,
                priority=lambda arg, up=up: jnp.int32(up),
            ),
            "dg_query": BatchSpec(
                qk.batch_body, width=width, prefetch=False,
                priority=lambda arg, qp=qp: jnp.int32(qp),
            ),
        }
        scratch = dict(fk.batch_scratch(width))
        scratch.update(upd.scratch(width))
        if lane_max_age is None:
            from ..runtime.env import env_set

            if env_set("HCLIB_TPU_LANE_MAX_AGE"):
                lane_max_age = None  # env wins, Megakernel resolves it
            elif priority_buckets:
                lane_max_age = 2 * capacity  # starvation backstop
            else:
                lane_max_age = 4 * width
    else:
        kernels = [
            (fk.name, fk.scalar_kernel),
            ("dg_update", upd.scalar_kernel),
            ("dg_query", qk.scalar_kernel),
        ]
        route = None
        scratch = dict(fk.scalar_scratch())
        scratch.update(upd.scratch(1))
        lane_max_age = 0 if lane_max_age is None else lane_max_age
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
    )
    mk._frontier_layout = (fk.name, graph.n, graph.nblocks, graph.st_base)
    # The dyngraph layout stamp: hclint's splice-protocol check, the
    # checkpoint snapshot path, and reshard's canonical rebuild all key
    # off it (plain ints, so it serializes into bundle meta verbatim).
    mk._dyngraph = {
        "kind": kind,
        "n": graph.n,
        "spare": graph.spare,
        "spare_base": graph.spare_base,
        "total_blocks": graph.nblocks,
        "bcs_base": graph.bcs_base,
        "flag_base": graph.flag_base,
        "upd_cap": graph.upd_cap,
        "st_base": graph.st_base,
        "weighted": bool(fk.weighted),
        "update_kind": DG_UPDATE,
        "query_kind": DG_QUERY,
        "update_priority": int(update_priority),
        "buckets": priority_buckets,
        "reps": int(getattr(fk, "reps", 0) or 0),
    }
    # Schedule-independence claim over the MUTATED fixpoint: updates
    # stamp in at run time (run_dyngraph), the tile-claim discipline -
    # an unbound claim certifies as "unbound" rather than lying.
    mk.si_claim = ("dyngraph", kind, getattr(fk, "reps", None),
                   priority_buckets, None)
    return mk


def _bind_updates(mk: Megakernel, graph: DynGraph) -> None:
    """Stamp the registered update stream into the si claim (the bound
    spelling certify_claim actually certifies) AND the layout stamp
    (checkpoint manifests carry it; reshard's canonical rebuild maps
    applied-flag uids back to their (u, v, w) endpoints through it)."""
    tag, kind, reps, buckets, _ = mk.si_claim
    mk.si_claim = (tag, kind, reps, buckets, tuple(graph.updates))
    mk._dyngraph["updates"] = [
        [int(u), int(v), int(w)] for u, v, w in graph.updates
    ]


# ------------------------------------------------------------ host twin


def host_dyngraph(
    kind: str,
    graph: DynGraph,
    src: int = 0,
    *,
    m0: int = 1 << 14,
    reps: int = 64,
) -> np.ndarray:
    """The from-scratch host reference ON THE MUTATED GRAPH - what the
    incremental device fixpoint must match bit-for-bit (bfs/sssp)."""
    g = graph.mutated()
    if kind == "bfs":
        return host_bfs(g, src)
    if kind == "sssp":
        return host_sssp(g, src)
    if kind == "pagerank":
        rank, _ = host_pagerank_push(g, m0=m0, reps=reps)
        return rank
    raise ValueError(f"unknown dyngraph kind {kind!r}")


def host_incremental(
    kind: str,
    graph: DynGraph,
    src: int = 0,
    *,
    order: Optional[Sequence[int]] = None,
) -> np.ndarray:
    """Pure-python incremental twin (bfs/sssp): apply seed expansion and
    the update stream as a SINGLE op pool processed in ``order`` (a
    permutation of the initial ops; spawned re-expansions append), each
    update splicing then relaxing with u's current label - exactly the
    device protocol. The certifier runs this under K permutations and
    asserts every fixpoint equals the from-scratch reference."""
    if kind not in ("bfs", "sssp"):
        raise ValueError(
            "host_incremental models the label-correcting kinds "
            f"(bfs|sssp), got {kind!r}"
        )
    n = graph.n
    adj: List[List[Tuple[int, int]]] = [
        [(int(t), int(w)) for t, w in zip(graph.adj[v], graph.adj_w[v])]
        for v in range(n)
    ]
    deg = graph.deg.astype(np.int64).copy()
    bc = graph.blk_count.astype(np.int64).copy()
    dist = np.full(n, INF, np.int64)
    dist[int(src)] = 0
    ops: List[Tuple] = [("expand", int(src))]
    ops += [("update", u, v, w) for (u, v, w) in graph.updates]
    if order is None:
        order = range(len(ops))
    pending: List[Tuple] = [ops[i] for i in order]
    if len(pending) != len(ops):
        raise ValueError("order must be a permutation of the op pool")

    def relax(u, v, w):
        nd = dist[u] + (1 if kind == "bfs" else w)
        if dist[u] < INF and nd < dist[v]:
            dist[v] = nd
            pending.append(("expand", v))

    while pending:
        op = pending.pop(0)
        if op[0] == "expand":
            v = op[1]
            for t, w in list(adj[v]):
                relax(v, t, w)
        else:
            _, u, v, w = op
            if deg[u] == bc[u] * EBLOCK:  # tail full
                if bc[u] - int(graph.blk_count[u]) >= graph.spare:
                    continue  # dropped, exactly as the device drops it
                bc[u] += 1
            deg[u] += 1
            adj[u].append((v, w))
            relax(u, v, w)
    return dist.astype(np.int32)


def host_incremental_pagerank(
    graph: DynGraph,
    *,
    m0: int = 1 << 14,
    reps: int = 64,
    order: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, int]:
    """Pure-python incremental pagerank twin: deliveries and splices
    interleave in ``order``; splices are mass-neutral (degree steers
    only FUTURE splits), so ``rank.sum() == n * m0`` holds for EVERY
    order - the conservation certificate. Returns (rank, deliveries)."""
    from .frontier import _pr_split

    n = graph.n
    adj: List[List[int]] = [
        [int(t) for t in graph.adj[v]] for v in range(n)
    ]
    deg = graph.deg.astype(np.int64).copy()
    bc = graph.blk_count.astype(np.int64).copy()
    rank = np.zeros(n, np.int64)
    ops: List[Tuple] = []
    for v in range(n):
        d = int(deg[v])
        qc = _pr_split(m0, d)
        if m0 >= reps and qc > 0 and d > 0:
            rank[v] = m0 - d * qc
            for u in adj[v]:
                ops.append(("deliver", int(u), qc))
        else:
            rank[v] = m0
    ops += [("update", u, v, w) for (u, v, w) in graph.updates]
    if order is None:
        order = range(len(ops))
    pending: List[Tuple] = [ops[i] for i in order]
    if len(pending) != len(ops):
        raise ValueError("order must be a permutation of the op pool")
    deliveries = 0
    while pending:
        op = pending.pop(0)
        if op[0] == "update":
            _, u, v, w = op
            if deg[u] == bc[u] * EBLOCK:
                if bc[u] - int(graph.blk_count[u]) >= graph.spare:
                    continue
                bc[u] += 1
            deg[u] += 1
            adj[u].append(int(v))
            continue
        _, u, q = op
        deliveries += 1
        d = int(deg[u])
        qc = _pr_split(q, d)
        if q >= reps and qc > 0 and d > 0:
            rank[u] += q - d * qc
            for t in list(adj[u]):
                pending.append(("deliver", int(t), qc))
        else:
            rank[u] += q
    return rank, deliveries


# ---------------------------------------------------------------- runner


def _seed_builders(
    graph: DynGraph,
    kind: str,
    src: int,
    m0: int,
    reps: int,
    queries: Sequence[int],
    num_values: int,
    ndev: int,
    dev_of,
) -> Tuple[List[TaskGraphBuilder], List[int]]:
    """Per-device builders: traversal seeds dealt by placement, the
    update stream BROADCAST to every device (UPDATE is non-migratable
    and idempotent - every replica applies every splice), queries dealt
    round-robin with out slots above the state region."""
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for b in builders:
        b.reserve_values(graph.num_value_slots)
    seeds = seed_frontier(None, graph, kind, src=src, m0=m0, reps=reps)
    pcounts = [0] * ndev
    for i, args in enumerate(seeds):
        d = int(dev_of(i, max(1, len(seeds))))
        if not 0 <= d < ndev:
            raise ValueError(
                f"placement sent seed {i} to device {d} (mesh has {ndev})"
            )
        builders[d].add(FR_EXPAND, args=list(args))
        pcounts[d] += 1
    for uid, (u, v, w) in enumerate(graph.updates):
        for b in builders:
            b.add(DG_UPDATE, args=[u, v, w, uid])
    qbase = graph.st_base + graph.n
    for qi, v in enumerate(queries):
        slot = qbase + qi
        if slot >= num_values:
            raise ValueError(
                f"query {qi} wants out slot {slot} >= num_values "
                f"{num_values}: raise num_values"
            )
        builders[qi % ndev].add(DG_QUERY, args=[int(v)], out=slot)
    return builders, pcounts


def run_dyngraph(
    kind: str,
    graph: DynGraph,
    src: int = 0,
    *,
    updates: Optional[Sequence[Tuple[int, int, int]]] = None,
    queries: Sequence[int] = (),
    width: int = 8,
    m0: int = 1 << 14,
    reps: int = 64,
    capacity: int = 512,
    interpret: Optional[bool] = None,
    trace=None,
    fuel: Optional[int] = None,
    lane_max_age: Optional[int] = None,
    priority_buckets: Optional[int] = None,
    delta: Optional[int] = None,
    update_priority: Optional[int] = None,
    mk: Optional[Megakernel] = None,
    placement=None,
    mesh=None,
    quantum: int = 64,
    window: int = 16,
    hop_order=None,
) -> Tuple[np.ndarray, Dict]:
    """One concurrent traversal + update storm to the fixpoint.
    ``updates`` (``(u, v[, w])`` tuples) register on the graph and ride
    as UPDATE descriptors - on a mesh, broadcast to every device.
    Returns ``(result, info)``: the exact fixpoint ON THE MUTATED GRAPH
    (bit-identical to ``host_dyngraph`` for bfs/sssp; mass-conserving
    for pagerank), with ``info`` carrying ``edges``/``relaxations``
    plus ``updates_applied``/``spare_in_use``/``dropped``/``queries``
    and per-query out values (``query_values``; tentative when queries
    raced the traversal, exact once it drained first)."""
    for up in updates or ():
        if len(up) == 2:
            graph.add_update(up[0], up[1])
        else:
            graph.add_update(up[0], up[1], up[2])
    if mk is None:
        mk = make_dyngraph_megakernel(
            kind, graph, width=width, capacity=capacity,
            interpret=interpret, trace=trace, lane_max_age=lane_max_age,
            priority_buckets=priority_buckets, delta=delta,
            update_priority=update_priority, reps=reps,
        )
    else:
        dg = getattr(mk, "_dyngraph", None)
        if dg is None or dg["n"] != graph.n or dg["kind"] != kind or (
            dg["st_base"] != graph.st_base
        ):
            raise ValueError(
                "prebuilt megakernel is not bound to this dyngraph "
                f"layout (stamp {dg}): build one per (kind, graph) via "
                "make_dyngraph_megakernel"
            )
    _bind_updates(mk, graph)
    fk_state0 = INF if kind in ("bfs", "sssp") else 0
    st = graph.st_base
    iv = graph.preset_values(mk.num_values, fk_state0)
    if kind in ("bfs", "sssp"):
        iv[st + int(src)] = 0
    else:
        iv[st : st + graph.n] = _pr_seed_rank(graph, m0, reps).astype(
            np.int32
        )

    def finish(iv_rows, info):
        rows = np.asarray(iv_rows, np.int64)
        if rows.ndim == 1:
            rows = rows[None]
        states = rows[:, st : st + graph.n]
        if kind in ("bfs", "sssp"):
            result = states.min(axis=0).astype(np.int32)
        else:
            result = states.sum(axis=0) - (
                (rows.shape[0] - 1) * iv[st : st + graph.n].astype(np.int64)
            )
        flags = rows[:, graph.flag_base : graph.flag_base + graph.upd_cap]
        info["edges"] = int(rows[:, V_EDGES].sum())
        info["relaxations"] = int(rows[:, V_RELAX].sum())
        info["updates_applied"] = int((flags.max(axis=0) != 0).sum())
        info["spare_in_use"] = int(rows[:, V_FREE].max())
        info["dropped"] = int(rows[:, V_DROPPED].max())
        info["queries"] = int(rows[:, V_QUERIES].sum())
        qbase = st + graph.n
        info["query_values"] = [
            int(rows[qi % rows.shape[0], qbase + qi])
            for qi in range(len(queries))
        ]
        return result, info

    if placement is None:
        builders, _ = _seed_builders(
            graph, kind, src, m0, reps, queries, mk.num_values, 1,
            lambda i, tot: 0,
        )
        iv_o, _, info = mk.run(
            builders[0], data=dict(fk_data(graph, mk)), ivalues=iv,
            fuel=1 << 22 if fuel is None else fuel,
        )
        return finish(iv_o, info)

    if fuel is not None:
        raise ValueError(
            "fuel= applies to the single-device path only; bound a mesh "
            "run with quantum= instead"
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
    dev_of = p.device_of if isinstance(p, MeshPlacement) else (
        lambda i, tot: p(1, i, tot)
    )
    builders, pcounts = _seed_builders(
        graph, kind, src, m0, reps, queries, mk.num_values, ndev, dev_of
    )
    data = fk_data(graph, mk)
    stacked_iv = np.broadcast_to(iv, (ndev,) + iv.shape).copy()
    stacked = {
        k: np.broadcast_to(v, (ndev,) + v.shape).copy()
        for k, v in data.items()
    }
    from .sharded import ShardedMegakernel

    if hop_order is None and isinstance(p, MeshPlacement):
        hop_order = p.hop_order()
    smk = ShardedMegakernel(mk, mesh, migratable_fns=[FR_EXPAND])
    iv_o, _, info = smk.run(
        builders, data=stacked, ivalues=stacked_iv, steal=True,
        quantum=quantum, window=window, hop_order=hop_order,
    )
    info["placement_counts"] = pcounts
    info["hop_order"] = list(hop_order) if hop_order else None
    return finish(iv_o, info)


def fk_data(graph: DynGraph, mk: Megakernel) -> Dict[str, np.ndarray]:
    """The device data buffers (static rows + pristine spare rows)."""
    d = {"indices": graph.indices}
    if mk._dyngraph["weighted"]:
        d["weights"] = graph.weights
    return d


# -------------------------------------------------------- serving loop


def serve_dyngraph(
    kind: str,
    graph: DynGraph,
    src: int = 0,
    *,
    updates: Sequence[Tuple[int, ...]] = (),
    queries: Sequence[int] = (),
    update_tenant: str = "updates",
    query_tenant: str = "queries",
    width: int = 0,
    m0: int = 1 << 14,
    reps: int = 64,
    capacity: int = 512,
    interpret: Optional[bool] = None,
    trace=None,
    checkpoint: Optional[bool] = None,
    lane_max_age: Optional[int] = None,
    priority_buckets: Optional[int] = None,
    delta: Optional[int] = None,
    update_priority: Optional[int] = None,
    ring_capacity: int = 64,
    egress_depth: int = 64,
    quantum: int = 1 << 10,
    max_rounds: int = 256,
    result_timeout_s: float = 30.0,
) -> Tuple[np.ndarray, Dict]:
    """Serve one resident adjacency to concurrent tenants through the
    front door: an ``updates`` lane and a ``queries`` lane submit
    UPDATE/QUERY descriptors against the SAME running traversal, each
    submission returning a completion-mailbox future (``Admission.
    future``) that resolves to the retired row's out-slot value - a
    query future resolves to the label the service published (tentative
    while the traversal races, exact once it drained). The lanes are
    distinct WRR classes at the ring (TenantSpec weights); the DEVICE
    priority classes (``update_priority=`` over bucket rings) are the
    batched mesh arm's - the stream embedding is scalar-tier only.
    Returns ``(result, info)`` shaped like
    ``run_dyngraph`` plus ``info['query_results']`` (future-resolved
    values), ``info['serve_stats']`` (lane + egress ledgers, the
    conservation identity closed) and ``info['splice_trace']`` (one
    host TR_SPLICE record in the flight-recorder ABI)."""
    import time as _time

    from .egress import EgressSpec
    from .inject import StreamingMegakernel
    from .tenants import TenantSpec, TenantTable
    from .tracebuf import TR_SPLICE, host_trace_info

    if width:
        raise ValueError(
            "serve_dyngraph runs the scalar arm (width=0): the stream "
            "front door's core embedding carries no batch-lane scratch; "
            "bucketed/batched service rides the mesh path "
            "(run_dyngraph(placement=...))"
        )
    for up in updates or ():
        graph.add_update(*up)
    mk = make_dyngraph_megakernel(
        kind, graph, width=width, capacity=capacity,
        interpret=interpret, trace=trace, checkpoint=checkpoint,
        lane_max_age=lane_max_age, priority_buckets=priority_buckets,
        delta=delta, update_priority=update_priority, reps=reps,
    )
    _bind_updates(mk, graph)
    region = -(-int(ring_capacity) // 16) * 8  # two lanes over the ring
    table = TenantTable(
        [TenantSpec(update_tenant), TenantSpec(query_tenant)],
        max(8, region), egress=EgressSpec(depth=egress_depth),
    )
    sm = StreamingMegakernel(mk, ring_capacity=ring_capacity,
                             tenants=table)
    st = graph.st_base
    fk_state0 = INF if kind in ("bfs", "sssp") else 0
    iv = graph.preset_values(mk.num_values, fk_state0)
    if kind in ("bfs", "sssp"):
        iv[st + int(src)] = 0
    else:
        iv[st : st + graph.n] = _pr_seed_rank(graph, m0, reps).astype(
            np.int32
        )
    seed = TaskGraphBuilder()
    seed.reserve_values(graph.num_value_slots)
    for args in seed_frontier(None, graph, kind, src=src, m0=m0,
                              reps=reps):
        seed.add(FR_EXPAND, args=list(args))
    upd_futs = []
    for uid, (u, v, w) in enumerate(graph.updates):
        adm = sm.submit(update_tenant, DG_UPDATE, args=[u, v, w, uid])
        if not adm.accepted:
            raise RuntimeError(
                f"update lane rejected uid {uid}: {adm.reason!r}"
            )
        upd_futs.append(adm.future)
    qbase = st + graph.n
    q_futs = []
    for qi, v in enumerate(queries):
        slot = qbase + qi
        if slot >= mk.num_values:
            raise ValueError(
                f"query {qi} wants out slot {slot} >= num_values "
                f"{mk.num_values}: raise num_values"
            )
        adm = sm.submit(query_tenant, DG_QUERY, args=[int(v)], out=slot)
        if not adm.accepted:
            raise RuntimeError(
                f"query lane rejected query {qi}: {adm.reason!r}"
            )
        q_futs.append(adm.future)
    sm.close()
    t0 = _time.monotonic_ns()
    iv_o, info = sm.run_stream(
        seed, ivalues=iv, data=dict(fk_data(graph, mk)),
        quantum=quantum, max_rounds=max_rounds,
    )
    t1 = _time.monotonic_ns()
    rows = np.asarray(iv_o, np.int64)[None]
    if kind in ("bfs", "sssp"):
        result = rows[0, st : st + graph.n].astype(np.int32)
    else:
        result = rows[0, st : st + graph.n]
    flags = rows[0, graph.flag_base : graph.flag_base + graph.upd_cap]
    info["edges"] = int(rows[0, V_EDGES])
    info["relaxations"] = int(rows[0, V_RELAX])
    info["updates_applied"] = int((flags != 0).sum())
    info["spare_in_use"] = int(rows[0, V_FREE])
    info["dropped"] = int(rows[0, V_DROPPED])
    info["queries"] = int(rows[0, V_QUERIES])
    info["query_values"] = [
        int(rows[0, qbase + qi]) for qi in range(len(queries))
    ]
    info["update_futures"] = upd_futs
    info["query_futures"] = q_futs
    info["query_results"] = [
        int(f.result(timeout=result_timeout_s)) for f in q_futs
    ]
    for f in upd_futs:
        f.result(timeout=result_timeout_s)
    info["serve_stats"] = sm.stats_dict()
    applied, dropped = info["updates_applied"], info["dropped"]
    info["splice_trace"] = host_trace_info(
        [[TR_SPLICE, 0, (applied << 16) | dropped,
          info["spare_in_use"]]],
        t0, max(t1, t0 + 1),
    )
    return result, info


# ----------------------------------------------------- elastic reshard


def reshard_dyngraph(bundle, ndev_new: int):
    """Re-home a quiesced dyngraph bundle onto ``ndev_new`` devices -
    the mutated-adjacency arm of ``CheckpointBundle.reshard`` (which
    delegates here off ``meta['dyngraph']``).

    The generic reshard refuses per-device data buffers because no
    generic fold exists; a dyngraph bundle has exactly the fold the
    generic path lacks. Each device's adjacency is the static graph
    plus the subset of the (broadcast, idempotent) update stream that
    device has applied, appended at the tail of each endpoint's chain.
    So the merge rebuilds ONE canonical adjacency - static rows plus
    the union-applied updates spliced in uid order - and broadcasts it
    (with the matching vt / applied flags / free cursor) to every new
    device. Canonical uid order may permute edges WITHIN a vertex's
    appended tail relative to what some replica held; the fixpoint is
    adjacency-order-free (that is the certified claim), so results are
    unchanged. Labels min-fold (bfs/sssp; a pagerank mid-run reshard is
    refused - per-device rank shares have no device-count-free fold),
    accumulator counters sum-fold, and the conservation identity
    ``sum(deg) == m_static + |union-applied|`` is asserted, as is each
    old device's free-cursor ledger (``V_FREE``) against its own vt.

    Pending residue: EXPAND and QUERY rows deal round-robin (QUERY's
    dynamic out slot is safe precisely because the value region is
    broadcast-identical); pending UPDATE replicas dedupe by uid, drop
    the union-applied ones (their splice already rides the canonical
    arrays; re-delivery would be a no-op anyway), and BROADCAST to
    every new device - the mesh invariant "every replica sees every
    update" survives the resize."""
    from ..runtime.checkpoint import CheckpointBundle, CheckpointError
    from .descriptor import (
        DESC_WORDS, F_A0, F_CSR_N, F_DEP, F_FN, F_HOME, F_SUCC0,
        F_SUCC1, NO_TASK, ring_len,
    )
    from .megakernel import C_ALLOC, C_EXECUTED, C_PENDING, C_VALLOC

    dg = dict(bundle.meta["dyngraph"])
    kind = dg["kind"]
    if kind == "pagerank":
        raise CheckpointError(
            "dyngraph reshard supports bfs/sssp only: pagerank's "
            "per-device rank shares combine by sum-minus-preset over "
            "the ORIGINAL device count, so no device-count-free fold "
            "exists mid-run - drain to the fixpoint and reseed instead"
        )
    n = int(dg["n"])
    spare = int(dg["spare"])
    spare_base = int(dg["spare_base"])
    bcs_base = int(dg["bcs_base"])
    flag_base = int(dg["flag_base"])
    upd_cap = int(dg["upd_cap"])
    st_base = int(dg["st_base"])
    updates = [tuple(int(x) for x in u) for u in (dg.get("updates") or ())]
    upd_kind = int(dg.get("update_kind", DG_UPDATE))
    q_kind = int(dg.get("query_kind", DG_QUERY))

    tasks = np.asarray(bundle.arrays["tasks"])
    counts = np.asarray(bundle.arrays["counts"])
    ivalues = np.asarray(bundle.arrays["ivalues"]).astype(np.int64)
    ndev, cap, _ = tasks.shape
    waits = bundle.arrays.get("waits")
    if waits is not None and int(np.asarray(waits)[:, 0, 0].sum()):
        raise CheckpointError(
            "dyngraph reshard cannot re-home parked waits (the service "
            "kinds never wait on-device); drain the wait table first"
        )
    if "ictl" in bundle.arrays and int(
        np.asarray(bundle.arrays["ictl"])[:, 0].sum()
    ):
        raise CheckpointError(
            "dyngraph reshard: inject-ring residue present - let the "
            "poll consume the ring (or close and drain) before a resize "
            "so every update/query is a scheduler row or a flag"
        )
    if int(ivalues[:, V_DROPPED].max()):
        raise CheckpointError(
            "dyngraph reshard: a replica dropped splices on spare "
            "exhaustion (V_DROPPED != 0) - the adjacency is no longer "
            "the registered stream's; rebuild with more spare blocks"
        )
    other = [
        k for k in bundle.arrays
        if k.startswith("data/") and k not in ("data/indices",
                                               "data/weights")
    ]
    if other:
        raise CheckpointError(
            f"dyngraph reshard: no fold for extra data buffers {other}"
        )
    ind = np.asarray(bundle.arrays["data/indices"]).astype(np.int32)
    weighted = bool(dg.get("weighted")) and "data/weights" in bundle.arrays
    wgt = (
        np.asarray(bundle.arrays["data/weights"]).astype(np.int32)
        if weighted else None
    )

    # ---- union-applied flags -> the canonical update subset ----
    flags = ivalues[:, flag_base : flag_base + upd_cap]
    union = flags.max(axis=0)
    if int(union[len(updates):].max(initial=0)):
        raise CheckpointError(
            "dyngraph reshard: applied flag set beyond the registered "
            f"update stream ({len(updates)} updates in the manifest) - "
            "the bundle and its meta disagree"
        )
    applied_uids = [u for u in range(len(updates)) if union[u]]

    # ---- per-device ledgers + the shared static skeleton ----
    vt = ivalues[:, VT_BASE : VT_BASE + 3 * n].reshape(ndev, n, 3)
    bcs = ivalues[0, bcs_base : bcs_base + n]
    bs = vt[0, :, 0]
    for d in range(1, ndev):
        if not np.array_equal(ivalues[d, bcs_base : bcs_base + n], bcs):
            raise CheckpointError(
                f"dyngraph reshard: device {d} static block counts "
                "diverged from device 0 (immutable region corrupt)"
            )
        if not np.array_equal(vt[d, :, 0], bs):
            raise CheckpointError(
                f"dyngraph reshard: device {d} block starts diverged "
                "(immutable region corrupt)"
            )
    per_dev_applied = np.zeros((ndev, n), np.int64)
    for d in range(ndev):
        for uid in range(len(updates)):
            if flags[d, uid]:
                per_dev_applied[d, updates[uid][0]] += 1
    deg0 = vt[0, :, 2] - per_dev_applied[0]
    for d in range(ndev):
        if not np.array_equal(vt[d, :, 2] - per_dev_applied[d], deg0):
            raise CheckpointError(
                f"dyngraph reshard: device {d} degrees minus its own "
                "applied splices disagree with the static degrees - "
                "edge-count conservation does not hold"
            )
        used_d = int((vt[d, :, 1] - bcs).sum())
        if used_d != int(ivalues[d, V_FREE]):
            raise CheckpointError(
                f"dyngraph reshard: device {d} free-cursor ledger "
                f"(V_FREE={int(ivalues[d, V_FREE])}) != its vt spare "
                f"occupancy ({used_d})"
            )
    if int(deg0.min(initial=0)) < 0:
        raise CheckpointError(
            "dyngraph reshard: negative static degree reconstructed - "
            "the applied flags and the vertex table disagree"
        )

    # ---- canonical rebuild: truncate device 0 to static, replay ----
    def _pos(u: int, p: int) -> Tuple[int, int]:
        blk = p // EBLOCK
        if blk < int(bcs[u]):
            return int(bs[u]) + blk, p % EBLOCK
        return spare_base + u * spare + (blk - int(bcs[u])), p % EBLOCK

    can_ind = ind[0].copy()
    can_wgt = wgt[0].copy() if weighted else None
    for u in range(n):
        for p in range(int(deg0[u]), int(vt[0, u, 2])):
            r, c = _pos(u, p)
            can_ind[r, c] = -1
            if weighted:
                can_wgt[r, c] = 0
    can_bc = bcs.copy()
    can_deg = deg0.copy()
    for uid in applied_uids:
        u, v, w = updates[uid]
        if can_deg[u] == can_bc[u] * EBLOCK:
            if can_bc[u] - bcs[u] >= spare:
                raise CheckpointError(
                    f"dyngraph reshard: replaying uid {uid} overflows "
                    f"vertex {u}'s spare region - a flag is set for a "
                    "splice the device could not have applied"
                )
            r, c = spare_base + u * spare + int(can_bc[u] - bcs[u]), 0
            can_ind[r, :] = -1
            if weighted:
                can_wgt[r, :] = 0
            can_bc[u] += 1
        else:
            r, c = _pos(u, int(can_deg[u]))
        can_ind[r, c] = v
        if weighted:
            can_wgt[r, c] = w
        can_deg[u] += 1
    m_static = int(deg0.sum())
    if int(can_deg.sum()) != m_static + len(applied_uids):
        raise CheckpointError(
            "dyngraph reshard edge-count conservation failed: "
            f"{int(can_deg.sum())} canonical edges != {m_static} static "
            f"+ {len(applied_uids)} union-applied"
        )

    # ---- residue scan: classify, dedupe, refuse links ----
    expand_rows: List[np.ndarray] = []
    query_rows: List[np.ndarray] = []
    upd_rows: Dict[int, np.ndarray] = {}
    for d in range(ndev):
        for i in range(int(counts[d, C_ALLOC])):
            row = tasks[d, i]
            if int(row[F_DEP]) == -1:
                continue  # tombstone
            if (
                int(row[F_DEP]) != 0
                or int(row[F_SUCC0]) != NO_TASK
                or int(row[F_SUCC1]) != NO_TASK
                or int(row[F_CSR_N]) != 0
                or int(row[F_HOME]) >= 0
            ):
                raise CheckpointError(
                    f"dyngraph reshard: device {d} row {i} is not "
                    "link-free; quiesce at a round boundary drains "
                    "dependent subgraphs first"
                )
            fn = int(row[F_FN])
            if fn == upd_kind:
                uid = int(row[F_A0 + 3])
                if not 0 <= uid < len(updates):
                    raise CheckpointError(
                        f"dyngraph reshard: pending UPDATE row carries "
                        f"uid {uid} outside the registered stream"
                    )
                if not union[uid]:
                    upd_rows.setdefault(uid, row.copy())
            elif fn == q_kind:
                query_rows.append(row.copy())
            else:
                expand_rows.append(row.copy())
    pend_upd = [upd_rows[k] for k in sorted(upd_rows)]

    # ---- deal + rebuild the scheduler arrays ----
    va = int(counts[:, C_VALLOC].max())
    V = ivalues.shape[1]
    tasks_new = np.zeros((ndev_new, cap, DESC_WORDS), np.int32)
    ready_new = np.full((ndev_new, ring_len(cap)), NO_TASK, np.int32)
    counts_new = np.zeros((ndev_new, 8), np.int32)
    parts: List[List[np.ndarray]] = [list(pend_upd)
                                     for _ in range(ndev_new)]
    for i, row in enumerate(expand_rows):
        parts[i % ndev_new].append(row)
    for i, row in enumerate(query_rows):
        parts[i % ndev_new].append(row)
    for j, p in enumerate(parts):
        if len(p) > cap:
            raise CheckpointError(
                f"dyngraph reshard {ndev} -> {ndev_new}: device {j} "
                f"would hold {len(p)} rows > capacity {cap} (updates "
                "broadcast to every device); scale in less aggressively "
                "or rebuild with a larger capacity"
            )
        for i, row in enumerate(p):
            tasks_new[j, i] = row
            ready_new[j, i] = i
        counts_new[j, 0] = 0
        counts_new[j, 1] = len(p)
        counts_new[j, C_ALLOC] = len(p)
        counts_new[j, C_PENDING] = len(p)
        counts_new[j, C_VALLOC] = va
    iv_new = np.zeros((ndev_new, V), np.int64)
    for d in range(ndev):
        j = d % ndev_new
        counts_new[j, C_EXECUTED] += int(counts[d, C_EXECUTED])
        for s in (V_EDGES, V_RELAX, V_QUERIES, 6, 7):
            iv_new[j, s] += ivalues[d, s]
    iv_new[:, V_UPDATES] = len(applied_uids)
    iv_new[:, V_FREE] = int((can_bc - bcs).sum())
    iv_new[:, V_DROPPED] = 0
    can_vt = vt[0].copy()
    can_vt[:, 1] = can_bc
    can_vt[:, 2] = can_deg
    iv_new[:, VT_BASE : VT_BASE + 3 * n] = can_vt.reshape(-1)
    iv_new[:, bcs_base : bcs_base + n] = bcs
    iv_new[:, flag_base : flag_base + upd_cap] = union
    iv_new[:, st_base : st_base + n] = (
        ivalues[:, st_base : st_base + n].min(axis=0)
    )
    if V > st_base + n:
        # Query out slots: written by at most one (owner) device, zero
        # elsewhere - elementwise max is the published value, broadcast
        # so pending QUERY rows may land anywhere.
        iv_new[:, st_base + n :] = ivalues[:, st_base + n :].max(axis=0)
    scap = np.asarray(bundle.arrays["succ"]).shape[1]
    arrays: Dict[str, np.ndarray] = {
        "tasks": tasks_new,
        "succ": np.full((ndev_new, scap), NO_TASK, np.int32),
        "ready": ready_new,
        "counts": counts_new,
        "ivalues": iv_new.astype(np.int32),
        "data/indices": np.broadcast_to(
            can_ind, (ndev_new,) + can_ind.shape
        ).copy(),
    }
    if weighted:
        arrays["data/weights"] = np.broadcast_to(
            can_wgt, (ndev_new,) + can_wgt.shape
        ).copy()
    if waits is not None:
        arrays["waits"] = np.zeros(
            (ndev_new,) + np.asarray(waits).shape[1:], np.int32
        )
    if "ring_rows" in bundle.arrays:
        rr = np.asarray(bundle.arrays["ring_rows"])
        ic = np.asarray(bundle.arrays["ictl"])
        arrays["ring_rows"] = np.zeros(
            (ndev_new,) + rr.shape[1:], np.int32
        )
        ic_new = np.zeros((ndev_new, 8), np.int32)
        ic_new[:, 1] = ic[:, 1].max() if ic.size else 0  # close flag
        arrays["ictl"] = ic_new
    for k in ("tctl", "tstats", "etok", "tele", "tlat"):
        if k in bundle.arrays:
            arrays[k] = np.asarray(bundle.arrays[k]).copy()
    meta = dict(bundle.meta)
    meta["ndev"] = int(ndev_new)
    meta["resharded_from"] = int(ndev)
    meta["dyngraph_reshard"] = {
        "union_applied": len(applied_uids),
        "pending_updates": len(pend_upd),
        "edges": int(can_deg.sum()),
        "m_static": m_static,
    }
    return CheckpointBundle("resident", meta, arrays)
