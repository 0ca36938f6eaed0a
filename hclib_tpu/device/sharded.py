"""Sharded megakernel: one resident scheduler per mesh device.

SPMD re-design of the reference's multi-worker runtime: instead of pthreads
stealing from each other's deques, every mesh device runs the single-core
megakernel over its own queue partition under ``shard_map``, and global
results/termination combine with XLA collectives (psum). This is the
"locality graph over the mesh": locale i's deque is device i's task table.

The host partitions the task graph round-robin across devices (each
partition must be internally closed under dependencies, like the reference's
per-locale task placement); optional **bulk-synchronous work stealing**
rebalances load at runtime: each round, every device runs its resident
scheduler for a bounded quantum, then surplus *migratable* ready tasks
(successor-free descriptors whose kernel is whitelisted) exchange over the
ICI ring at hop distances 1, 2, 4, ... (hypercube diffusion: a fully-skewed
load reaches every device in one round), and a ``psum`` over the pending
counters decides termination. This is the reference's work-stealing loop
(src/hclib-deque.c steals, src/hclib-runtime.c:403-421 done-flag join)
re-designed for XLA's SPMD model: instead of thieves CASing a victim's deque
top, surplus diffuses over the ICI ring in bulk steps, and the pthread-join
termination becomes a collective.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.progcache import building
from ..runtime.spans import span
from .descriptor import (
    DESC_WORDS,
    F_CSR_N,
    F_DEP,
    F_FN,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    TaskGraphBuilder,
    relay_ring,
    ring_slot,
)
from .megakernel import (
    C_ALLOC,
    C_EXECUTED,
    C_HEAD,
    C_OVERFLOW,
    C_PENDING,
    C_ROUNDS,
    C_TAIL,
    C_VALLOC,
    Megakernel,
    TS_BECAME,
    TS_WALKED,
    TS_WORDS,
    ran_on,
)

__all__ = [
    "ShardedMegakernel",
    "round_robin_partition",
    "partition_builders",
    "abort_words",
]


def abort_words(abort, ndev: int) -> np.ndarray:
    """Normalize a runner's ``abort=`` argument (None / truthy scalar /
    per-device sequence of flags) into the (ndev, 8) int32 abort-word
    array the round-loop kernels re-read from HBM. One definition so the
    length validation applies to every runner."""
    arr = np.zeros((ndev, 8), np.int32)
    if abort is None:
        return arr
    if isinstance(abort, np.ndarray) and abort.ndim == 0:
        abort = bool(abort)  # 0-d array: a scalar flag, not a sequence
    flags = (
        list(abort)
        if isinstance(abort, (list, tuple, np.ndarray))
        else [abort] * ndev
    )
    if len(flags) != ndev:
        raise ValueError(
            f"abort wants {ndev} per-device flags, got {len(flags)}"
        )
    for d, f in enumerate(flags):
        arr[d, 0] = 1 if f else 0
    return arr


def partition_builders(
    mk: Megakernel, ndev: int, builders: Sequence[TaskGraphBuilder]
):
    """Finalize one builder per device into stacked (tasks, succ, ring,
    counts) arrays - shared by every multi-device runner."""
    if len(builders) != ndev:
        raise ValueError(f"need {ndev} partitions, got {len(builders)}")
    cap, scap = mk.capacity, mk.succ_capacity
    parts = [b.finalize(capacity=cap, succ_capacity=scap) for b in builders]
    return (
        np.stack([p[0] for p in parts]),
        np.stack([p[1] for p in parts]),
        np.stack([p[2] for p in parts]),
        np.stack([p[3] for p in parts]),
    )


def execute_partitions(
    mk: Megakernel,
    mesh: Mesh,
    ndev: int,
    jitted,
    builders: Sequence[TaskGraphBuilder],
    data: Optional[Dict[str, np.ndarray]],
    ivalues: Optional[np.ndarray],
    with_rounds: bool,
    mutate=None,
    extra_inputs: Sequence[np.ndarray] = (),
    state=None,
    keep_inputs: bool = False,
):
    """Shared host-side driver for the multi-device runners: partition the
    builders, widen per-device value allocs over presets, validate data
    keys, device_put everything sharded on the mesh axis, invoke, and
    unpack (ivalues, data, info). Raising on overflow/stall is left to the
    caller (the runners word their diagnostics differently).

    ``mutate(tasks, succ, ring, counts)`` lets a runner adjust the
    partitioned arrays in place before upload (e.g. the PGAS runner's
    wait-dependency bumps); ``extra_inputs`` are device_put after the data
    buffers (same leading device axis). ``state`` (a checkpoint snapshot:
    stacked per-device tasks/succ/ready/counts/ivalues) bypasses the
    builder partitioning and preset widening entirely - the arrays are a
    quiesced run's exported state, already consistent. ``keep_inputs``
    surfaces the uploaded input arrays as ``info['inputs']`` (the
    checkpoint path needs the succ CSR, which is input-only).

    The four phases are the profiler spans ``mesh.partition``,
    ``mesh.upload``, ``mesh.run`` (the launch and the wait for it: the
    first output read to the host) and ``mesh.readback`` (the rest) of
    ``runtime/spans.py:STAGES``."""
    with span("mesh.partition"):
        if state is not None:
            tasks = np.asarray(state["tasks"]).copy()
            succ = np.asarray(state["succ"]).copy()
            counts = np.asarray(state["counts"]).copy()
            # (a snapshot from before the rings grew to ring_len is
            # told by its shape and re-laid)
            ring = relay_ring(state["ready"], counts, mk.ring_len).copy()
            ivalues = np.asarray(state["ivalues"]).copy()
        else:
            tasks, succ, ring, counts = partition_builders(
                mk, ndev, builders
            )
            if ivalues is None:
                ivalues = np.zeros((ndev, mk.num_values), np.int32)
            else:
                ivalues = np.asarray(ivalues)
                for d in range(ndev):
                    mk.widen_value_alloc(counts[d], ivalues[d])
        # Mutate AFTER preset widening: runners that symmetrize or
        # validate the per-device value_alloc (ResidentKernel's
        # symmetric-heap layout and migration result-slot check) must see
        # the final values.
        if mutate is not None:
            mutate(tasks, succ, ring, counts)
        for c in counts:
            mk.check_row_values(int(c[C_VALLOC]))
        data = dict(data or {})
        if set(data.keys()) != set(mk.data_specs.keys()):
            raise ValueError(
                f"data buffers {sorted(data)} != declared "
                f"{sorted(mk.data_specs)}"
            )
    sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    put = lambda x: jax.device_put(np.ascontiguousarray(x), sh)  # noqa: E731
    with span("mesh.upload"):
        args = [
            put(tasks), put(succ), put(ring), put(counts), put(ivalues),
            *[put(data[k]) for k in mk.data_specs.keys()],
            *[put(x) for x in extra_inputs],
        ]
    with span("mesh.run"):
        outs = jitted(*args)
        counts_o, iv_o, gcounts = outs[0], outs[1], outs[2]
        g = np.asarray(gcounts)[0]  # the wait; identical on every row
    with span("mesh.readback"):
        nd = len(mk.data_specs)
        data_o = dict(zip(mk.data_specs.keys(), outs[3 : 3 + nd]))
        info = {
            "executed": int(g[C_EXECUTED]),
            "pending": int(g[C_PENDING]),
            "overflow": bool(g[C_OVERFLOW]),
            "per_device_counts": np.asarray(counts_o),
            **ran_on(counts_o, mk.interpret),
            # Fewest devices any input is spread over: ndev unless
            # something sits whole on one device.
            "input_devices": min(
                len(a.sharding.device_set) for a in args
            ),
        }
        # Runner-specific trailing outputs (e.g. the resident kernel's
        # per-device fault/abort stats) ride after the data buffers.
        info["extra_outputs"] = [np.asarray(x) for x in outs[3 + nd :]]
        if keep_inputs:
            info["inputs"] = {"succ": succ}
        if with_rounds:
            info["steal_rounds"] = int(np.asarray(counts_o)[0][C_ROUNDS])
        iv_host = np.asarray(iv_o)
    return iv_host, data_o, info


class ShardedMegakernel:
    """Runs one ``Megakernel`` instance per device of a 1D mesh.

    ``data_specs`` shapes are per-device; the sharded run takes per-device
    data stacked on a leading mesh axis.
    """

    def __init__(
        self,
        mk: Megakernel,
        mesh: Mesh,
        migratable_fns: Iterable[int] = (),
    ) -> None:
        if len(mesh.axis_names) != 1:
            raise ValueError("ShardedMegakernel wants a 1D mesh (queue axis)")
        # Batch-routed kernels ride this runner via the SPILL DISCIPLINE:
        # _build_raw allocates the per-kind lanes, and sched() spills every
        # unrun lane entry back to the ready ring at each kernel exit, so
        # the bulk-synchronous steal/export pass between entries only ever
        # scans ring rows - a lane-resident descriptor can never be
        # invisible to a thief because lanes are empty whenever the
        # exchange runs. The appended tstats output is threaded through
        # both step functions below (accumulated across steal rounds) and
        # decoded into per-device info['tiers'].
        # The trace ring cannot ride this runner: same appended-output
        # problem as tstats (positional out_specs), and the bulk-
        # synchronous steal loop re-enters the kernel per round (each
        # entry resets the ring). The fully-resident runners trace.
        self._suppress_trace = False
        if mk.trace is not None:
            if getattr(mk, "trace_from_env", False):
                # HCLIB_TPU_TRACE is a process-wide opt-in; building this
                # runner untraced beats failing a run the env owner never
                # wrote trace= into. Suppression is LOCAL to this runner's
                # builds - the shared Megakernel keeps its ring for
                # mk.run() / the resident runners.
                import logging

                logging.getLogger("hclib_tpu.device").warning(
                    "ShardedMegakernel cannot trace; ignoring "
                    "HCLIB_TPU_TRACE for this runner's builds"
                )
                self._suppress_trace = True
            else:
                raise ValueError(
                    "ShardedMegakernel does not support the trace ring; "
                    "use ResidentKernel tracing or "
                    "build the Megakernel with trace=None"
                )
        # Checkpoint quiesce cannot ride this runner either: the appended
        # qstat output breaks the positional out_specs, and the bulk-
        # synchronous steal loop re-enters the kernel per round with its
        # OWN state threading (quiesce mid-round would race the exchange).
        # Use ResidentKernel(checkpoint) for mesh checkpoints.
        self._suppress_ckpt = False
        if mk.checkpoint:
            if getattr(mk, "checkpoint_from_env", False):
                import logging

                logging.getLogger("hclib_tpu.device").warning(
                    "ShardedMegakernel cannot checkpoint; ignoring "
                    "HCLIB_TPU_CHECKPOINT for this runner's builds"
                )
                self._suppress_ckpt = True
            else:
                raise ValueError(
                    "ShardedMegakernel does not support checkpoint "
                    "quiesce; use ResidentKernel for mesh checkpoint/"
                    "restore or build the Megakernel with checkpoint=False"
                )
        self.mk = mk
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.ndev = int(np.prod(mesh.devices.shape))
        # Kernel-table ids whose tasks may migrate between devices. A
        # migratable kernel must be location-independent: it may only read
        # its args and write accumulate-style value slots (the host combines
        # per-device ivalues), like forasync tiles or UTS node counters.
        self.migratable_fns = frozenset(int(f) for f in migratable_fns)
        # The claim itself must index the kernel table (the exchange
        # whitelist is a per-kind mask): an out-of-range id would
        # silently never migrate, so refuse unconditionally - the check
        # is a cheap scan, no reason to gate it on the verifier flag.
        # Kind-LEVEL classification is deliberately NOT enforced here:
        # the exchange carries its own row-level link filter, so
        # claiming a home-linked kind (fib forests) legally moves just
        # its link-free rows; the classification rides
        # Megakernel.describe() and the checkpoint bundles for
        # reshard's upfront diagnostics.
        bad = [f for f in self.migratable_fns
               if not 0 <= f < len(mk.kernel_names)]
        if bad:
            raise ValueError(
                f"migratable_fns {sorted(bad)} outside the kernel "
                f"table (0..{len(mk.kernel_names) - 1})"
            )
        self._jitted: Dict[Any, Any] = {}
        self._pc_stats: Optional[Dict[str, Any]] = None

    @contextlib.contextmanager
    def _maybe_untraced(self):
        """Build-time trace/checkpoint suppression for env-derived
        enablement: restores mk state afterwards so other runners sharing
        the kernel keep the capability."""
        if not (self._suppress_trace or self._suppress_ckpt):
            yield
            return
        saved_trace = self.mk.trace
        saved_ckpt = self.mk.checkpoint
        if self._suppress_trace:
            self.mk.trace = None
        if self._suppress_ckpt:
            self.mk.checkpoint = False
        try:
            yield
        finally:
            self.mk.trace = saved_trace
            self.mk.checkpoint = saved_ckpt

    def _build(self, fuel: int):
        # Single kernel entry per launch: lean value staging suffices (run()
        # widens value_alloc over presets before the call).
        with self._maybe_untraced():
            inner = self.mk._build_raw(fuel)
        ndata = len(self.mk.data_specs)
        axis = self.axis

        def step(tasks, succ, ring, counts, iv, *data):
            outs = inner(
                tasks[0], succ[0], ring[0], counts[0], iv[0], *[d[0] for d in data]
            )
            tasks_o, ready_o, counts_o, iv_o = outs[:4]
            data_o = outs[4 : 4 + ndata]
            # The tier counters ride last (appended by _build_raw):
            # surfaced per device.
            tstats_o = outs[4 + ndata :]
            # Global termination/health: executed/pending/overflow summed
            # across the mesh (the reference's done-flag join becomes a
            # collective - src/hclib-runtime.c:403-421).
            gcounts = jax.lax.psum(counts_o, axis)
            return (
                counts_o[None],
                iv_o[None],
                gcounts[None],
                *[d[None] for d in data_o],
                *[t[None] for t in tstats_o],
            )

        nin = 5 + ndata
        f = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(self.axis),) * nin,
            out_specs=(P(self.axis),) * (4 + ndata),
            check_vma=False,
        )
        return jax.jit(f)

    def _build_steal(
        self, quantum: int, window: int, max_rounds: int,
        hop_order: Optional[Sequence[int]] = None,
    ):
        """Steal-round executor: run-for-quantum, migrate surplus over the
        device ring, repeat until psum(pending) == 0.

        ``hop_order`` overrides the default hypercube hop sequence
        [1, 2, 4, ...] with a caller-supplied scan order - the locality
        hook: ``runtime.locality.steal_hop_order`` derives it from a
        machine graph so each exchange reaches graph-NEAR peers first
        (on a 2x2 ICI ring, hop 2 is the adjacent chip and hop 1 the
        diagonal, so the graph flips the scan to [2, 1]). Any nonempty
        set of distances in [1, ndev) terminates - backlog still
        diffuses every round and the psum decides completion - but
        covering the hypercube set keeps one-round full diffusion."""
        # Full value staging: the round loop re-enters the kernel, and value
        # slots above value_alloc (row-owned blocks, bump allocations) carry
        # live results between entries. Descriptor rows freed in earlier
        # rounds ARE reusable (stage() rebuilds the row free stack from
        # completion tombstones), so capacity tracks the live set; only
        # bump-side alloc_values blocks ratchet across rounds.
        with self._maybe_untraced():
            inner = self.mk._build_raw(quantum, stage_all_values=True)
        ndata = len(self.mk.data_specs)
        axis = self.axis
        ndev = self.ndev
        cap = self.mk.capacity
        rlen = self.mk.ring_len  # the ring's modulus; cap is the table's
        K = window
        wl_host = np.zeros(max(1, len(self.mk.kernel_fns)), bool)
        for f in self.migratable_fns:
            wl_host[f] = True
        # Hypercube diffusion: each round exchanges at hop distances 1, 2,
        # 4, ... so a fully-skewed load reaches every device in ONE round
        # (log2(ndev) ppermutes) instead of diffusing one neighbor per
        # round - the SPMD rendering of the reference thief scanning ALL
        # victims along its steal path (src/hclib-locality-graph.c:843-888),
        # rather than only the adjacent one.
        if hop_order is None:
            hop_dists = [d for d in (1 << k for k in range(16)) if d < ndev]
        else:
            hop_dists = [int(d) for d in hop_order]
            if not hop_dists or any(
                not 1 <= d < ndev for d in hop_dists
            ):
                raise ValueError(
                    f"hop_order must be nonempty distances in "
                    f"[1, {ndev}), got {hop_dists}"
                )

        def step(tasks, succ, ring, counts, iv, *data):
            succ0 = succ[0]
            wl = jnp.asarray(wl_host)
            j = jnp.arange(K)

            def exchange(tasks, ring_, counts, perm):
                # ---- export: eligible tasks from the head-side window,
                # oldest first (the Chase-Lev thief steals from the top;
                # here the "thief" is the ring neighbor). Eligible
                # candidates are COMPACTED across the whole scanned window
                # - a non-migratable task at the head does not block the
                # ones behind it; the survivors are compacted back toward
                # the head so the ring stays dense.
                head, tail = counts[C_HEAD], counts[C_TAIL]
                backlog = tail - head
                gavg = jax.lax.psum(backlog, axis) // ndev
                quota = jnp.clip(backlog - gavg, 0, K)
                scanned = j < jnp.minimum(backlog, K)
                ring_idx = ring_slot(head + j, rlen)
                cand = ring_[ring_idx]
                desc = tasks[jnp.clip(cand, 0, cap - 1)]
                elig = (
                    scanned
                    & (cand >= 0)
                    & wl[jnp.clip(desc[:, F_FN], 0, wl.shape[0] - 1)]
                    & (desc[:, F_SUCC0] == NO_TASK)
                    & (desc[:, F_SUCC1] == NO_TASK)
                    & (desc[:, F_CSR_N] == 0)
                )
                rank_e = jnp.cumsum(elig.astype(jnp.int32)) - 1
                send = elig & (rank_e < quota)
                nsend = jnp.sum(send.astype(jnp.int32))
                # Gather exported descriptors densely into sendbuf[0:nsend]
                # (OOB scatter lanes drop the non-send rows).
                sendbuf = (
                    jnp.zeros((K, DESC_WORDS), jnp.int32)
                    .at[jnp.where(send, rank_e, K)]
                    .set(desc)
                )
                # Compact the scanned-but-kept entries to the new head so
                # no live slot is skipped when head advances.
                keep = scanned & jnp.logical_not(send)
                rank_k = jnp.cumsum(keep.astype(jnp.int32)) - 1
                ring_ = ring_.at[
                    jnp.where(
                        keep, ring_slot(head + nsend + rank_k, rlen), rlen
                    )
                ].set(cand, mode="drop")
                # Tombstone the exported rows (F_DEP=-1): the task now lives
                # on the neighbor, so the victim's row is dead and stage()
                # can hand it to future spawns/imports. Unmasked lanes point
                # out of bounds - scatter drops OOB updates, so there are
                # no duplicate-index write races.
                tasks = tasks.at[jnp.where(send, cand, cap), F_DEP].set(-1)
                counts = counts.at[C_HEAD].add(nsend).at[C_PENDING].add(-nsend)
                # ---- exchange over the ICI ring at this hop distance.
                recvbuf = jax.lax.ppermute(sendbuf, axis, perm)
                nrecv = jax.lax.ppermute(
                    nsend.reshape(1), axis, perm
                )[0]
                # ---- import: reuse tombstoned (freed/exported) rows first,
                # then fresh rows from the bump cursor - so steal-heavy runs
                # only need capacity for the LIVE set, not cumulative
                # imports.
                alloc, tail = counts[C_ALLOC], counts[C_TAIL]
                tomb = (tasks[:, F_DEP] == -1) & (
                    jnp.arange(cap) < alloc
                )
                # First (at most) K tombstoned row indices, ascending; the
                # cap fill value is only reachable on lanes j >= nre, which
                # take the fresh-row branch below.
                (reuse,) = jnp.nonzero(tomb, size=K, fill_value=cap)
                ntomb = jnp.sum(tomb.astype(jnp.int32))
                can = jnp.minimum(nrecv, ntomb + (cap - alloc))
                nre = jnp.minimum(can, ntomb)
                take = j < can
                rows = jnp.where(j < nre, reuse[j], alloc + j - nre)
                # OOB indices on untaken lanes: scatter drops them, avoiding
                # duplicate-index races with the taken lanes' writes.
                tasks = tasks.at[jnp.where(take, rows, cap)].set(recvbuf)
                slot = jnp.where(take, ring_slot(tail + j, rlen), rlen)
                ring_ = ring_.at[slot].set(rows)
                counts = (
                    counts.at[C_ALLOC].add(can - nre)
                    .at[C_TAIL].add(can)
                    .at[C_PENDING].add(can)
                    .at[C_OVERFLOW].max(
                        jnp.where(nrecv > can, 1, 0).astype(jnp.int32)
                    )
                )
                return tasks, ring_, counts

            def cond(carry):
                tasks, ring_, counts, iv, data, tacc, rounds = carry
                return (jax.lax.psum(counts[C_PENDING], axis) > 0) & (
                    rounds < max_rounds
                )

            def body(carry):
                tasks, ring_, counts, iv, data, tacc, rounds = carry
                outs = inner(tasks, succ0, ring_, counts, iv, *data)
                tasks, ring_, counts, iv = outs[:4]
                data = tuple(outs[4 : 4 + ndata])
                # tstats resets at every kernel entry (per-entry
                # scratch semantics), so the steal loop accumulates
                # the rounds' counters into a cumulative per-device
                # row - occupancy over the whole run, not the last
                # quantum.
                tacc = tacc + outs[4 + ndata]
                for d in hop_dists:
                    perm = [(i, (i + d) % ndev) for i in range(ndev)]
                    tasks, ring_, counts = exchange(tasks, ring_, counts, perm)
                return (tasks, ring_, counts, iv, data, tacc, rounds + 1)

            init = (
                tasks[0], ring[0], counts[0], iv[0], tuple(d[0] for d in data),
                jnp.zeros((TS_WORDS,), jnp.int32),
                jnp.int32(0),
            )
            tasks_o, ring_o, counts_o, iv_o, data_o, tacc_o, rounds = (
                jax.lax.while_loop(cond, body, init)
            )
            counts_o = counts_o.at[C_ROUNDS].set(rounds)
            gcounts = jax.lax.psum(counts_o, axis)
            return (
                counts_o[None],
                iv_o[None],
                gcounts[None],
                *[d[None] for d in data_o],
                tacc_o[None],
            )

        nin = 5 + ndata
        f = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(self.axis),) * nin,
            out_specs=(P(self.axis),) * (4 + ndata),
            check_vma=False,
        )
        return jax.jit(f)

    def partition(self, builders: Sequence[TaskGraphBuilder]):
        """Finalize one builder per device into stacked arrays."""
        return partition_builders(self.mk, self.ndev, builders)

    def run(
        self,
        builders: Sequence[TaskGraphBuilder],
        data: Optional[Dict[str, np.ndarray]] = None,
        ivalues: Optional[np.ndarray] = None,
        fuel: int = 1 << 22,
        steal: bool = False,
        quantum: int = 256,
        window: int = 32,
        max_rounds: int = 1 << 16,
        hop_order: Optional[Sequence[int]] = None,
    ):
        """Execute all partitions; returns (ivalues[ndev, V], data, info).

        ``steal=True`` enables bulk-synchronous work stealing: devices run
        ``quantum`` tasks per round, then up to ``window`` surplus migratable
        ready tasks hop one device along the ring between rounds.
        ``hop_order`` reorders the exchange's hop-distance scan (see
        ``_build_steal``; ``runtime.locality.steal_hop_order`` derives a
        near-neighbors-first order from a machine graph)."""
        # fuel is unused on the steal path (each round runs `quantum`), so
        # keep it out of that cache key - varying fuel must not recompile.
        hops = tuple(hop_order) if hop_order is not None else None
        key = (
            (True, quantum, window, max_rounds, hops)
            if steal else (False, fuel)
        )
        first_build = key not in self._jitted
        if first_build:
            # Content-keyed program cache (runtime/progcache.py): the
            # variant names every static fact this runner compiles in
            # beyond the Megakernel's own content - mesh shape/devices,
            # migration whitelist, the env-suppression flags (a
            # suppressed-trace build is a DIFFERENT program than the
            # same mk built by a resident runner), and the steal
            # parameters.
            from ..runtime.progcache import mesh_key, shared_build

            self._jitted[key], self._pc_stats = shared_build(
                self.mk,
                ("sharded", mesh_key(self.mesh),
                 tuple(sorted(self.migratable_fns)),
                 self._suppress_trace, self._suppress_ckpt) + key,
                lambda: (
                    self._build_steal(quantum, window, max_rounds, hops)
                    if steal
                    else self._build(fuel)
                ),
            )
        # jax.jit is lazy: the first call of a program pays its trace,
        # lowering and compile (the Megakernel._execute discipline), so
        # the ledger's bracket goes around it.
        with building(
            "sharded", self._jitted[key],
            self._pc_stats if first_build else None,
        ):
            iv_o, data_o, info = execute_partitions(
                self.mk, self.mesh, self.ndev, self._jitted[key], builders,
                data, ivalues, with_rounds=steal,
            )
        if self._pc_stats is not None:
            info["program_cache"] = dict(self._pc_stats)
        # Per-device tier counters (cumulative over the steal rounds on
        # the steal path).
        trows = info.pop("extra_outputs")[-1]
        info["became"] = int(trows[:, TS_BECAME].sum())
        info["walked"] = int(trows[:, TS_WALKED].sum())
        if self.mk.batch_specs:
            # info['tiers'][d] mirrors the single-device decode, so mesh
            # occupancy reads the same way.
            info["tiers"] = [
                self.mk.decode_tier_stats(trows[d])
                for d in range(self.ndev)
            ]
        if info["overflow"]:
            raise RuntimeError("sharded megakernel task-table overflow")
        if info["pending"] != 0:
            raise RuntimeError(
                f"sharded megakernel stalled with {info['pending']} pending "
                f"tasks after {info['executed']} executed (dependency cycle "
                f"or fuel {fuel} exhausted)"
            )
        return iv_o, data_o, info


def round_robin_partition(
    items: Sequence[Any], ndev: int
) -> List[List[Any]]:
    """Deal independent work items across devices."""
    parts: List[List[Any]] = [[] for _ in range(ndev)]
    for i, it in enumerate(items):
        parts[i % ndev].append(it)
    return parts
