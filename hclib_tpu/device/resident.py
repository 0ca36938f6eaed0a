"""The unified resident kernel: ONE per-device scheduler composing work
stealing, one-sided PGAS, active messages, remote atomics/locks, and host
injection - with **general migration of dependency-bearing tasks**.

This is the device-side analogue of the reference's module architecture,
where every module adds locales to a SINGLE scheduler instead of spawning a
private runtime (/root/reference/inc/hclib-module.h:79-97,
src/hclib-runtime.c:294-317): one kernel per device that steals, puts,
AMs, waits, and polls an injection ring in the same round loop. It is the
only runner whose kernel lives across chips; the narrower runners it
replaced are configurations of it - steal-only whole-row migration is
``steal=True, homed=False``, one-sided PGAS alone is ``steal=False`` plus
``channels``. (``ShardedMegakernel`` re-enters the single-chip kernel from
XLA every round; ``StreamingMegakernel`` is one chip.)

**General task migration** (the round-3 gap: only successor-free
whitelisted rows could move). The reference thief takes ANY task out of a
victim's deque - finish scopes, dependency edges, continuations included
(/root/reference/src/hclib-deque.c:75-106, src/hclib-locality-graph.c:
843-888) - because shared memory makes its pointers location-transparent.
On a TPU mesh the links are device-local row/slot indices, so migration is
re-designed as a **home-link protocol**:

- Exporting a ready row WITH successor links keeps the row at home as a
  *proxy* (off the ready ring, still pending, links intact) and ships a
  copy whose F_HOME/F_HROW words name the proxy.
- The copy executes on the thief like any local task; a copy that
  re-arms as its own continuation (``ctx.become``) keeps the home-link
  where it lies, on its row.
- Whoever ends the chain forwards its out-slot value home in a
  **remote-completion active message**; the home device writes the value
  into the proxy's out slot and completes the proxy - firing the real
  successor edges exactly as if the task had run at home.
- Copies migrate ONCE: re-exporting a homed copy would leave an extra
  proxy row on every intermediate device until the completion chain
  unwinds (measured as task-table exhaustion under churny windows), so
  copies are steal-ineligible; load still spreads through the fresh
  tasks migrated work spawns on the thief.
- A migrated kernel's *value-slot arguments* (args that index the local
  ivalues buffer, declared per kernel id in ``migratable_fns``) are
  dereferenced at export - they are final, the row was ready - and
  rehydrated into thief-local slots at install (the closure-capture of
  the reference's AM lambda serialization, modules/openshmem-am).
- Copies write results into a reserved per-row region at the top of the
  value buffer ([num_values - capacity, num_values)), sized/validated at
  run(): the slot is written by the chain-ending task and read by its
  completion hook in the same scheduler step, so the serial per-device
  scheduler makes slot reuse race-free by construction.

**Remote atomics and locks** (round-3 gap #3, matching the reference
SHMEM layer's AMO + promise-chained locks,
/root/reference/modules/openshmem/src/hclib_openshmem.cpp:572-600,
124-134): owner-computes via *builtin* active messages, dispatched by
negative F_FN ids at drain time. The owner applies fetch-add /
compare-swap on its own value slots - the per-device scheduler is serial,
so owner-side application IS the atomicity - and replies with another AM
that deposits the old value and dep-decrements the caller's parked
continuation row. Locks keep a FIFO of (device, row) waiters in the
owner's value slots; RC_GRANT releases the next waiter's row - the
device translation of the reference chaining lock requests through
promises.

**Termination and flow control.** A counting protocol, Mattern-style:
senders count messages per (target, channel); every round the counts are
exchanged, so each device learns exactly how many messages were directed
at it and *consumes* exactly that many arrival-semaphore signals with
matching ``wait_recv`` descriptors - blocking, but only for messages
already launched, never speculative, and data is read only after its
semaphore count is consumed, so no torn payload is ever observed. The
kernel exits when global pending == 0, outboxes and injection rings are
empty, and messages sent == received: an in-flight message therefore
always blocks exit, and every semaphore is drained to zero at kernel
exit. The per-round stat exchange is built for pod scale: each round
runs log2(ndev) paired XOR hops that (1) recursive-double the five
scalar sums, and (2) route the per-destination send counts with the
hypercube XOR all-to-all (slot p of device v ends holding the count from
source v^p) - payload O(ndev + ndev*nchan) words per hop, O(ndev log
ndev) per round, where a ring allreduce of the send matrix would carry
O(ndev^2).

The same hops carry the backlog-equalizing steal exchange: at each hop
the pair sends (mine - theirs)/2 rows, ``window``-capped, by remote-DMAing
descriptor rows straight between SMEM task tables, importing before the
next hop so received work diffuses further the same round. Every (hop,
sub-channel) inbox is 1-deep with a fixed writer: the receiver signals
that writer's REGULAR *credit* semaphore after consuming, and the writer
waits a credit before its next-round write, so an inbox is never
overwritten before it is consumed, without any global barrier. Receive
DMA semaphores are per-hop: a device two hops ahead may deliver early,
and a shared receive semaphore would hand its signal to a wait for a
different hop's message. All devices execute the identical hop schedule,
so every semaphore wait has a matching signal by construction (lockstep
SPMD, no dynamic handshakes to deadlock on). Termination, stealing, and
message accounting ride this one credited lockstep schedule.

Active messages need no credit round-trips: device s owns inbox row
``inbox[s, :]`` on every target (``am_window`` slots, cycled). A receiver
drains everything a round's counts announced during that round, and the
next round's fold completes only after every device finished that drain,
so a sender that launches at most ``am_window // 2`` AMs per target per
round can never overwrite an unconsumed slot; the rest wait in a local
outbox drained by the round loop.

Arrival correctness: every (source, channel) pair has its OWN DMA
semaphore (``am_sems[src]``, ``chan_sems[src, chan]``), and receivers
wait exactly the announced per-source count before reading - with one
semaphore shared between sources, an early next-round arrival from a
fast device could satisfy a wait for a slower device's still-in-flight
message.

Meshes: 1D, 2D, or 3D (v4/v5p slices are 3D tori), power-of-two per axis
(TPU slices are pof2 per axis); multi-axis hops decompose into per-axis
transfers (row-major flattening, low XOR bits = minor axis, so each
hypercube hop flips exactly one mesh coordinate).
Tested on 8-device 1D, 4x2, and 2x2x2 interpret meshes (including under
the Mosaic race detector) and compiled/run on the real 1-device TPU
(self-loop AMs, atomics, locks).

**Placement seeding (forasync device tier, ISSUE 9).** The per-device
ready rings this runner stages are seeded by whatever the caller put in
its builders - ``device.forasync_tier.place_tiles`` maps a tile loop's
flat tiles onto the roster through a JSON placement descriptor or dist
func (runtime/locality.py), so data-driven placement works here exactly
as on the sharded runner (tests/test_forasync_device.py's resident
seeding test). The XOR-hop exchange partner sequence is graph-ordered
too (the PR 9 residual, closed by ISSUE 10): ``run(hop_order=)`` takes
a permutation of the XOR partner deltas - ``runtime.locality.
xor_hop_order`` / ``MeshPlacement.xor_hop_order()`` derive it
near-neighbors-first from the machine graph's ICI distances, like the
sharded runner's ``steal_hop_order`` - validated, compile-cache-keyed,
and graph-absent behavior (bit-position order, minor axis first)
unchanged. Order is free because the fold's per-dimension exchanges
commute; coverage is not, so partial hop lists are refused.
"""

from __future__ import annotations

import functools
import time
import types
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from .descriptor import (
    DESC_WORDS,
    F_A0,
    F_CSR_N,
    F_CSR_OFF,
    F_DEP,
    F_FN,
    F_HOME,
    F_HROW,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    F_VMASK,
    NO_TASK,
    NUM_ARGS,
    RING_ROW,
    TEN_EXPIRED,
    TEN_ID,
    TaskGraphBuilder,
    ring_slot,
)
from ..runtime.progcache import building
from ..runtime.resilience import DeviceFaultPlan, StallError
from .inject import region_slot
from .tenants import (
    TC_CONSUMED,
    TC_DROPPED,
    TC_EXPIRED,
    TC_INSTALLED,
    TC_PAUSE,
    TC_TAIL,
    TC_WEIGHT,
    build_row,
)
from .megakernel import (
    fault_mix,
    interpret_mode,
    C_EXECUTED,
    OVF_LOCKQ,
    OVF_OUTBOX,
    OVF_WAITS,
    C_HEAD,
    C_OVERFLOW,
    C_PENDING,
    C_ROUNDS,
    C_TAIL,
    C_VBASE,
    Megakernel,
    RA_BECAME,
    RA_WALKED,
    TS_WORDS,
    VBLOCK,
)
from .tracebuf import (
    CR_DROPPED,
    CR_DUPED,
    CR_REGENERATED,
    FLT_DEAD_QUARANTINE,
    FLT_DELAY,
    NullTracer,
    TR_ABORT,
    TR_CKPT,
    TR_CREDIT,
    TR_FAULT,
    TR_INJECT,
    TR_QUIESCE,
    TR_TENANT,
    TR_XFER,
    Tracer,
    trace_info,
)

__all__ = [
    "ResidentKernel",
    "decode_fault_stats",
    "pack_inject_rows",
    "RC_COMPLETE",
    "RC_FADD",
    "RC_FADD_R",
    "RC_CSWAP",
    "RC_REPLY",
    "RC_LOCK",
    "RC_UNLOCK",
    "RC_GRANT",
    "lock_block_slots",
]

# Builtin active-message ids (negative F_FN values, dispatched at drain
# time by the receiving scheduler - they never occupy a task row).
RC_COMPLETE = -2  # [proxy_row, value]: forward a migrated task's result home
RC_FADD = -3      # [slot, delta]: fire-and-forget remote fetch-add
RC_FADD_R = -4    # [slot, delta, src, row, rslot]: fetch-add, reply old value
RC_CSWAP = -5     # [slot, expected, new, src, row, rslot]: compare-swap
RC_REPLY = -6     # [row, value, rslot]: deposit value, dep-decrement row
RC_LOCK = -7      # [lbase, src, row, qcap]: acquire or enqueue
RC_UNLOCK = -8    # [lbase, qcap]: release / grant next waiter
RC_GRANT = -9     # [row]: lock granted - dep-decrement the parked row

AMROW = 128  # padded AM wire row (SMEM DMA minor dim wants 128-word units)
# RING_ROW (the padded injection-ring row, 256 words) now lives in
# descriptor.py beside the TEN_* transport-metadata words it carries;
# imported above and re-exported here for existing callers.


def pack_inject_rows(rows: Sequence, R: int, dev: int = 0):
    """Pack one device's ``inject_rows`` specs into its ``(R, RING_ROW)``
    ring image: tuples ``(fn, args[, out[, tenant_lane]])`` or prebuilt
    RING_ROW numpy rows (``tenants.build_row`` + a TEN_ID stamp - the
    transport metadata rides the row, so tenant identity survives the
    checkpoint residue export and reshard's round-robin re-deal).
    Returns ``(ring, n)``."""
    ring = np.zeros((R, RING_ROW), np.int32)
    if len(rows) > R:
        raise ValueError(f"device {dev}: injection ring overflow")
    for i, spec in enumerate(rows):
        if isinstance(spec, np.ndarray):
            ring[i] = np.asarray(spec, np.int32).reshape(RING_ROW)
            continue
        fn, args = spec[0], spec[1]
        out = spec[2] if len(spec) > 2 else 0
        ring[i] = build_row(fn, args, out)
        if len(spec) > 3:
            ring[i, TEN_ID] = int(spec[3])
    return ring, len(rows)


def lock_block_slots(qcap: int) -> int:
    """Value slots a lock block occupies: [held, qlen, head, (dev,row)*qcap].
    Host presets the block to zero at ``lbase`` on the owner device."""
    return 3 + 2 * int(qcap)


# Per-device fault/abort stats row (an extra SMEM output of every run; the
# device-side fault trace - byte-reproducible from a DeviceFaultPlan seed).
FS_DROPPED = 0      # credits I (as granter) dropped
FS_REGEN = 1        # starved-channel waits I skipped (credit regeneration)
FS_DUPED = 2        # duplicate credits I signalled
FS_DELAYED = 3      # hops where my export quota was zeroed (delay fault)
FS_DEAD_ROUND = 4   # round I first quarantined a dead peer (-1: none)
FS_QMASK = 5        # bitmask of peers I consider dead
FS_REHOMED = 6      # rows I exported while dead (queue re-homing)
FS_ABORT_ROUND = 7  # round the folded abort word was observed (-1: none)
FS_STARVED = 8      # ((hop << 8) | granter) + 1 of my starved channel
FS_HB = 9           # my final heartbeat
FS_QUIESCE_ROUND = 10  # round the folded quiesce word was observed (-1)
FS_TEN_EXPIRED = 11 # tenant-tagged ring rows I dropped expired (the
                    # mesh half of deadline admission: the host marks
                    # TEN_EXPIRED on published rows, the poll skips them)
FS_EXPORTED = 12    # rows I put on the wire in the steal exchange
FS_IMPORTED = 13    # rows I installed from the steal exchange's inboxes
FS_BECAME = 14      # my dispatches that ended re-armed (ctx.become): of
                    # my executed count, the ones whose row stayed pending
FS_WALKED = 15      # my retirements that walked more than F_SUCC0 (a
                    # second inline successor or a CSR list)
FS_WORDS = 16


def decode_fault_stats(row) -> Dict[str, Any]:
    """Human shape of one device's FS_* stats row."""
    row = [int(x) for x in row]
    st = row[FS_STARVED]
    return {
        "credits_dropped": row[FS_DROPPED],
        "credits_regenerated": row[FS_REGEN],
        "credits_duplicated": row[FS_DUPED],
        "xfers_delayed": row[FS_DELAYED],
        "dead_detected_round": row[FS_DEAD_ROUND],
        "quarantined": [d for d in range(31) if (row[FS_QMASK] >> d) & 1],
        "rehomed_rows": row[FS_REHOMED],
        "abort_round": row[FS_ABORT_ROUND],
        "starved_channel": (
            None if st == 0
            else {"hop": (st - 1) >> 8, "granter": (st - 1) & 0xFF}
        ),
        "heartbeat": row[FS_HB],
        "quiesce_round": row[FS_QUIESCE_ROUND],
        "tenant_expired": row[FS_TEN_EXPIRED],
        "steal_exported": row[FS_EXPORTED],
        "steal_imported": row[FS_IMPORTED],
        "became": row[FS_BECAME],
        "walked": row[FS_WALKED],
    }


class ResidentKernel:
    """One resident scheduler per device of a 1D/2D/3D pof2 mesh, composing
    stealing + PGAS + AM/atomics/locks + injection (see module docstring).

    ``migratable_fns``: iterable of kernel-table ids eligible to migrate
    (dependency-bearing rows included, via the home-link protocol), or a
    dict ``{fn_id: (value_arg_index, ...)}`` naming which arg words of
    that kernel are value-slot references to dereference at export.
    ``channels``: ``{name: (data_buffer, rows)}`` - every put on a channel
    moves exactly that many leading-axis rows of the named ``data_specs``
    buffer (the static-shape contract that lets receivers consume arrival
    semaphores with matching descriptors); ``chan_id[name]`` is the index
    kernels use.
    ``inject=True`` adds a per-device host injection ring (rows published
    before entry are discovered by the in-kernel poll).

    ``tenants=`` (mesh-wide tenancy, device/tenants.py; needs
    ``inject=True``): an int N, a sequence of TenantSpec/str/dict lane
    specs, None for the ``HCLIB_TPU_MESH_TENANTS`` env spelling, False
    to force off. With lanes enabled every device's injection ring is
    partitioned into per-tenant regions with a per-device ``tctl[T, 8]``
    control block (host-published per entry, echoed back), and the
    in-kernel poll becomes the same weighted-round-robin lane scan the
    single-device stream compiles - at most ``weight`` rows per lane
    per poll, start lane rotating per round, installs bounded by live
    scheduler headroom, host-marked-expired rows dropped counted.
    Admission routes through a :class:`MeshTenantTable`
    (``run(tenant_table=...)``). A ``tenants=None`` build (no env)
    compiles ZERO new device words - no extra inputs, outputs, or
    branches - bit-identical to the pre-tenancy mesh kernel.

    **Device resilience** (ISSUE 2): every run polls a host-writable abort
    word (HBM, one per device) inside the round loop and folds it into the
    termination collective, so ``run(abort=...)`` stops a running mesh
    within one round in lockstep (``info['aborted']``, per-device abort
    round in ``info['fault_stats']``). ``fault_plan`` (a seeded
    ``DeviceFaultPlan``) compiles deterministic fault injection INTO the
    kernel - dropped/duplicated steal credits with timeout + regeneration,
    delayed transfers, and a dead chip with heartbeat detection,
    quarantine, and task re-homing; see the class docstring in
    runtime/resilience.py. Zero-cost when None.
    """

    def __init__(
        self,
        mk: Megakernel,
        mesh: Mesh,
        *,
        steal: bool = True,
        migratable_fns: Union[Iterable[int], Dict[int, Sequence[int]]] = (),
        homed: bool = True,
        channels: Optional[Dict[str, Tuple[str, int]]] = None,
        inject: bool = False,
        window: int = 8,
        scan: Optional[int] = None,
        am_window: int = 8,
        outbox: int = 256,
        max_waits: int = 64,
        ring_capacity: int = 256,
        proxy_cap: Optional[int] = None,
        fault_plan: Optional[DeviceFaultPlan] = None,
        tenants=None,
    ) -> None:
        if len(mesh.axis_names) not in (1, 2, 3):
            raise ValueError(
                "ResidentKernel wants a 1D/2D/3D mesh (TPU slices are at "
                "most 3D tori)"
            )
        dims = tuple(int(d) for d in mesh.devices.shape)
        for d in dims:
            if d & (d - 1):
                raise ValueError(
                    f"mesh axes must be power-of-two, got {dims} (the "
                    "hypercube hop schedule is pof2-only: drop each axis "
                    "to the next power of two below it)"
                )
        if am_window < 2:
            raise ValueError("am_window must be >= 2")
        self.mk = mk
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.dims = dims
        self.ndev = int(np.prod(dims))
        self.nh = self.ndev.bit_length() - 1  # log2 hops (0 for 1 device)
        self.steal = bool(steal)
        # homed=False restricts migration to link-free rows, which move
        # whole (no proxies, no result forwarding, no value-slot
        # reservation): the steal-only configuration.
        self.homed = bool(homed)
        if isinstance(migratable_fns, dict):
            self.migratable: Dict[int, Tuple[int, ...]] = {
                int(f): tuple(int(i) for i in v)
                for f, v in migratable_fns.items()
            }
        else:
            self.migratable = {int(f): () for f in migratable_fns}
        # The scheduler must maintain descriptor home-link words on
        # spawn/continuation transfer (plain megakernels skip these
        # scalar writes - see Megakernel.tracks_home). homed=False too:
        # export eligibility (migrate-once), the import fix-up and
        # CheckpointBundle.reshard all read F_HOME, and a row spawned
        # into a never-staged table slot would otherwise carry whatever
        # the buffer held there.
        mk.tracks_home = True
        # A claimed kernel id outside the table would silently never
        # migrate (the whitelist is a per-kind mask) - refuse
        # unconditionally, verifier on or off.
        bad = [f for f in self.migratable
               if not 0 <= f < len(mk.kernel_names)]
        if bad:
            raise ValueError(
                f"migratable_fns {sorted(bad)} outside the kernel "
                f"table (0..{len(mk.kernel_names) - 1})"
            )
        for f, vargs in self.migratable.items():
            if len(vargs) > VBLOCK:
                raise ValueError(
                    f"kernel {f}: at most {VBLOCK} value args (rehydration "
                    "uses the row's own value block)"
                )
            if vargs and not mk.uses_row_values:
                raise ValueError(
                    "value-arg rehydration needs uses_row_values=True "
                    "(arriving rows rehydrate into their own row block)"
                )
        self.channels: List[Tuple[str, int]] = []
        self.chan_id: Dict[str, int] = {}
        for cname, (bname, rows) in (channels or {}).items():
            if bname not in mk.data_specs:
                raise ValueError(
                    f"channel {cname!r}: no data buffer {bname!r}"
                )
            if rows < 1 or rows > mk.data_specs[bname].shape[0]:
                raise ValueError(f"channel {cname!r}: bad row count {rows}")
            self.chan_id[cname] = len(self.channels)
            self.channels.append((bname, int(rows)))
        self.nchan = max(1, len(self.channels))
        self.inject = bool(inject)
        self.window = int(window)
        self.scan = int(scan) if scan is not None else 2 * self.window
        self.am_window = int(am_window)
        self.outbox = int(outbox)
        self.max_waits = int(max_waits)
        self.ring_capacity = -(-int(ring_capacity) // 8) * 8
        # Mesh-wide tenancy (device/tenants.py): per-tenant ring regions
        # + a per-device tctl WRR control block. Off (the default, and
        # the env-less default) compiles ZERO new device words.
        from .tenants import normalize_mesh_tenants

        specs = normalize_mesh_tenants(tenants)
        if specs is not None and not self.inject:
            raise ValueError(
                "tenants= partitions the injection ring into per-tenant "
                "regions: needs inject=True"
            )
        self.tenant_specs = specs
        self.T = 0 if specs is None else len(specs)
        if self.T:
            self.region_rows = -(-self.ring_capacity // (8 * self.T)) * 8
            # The ring is exactly the concatenation of the lane regions.
            self.ring_capacity = self.T * self.region_rows
        else:
            self.region_rows = 0
        # Outstanding-proxy budget: a homed export pins a proxy row until
        # the migrated SUBTREE completes remotely (its continuation chain
        # sends the completion), so unthrottled migration of dep-bearing
        # work can pin O(migrations) rows for O(subtree) time - measured
        # as task-table exhaustion. Above this many live proxies a device
        # stops exporting dep-bearing rows (link-free rows still move);
        # local execution continues, so this throttles, never deadlocks.
        self.proxy_cap = (
            int(proxy_cap) if proxy_cap is not None
            else max(8, mk.capacity // 4)
        )
        # Migration result slots: one per descriptor row, at the top of the
        # value buffer. The chain-ending task writes its result there and
        # its completion hook reads it in the same scheduler step, so the
        # serial scheduler makes reuse race-free (module docstring).
        self.rbase = (
            mk.num_values - mk.capacity
            if (self.migratable and self.homed)
            else mk.num_values
        )
        if self.rbase <= 0:
            raise ValueError(
                "migration needs num_values > capacity (one result slot "
                "per row is reserved at the top of the value buffer)"
            )
        # Compiled-in fault injection (None = no fault code emitted).
        self.plan = (
            fault_plan
            if fault_plan is not None and fault_plan.enabled()
            else None
        )
        if self.plan is not None:
            if not self.steal:
                raise ValueError(
                    "DeviceFaultPlan faults target the steal exchange "
                    "(credits, dead-chip re-homing): needs steal=True"
                )
            if self.ndev > 31:
                raise ValueError(
                    f"DeviceFaultPlan supports at most 31 devices (the "
                    f"quarantine bitmask is one int32 stats word), got "
                    f"{self.ndev}"
                )
            if self.plan.dead_device is not None and not (
                0 <= self.plan.dead_device < self.ndev
            ):
                raise ValueError(
                    f"dead_device {self.plan.dead_device} out of range for "
                    f"a {self.ndev}-device mesh"
                )
        # Stat-vector layout (exchanged every hop). Words [0, SX_AM) are
        # recursive-doubling SUMS; [SX_AM, S_BL) route by the hypercube
        # XOR all-to-all (slot p ends holding source me^p's count);
        # [S_BL] is the sender's CURRENT backlog, read raw per hop.
        # SF_ABORT/SF_WEDGE fold the per-device abort word and the
        # starved-channel wedge flag, so a local abort (or an unrecoverable
        # dropped credit) exits the WHOLE mesh in lockstep one fold later -
        # a divergent exit would strand partners in the paired exchanges.
        # SF_QUIESCE (checkpoint builds only - the word costs an exchanged
        # stat slot, so a non-checkpoint build compiles none of it) folds
        # the host quiesce word the same way: on observing it every device
        # stops popping (sched quantum -> 0) but KEEPS the exchange rounds
        # - outboxes drain, in-flight AMs land, sent == recv - and the
        # mesh exits in lockstep with nothing on the wire, every device's
        # live scheduler state in its aliased outputs (the clean-cut
        # property a checkpoint needs that an abort does not provide).
        self.SF_PEND = 0
        self.SF_RECV = 1
        self.SF_OUTB = 2
        self.SF_SENT = 3
        self.SF_INJ = 4
        self.SF_ABORT = 5
        self.SF_WEDGE = 6
        self.checkpoint = bool(mk.checkpoint)
        if self.checkpoint:
            self.SF_QUIESCE = 7
            self.SX_AM = 8
        else:
            self.SF_QUIESCE = None
            self.SX_AM = 7
        self.SX_DATA = self.SX_AM + self.ndev
        nxt = self.SX_DATA + self.ndev * self.nchan
        if self.plan is not None:
            # Heartbeat section (dead-chip detection): routed by the same
            # XOR all-to-all - slot p of device v ends holding v^p's
            # heartbeat, so every device observes every peer every round.
            self.SX_HB = nxt
            nxt += self.ndev
        self.S_BL = nxt
        self.S = self.S_BL + 1
        self._jitted: Dict[Any, Any] = {}
        self._pc_stats: Optional[Dict[str, Any]] = None

    def _cache_variant(self, key) -> tuple:
        """Everything this runner compiles into the program beyond the
        Megakernel's own content: the program-cache variant key
        (runtime/progcache.py). ``key`` is the per-run (quantum,
        max_rounds, hop_bits) tuple the L1 dict uses."""
        from ..runtime.progcache import mesh_key

        return (
            "resident", mesh_key(self.mesh), self.steal, self.homed,
            tuple(sorted(self.migratable.items())),
            tuple(self.channels), self.inject, self.window, self.scan,
            self.am_window, self.outbox, self.max_waits,
            self.ring_capacity, self.T, self.region_rows,
            self.proxy_cap, self.plan, self.checkpoint,
        ) + tuple(key)

    def program_cached(
        self, quantum: int = 64, max_rounds: int = 1 << 14,
        hop_order=None,
    ) -> bool:
        """True when the compiled program for a ``run()`` with these
        parameters is already warm - in this instance's own jit table
        or the process-wide program cache (so a resize onto a shape
        ANY kernel of this process ever built reports hot). The read
        ``Autoscaler`` records as ``ScaleEvent.cache_hit``."""
        key = (quantum, max_rounds, self._hop_bits(hop_order))
        if key in self._jitted:
            return True
        from ..runtime.progcache import probe

        return probe(self.mk, self._cache_variant(key))

    # -- mesh addressing --

    def _flat_me(self):
        # Row-major flattening over the mesh axes; with pof2 dims the XOR
        # hop bits partition per axis (minor axis = low bits), so every
        # hypercube hop flips exactly one mesh coordinate - the same
        # decomposition for 1D, 2D, and 3D tori.
        f = jax.lax.axis_index(self.axes[0])
        for ax, d in zip(self.axes[1:], self.dims[1:]):
            f = f * d + jax.lax.axis_index(ax)
        return f

    def _did(self, flat):
        if len(self.axes) == 1:
            return flat
        coords = []
        rem = flat
        for d in self.dims[:0:-1]:
            coords.append(rem % d)
            rem = rem // d
        coords.append(rem)
        return tuple(reversed(coords))

    @property
    def _did_type(self):
        return (
            pltpu.DeviceIdType.LOGICAL
            if len(self.axes) == 1
            else pltpu.DeviceIdType.MESH
        )

    # -- the kernel --

    def _hop_bits(self, hop_order) -> Tuple[int, ...]:
        """Normalize a ``hop_order`` (XOR partner deltas, e.g. from
        ``runtime.locality.xor_hop_order`` / a placement descriptor's
        ``xor_hop_order()``) into the bit-index sequence the exchange
        loop iterates. None = the default bit-position order (minor axis
        first) - graph-absent behavior unchanged. The fold needs every
        hypercube dimension each round (recursive-doubling sums and the
        XOR all-to-all are products of commuting per-dimension
        exchanges, so ORDER is free but coverage is not): anything short
        of a full permutation of the power-of-two deltas is refused."""
        if hop_order is None:
            return tuple(range(self.nh))
        deltas = [int(d) for d in hop_order]
        if sorted(deltas) != [1 << k for k in range(self.nh)]:
            raise ValueError(
                f"hop_order must be a permutation of the XOR deltas "
                f"{[1 << k for k in range(self.nh)]} (every hypercube "
                f"dimension exactly once), got {deltas}"
            )
        return tuple(d.bit_length() - 1 for d in deltas)

    def _kernel(
        self, quantum: int, max_rounds: int, trace, hop_bits, *refs
    ) -> None:
        # ``trace`` is captured at _build time (pallas traces lazily;
        # reading mk.trace here could disagree with the built out tree).
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = len(mk.batch_specs)
        ntrace = 1 if trace is not None else 0
        nten = 1 if self.T else 0
        # + abort word (last); tenant builds add the per-device tctl
        # block between ictl and it.
        n_in = 7 + ndata + (2 if self.inject else 0) + nten
        in_refs = refs[:n_in]
        # + (tenant builds) the tctl echo after the ctl echo, + (batch-
        # routed builds) the per-device tstats row, + fstats, then
        # (checkpoint builds only) the exported wait table - the lifted
        # scratch limit: quiesce with pending host-declared waits now
        # exports them instead of refusing - then the optional
        # flight-recorder ring (always last).
        n_out = (
            5 + ndata + (1 if self.inject else 0) + nten
            + (1 if nbatch else 0)
            + (1 if self.checkpoint else 0) + ntrace
        )
        out_refs = refs[n_in : n_in + n_out]
        rest = refs[n_in + n_out :]
        nscratch = len(mk.scratch_specs)
        scratch_refs = rest[:nscratch]
        tail = list(rest[nscratch:])

        def take(n):
            head, tail[:n] = tail[:n], []
            return head

        nckpt = 1 if self.checkpoint else 0
        nh = self.nh
        (free, vfree, rearm, candbuf, sendbuf, statacc, statsnd) = take(7)
        statrcv = take(nh)
        inboxes = take(nh) if self.steal else []
        (
            outq_tgt, outq_desc, obctl, ambuf, inbox, am_sent, am_recv,
            sent_round, data_sent, chan_recv, chan_tot, pstate, wait_tab,
        ) = take(13)
        if self.inject:
            ctlbuf, rowbuf = take(2)
        (ssems, rsems, csems, am_sems, chan_sems) = take(5)
        if self.inject:
            (isem,) = take(1)
        (abuf, asem) = take(2)  # abort-word staging + its DMA semaphore
        if nbatch:
            # Batched same-kind dispatch tier (ISSUE 7): the per-kind lane
            # scratch, re-entrant across sched() entries by the spill
            # discipline - every sched exit (quantum, quiesce hold)
            # spills unrun lane entries to the ready ring's cold end, so
            # the steal export scan, queue re-homing, and checkpoint
            # export below only ever see ring rows.
            (lanes, lstate) = take(2)
        else:
            lanes = lstate = None
        plan = self.plan
        if plan is not None:
            # Fault-layer state (per steal channel k / per peer device):
            # pair_down[k] = last round of the current starvation window,
            # owed[k] = dropped credits not yet compensated by a skipped
            # wait, cbal[k] = live credit balance (signals in - waits
            # done; the exit drain consumes exactly this), hb_seen/
            # hb_round/deadmask = heartbeat detection + quarantine.
            (pair_down, owed, cbal, hb_seen, hb_round, deadmask) = take(6)
        assert not tail, f"{len(tail)} unconsumed scratch refs"

        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        waits_in = in_refs[5 + ndata]
        if self.inject:
            iring, ictl = in_refs[6 + ndata], in_refs[7 + ndata]
        tctl_in = in_refs[8 + ndata] if nten else None
        abort_in = in_refs[n_in - 1]
        tasks, ready, counts, ivalues = out_refs[:4]
        data = dict(zip(mk.data_specs.keys(), out_refs[4 : 4 + ndata]))
        if self.inject:
            ctl_out = out_refs[4 + ndata]
        # Tenant lane cursors + cumulative counters: host-seeded per
        # entry, mutated in place by the WRR poll, echoed back at exit
        # (right after the ctl echo).
        tctl_out = out_refs[5 + ndata] if nten else None
        # Per-device batched-tier counters (appended after the ctl/tctl
        # echoes): decoded host-side into info['tiers'][d], the mesh
        # occupancy the perf guard and the lane-firing-policy detector
        # watch.
        tstats = (
            out_refs[4 + ndata + (1 if self.inject else 0) + nten]
            if nbatch else None
        )
        fstats = out_refs[n_out - 1 - ntrace - nckpt]
        waits_out = out_refs[n_out - 1 - ntrace] if self.checkpoint else None
        tr = (
            Tracer(out_refs[n_out - 1], trace.capacity)
            if ntrace
            else NullTracer()
        )
        scratch = dict(zip(mk.scratch_specs.keys(), scratch_refs))

        ndev = self.ndev
        nchan = self.nchan
        AMW = self.am_window
        OUTQ = self.outbox
        MAXW = self.max_waits
        W = self.window
        SCAN = self.scan
        rlen = mk.ring_len  # the ready ring's modulus (a power of two)
        RBASE = self.rbase
        SF_PEND, SF_RECV, SF_OUTB, SF_SENT, SF_INJ = (
            self.SF_PEND, self.SF_RECV, self.SF_OUTB, self.SF_SENT,
            self.SF_INJ,
        )
        SF_ABORT, SF_WEDGE = self.SF_ABORT, self.SF_WEDGE
        SF_QUIESCE, ckpt = self.SF_QUIESCE, self.checkpoint
        SX_AM, SX_DATA, S_BL, S = self.SX_AM, self.SX_DATA, self.S_BL, self.S
        did_type = self._did_type
        me = self._flat_me()

        # pstate slots
        PS_RECV, PS_NWAIT, PS_SENT, PS_PROXIES = 0, 1, 2, 3
        PS_HB, PS_WEDGE, PS_QUIESCE = 4, 5, 6

        # ---- compiled-in fault predicates (None plan emits nothing) ----

        if plan is not None:
            def _pred(site, millis, exact, r, k, g):
                """Does fault ``site`` fire at (round r, hop k, granter g)?
                Pure in (seed, site, r, k, g): every device of the
                lockstep mesh computes the identical answer, for any
                (k, g) - injector, victim, and bystanders agree."""
                p = jnp.bool_(False)
                if millis > 0:
                    p = fault_mix(plan.seed, site, r, k, g) < millis
                for (rr, kk, gg) in exact:
                    if kk == k:
                        p = p | ((r == jnp.int32(rr)) & (g == jnp.int32(gg)))
                return p

            def pred_drop(r, k, g):
                return _pred(0, plan.drop_millis, plan.drop_credit_at,
                             r, k, g)

            def pred_dup(r, k, g):
                return _pred(1, plan.dup_millis, plan.dup_credit_at,
                             r, k, g)

            def pred_delay(r, k, g):
                return _pred(2, plan.delay_millis, (), r, k, g)

            def is_dead(r):
                """Is THIS device the plan's dead chip at round r?"""
                if plan.dead_device is None:
                    return jnp.bool_(False)
                return (me == jnp.int32(plan.dead_device)) & (
                    r >= jnp.int32(plan.dead_round)
                )

            if plan.drops_credits() and plan.credit_timeout == 0:
                # Regeneration disabled: ANY drop wedges the mesh. Every
                # device evaluates the all-pairs drop schedule, so all
                # skip the row exchanges of the following round in
                # lockstep (a starved writer must never reach its wait)
                # and exit together at the next fold.
                def any_drop(r):
                    p = jnp.bool_(False)
                    for k in range(nh):
                        for g in range(ndev):
                            p = p | pred_drop(r, k, jnp.int32(g))
                    return p

        # ---- outbox / active messages ----

        def op_am(dev, fn, args: Sequence = (), out=0) -> None:
            """Queue a descriptor (or builtin op, fn < 0) for device
            ``dev``'s scheduler; the round loop launches it under the
            per-target inbox window."""
            if len(args) > NUM_ARGS:
                raise ValueError(f"at most {NUM_ARGS} args per AM")
            h = obctl[1]
            ok = h - obctl[0] < OUTQ
            slot = h % OUTQ

            @pl.when(ok)
            def _():
                outq_tgt[slot] = dev
                outq_desc[slot, F_FN] = jnp.int32(fn)
                outq_desc[slot, F_DEP] = 0
                outq_desc[slot, F_SUCC0] = jnp.int32(NO_TASK)
                outq_desc[slot, F_SUCC1] = jnp.int32(NO_TASK)
                outq_desc[slot, F_CSR_OFF] = 0
                outq_desc[slot, F_CSR_N] = 0
                for i in range(NUM_ARGS):
                    outq_desc[slot, F_A0 + i] = (
                        jnp.int32(args[i]) if i < len(args) else 0
                    )
                outq_desc[slot, F_OUT] = jnp.int32(out)
                outq_desc[slot, F_HOME] = jnp.int32(NO_TASK)
                outq_desc[slot, F_HROW] = 0
                outq_desc[slot, F_VMASK] = 0
                obctl[1] = h + 1

            @pl.when(jnp.logical_not(ok))
            def _():
                counts[C_OVERFLOW] = counts[C_OVERFLOW] | OVF_OUTBOX

        def op_put(dev, chan: int, dst_row, src_row) -> None:
            """One-sided channel write (SHMEM put): local completion on
            return; target-side arrival is what wait_until observes."""
            if not isinstance(chan, int):
                raise TypeError("chan must be a static channel id")
            if not (0 <= chan < len(self.channels)):
                raise ValueError(
                    f"channel id {chan} not configured (have "
                    f"{len(self.channels)}): a kernel using ctx.pgas.put "
                    "needs its channel declared in ResidentKernel(channels=)"
                )
            bname, rows = self.channels[chan]
            buf = data[bname]
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[pl.ds(src_row, rows)],
                dst_ref=buf.at[pl.ds(dst_row, rows)],
                send_sem=ssems.at[2],
                # Per-(source, channel) arrival semaphore: slot [me, chan]
                # on the TARGET (symmetric allocation).
                recv_sem=chan_sems.at[me, chan],
                device_id=self._did(dev),
                device_id_type=did_type,
            )
            rdma.start()
            rdma.wait_send()
            data_sent[dev, chan] = data_sent[dev, chan] + 1
            pstate[PS_SENT] = pstate[PS_SENT] + 1

        def op_wait_until(chan, need, row) -> None:
            n = pstate[PS_NWAIT]
            ok = n < MAXW
            nc = jnp.minimum(n, MAXW - 1)

            @pl.when(ok)
            def _():
                wait_tab[nc, 0] = chan
                wait_tab[nc, 1] = need
                wait_tab[nc, 2] = row
                pstate[PS_NWAIT] = n + 1

            @pl.when(jnp.logical_not(ok))
            def _():
                counts[C_OVERFLOW] = counts[C_OVERFLOW] | OVF_WAITS

        def op_count(chan: int):
            return chan_tot[chan]

        def op_fadd(dev, slot, delta) -> None:
            """Fire-and-forget remote fetch-add (owner-computes)."""
            op_am(dev, RC_FADD, (slot, delta))

        def op_fadd_get(dev, slot, delta, row, rslot) -> None:
            """Fetch-add whose OLD value lands in local slot ``rslot`` and
            dep-decrements parked row ``row`` (spawn it with an extra
            dep)."""
            op_am(dev, RC_FADD_R, (slot, delta, me, row, rslot))

        def op_cswap(dev, slot, expected, new, row, rslot) -> None:
            """Remote compare-swap; old value replies to (row, rslot)."""
            # me is the wire's src word: the owner replies to it. Dropping
            # it shifted every later arg (the reply went to device=row,
            # row=rslot, slot=garbage) - caught by the volume stress test.
            op_am(dev, RC_CSWAP, (slot, expected, new, me, row, rslot))

        def op_lock(dev, lbase, row, qcap: int) -> None:
            """Acquire the lock block at ``lbase`` on ``dev``; parked row
            ``row`` (one extra dep) is dep-decremented when granted."""
            op_am(dev, RC_LOCK, (lbase, me, row, qcap))

        def op_unlock(dev, lbase, qcap: int) -> None:
            op_am(dev, RC_UNLOCK, (lbase, qcap))

        def ctx_hook(ctx) -> None:
            ctx.pgas = types.SimpleNamespace(
                put=op_put, am=op_am, wait_until=op_wait_until,
                count=op_count, fadd=op_fadd, fadd_get=op_fadd_get,
                cswap=op_cswap, lock=op_lock, unlock=op_unlock,
                me=me, ndev=ndev, nchan=len(self.channels),
            )

        def complete_hook(idx) -> None:
            """Migrated chains forward their result to the home proxy on
            completion (module docstring: the home-link protocol)."""

            @pl.when(tasks[idx, F_HOME] >= 0)
            def _():
                op_am(
                    tasks[idx, F_HOME],
                    RC_COMPLETE,
                    (tasks[idx, F_HROW], ivalues[tasks[idx, F_OUT]]),
                )

        core = mk._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            rearm, tasks_in, ready_in, counts_in, ivalues_in, True, ctx_hook,
            complete_hook if (self.migratable and self.homed) else None,
            value_limit=RBASE,
            lanes=lanes, lstate=lstate, tstats=tstats,
            tracer=tr if tr.enabled else None,
        )

        def dep_dec(row) -> None:
            d = tasks[row, F_DEP] - 1
            tasks[row, F_DEP] = d

            @pl.when(d == 0)
            def _():
                core.push_ready(row)

        # ---- stage ----

        def stage_resident() -> None:
            def z(i, _):
                am_sent[i] = 0
                am_recv[i] = 0
                sent_round[i] = 0
                for c in range(nchan):
                    data_sent[i, c] = 0
                    chan_recv[i, c] = 0
                return 0

            jax.lax.fori_loop(0, ndev, z, 0)
            for c in range(nchan):
                chan_tot[c] = 0
            for i in range(8):
                pstate[i] = 0
            for i in range(FS_WORDS):
                fstats[i] = 0
            fstats[FS_DEAD_ROUND] = -1
            fstats[FS_ABORT_ROUND] = -1
            fstats[FS_QUIESCE_ROUND] = -1
            if plan is not None:
                for k in range(nh):
                    pair_down[k] = -1
                    owed[k] = 0
                    cbal[k] = 0

                def zf(i, _):
                    hb_seen[i] = 0
                    hb_round[i] = 0
                    deadmask[i] = 0
                    return 0

                jax.lax.fori_loop(0, ndev, zf, 0)
            pstate[PS_NWAIT] = waits_in[0, 0]
            obctl[0] = 0
            obctl[1] = 0

            def cw(i, _):
                for w in range(3):
                    wait_tab[i, w] = waits_in[1 + i, w]
                return 0

            jax.lax.fori_loop(0, waits_in[0, 0], cw, 0)

        # ---- import fixups (stolen rows, AM task rows) ----

        has_vargs = any(v for v in self.migratable.values())

        def install_fixed(read_word):
            """Adopt an external row, then apply migration fixups: homed
            rows get a local result slot; dereferenced value args
            rehydrate into the row's own value block."""
            row = core.install_descriptor(read_word)

            @pl.when(tasks[row, F_HOME] >= 0)
            def _():
                tasks[row, F_OUT] = jnp.int32(RBASE) + row

            if has_vargs:
                mask = tasks[row, F_VMASK]
                base = counts[C_VBASE] + row * VBLOCK
                jj = jnp.int32(0)
                for i in range(NUM_ARGS):
                    bit = (mask >> i) & 1

                    @pl.when(bit == 1)
                    def _(i=i, jj=jj):
                        ivalues[base + jj] = tasks[row, F_A0 + i]
                        tasks[row, F_A0 + i] = base + jj

                    jj = jj + bit
                # Cleared HERE (not in spawn): wire copies are the only
                # writers of F_VMASK, so the import path owns its reset.
                tasks[row, F_VMASK] = 0
            return row

        # ---- steal export / import (general migration) ----

        wl = sorted(self.migratable)

        def homed_elig_of(cand):
            """Rows migrate as homed copies when they carry successor
            links or write a DYNAMIC value slot (>= the symmetric host
            region): a dynamic out address is only valid on its home
            device, so the result must forward home rather than land at
            the same index on the thief (where it could alias a live
            block). (Rows that are already migrated copies never reach
            this classification - elig_of's migrate-once term excludes
            F_HOME >= 0 rows from export entirely.)"""
            return (
                (tasks[cand, F_SUCC0] != NO_TASK)
                | (tasks[cand, F_SUCC1] != NO_TASK)
                | (tasks[cand, F_CSR_N] > 0)
                | (tasks[cand, F_OUT] >= counts[C_VBASE])
            )

        def elig_of(cand, allow_homed):
            """``allow_homed`` is SNAPSHOTTED once per export scan: the
            proxy counter moves while classify takes rows, and an
            eligibility that flipped mid-scan would ship fewer rows than
            the announced count (stale sendbuf entries on the wire)."""
            d_fn = tasks[cand, F_FN]
            ok = jnp.bool_(False)
            for f in wl:
                ok = ok | (d_fn == f)
            # Migrate-once: a row that is already a migrated copy (carries
            # a home-link) never re-exports. Re-stealing would work
            # protocol-wise (completions chain through intermediate
            # proxies), but every extra hop leaves ANOTHER proxy row alive
            # until the completion propagates back - measured as
            # task-table exhaustion when churny windows bounce tasks
            # between devices. Bounding chains at length 1 keeps proxy
            # liveness = in-flight migrations, and thieves still rebalance
            # through the fresh tasks migrated work spawns locally.
            ok = ok & (tasks[cand, F_HOME] < 0)
            if not self.homed:
                # Round-3 semantics: only link-free rows may move.
                ok = ok & jnp.logical_not(homed_elig_of(cand))
            else:
                # Proxy budget: dep-bearing rows stop exporting while too
                # many migrated subtrees are outstanding (see proxy_cap).
                ok = ok & (jnp.logical_not(homed_elig_of(cand)) | allow_homed)
            return ok

        def export(quota):
            """Move up to ``quota`` eligible ready rows into sendbuf.
            Rows with successor links (or an existing home-link) export as
            homed copies and leave a proxy; link-free rows move whole."""
            head = counts[C_HEAD]
            backlog = counts[C_TAIL] - head
            Sn = jnp.minimum(backlog, SCAN)

            def copy_cand(j, _):
                candbuf[j] = ready[ring_slot(head + j, rlen)]
                return 0

            jax.lax.fori_loop(0, Sn, copy_cand, 0)

            allow_homed = pstate[PS_PROXIES] < self.proxy_cap

            def count_elig(j, n):
                return n + elig_of(candbuf[j], allow_homed).astype(jnp.int32)

            nelig = jax.lax.fori_loop(0, Sn, count_elig, jnp.int32(0))
            nsend = jnp.minimum(quota, nelig)

            def homed_of(cand):
                if not self.homed:
                    return jnp.bool_(False)  # eligibility already excluded
                return homed_elig_of(cand)

            def classify(j, carry):
                se, kp, nw = carry
                cand = candbuf[j]
                tk = elig_of(cand, allow_homed) & (se < nsend)

                @pl.when(tk)
                def _():
                    for w in range(DESC_WORDS):
                        sendbuf[se, w] = tasks[cand, w]
                    # The wire's value-mask is OWNED BY EXPORT, never
                    # copied from the row: spawn leaves F_VMASK unwritten
                    # (a dead word locally), so a recycled/bump row holds
                    # garbage there - and a garbage mask would make the
                    # importer rehydrate ALL SIX args of the copy,
                    # corrupting its descriptor (observed: FIB(4) arriving
                    # as FIB(<block address>), spawning unbounded trees).
                    sendbuf[se, F_VMASK] = 0
                    links = homed_of(cand)

                    @pl.when(links)
                    def _():
                        # Homed copy: links stay on the proxy; the copy
                        # names us as home. (Copies themselves never
                        # re-export - migrate-once in elig_of - so every
                        # home-link points at the row's origin device.)
                        sendbuf[se, F_SUCC0] = jnp.int32(NO_TASK)
                        sendbuf[se, F_SUCC1] = jnp.int32(NO_TASK)
                        sendbuf[se, F_CSR_OFF] = 0
                        sendbuf[se, F_CSR_N] = 0
                        sendbuf[se, F_HOME] = me
                        sendbuf[se, F_HROW] = cand
                        pstate[PS_PROXIES] = pstate[PS_PROXIES] + 1

                    @pl.when(jnp.logical_not(links))
                    def _():
                        # Whole-row migration: the task now lives on the
                        # target; tombstone + free the home row.
                        tasks[cand, F_DEP] = -1
                        nf = free[0] + 1
                        free[0] = nf
                        free[nf] = cand

                    # Dereference declared value-slot args (final: the
                    # row was ready, all predecessors completed).
                    for f, vargs in self.migratable.items():
                        if not vargs:
                            continue
                        m = 0
                        for i in vargs:
                            m |= 1 << i

                        @pl.when(tasks[cand, F_FN] == f)
                        def _(f=f, vargs=vargs, m=m):
                            for i in vargs:
                                sendbuf[se, F_A0 + i] = ivalues[
                                    tasks[cand, F_A0 + i]
                                ]
                            sendbuf[se, F_VMASK] = m

                @pl.when(jnp.logical_not(tk))
                def _():
                    ready[ring_slot(head + nsend + kp, rlen)] = cand

                # Safe to re-evaluate after the mutation above: homed
                # export leaves tasks[cand] untouched, and whole-row
                # export only tombstones F_DEP, which homed_of never reads.
                whole = tk & jnp.logical_not(homed_of(cand))
                return (
                    se + tk.astype(jnp.int32),
                    kp + (1 - tk.astype(jnp.int32)),
                    nw + whole.astype(jnp.int32),
                )

            _, _, nwhole = jax.lax.fori_loop(
                0, Sn, classify, (jnp.int32(0), jnp.int32(0), jnp.int32(0))
            )
            counts[C_HEAD] = head + nsend
            # Homed exports stay pending at home (the proxy); only
            # whole-row exports hand their pending count to the thief.
            counts[C_PENDING] = counts[C_PENDING] - nwhole
            fstats[FS_EXPORTED] = fstats[FS_EXPORTED] + nsend
            return nsend

        def import_rows(box):
            n = box[W, 0]
            fstats[FS_IMPORTED] = fstats[FS_IMPORTED] + n

            def one(i, _):
                install_fixed(lambda w: box[i, w])
                return 0

            jax.lax.fori_loop(0, n, one, 0)

        # ---- AM drain machinery ----

        def drain_outbox() -> None:
            """Launch queued AMs under the per-target inbox window (FIFO;
            a capped head entry stalls until next round, preserving
            per-target order)."""

            def zz(i, _):
                sent_round[i] = 0
                return 0

            jax.lax.fori_loop(0, ndev, zz, 0)

            def cond(h):
                more = h < obctl[1]
                t = outq_tgt[h % OUTQ]
                return more & (
                    sent_round[jnp.where(more, t, 0)] < AMW // 2
                )

            def body(h):
                slot_q = h % OUTQ
                t = outq_tgt[slot_q]
                slot = am_sent[t] % AMW
                for w in range(DESC_WORDS):
                    ambuf[w] = outq_desc[slot_q, w]
                rdma = pltpu.make_async_remote_copy(
                    src_ref=ambuf,
                    dst_ref=inbox.at[me, slot],
                    send_sem=ssems.at[3],
                    # Slot [me] on the TARGET: per-source arrivals.
                    recv_sem=am_sems.at[me],
                    device_id=self._did(t),
                    device_id_type=did_type,
                )
                rdma.start()
                rdma.wait_send()
                am_sent[t] = am_sent[t] + 1
                sent_round[t] = sent_round[t] + 1
                pstate[PS_SENT] = pstate[PS_SENT] + 1
                return h + 1

            obctl[0] = jax.lax.while_loop(cond, body, obctl[0])

        def handle_am(s, slot) -> None:
            """Dispatch one landed AM: builtin ops (fn < 0) run inline at
            the receiving scheduler; task descriptors install."""
            fn = inbox[s, slot, F_FN]

            def a(i):
                return inbox[s, slot, F_A0 + i]

            @pl.when(fn >= 0)
            def _():
                install_fixed(lambda w: inbox[s, slot, w])

            @pl.when(fn == RC_COMPLETE)
            def _():
                hrow = a(0)
                ivalues[tasks[hrow, F_OUT]] = a(1)
                core.complete(hrow)
                # The execution was already counted on the thief.
                counts[C_EXECUTED] = counts[C_EXECUTED] - 1
                pstate[PS_PROXIES] = pstate[PS_PROXIES] - 1

            @pl.when(fn == RC_FADD)
            def _():
                ivalues[a(0)] = ivalues[a(0)] + a(1)

            @pl.when(fn == RC_FADD_R)
            def _():
                old = ivalues[a(0)]
                ivalues[a(0)] = old + a(1)
                op_am(a(2), RC_REPLY, (a(3), old, a(4)))

            @pl.when(fn == RC_CSWAP)
            def _():
                old = ivalues[a(0)]
                ivalues[a(0)] = jnp.where(old == a(1), a(2), old)
                op_am(a(3), RC_REPLY, (a(4), old, a(5)))

            @pl.when(fn == RC_REPLY)
            def _():
                ivalues[a(2)] = a(1)
                dep_dec(a(0))

            @pl.when(fn == RC_LOCK)
            def _():
                lbase, src, row, qcap = a(0), a(1), a(2), a(3)
                held = ivalues[lbase]

                @pl.when(held == 0)
                def _():
                    ivalues[lbase] = 1
                    op_am(src, RC_GRANT, (row,))

                @pl.when(held != 0)
                def _():
                    qlen = ivalues[lbase + 1]
                    head_q = ivalues[lbase + 2]
                    okq = qlen < qcap
                    pos = lbase + 3 + 2 * ((head_q + qlen) % qcap)

                    @pl.when(okq)
                    def _():
                        ivalues[pos] = src
                        ivalues[pos + 1] = row
                        ivalues[lbase + 1] = qlen + 1

                    @pl.when(jnp.logical_not(okq))
                    def _():
                        counts[C_OVERFLOW] = counts[C_OVERFLOW] | OVF_LOCKQ

            @pl.when(fn == RC_UNLOCK)
            def _():
                lbase, qcap = a(0), a(1)
                qlen = ivalues[lbase + 1]

                @pl.when(qlen == 0)
                def _():
                    ivalues[lbase] = 0

                @pl.when(qlen > 0)
                def _():
                    head_q = ivalues[lbase + 2]
                    pos = lbase + 3 + 2 * (head_q % qcap)
                    ivalues[lbase + 2] = (head_q + 1) % qcap
                    ivalues[lbase + 1] = qlen - 1
                    # Lock stays held; hand it to the next waiter.
                    op_am(ivalues[pos], RC_GRANT, (ivalues[pos + 1],))

            @pl.when(fn == RC_GRANT)
            def _():
                dep_dec(a(0))

        def drain_receives() -> None:
            """Consume exactly the per-source arrivals the fold announced:
            wait each (source, channel) semaphore down by its announced
            delta BEFORE reading - payloads are never observed partially
            written, and a fast device's next-round message can never
            satisfy a wait for a slower source (per-source semaphores)."""
            me_did = self._did(me)
            for c, (bname, rows) in enumerate(self.channels):
                buf = data[bname]
                for p in range(ndev):
                    src = me ^ p
                    expected = statacc[SX_DATA + p * nchan + c]
                    delta = expected - chan_recv[src, c]
                    waiter = pltpu.make_async_remote_copy(
                        src_ref=buf.at[pl.ds(0, rows)],
                        dst_ref=buf.at[pl.ds(0, rows)],
                        send_sem=ssems.at[2],
                        recv_sem=chan_sems.at[src, c],
                        device_id=me_did,
                        device_id_type=did_type,
                    )

                    def one(i, _):
                        waiter.wait_recv()
                        return 0

                    jax.lax.fori_loop(0, delta, one, 0)
                    chan_recv[src, c] = expected
                    chan_tot[c] = chan_tot[c] + delta
                    pstate[PS_RECV] = pstate[PS_RECV] + delta

            for p in range(ndev):
                src = me ^ p
                expected = statacc[SX_AM + p]
                base = am_recv[src]
                delta = expected - base
                waiter = pltpu.make_async_remote_copy(
                    src_ref=inbox.at[0, 0],
                    dst_ref=inbox.at[0, 0],
                    send_sem=ssems.at[3],
                    recv_sem=am_sems.at[src],
                    device_id=me_did,
                    device_id_type=did_type,
                )

                def wait_one(i, _):
                    waiter.wait_recv()
                    return 0

                jax.lax.fori_loop(0, delta, wait_one, 0)

                def install_one(i, _):
                    handle_am(src, (base + i) % AMW)
                    return 0

                jax.lax.fori_loop(0, delta, install_one, 0)
                am_recv[src] = expected
                pstate[PS_RECV] = pstate[PS_RECV] + delta

        def scan_waits() -> None:
            n = pstate[PS_NWAIT]

            def one(i, kept):
                ch = wait_tab[i, 0]
                need = wait_tab[i, 1]
                row = wait_tab[i, 2]
                fire = chan_tot[ch] >= need

                @pl.when(fire)
                def _():
                    dep_dec(row)

                @pl.when(jnp.logical_not(fire))
                def _():
                    wait_tab[kept, 0] = ch
                    wait_tab[kept, 1] = need
                    wait_tab[kept, 2] = row

                return kept + jnp.where(fire, 0, 1)

            pstate[PS_NWAIT] = jax.lax.fori_loop(0, n, one, jnp.int32(0))

        # ---- injection ring poll (as device/inject.py) ----

        if self.inject:

            def poll(consumed, quiescing=None):
                cp = pltpu.make_async_copy(ictl, ctlbuf, isem.at[0])
                cp.start()
                cp.wait()
                tl = ctlbuf[0]
                if quiescing is not None:
                    # Quiescing round: consume nothing (tl clamps to the
                    # cursor, the chunk loop is immediately done) - the
                    # unread rows are the exported ring residue.
                    tl = jnp.where(quiescing, jnp.minimum(tl, consumed), tl)

                def chunk(c):
                    base = (c // 8) * 8
                    rp = pltpu.make_async_copy(
                        iring.at[pl.ds(base, 8)], rowbuf, isem.at[1]
                    )
                    rp.start()
                    rp.wait()
                    n = jnp.minimum(tl - c, 8 - (c - base))

                    def ins(i, _):
                        # Tenant deadline admission, mesh half: the host
                        # marks TEN_EXPIRED on a published row whose
                        # admission deadline lapsed; the poll drops it
                        # (counted, TR_TENANT names the lane) instead of
                        # installing stale work.
                        slot = c - base + i
                        expired = rowbuf[slot, TEN_EXPIRED] != 0

                        @pl.when(jnp.logical_not(expired))
                        def _():
                            install_fixed(lambda w: rowbuf[slot, w])

                        @pl.when(expired)
                        def _():
                            fstats[FS_TEN_EXPIRED] = (
                                fstats[FS_TEN_EXPIRED] + 1
                            )
                            tr.emit(
                                TR_TENANT, tr.now(),
                                rowbuf[slot, TEN_ID] << 16, 1,
                            )

                        return 0

                    jax.lax.fori_loop(0, n, ins, 0)
                    return c + n

                return jax.lax.while_loop(lambda c: c < tl, chunk, consumed)

        if self.inject and nten:
            T, region = self.T, self.region_rows

            def tpoll(r, quiescing):
                """Mesh half of the tenant front door: the same WRR
                lane scan the single-device stream compiles
                (device/inject.py ``tpoll``), over THIS device's ring
                regions. Per lane visit it installs at most ``weight``
                rows, never more than the scheduler's live
                ``headroom()`` (a full task table turns into ring
                backpressure the host reads off the cursor echo; row
                ``c`` of a lane lies in slot ``c`` modulo the region,
                ``region_slot``, so a region recycles), drops
                rows the host marked expired (counted: FS_TEN_EXPIRED +
                the tctl echo + a TR_TENANT record), and sweeps paused
                lanes. Quiescing rounds freeze the scan entirely -
                published rows stay put and export as the checkpoint's
                per-lane residue."""
                newly = jnp.int32(0)
                for k in range(T):
                    lane = jax.lax.rem(r + k, T)
                    tail = tctl_out[lane, TC_TAIL]
                    cons = tctl_out[lane, TC_CONSUMED]
                    paused = tctl_out[lane, TC_PAUSE] != 0
                    avail = tail - cons
                    weight = tctl_out[lane, TC_WEIGHT]
                    take = jnp.where(
                        paused | quiescing,
                        0,
                        jnp.minimum(
                            jnp.minimum(weight, avail), core.headroom()
                        ),
                    )
                    target = cons + take

                    def chunk(carry, lane=lane, target=target):
                        c, inst, exp = carry
                        base = (c // 8) * 8
                        rp = pltpu.make_async_copy(
                            iring.at[pl.ds(
                                lane * region
                                + region_slot(c // 8, region // 8) * 8, 8,
                            )], rowbuf, isem.at[1],
                        )
                        rp.start()
                        rp.wait()
                        n = jnp.minimum(target - c, 8 - (c - base))

                        def ins(i, ie, c=c, base=base):
                            inst0, exp0 = ie
                            slot = c - base + i
                            expired = rowbuf[slot, TEN_EXPIRED] != 0

                            @pl.when(jnp.logical_not(expired))
                            def _():
                                install_fixed(lambda w: rowbuf[slot, w])

                            one = jnp.int32(1)
                            return (
                                inst0 + jnp.where(expired, 0, one),
                                exp0 + jnp.where(expired, one, 0),
                            )

                        inst, exp = jax.lax.fori_loop(
                            0, n, ins, (inst, exp)
                        )
                        return c + n, inst, exp

                    c, inst, exp = jax.lax.while_loop(
                        lambda cr, target=target: cr[0] < target,
                        chunk,
                        (cons, jnp.int32(0), jnp.int32(0)),
                    )
                    sweep = paused & jnp.logical_not(quiescing)
                    tctl_out[lane, TC_CONSUMED] = jnp.where(
                        sweep, tail, c
                    )
                    tctl_out[lane, TC_DROPPED] = (
                        tctl_out[lane, TC_DROPPED]
                        + jnp.where(sweep, avail, 0)
                    )
                    tctl_out[lane, TC_INSTALLED] = (
                        tctl_out[lane, TC_INSTALLED] + inst
                    )
                    tctl_out[lane, TC_EXPIRED] = (
                        tctl_out[lane, TC_EXPIRED] + exp
                    )
                    fstats[FS_TEN_EXPIRED] = fstats[FS_TEN_EXPIRED] + exp

                    @pl.when((inst > 0) | (exp > 0))
                    def _(lane=lane, inst=inst, exp=exp):
                        tr.emit(
                            TR_TENANT, tr.now(), (lane << 16) | inst, exp
                        )

                    newly = newly + inst
                return newly

            def lane_backlog():
                b = jnp.int32(0)
                for i in range(T):
                    b = b + (
                        tctl_out[i, TC_TAIL] - tctl_out[i, TC_CONSUMED]
                    )
                return b

        # ---- the fold + steal hops ----

        def fold_and_steal(r, inj_backlog, am_dead, local_abort,
                           local_quiesce):
            statacc[SF_PEND] = counts[C_PENDING]
            statacc[SF_RECV] = pstate[PS_RECV]
            statacc[SF_OUTB] = obctl[1] - obctl[0]
            statacc[SF_SENT] = pstate[PS_SENT]
            statacc[SF_INJ] = inj_backlog
            statacc[SF_ABORT] = local_abort.astype(jnp.int32)
            statacc[SF_WEDGE] = pstate[PS_WEDGE]
            if ckpt:
                statacc[SF_QUIESCE] = local_quiesce.astype(jnp.int32)

            def f1(p, _):
                statacc[SX_AM + p] = am_sent[me ^ p]
                for c in range(nchan):
                    statacc[SX_DATA + p * nchan + c] = data_sent[me ^ p, c]
                if plan is not None:
                    statacc[self.SX_HB + p] = pstate[PS_HB]
                return 0

            jax.lax.fori_loop(0, ndev, f1, 0)

            # Exchange order: ``hop_bits`` (default 0..nh-1, minor axis
            # first; a locality graph reorders it near-neighbors-first
            # via run(hop_order=)). Per-hop state (semaphores, inboxes,
            # credit balances, fault predicates) stays indexed by the
            # PHYSICAL bit k, so both endpoints of a pair - and the
            # seeded fault schedule - agree regardless of scan order.
            for k in hop_bits:
                partner = me ^ (1 << k)
                pdev = self._did(partner)

                def cpy(i, _):
                    statsnd[i] = statacc[i]
                    return 0

                jax.lax.fori_loop(0, S, cpy, 0)
                statsnd[S_BL] = counts[C_TAIL] - counts[C_HEAD]

                @pl.when(r > 0)
                def _(k=k):
                    pltpu.semaphore_wait(csems.at[2 * k], 1)

                rdma = pltpu.make_async_remote_copy(
                    src_ref=statsnd, dst_ref=statrcv[k],
                    send_sem=ssems.at[0], recv_sem=rsems.at[2 * k],
                    device_id=pdev, device_id_type=did_type,
                )
                rdma.start()
                rdma.wait()
                for i in range(SX_AM):  # the scalar sums (incl abort/wedge)
                    statacc[i] = statacc[i] + statrcv[k][i]

                def mrg(p, _, k=k):
                    swap = ((p >> k) & 1) == 1

                    @pl.when(swap)
                    def _():
                        statacc[SX_AM + p] = statrcv[k][SX_AM + p]
                        for c in range(nchan):
                            statacc[SX_DATA + p * nchan + c] = statrcv[k][
                                SX_DATA + p * nchan + c
                            ]
                        if plan is not None:
                            statacc[self.SX_HB + p] = statrcv[k][
                                self.SX_HB + p
                            ]

                    return 0

                jax.lax.fori_loop(0, ndev, mrg, 0)
                peer_b = statrcv[k][S_BL]
                pltpu.semaphore_signal(
                    csems.at[2 * k], inc=1, device_id=pdev,
                    device_id_type=did_type,
                )
                if self.steal:
                    myb = counts[C_TAIL] - counts[C_HEAD]
                    # DEMAND-DRIVEN (the reference steals when a worker
                    # runs dry, src/hclib-runtime.c:646-694): export only
                    # to a STARVING partner (ready backlog under one
                    # quantum). Continuous backlog equalization measured
                    # pathological on recursive graphs: ready counts don't
                    # reflect subtree sizes, so busy-busy pairs ping-pong
                    # "surplus" forever, and every bounced dep-bearing row
                    # pins a proxy until its subtree completes remotely -
                    # the table fills with proxies instead of work.
                    starving = peer_b < jnp.int32(min(quantum, W))
                    quota = jnp.where(
                        starving, jnp.clip((myb - peer_b + 1) // 2, 0, W), 0
                    )
                    if plan is None:
                        sendbuf[W, 0] = 0

                        @pl.when(quota > 0)
                        def _():
                            sendbuf[W, 0] = export(quota)

                        @pl.when(sendbuf[W, 0] > 0)
                        def _(partner=partner):
                            tr.emit(
                                TR_XFER, tr.now(), partner, sendbuf[W, 0]
                            )

                        @pl.when(r > 0)
                        def _(k=k):
                            pltpu.semaphore_wait(csems.at[2 * k + 1], 1)

                        rdma2 = pltpu.make_async_remote_copy(
                            src_ref=sendbuf, dst_ref=inboxes[k],
                            send_sem=ssems.at[1], recv_sem=rsems.at[2 * k + 1],
                            device_id=pdev, device_id_type=did_type,
                        )
                        rdma2.start()
                        rdma2.wait()
                        import_rows(inboxes[k])
                        pltpu.semaphore_signal(
                            csems.at[2 * k + 1], inc=1, device_id=pdev,
                            device_id_type=did_type,
                        )
                    else:
                        # ---- faulty row exchange. Granter ids are
                        # ABSOLUTE device ids, so both endpoints (and any
                        # bystander) evaluate identical predicates: my
                        # partner grants my channel's credits, I grant
                        # theirs.
                        drop_mine = pred_drop(r, k, partner)
                        drop_theirs = pred_drop(r, k, me)
                        dup_mine = jnp.logical_not(drop_mine) & pred_dup(
                            r, k, partner
                        )
                        dup_theirs = jnp.logical_not(drop_theirs) & pred_dup(
                            r, k, me
                        )
                        delay_me = pred_delay(r, k, me)
                        # A starvation window downs the PAIR's hop-k row
                        # exchange (both sides skip: the paired DMA needs
                        # both writers) - the visible cost of credit
                        # detection latency. A global wedge (timeout 0)
                        # downs every exchange until the lockstep exit.
                        down = (r <= pair_down[k]) | (
                            pstate[PS_WEDGE] != 0
                        )
                        quota = jnp.where(delay_me, 0, quota)
                        if plan.dead_device is not None:
                            # Quarantine: no work to a dead partner; the
                            # dead chip itself re-homes its whole backlog
                            # regardless of demand.
                            quota = jnp.where(
                                deadmask[partner] != 0, 0, quota
                            )
                            quota = jnp.where(
                                am_dead, jnp.clip(myb, 0, W), quota
                            )

                        @pl.when(jnp.logical_not(down))
                        def _(k=k, quota=quota, partner=partner, pdev=pdev,
                              drop_mine=drop_mine, drop_theirs=drop_theirs,
                              dup_mine=dup_mine, dup_theirs=dup_theirs,
                              delay_me=delay_me):
                            fstats[FS_DELAYED] = fstats[
                                FS_DELAYED
                            ] + delay_me.astype(jnp.int32)

                            @pl.when(delay_me)
                            def _(k=k):
                                tr.emit(
                                    TR_FAULT, tr.now(), FLT_DELAY, k
                                )

                            sendbuf[W, 0] = 0

                            @pl.when(quota > 0)
                            def _():
                                sendbuf[W, 0] = export(quota)

                            @pl.when(sendbuf[W, 0] > 0)
                            def _(partner=partner):
                                tr.emit(
                                    TR_XFER, tr.now(), partner,
                                    sendbuf[W, 0],
                                )

                            if plan.dead_device is not None:
                                fstats[FS_REHOMED] = fstats[
                                    FS_REHOMED
                                ] + jnp.where(am_dead, sendbuf[W, 0], 0)
                            # Credit wait, with REGENERATION: one wait is
                            # skipped per owed (dropped) credit. Safe: the
                            # partner consumed our inbox before dropping
                            # its signal, so the write below cannot
                            # overwrite an unconsumed transfer.
                            skip = owed[k] > 0

                            @pl.when((r > 0) & jnp.logical_not(skip))
                            def _(k=k):
                                pltpu.semaphore_wait(csems.at[2 * k + 1], 1)
                                cbal[k] = cbal[k] - 1

                            @pl.when((r > 0) & skip)
                            def _(k=k, partner=partner):
                                owed[k] = owed[k] - 1
                                fstats[FS_REGEN] = fstats[FS_REGEN] + 1
                                tr.emit(
                                    TR_CREDIT, tr.now(),
                                    (jnp.int32(k) << 8) | partner,
                                    CR_REGENERATED,
                                )

                            rdma2 = pltpu.make_async_remote_copy(
                                src_ref=sendbuf, dst_ref=inboxes[k],
                                send_sem=ssems.at[1],
                                recv_sem=rsems.at[2 * k + 1],
                                device_id=pdev, device_id_type=did_type,
                            )
                            rdma2.start()
                            rdma2.wait()
                            import_rows(inboxes[k])

                            # FAULT SITE: the credit I owe my partner
                            # after consuming its transfer.
                            @pl.when(jnp.logical_not(drop_theirs))
                            def _(k=k):
                                pltpu.semaphore_signal(
                                    csems.at[2 * k + 1], inc=1,
                                    device_id=pdev, device_id_type=did_type,
                                )

                            @pl.when(dup_theirs)
                            def _(k=k, partner=partner):
                                pltpu.semaphore_signal(
                                    csems.at[2 * k + 1], inc=1,
                                    device_id=pdev, device_id_type=did_type,
                                )
                                fstats[FS_DUPED] = fstats[FS_DUPED] + 1
                                tr.emit(
                                    TR_CREDIT, tr.now(),
                                    (jnp.int32(k) << 8) | partner, CR_DUPED,
                                )

                            @pl.when(drop_theirs)
                            def _(k=k, partner=partner):
                                fstats[FS_DROPPED] = fstats[FS_DROPPED] + 1
                                tr.emit(
                                    TR_CREDIT, tr.now(),
                                    (jnp.int32(k) << 8) | partner,
                                    CR_DROPPED,
                                )

                            # Deterministic mirror of the partner's signal
                            # decisions: the live balance the exit drain
                            # consumes (signals in - waits done).
                            cbal[k] = cbal[k] + jnp.where(
                                drop_mine, 0, 1 + dup_mine.astype(jnp.int32)
                            )

                            @pl.when(drop_mine)
                            def _(k=k, partner=partner):
                                owed[k] = owed[k] + 1
                                if plan.credit_timeout == 0:
                                    st = (jnp.int32(k << 8) | partner) + 1
                                    fstats[FS_STARVED] = jnp.where(
                                        fstats[FS_STARVED] == 0, st,
                                        fstats[FS_STARVED],
                                    )

                            if plan.credit_timeout > 0:

                                @pl.when(drop_mine | drop_theirs)
                                def _(k=k):
                                    pair_down[k] = r + jnp.int32(
                                        plan.credit_timeout
                                    )

            if plan is not None and plan.dead_device is not None:
                # Heartbeat detection (GENUINE, not oracle-driven: it
                # observes only the folded heartbeat words): quarantine
                # any peer whose heartbeat has not advanced for
                # heartbeat_timeout rounds. Quarantined ids leave the
                # eligibility side of the steal exchange next round.
                def det(p, _):
                    src = me ^ p
                    hb = statacc[self.SX_HB + p]
                    changed = hb != hb_seen[src]
                    hb_seen[src] = hb
                    hb_round[src] = jnp.where(changed, r, hb_round[src])
                    stale = (
                        r - hb_round[src]
                        >= jnp.int32(plan.heartbeat_timeout)
                    ) & (src != me)
                    newly = stale & (deadmask[src] == 0)

                    @pl.when(newly)
                    def _():
                        deadmask[src] = 1
                        fstats[FS_QMASK] = fstats[FS_QMASK] | (
                            jnp.int32(1) << src
                        )
                        fstats[FS_DEAD_ROUND] = jnp.where(
                            fstats[FS_DEAD_ROUND] < 0, r,
                            fstats[FS_DEAD_ROUND],
                        )
                        tr.emit(
                            TR_FAULT, tr.now(), FLT_DEAD_QUARANTINE, src
                        )

                    return 0

                jax.lax.fori_loop(0, ndev, det, 0)

        # ---- the round loop ----

        core.stage()
        stage_resident()
        if nten:
            # Lane cursors + cumulative counters: host-seeded per entry,
            # mutated in place by the WRR poll, echoed back at exit.
            for i in range(self.T):
                for w in range(8):
                    tctl_out[i, w] = tctl_in[i, w]
        if self.inject:
            cp0 = pltpu.make_async_copy(ictl, ctlbuf, isem.at[0])
            cp0.start()
            cp0.wait()
            consumed0 = ctlbuf[2]
        else:
            consumed0 = jnp.int32(0)

        def cond(carry):
            r, done, consumed = carry
            return jnp.logical_not(done) & (r < max_rounds)

        def body(carry):
            r, done, consumed = carry
            # Dead chip: the scalar-core scheduler is wedged (fuel 0, no
            # heartbeat tick) but the wire - exchanges, drains, re-homing
            # exports - stays up, like a real chip whose ICI router
            # outlives its core.
            am_dead = is_dead(r) if plan is not None else jnp.bool_(False)
            # Host abort word: re-read from HBM every round (BEFORE the
            # sched/poll so the quiesce flag can gate both), folded into
            # the termination collective below so the whole mesh exits in
            # lockstep within one fold of the write landing.
            cpa = pltpu.make_async_copy(abort_in, abuf, asem.at[0])
            cpa.start()
            cpa.wait()
            local_abort = abuf[0] != 0
            # Quiesce word rides the same per-device HBM row (word [1],
            # threshold in [2]): every device compares the same r, so the
            # flag is lockstep-consistent without waiting for the fold.
            if ckpt:
                local_quiesce = (abuf[1] != 0) & (r >= abuf[2])
            else:
                local_quiesce = jnp.bool_(False)
            # Quiesce drain rounds: from the threshold round on, stop
            # popping (fuel 0 - the round boundary the export contract
            # promises) but keep the exchange machinery live until the
            # wire is empty; heartbeats keep ticking so the drain cannot
            # be mistaken for a dead chip.
            #
            # Batched-tier residue is handled INSIDE this sched call, the
            # same way SF_INJ residue is handled by the poll below: every
            # sched() exit - a drained quantum AND the fuel-0 hold rounds
            # - retires any in-flight operand prefetch through the PR 3
            # ``drain`` callback and spills unrun lane entries back to
            # the ready ring's cold end. So by the time the fold, the
            # steal export scan, or the settled exit below run, no
            # prefetch DMA is outstanding and no descriptor is
            # lane-resident: the checkpoint cut only ever sees ring rows
            # and a quiet local DMA engine (prefetches are device-local,
            # so they never gate the sent == recv wire settle).
            hold = am_dead
            if ckpt:
                hold = hold | local_quiesce | (pstate[PS_QUIESCE] != 0)
            core.sched(jnp.where(hold, 0, quantum))
            pstate[PS_HB] = pstate[PS_HB] + jnp.where(am_dead, 0, 1)
            if self.inject:
                # Quiescing also stops RING consumption: published-but-
                # unconsumed rows stay put and export as the checkpoint's
                # ring residue (with the consumed cursor), instead of
                # being installed into the cut - the poll is the consumer
                # half of the cursor contract the bundle preserves.
                if ckpt:
                    quiescing = (
                        local_quiesce | (pstate[PS_QUIESCE] != 0)
                    )
                else:
                    quiescing = jnp.bool_(False)
                if nten:
                    # Tenant lanes: rows come off the per-lane regions
                    # through the WRR poll; cursors live in the tctl
                    # echo, not the loop carry.
                    newly = tpoll(r, quiescing)

                    @pl.when(newly > 0)
                    def _():
                        tr.emit(TR_INJECT, tr.now(), newly)

                    inj_backlog = lane_backlog()
                else:
                    c_new = poll(consumed, quiescing)

                    @pl.when(c_new > consumed)
                    def _():
                        tr.emit(TR_INJECT, tr.now(), c_new - consumed)

                    consumed = c_new
                    inj_backlog = ctlbuf[0] - consumed
            else:
                inj_backlog = jnp.int32(0)
            drain_outbox()
            fold_and_steal(r, inj_backlog, am_dead, local_abort,
                           local_quiesce)
            aborted = statacc[SF_ABORT] > 0

            @pl.when(aborted & (fstats[FS_ABORT_ROUND] < 0))
            def _():
                tr.emit(TR_ABORT, tr.now(), r)

            fstats[FS_ABORT_ROUND] = jnp.where(
                aborted & (fstats[FS_ABORT_ROUND] < 0), r,
                fstats[FS_ABORT_ROUND],
            )
            wire_idle = (
                (statacc[SF_OUTB] == 0)
                & (statacc[SF_INJ] == 0)
                & (statacc[SF_SENT] == statacc[SF_RECV])
            )
            settled = jnp.bool_(False)
            if ckpt:
                quiescing = statacc[SF_QUIESCE] > 0

                @pl.when(quiescing & (fstats[FS_QUIESCE_ROUND] < 0))
                def _():
                    fstats[FS_QUIESCE_ROUND] = r
                    tr.emit(TR_QUIESCE, tr.now(), r)

                pstate[PS_QUIESCE] = pstate[PS_QUIESCE] | quiescing.astype(
                    jnp.int32
                )
                # Lockstep clean-cut exit: quiesced AND the wire is empty
                # (pending work intentionally remains - that is the
                # checkpoint). Unconsumed INJECT rows also remain, by
                # design: the poll stopped consuming at the quiesce, so
                # the ring residue + cursor export with the state rather
                # than gating the exit (SF_INJ is a normal-termination
                # condition only).
                settled = quiescing & (
                    (statacc[SF_OUTB] == 0)
                    & (statacc[SF_SENT] == statacc[SF_RECV])
                )
            done = (
                ((statacc[SF_PEND] == 0) & wire_idle)
                | aborted | (statacc[SF_WEDGE] > 0) | settled
            )
            if plan is not None and (
                plan.drops_credits() and plan.credit_timeout == 0
            ):
                # Unrecoverable drop anywhere this round: every device
                # raises the wedge flag for the next fold and skips all
                # row exchanges meanwhile (a starved writer must never
                # reach its wait).
                pstate[PS_WEDGE] = pstate[PS_WEDGE] | any_drop(r).astype(
                    jnp.int32
                )
            # Unconditional: on the done round every delta is zero; on a
            # max_rounds cutoff this consumes every announced arrival.
            drain_receives()
            scan_waits()
            return r + 1, done, consumed

        r, done, consumed = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False), consumed0)
        )
        counts[C_ROUNDS] = r
        if ckpt:
            # State-export record (the checkpoint bracket's device half).
            @pl.when(pstate[PS_QUIESCE] != 0)
            def _():
                tr.emit(
                    TR_CKPT, tr.now(), counts[C_PENDING],
                    counts[C_TAIL] - counts[C_HEAD],
                )

            # Export the live wait table (the lifted kernel-scratch
            # limit): pending waits leave with their needs REBASED to
            # arrivals-since-entry (need - chan_tot), so a resume that
            # restages with fresh channel counters fires them at exactly
            # the same residual arrival count. Rows beyond the count are
            # zeroed - the exported array must be a pure function of the
            # run, not of stale SMEM (bundle sha256 determinism).
            for i in range(MAXW + 1):
                for w in range(3):
                    waits_out[i, w] = 0
            waits_out[0, 0] = pstate[PS_NWAIT]

            def wexp(i, _):
                ch = wait_tab[i, 0]
                waits_out[1 + i, 0] = ch
                waits_out[1 + i, 1] = wait_tab[i, 1] - chan_tot[ch]
                waits_out[1 + i, 2] = wait_tab[i, 2]
                return 0

            jax.lax.fori_loop(0, pstate[PS_NWAIT], wexp, 0)
        if self.inject:
            ctl_out[0] = ctlbuf[0]
            ctl_out[1] = ctlbuf[1]
            ctl_out[2] = consumed
            for i in range(3, 8):
                ctl_out[i] = 0
        if plan is not None:
            fstats[FS_HB] = pstate[PS_HB]
        fstats[FS_BECAME] = rearm[RA_BECAME]
        fstats[FS_WALKED] = rearm[RA_WALKED]
        # Credit drain: every executed round ran every hop, and the first
        # send of each credited channel never waited - exactly one
        # outstanding credit per used channel once any round ran. Under a
        # fault plan the row channels drain their TRACKED balance instead
        # (signals received minus waits done): drops, dups, regeneration,
        # and down rounds all move it, and it must reach zero here or the
        # kernel cannot exit - the protocol's own conservation check.
        for k in range(2 * nh):
            if not self.steal and k % 2 == 1:
                continue
            if plan is not None and k % 2 == 1:

                def one(i, _, k=k):
                    pltpu.semaphore_wait(csems.at[k], 1)
                    return 0

                jax.lax.fori_loop(0, cbal[k // 2], one, 0)
                continue

            @pl.when(r >= 1)
            def _(k=k):
                pltpu.semaphore_wait(csems.at[k], 1)

    # -- host entry --

    def _build(self, quantum: int, max_rounds: int, hop_bits=None):
        mk = self.mk
        ndata = len(mk.data_specs)
        ndev, nchan, nh = self.ndev, self.nchan, self.nh
        W = self.window
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        anyspace = functools.partial(pl.BlockSpec, memory_space=pl.ANY)
        nten = 1 if self.T else 0
        in_specs = [smem()] * 5 + [anyspace()] * ndata + [smem()]
        if self.inject:
            in_specs += [anyspace(), anyspace()]  # iring, ictl (HBM)
        if nten:
            in_specs += [smem()]  # per-device tctl block (tiny)
        in_specs += [anyspace()]  # abort word (HBM: re-read every round)
        out_specs = [smem()] * 4 + [anyspace()] * ndata
        data_shapes = [
            jax.ShapeDtypeStruct(s.shape, s.dtype)
            for s in mk.data_specs.values()
        ]
        out_shape = [
            jax.ShapeDtypeStruct((mk.capacity, DESC_WORDS), jnp.int32),
            jax.ShapeDtypeStruct((mk.ring_len,), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((mk.num_values,), jnp.int32),
        ] + data_shapes
        if self.inject:
            out_specs.append(smem())
            out_shape.append(jax.ShapeDtypeStruct((8,), jnp.int32))
        if nten:
            # The tctl echo (lane cursors + cumulative counters), right
            # after the ctl echo.
            out_specs.append(smem())
            out_shape.append(
                jax.ShapeDtypeStruct((self.T, 8), jnp.int32)
            )
        if mk.batch_specs:
            # Batched-tier counters (TS_* words) per device, appended
            # after the ctl echo: decoded into info['tiers'][d].
            out_specs.append(smem())
            out_shape.append(jax.ShapeDtypeStruct((TS_WORDS,), jnp.int32))
        # Per-device fault/abort stats (FS_* words), then (checkpoint
        # builds) the exported wait table, then the optional flight-
        # recorder ring - appended outputs, existing indices intact.
        out_specs.append(smem())
        out_shape.append(jax.ShapeDtypeStruct((FS_WORDS,), jnp.int32))
        if self.checkpoint:
            out_specs.append(smem())
            out_shape.append(
                jax.ShapeDtypeStruct((self.max_waits + 1, 3), jnp.int32)
            )
        if mk.trace is not None:
            out_specs.append(smem())
            out_shape.append(mk.trace.out_shape())
        aliases = {0: 0, 2: 1, 3: 2, 4: 3}
        for i in range(ndata):
            aliases[5 + i] = 4 + i
        free, vfree, rearm, *lane_scratch = mk.core_scratch()
        scratch = list(mk.scratch_specs.values()) + [
            free,
            vfree,
            rearm,
            pltpu.SMEM((self.scan,), jnp.int32),  # candbuf
            pltpu.SMEM((W + 1, DESC_WORDS), jnp.int32),  # sendbuf
            pltpu.SMEM((self.S,), jnp.int32),  # statacc
            pltpu.SMEM((self.S,), jnp.int32),  # statsnd
        ]
        scratch += [pltpu.SMEM((self.S,), jnp.int32) for _ in range(nh)]
        if self.steal:
            scratch += [
                pltpu.SMEM((W + 1, DESC_WORDS), jnp.int32)
                for _ in range(nh)
            ]
        scratch += [
            pltpu.SMEM((self.outbox,), jnp.int32),  # outq_tgt
            pltpu.SMEM((self.outbox, DESC_WORDS), jnp.int32),  # outq_desc
            pltpu.SMEM((2,), jnp.int32),  # obctl
            pltpu.SMEM((AMROW,), jnp.int32),  # ambuf
            pltpu.SMEM((ndev, self.am_window, AMROW), jnp.int32),  # inbox
            pltpu.SMEM((ndev,), jnp.int32),  # am_sent
            pltpu.SMEM((ndev,), jnp.int32),  # am_recv
            pltpu.SMEM((ndev,), jnp.int32),  # sent_round
            pltpu.SMEM((ndev, nchan), jnp.int32),  # data_sent
            pltpu.SMEM((ndev, nchan), jnp.int32),  # chan_recv
            pltpu.SMEM((nchan,), jnp.int32),  # chan_tot
            pltpu.SMEM((8,), jnp.int32),  # pstate
            pltpu.SMEM((self.max_waits, 3), jnp.int32),  # wait_tab
        ]
        if self.inject:
            scratch += [
                pltpu.SMEM((8,), jnp.int32),  # ctlbuf
                pltpu.SMEM((8, RING_ROW), jnp.int32),  # rowbuf
            ]
        scratch += [
            pltpu.SemaphoreType.DMA((4,)),  # ssems: stat,row,put,am sends
            pltpu.SemaphoreType.DMA((max(1, 2 * nh),)),  # rsems (per hop)
            pltpu.SemaphoreType.REGULAR((max(1, 2 * nh),)),  # csems
            pltpu.SemaphoreType.DMA((ndev,)),  # am_sems (per source)
            pltpu.SemaphoreType.DMA((ndev, nchan)),  # chan_sems
        ]
        if self.inject:
            scratch += [pltpu.SemaphoreType.DMA((2,))]  # isem
        scratch += [
            pltpu.SMEM((8,), jnp.int32),  # abuf (abort-word staging)
            pltpu.SemaphoreType.DMA((1,)),  # asem
        ]
        # Batched dispatch tier lane scratch (lanes + lane state, none
        # unless batch-routed); re-entrant across sched() entries via the
        # spill discipline.
        scratch += lane_scratch
        if self.plan is not None:
            nhk = max(1, nh)
            scratch += [
                pltpu.SMEM((nhk,), jnp.int32),  # pair_down
                pltpu.SMEM((nhk,), jnp.int32),  # owed
                pltpu.SMEM((nhk,), jnp.int32),  # cbal
                pltpu.SMEM((ndev,), jnp.int32),  # hb_seen
                pltpu.SMEM((ndev,), jnp.int32),  # hb_round
                pltpu.SMEM((ndev,), jnp.int32),  # deadmask
            ]
        if hop_bits is None:
            hop_bits = tuple(range(nh))
        kern = pl.pallas_call(
            functools.partial(
                self._kernel, quantum, max_rounds, mk.trace, hop_bits
            ),
            out_shape=tuple(out_shape),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
            input_output_aliases=aliases,
            interpret=interpret_mode() if mk.interpret else False,
            name="resident_mesh",  # the kernel's name in a profiler trace
        )
        axes = self.axes

        ckpt = self.checkpoint

        def step(tasks, succ, ring, counts, iv, *rest):
            data_in = rest[:ndata]
            waits = rest[ndata]
            extra = rest[ndata + 1 :]
            outs = kern(
                tasks[0], succ[0], ring[0], counts[0], iv[0],
                *[d[0] for d in data_in], waits[0],
                *[x[0] for x in extra],
            )
            counts_o, iv_o = outs[2], outs[3]
            data_o = outs[4 : 4 + ndata]
            ntrace = 1 if self.mk.trace is not None else 0
            nckpt = 1 if ckpt else 0
            nbatch = 1 if self.mk.batch_specs else 0
            # Per-device batched-tier counters (appended after the ctl/
            # tctl echoes, before fstats): surfaced so info['tiers'][d]
            # reads mesh occupancy exactly like the single-device decode.
            tstats_o = (
                [outs[4 + ndata + (1 if self.inject else 0) + nten]]
                if nbatch else []
            )
            # The tctl echo rides out beside fstats on every tenant run
            # (the host table absorbs it after each entry).
            tctl_o = [outs[5 + ndata]] if nten else []
            fstats_o = outs[-1 - ntrace - nckpt]
            tail_o = ([outs[-1]] if ntrace else [])
            # Checkpoint builds export the mutated task table + ready
            # ring too - the per-device scheduler snapshot restore()
            # relaunches from (dropped by non-checkpoint builds, whose
            # positional consumers predate them) - plus the wait table
            # and (inject runs) the ctl echo carrying the inject-ring
            # consumed cursor, the two lifted coverage limits.
            state_o = [outs[0], outs[1], outs[-1 - ntrace]] if ckpt else []
            if ckpt and self.inject:
                state_o.append(outs[4 + ndata])
            gcounts = jax.lax.psum(counts_o, axes)
            return (
                counts_o[None],
                iv_o[None],
                gcounts[None],
                *[d[None] for d in data_o],
                *[t[None] for t in tstats_o],
                *[t[None] for t in tctl_o],
                fstats_o[None],
                *[s[None] for s in state_o],
                *[t[None] for t in tail_o],
            )

        nin = 7 + ndata + (2 if self.inject else 0) + nten
        # fstats (and the tstats / tctl echo / trace ring / checkpoint
        # state outputs, when built in) are per-device outputs too:
        # out_specs must cover them or shard_map rejects the pytree at
        # trace time.
        nout = (
            4 + ndata + (1 if self.mk.batch_specs else 0) + nten
            + (1 if self.mk.trace is not None else 0)
            + ((3 + (1 if self.inject else 0)) if ckpt else 0)
        )
        f = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(axes),) * nin,
            out_specs=(P(axes),) * nout,
            check_vma=False,
        )
        return jax.jit(f)

    def run(
        self,
        builders: Optional[Sequence[TaskGraphBuilder]] = None,
        data: Optional[Dict[str, np.ndarray]] = None,
        ivalues: Optional[np.ndarray] = None,
        waits: Optional[Sequence[Sequence[Tuple[int, int, int]]]] = None,
        inject_rows: Optional[Sequence[Sequence[Tuple]]] = None,
        quantum: int = 64,
        max_rounds: int = 1 << 14,
        abort=None,
        quiesce=None,
        resume_state: Optional[Dict[str, Any]] = None,
        hop_order: Optional[Sequence[int]] = None,
        tenant_table=None,
    ):
        """Execute all partitions fully on-device.

        ``waits[d]``: host-declared wait-sets (chan_id, need, task_index)
        for device d - the named task gains one extra dependency,
        satisfied when ``need`` messages have landed on the channel.
        ``inject_rows[d]``: descriptor tuples
        ``(fn, args[, out[, tenant_lane]])`` - or prebuilt RING_ROW
        numpy rows (``tenants.build_row``) - published on device d's
        injection ring before entry (requires ``inject=True``); the
        in-kernel poll discovers and installs them mid-run, dropping
        rows whose ``TEN_EXPIRED`` word the host set (counted in
        ``fault_stats['tenant_expired']``, TR_TENANT traced). Returns
        (ivalues[ndev, V], data, info).

        ``abort``: the host abort word - truthy (or a per-device sequence
        of flags) makes every round loop observe the abort inside one
        round and the mesh exit in lockstep with ``info['aborted']``
        (pending work abandoned, no stall raise). The kernels re-read the
        word from HBM every round, which is what a host with in-place
        device-buffer write access would need to stop a mesh mid-run;
        through this driver the word is uploaded at entry.
        ``info['fault_stats']`` carries
        each device's FS_* trace (abort round, credits dropped/regenerated/
        duplicated, quarantine mask, re-homed rows, heartbeat), and
        ``info['steal']`` the rows each device exported and imported over
        the steal exchange (``{"exported": [...], "imported": [...]}``,
        one entry a device; the sums are equal when nothing was lost).

        Checkpoint (``mk`` built with ``checkpoint=True``): ``quiesce``
        is the host quiesce word - truthy stops the mesh at its next
        round boundary, an int k at round >= k (the deterministic
        checkpoint-at-round-k spelling). Unlike abort, the exit is a
        clean cut: every device stops popping but the exchange rounds
        keep draining until outboxes are empty and sent == recv, then the
        mesh exits in lockstep with ``info['quiesced']=True`` and
        ``info['state']`` (the stacked per-device snapshot;
        ``run(resume_state=...)`` relaunches mid-graph, and
        ``runtime.checkpoint`` serializes / re-homes it onto a different
        mesh size). Pending host-declared ``waits`` survive the cut: the
        kernel exports its live wait table at exit (needs rebased to
        arrivals-since-entry), and ``resume_state`` restages it, so
        parked wait rows re-arm exactly. An injecting mesh exports its
        ring residue + consumed cursor the same way (``state['ring_rows']``
        / ``state['ictl']``), so a mid-stream quiesce loses nothing.

        Mesh tenancy (``tenants=`` at construction): ``tenant_table`` is
        the :class:`~hclib_tpu.device.tenants.MeshTenantTable` fronting
        this mesh - it pumps every device's lane regions + tctl block
        before entry and absorbs the echo after; rows enter ONLY through
        its ``submit`` routing (``inject_rows`` is refused). The echo
        rides out as ``info['tenant_ctl']`` and aggregate stats as
        ``info['tenants']``; a quiesced run's state carries the
        per-device tenant-tagged residue + aggregate tctl/tstats blocks
        (``tenant_table.export_state``), which a resume - on ANY mesh
        size, through ``CheckpointBundle.reshard`` - feeds back via
        ``run(resume_state=..., tenant_table=fresh_table)``.
        """
        from .sharded import execute_partitions

        mk = self.mk
        ndev = self.ndev
        if (builders is None) == (resume_state is None):
            raise ValueError(
                "run() wants exactly one of builders= or resume_state="
            )
        if quiesce is False:  # falsy boolean plumbing = off (see
            quiesce = None    # Megakernel.quiesce_words)
        if quiesce is not None and not self.checkpoint:
            raise ValueError(
                "quiesce= needs Megakernel(checkpoint=True): the quiesce "
                "word is compiled into the round loop only then"
            )
        if resume_state is not None:
            if waits or inject_rows:
                raise ValueError(
                    "resume_state= cannot be combined with waits/"
                    "inject_rows: the snapshot already carries every "
                    "pending row (incl. its wait table and inject-ring "
                    "residue)"
                )
            if data is not None or ivalues is not None:
                raise ValueError(
                    "resume_state= carries its own data/ivalues"
                )
            data = dict(resume_state.get("data") or {})
        waits = list(waits or [])
        if len(waits) < ndev:
            waits = waits + [[] for _ in range(ndev - len(waits))]
        if resume_state is not None and "waits" in resume_state:
            # Restage the exported wait table (needs already rebased to
            # arrivals-since-entry by the kernel's exit export; the
            # parked rows keep their dep bump in the snapshot, so no
            # bump_waits pass runs on resume).
            waits_arr = np.asarray(
                resume_state["waits"], np.int32
            ).reshape(-1, self.max_waits + 1, 3)
            if waits_arr.shape[0] != ndev:
                raise ValueError(
                    f"resume_state wait table covers "
                    f"{waits_arr.shape[0]} devices, this mesh has {ndev}"
                )
        else:
            waits_arr = np.zeros((ndev, self.max_waits + 1, 3), np.int32)
            for d, wlist in enumerate(waits):
                if len(wlist) > self.max_waits:
                    raise ValueError(f"device {d}: too many waits")
                waits_arr[d, 0, 0] = len(wlist)
                for i, (ch, need, row) in enumerate(wlist):
                    if not (0 <= ch < len(self.channels)):
                        raise ValueError(f"bad channel id {ch}")
                    if not (0 <= row < builders[d].num_tasks):
                        raise ValueError(
                            f"device {d}: wait names task {row} out of "
                            "range"
                        )
                    waits_arr[d, 1 + i] = (ch, need, row)
        if tenant_table is not None and not self.T:
            raise ValueError(
                "tenant_table= needs a tenant-enabled mesh: build the "
                "ResidentKernel with tenants= (or set "
                "HCLIB_TPU_MESH_TENANTS)"
            )
        extra: List[np.ndarray] = [waits_arr]
        if self.inject:
            R = self.ring_capacity
            iring = np.zeros((ndev, R, RING_ROW), np.int32)
            ictl = np.zeros((ndev, 8), np.int32)
            if self.T:
                # Mesh tenancy: rows enter through the MeshTenantTable's
                # routed admission only - the table pumps each device's
                # lane regions and builds the stacked tctl block this
                # entry uploads; the plain linear tail is unused.
                if inject_rows:
                    raise ValueError(
                        "a tenant-enabled mesh admits rows through its "
                        "MeshTenantTable (run(tenant_table=...)), not "
                        "inject_rows="
                    )
                if tenant_table is not None and (
                    len(tenant_table) != self.T
                    or tenant_table.ndev != ndev
                    or tenant_table.region_rows != self.region_rows
                ):
                    raise ValueError(
                        f"tenant_table shape mismatch: table has "
                        f"{len(tenant_table)} lanes x "
                        f"{tenant_table.ndev} devices x "
                        f"{tenant_table.region_rows} region rows; this "
                        f"mesh wants {self.T} x {ndev} x "
                        f"{self.region_rows}"
                    )
                if resume_state is not None and "tctl" in resume_state:
                    if tenant_table is None:
                        raise ValueError(
                            "resume state carries per-tenant lane "
                            "blocks (tctl/tstats): pass a fresh "
                            "tenant_table= so residue re-deals into "
                            "its lanes instead of being dropped"
                        )
                    tenant_table.resume_from(resume_state)
                elif resume_state is not None:
                    rr = resume_state.get("ring_rows")
                    rc = resume_state.get("ictl")
                    if (
                        rr is not None and rc is not None
                        and int(np.asarray(rc)[:, 0].sum()) > 0
                    ):
                        # A tenancy-off snapshot's residue has no lane
                        # identity: republishing it here would misfile
                        # every row, silently dropping it would lose
                        # tasks - refuse, like the mirror guard below.
                        raise ValueError(
                            "resume state carries untagged inject-ring "
                            "residue but no per-tenant lane blocks: it "
                            "was exported from a tenancy-off mesh and "
                            "cannot resume on a tenant-enabled one"
                        )
                ictl[:, 1] = 1  # closed: single-entry run drains fully
                if tenant_table is not None:
                    tctl_np = tenant_table.pump(iring)
                else:
                    tctl_np = np.zeros((ndev, self.T, 8), np.int32)
            elif resume_state is not None:
                if "tctl" in resume_state:
                    # Mirror of the tenant-resume guard: silently
                    # stripping every row's tenant identity would break
                    # the conservation contract.
                    raise ValueError(
                        "resume state carries per-tenant lane blocks "
                        "(tctl/tstats): it was exported from a "
                        "tenant-enabled mesh and cannot resume on a "
                        "tenancy-off one"
                    )
                # Re-publish the inject-ring residue (rows that were on
                # the ring but unconsumed at quiesce): packed from slot
                # 0 with a reset consumed cursor, so the in-kernel poll
                # discovers exactly the rows the cut left behind - the
                # cursor survives the checkpoint (and any reshard).
                rr = resume_state.get("ring_rows")
                rc = resume_state.get("ictl")
                if rr is not None and rc is not None:
                    rr = np.asarray(rr, np.int32)
                    rc = np.asarray(rc, np.int32)
                    if rr.shape[0] != ndev:
                        raise ValueError(
                            f"resume_state inject ring covers "
                            f"{rr.shape[0]} devices, this mesh has {ndev}"
                        )
                    for d in range(ndev):
                        n = int(rc[d, 0])
                        if n > R:
                            raise ValueError(
                                f"device {d}: {n} residue ring rows "
                                f"exceed ring_capacity {R}"
                            )
                        iring[d, :n] = rr[d, :n]
                        ictl[d, 0] = n
                        ictl[d, 1] = 1  # single-entry run drains fully
            else:
                for d, rows in enumerate(inject_rows or []):
                    iring[d], n = pack_inject_rows(rows, R, dev=d)
                    ictl[d, 0] = n
                    ictl[d, 1] = 1  # closed: single-entry run drains fully
            extra += [iring, ictl]
            if self.T:
                extra += [tctl_np]
        elif inject_rows:
            raise ValueError("inject_rows requires inject=True")
        from .sharded import abort_words

        abort_arr = abort_words(abort, ndev)
        abort_requested = bool(abort_arr[:, 0].any())
        quiesce_requested = quiesce is not None
        if quiesce_requested:
            # Quiesce word rides words [1] (flag) and [2] (round
            # threshold) of the same per-device HBM row the abort word
            # occupies - one ctl row per device, re-read every round.
            abort_arr[:, 1] = 1
            abort_arr[:, 2] = 0 if quiesce is True else int(quiesce)
        extra += [abort_arr]

        def bump_waits(tasks, succ, ring, counts):
            # Symmetric-heap layout: host value slots occupy the SAME range
            # on every device (the region below value_alloc), so a
            # whole-row-migrated task's host-slot F_OUT means the same
            # address everywhere and no device's dynamic row blocks overlap
            # another's host slots.
            va = max(int(counts[d][4]) for d in range(ndev))
            for d in range(ndev):
                counts[d][4] = va
            if self.migratable and self.homed:
                # The migration result-slot region [rbase, num_values)
                # must sit above every device's host value range and row
                # blocks, or homed copies' results would alias live slots.
                blocks = VBLOCK * mk.capacity if mk.uses_row_values else 0
                for d in range(ndev):
                    need = int(counts[d][4]) + blocks  # C_VALLOC
                    if need > self.rbase:
                        raise ValueError(
                            f"device {d}: value region [0, {need}) overlaps "
                            f"the migration result slots at [{self.rbase}, "
                            f"{mk.num_values}); grow num_values by at least "
                            f"{need - self.rbase}"
                        )
            for d, wlist in enumerate(waits):
                for (_, _, row) in wlist:
                    tasks[d, row, F_DEP] += 1
                bumped = {row for (_, _, row) in wlist}
                if not bumped:
                    continue
                old_n = counts[d][C_TAIL]
                keep = [x for x in ring[d][:old_n] if x not in bumped]
                ring[d][: len(keep)] = keep
                counts[d][C_TAIL] = len(keep)

        # hop_order (locality.xor_hop_order / a placement descriptor's
        # xor_hop_order()): reorders the paired XOR exchange scan
        # near-neighbors-first - validated to a full delta permutation
        # by _hop_bits, and part of the compile cache key (the loop is
        # unrolled into the kernel).
        hop_bits = self._hop_bits(hop_order)
        key = (quantum, max_rounds, hop_bits)
        first_build = key not in self._jitted
        if first_build:
            from ..runtime.progcache import shared_build

            self._jitted[key], self._pc_stats = shared_build(
                mk, self._cache_variant(key),
                lambda: self._build(quantum, max_rounds, hop_bits),
            )
        t0_ns = time.monotonic_ns()
        # jax.jit is lazy: the first call of a program pays its trace,
        # lowering and compile (the Megakernel._execute discipline), so
        # the ledger's bracket goes around it.
        with building(
            "resident", self._jitted[key],
            self._pc_stats if first_build else None,
        ):
            iv_o, data_o, info = execute_partitions(
                mk, self.mesh, ndev, self._jitted[key], builders, data,
                ivalues, with_rounds=True,
                mutate=bump_waits if resume_state is None else None,
                extra_inputs=extra, state=resume_state,
                keep_inputs=self.checkpoint,
            )
        t1_ns = time.monotonic_ns()
        if self._pc_stats is not None:
            info["program_cache"] = dict(self._pc_stats)
        info["rounds"] = info.pop("steal_rounds")
        inputs = info.pop("inputs", None)
        tail = info.pop("extra_outputs")
        if mk.trace is not None:
            trows = tail[-1]
            info["trace"] = trace_info(
                [trows[d] for d in range(ndev)], t0_ns, t1_ns,
                mk.trace.capacity,
            )
            tail = tail[:-1]
        if self.checkpoint:
            if self.inject:
                ictl_rows = tail[-1]
                tail = tail[:-1]
            waits_rows = tail[-1]
            tasks_rows, ready_rows = tail[-3], tail[-2]
            tail = tail[:-3]
        frows = tail[-1]
        fs = [decode_fault_stats(frows[d]) for d in range(ndev)]
        info["fault_stats"] = fs
        info["steal"] = {
            "exported": [f["steal_exported"] for f in fs],
            "imported": [f["steal_imported"] for f in fs],
        }
        info["became"] = sum(f["became"] for f in fs)
        info["walked"] = sum(f["walked"] for f in fs)
        info["aborted"] = any(f["abort_round"] >= 0 for f in fs)
        if self.T:
            # The stacked tctl echo (lane cursors + cumulative install/
            # expire/sweep counters): fold it back into the front door
            # so consume-cursor advances free in-flight budget and the
            # aggregate stats refresh.
            tctl_echo = np.asarray(tail[-2]).reshape(ndev, self.T, 8)
            info["tenant_ctl"] = tctl_echo
            if tenant_table is not None:
                tenant_table.absorb(tctl_echo)
                info["tenants"] = tenant_table.stats()
        if mk.batch_specs:
            # Per-device batched-tier occupancy (counters accumulate over
            # the whole resident entry): the mesh lane-firing-policy
            # signal the perf guard and MetricsRegistry gauges watch.
            trows = tail[-2 - (1 if self.T else 0)]
            info["tiers"] = [
                mk.decode_tier_stats(trows[d]) for d in range(ndev)
            ]
        if self.checkpoint:
            info["quiesced"] = any(f["quiesce_round"] >= 0 for f in fs)
            if self.inject:
                info["inject_ctl"] = np.asarray(ictl_rows)
            if info["quiesced"]:
                # The stacked per-device snapshot run(resume_state=)
                # relaunches from; runtime/checkpoint.py serializes it
                # and re-homes it onto a different mesh size. The wait
                # table (needs rebased at export) and the inject-ring
                # residue + cursor ride along - the two coverage limits
                # PR 6 lifted - so a mid-stream, waits-pending mesh
                # quiesces, migrates, and resumes without loss.
                info["state"] = {
                    "tasks": np.asarray(tasks_rows),
                    "succ": np.asarray(inputs["succ"]),
                    "ready": np.asarray(ready_rows),
                    "counts": np.asarray(info["per_device_counts"]),
                    "ivalues": np.asarray(iv_o),
                    "data": {k: np.asarray(v) for k, v in data_o.items()},
                    "waits": np.asarray(waits_rows),
                }
                if self.inject and self.T:
                    # Tenant mesh: the front door exports the per-lane
                    # residue (deadline-stamped, tenant-tagged) plus the
                    # aggregate tctl/tstats blocks. Without a table
                    # nothing was ever published (inject_rows is
                    # refused), so the state carries no tenant blocks
                    # and resumes table-less.
                    if tenant_table is not None:
                        info["state"].update(
                            tenant_table.export_state(iring)
                        )
                elif self.inject:
                    ic = np.asarray(ictl_rows)
                    rr = np.zeros(
                        (ndev, self.ring_capacity, RING_ROW), np.int32
                    )
                    nictl = np.zeros((ndev, 8), np.int32)
                    for d in range(ndev):
                        tl, cl, cons = (
                            int(ic[d, 0]), int(ic[d, 1]), int(ic[d, 2])
                        )
                        res = iring[d, cons:tl]
                        rr[d, : len(res)] = res
                        nictl[d, 0] = len(res)
                        nictl[d, 1] = cl
                    info["state"]["ring_rows"] = rr
                    info["state"]["ictl"] = nictl
        if info["overflow"]:
            from .megakernel import decode_overflow

            masks = [int(c[C_OVERFLOW]) for c in info["per_device_counts"]]
            agg = 0
            for m in masks:
                agg |= m
            raise RuntimeError(
                f"resident kernel overflow: {decode_overflow(agg)} "
                f"exhausted (per-device masks {masks}). Note: homed "
                "migration keeps a PROXY row at home until the remote "
                "completion lands, so the table must hold live + "
                "in-flight-proxy rows - raise capacity, shrink the steal "
                "window, or raise am_window to drain completions faster"
            )
        starved = [(d, f["starved_channel"]) for d, f in enumerate(fs)
                   if f["starved_channel"] is not None]
        if starved and info["pending"] != 0:
            d, ch = starved[0]
            raise StallError(
                f"ici steal credit starved: device {d}'s hop-{ch['hop']} "
                f"channel lost a flow-control credit from granter device "
                f"{ch['granter']} with regeneration disabled "
                f"(credit_timeout=0); mesh exited in lockstep with "
                f"{info['pending']} pending",
                stats=info,
            )
        if info["pending"] != 0 and not (
            abort_requested or info["aborted"] or info.get("quiesced")
        ):
            suspects = sorted({
                p for f in fs for p in f["quarantined"]
            })
            suspect = (
                f" suspect chip: device {suspects[0]} (quarantined by "
                f"heartbeat timeout; its unmigratable work cannot re-home)."
                if suspects else ""
            )
            raise StallError(
                f"resident kernel stalled: {info['pending']} pending after "
                f"{info['executed']} executed ({info['rounds']} rounds) - "
                "a wait/lock whose release never comes, or max_rounds too "
                f"small.{suspect}",
                stats=info,
            )
        return iv_o, data_o, info
