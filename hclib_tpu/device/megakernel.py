"""The persistent Pallas megakernel: a resident scheduler loop on a TPU core.

This is the TPU-first re-design of the reference's worker loop
(core_work_loop/find_and_run_task, src/hclib-runtime.c:646-724):

- worker pthread        -> one long-running ``pallas_call`` on the core
- Chase-Lev deque       -> SMEM ready ring (head/tail counters in SMEM)
- function-pointer call -> ``lax.switch`` over a static kernel table
  (TPU has no function pointers; tasks name kernels by table index)
- promise waiter walk   -> successor dep-counter decrement + ready push
- fiber swap            -> none: tasks are descriptors, not stacks; blocking
  is expressed as dependency edges, so "waiting" tasks simply aren't ready
- pthread join/done flag-> loop exits when the pending counter reaches zero

Control state (task table, ready ring, counters, scalar values) lives in
SMEM, where the scalar unit can do random access; bulk tensor data stays in
HBM/VMEM and is touched by tile kernels via DMA + MXU/VPU ops. Kernels may
spawn new tasks dynamically (fib/UTS-style recursion) through
``KernelContext.spawn``.
"""

from __future__ import annotations

import functools
import types
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.env import env_bool, env_int, env_raw
from ..runtime.progcache import building
from ..runtime.spans import span
from .descriptor import (
    DESC_WORDS,
    F_A0,
    F_CSR_N,
    F_CSR_OFF,
    F_DEP,
    F_FN,
    F_HOME,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    TaskGraphBuilder,
    relay_ring,
    ring_len,
    ring_slot,
)
from .tracebuf import (
    NullTracer,
    TR_CKPT,
    TR_FIRE_AGE,
    TR_FIRE_BATCH,
    TR_FIRE_BUCKET,
    TR_FIRE_SCALAR,
    TR_PREFETCH_DRAIN,
    TR_PREFETCH_ISSUE,
    TR_QUIESCE,
    TR_ROUND_BEGIN,
    TR_ROUND_END,
    TR_SPILL,
    TraceRing,
    Tracer,
    trace_info,
)

__all__ = [
    "KernelContext", "BatchContext", "BatchSpec", "Megakernel", "VBLOCK",
    "decode_overflow", "interpret_mode", "fault_mix",
    "DEVICE_TABLE", "device_row", "device_record", "require_tpu",
    "resolve_interpret", "ran_on", "smem_bytes", "SmemError",
]

# Facts of each supported chip, keyed by ``jax.Device.device_kind``. Peaks
# are the published ones (Google Cloud documentation, "TPU v5e": 197
# TFLOP/s bf16, 819 GB/s HBM); ``smem_bytes`` is what the v5e compiler
# itself reports ("Used 2.04M of 1.00M smem", PR 24). A kind that is not
# here is an error, never a default.
DEVICE_TABLE: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "smem_bytes": 1 << 20,
        "bf16_tflops": 197.0,
        "hbm_gbps": 819.0,
    },
}


def device_row(device_kind: str) -> Dict[str, float]:
    """The ``DEVICE_TABLE`` row of ``device_kind``; unknown kinds raise."""
    try:
        return DEVICE_TABLE[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} has no row in "
            f"megakernel.DEVICE_TABLE (known: {sorted(DEVICE_TABLE)}); add "
            "its SMEM size and published peaks before running on it"
        ) from None


def _target_row() -> Dict[str, float]:
    """The row a compiled build is held to: the attached chip's, or -
    compiling on a host with no chip, for a described one - the row with
    the least SMEM, so what passes here fits every supported chip."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return device_row(dev.device_kind)
    return min(DEVICE_TABLE.values(), key=lambda r: r["smem_bytes"])


def device_record() -> Dict[str, Any]:
    """The device JAX runs on by default, as every result names it."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu() -> Dict[str, Any]:
    """Entry points that measure or prove something call this FIRST: it
    returns ``device_record()`` on a TPU and raises anywhere else, so no
    such script can finish on the CPU by accident."""
    rec = device_record()
    if rec["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found {rec['count']} {rec['platform']!r} "
            f"device(s) ({rec['kind']}). This entry point runs compiled "
            "on the chip only; tests and tutorials say interpret=True."
        )
    return rec


# A host int32 data buffer of ``Megakernel.run`` rides the call's one
# upload slab while it is smaller than this, and crosses as an array of
# its own from here up (``_rides`` is the rule). Measured once on a v5e's
# host (PR 39, p50 of 150 round trips of a 130 KB slab, one buffer, one
# small program and one small read; buffer riding / alone): 256 KiB 1.38
# / 1.57 ms, 1 MiB 1.58 / 1.69, 2 MiB 1.96 / 1.92, 4 MiB 2.48 / 2.11. An
# array of its own costs 0.23-0.25 ms to send whatever its size and the
# join 0.14 ms a MiB, so riding wins by 0.1-0.2 ms up to 1 MiB, ties at
# 2 MiB (device_sw_wave with its two 2 MiB buffers riding read 41.33 ms
# a call against 41.14 alone) and loses from there.
SLAB_RIDE_BYTES = 1 << 20


def _split(slab, shapes: Dict[str, Tuple[int, ...]]) -> Dict[str, Any]:
    """The named blocks of a flat slab (numpy or traced), in order."""
    blocks, off = {}, 0
    for n, shape in shapes.items():
        size = int(np.prod(shape))
        blocks[n] = slab[off : off + size].reshape(shape)
        off += size
    return blocks


def _join(blocks: Dict[str, Any], shapes: Dict[str, Tuple[int, ...]]):
    """``_split``'s inverse on the host: the named blocks as one flat
    int32 slab. A block of another shape than its name's would shift
    every block behind it, so it is refused here."""
    parts = []
    for n, shape in shapes.items():
        a = np.asarray(blocks[n], np.int32)
        if a.shape != tuple(shape):
            raise ValueError(
                f"{n}: shape {a.shape} != the build's {tuple(shape)}"
            )
        parts.append(a.reshape(-1))
    return np.concatenate(parts)


def _rides(buf) -> bool:
    """Whether a data buffer of ``Megakernel.run`` rides the call's one
    upload slab: it is on the host, int32, and smaller than
    ``SLAB_RIDE_BYTES``. Read off the buffer alone."""
    if isinstance(buf, jax.Array):
        return False
    a = np.asarray(buf)
    return a.dtype == np.int32 and a.nbytes < SLAB_RIDE_BYTES


class _ExecLayout(NamedTuple):
    """``Megakernel._exec_layout``: block names by how they cross."""

    ins: List[str]
    outs: List[str]
    up: Dict[str, Tuple[int, ...]]
    alone: List[str]
    down: Dict[str, Tuple[int, ...]]
    stays: List[str]
    donated: Tuple[int, ...]


def ran_on(out, interpret: bool) -> Dict[str, Any]:
    """What every runner's info/result dict says about a finished run:
    whether it was the interpreter, and the platform the output array
    ``out`` actually lives on (not the one that was asked for)."""
    return {
        "interpret": bool(interpret),
        "platform": next(iter(out.devices())).platform,
    }


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place an unstated ``interpret=`` is decided: compiled on a
    TPU backend, the Pallas interpreter anywhere else. Library callers
    may leave it None; entry points and tests state it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return bool(interpret)


class SmemError(ValueError):
    """A build whose scheduler state does not fit the chip's SMEM
    (``Megakernel.check_smem``)."""


def smem_bytes(shape: Sequence[int]) -> int:
    """Padded bytes one int32 array takes in SMEM, as the v5e compiler
    lays it out (read off its allocation dumps and checked against the
    capacity at which it starts to refuse the fib kernel, PR 24): a 1-D
    array pads to a power-of-two tile of 128..1024 words; a 2-D array
    pads each row to 128 lanes (512 B - which is why a ``[capacity, 16]``
    task table costs 8x its words) and its rows to a power of two up to
    8, then to a multiple of 8."""
    shape = tuple(int(d) for d in shape)
    if len(shape) == 1:
        tile = min(1024, max(128, 1 << (shape[0] - 1).bit_length()))
        return 4 * tile * -(-shape[0] // tile)
    rows = 1
    for d in shape[:-1]:
        rows *= d
    tile = min(8, 1 << max(0, rows - 1).bit_length())
    return -(-rows // tile) * tile * -(-shape[-1] // 128) * 512


def fault_mix(seed: int, site: int, r, k: int, g):
    """Deterministic per-mille hash of (seed, site, round, hop, device) for
    in-kernel fault predicates (the scalar-core analogue of the host
    FaultPlan's blake2b decision table). ``r`` and ``g`` may be traced
    int32; ``seed``/``site``/``k`` are static. Every device of a lockstep
    mesh evaluates the identical value, so seeded injection, its detection,
    and its recovery all agree on the schedule - the property that makes a
    chaos run reproducible byte-for-byte from the seed."""
    x = (
        r * jnp.int32(-1640531527)          # 0x9E3779B9: round stride
        + g * jnp.int32(69069)
        + jnp.int32((k * 40503 + site * 2654435761 + seed * 2246822519)
                    & 0x7FFFFFFF)
    )
    x = x ^ (x >> 13)
    x = x * jnp.int32(1274126177)
    x = x ^ (x >> 16)
    return (x & jnp.int32(0x7FFFFFFF)) % 1000


def interpret_mode():
    """InterpretParams for interpret-mode kernel builds - the single
    construction point for every pallas_call in the package.

    Always the strict defaults. The fast variants were tried and are a
    trap on this jax build: ``out_of_bounds_reads="uninitialized"``
    measured ~20% faster on multi-device kernels but sporadically
    deadlocks the interpreter's io_callback buffer machinery on 1-vCPU
    hosts (device threads park in device_put - reproduced in three
    different tests), and ``dma_execution_mode="eager"`` does the same
    under shard_map. Keep the defaults until the interpreter's threading
    is fixed upstream; the race-detector tests construct their own
    params (detect_races=True) on top of the same defaults."""
    return pltpu.InterpretParams()


def decode_overflow(mask: int) -> str:
    """Human-readable exhaustion sources from a C_OVERFLOW bitmask."""
    names = [
        (OVF_ROWS, "task-table rows"),
        (OVF_VALUES, "value slots"),
        (OVF_ENGINE, "vector-tier lane stacks/step budget"),
        (OVF_OUTBOX, "AM outbox"),
        (OVF_WAITS, "wait table"),
        (OVF_LOCKQ, "lock FIFO"),
        (OVF_PROMISE, "promise-wait spin budget"),
    ]
    hit = [n for bit, n in names if mask & bit]
    return " + ".join(hit) if hit else f"unknown (mask {mask})"

# Value slots are allocated in fixed blocks of this many words so freed
# blocks are interchangeable (alloc_values' k is static per call site, so a
# shared free stack must hand out uniform sizes). Allocations larger than
# VBLOCK fall back to exact-size bump allocation without recycling.
VBLOCK = 4

# C_OVERFLOW is a BITMASK of exhaustion sources so a failed run names
# what ran out instead of guessing (OVF_* below; legacy paths that write
# a plain 1 read as OVF_ROWS).
OVF_ROWS = 1     # task-table rows (spawn/install)
OVF_VALUES = 2   # value slots (alloc_values/free_values)
OVF_ENGINE = 4   # vector-tier per-lane stacks / step budget
OVF_OUTBOX = 8   # resident AM outbox
OVF_WAITS = 16   # resident wait table
OVF_LOCKQ = 32   # resident lock FIFO
OVF_PROMISE = 64  # on-device promise wait spun out its bounded budget

# Dispatch tier statistics (the TS_WORDS-word tstats output a megakernel
# appends after its data outputs; a batch-routed build's are surfaced as
# info['tiers'] / Megakernel.stats_dict(), TS_BECAME and TS_WALKED by every
# build as info['became'] / info['walked']). All counters reset at every
# kernel entry: per-graph numbers, which is what occupancy tracking wants.
TS_BATCH_ROUNDS = 0   # batch rounds fired
TS_BATCH_TASKS = 1    # descriptors dispatched through batch bodies
TS_SCALAR_ROUNDS = 2  # descriptors dispatched through lax.switch
TS_ROUTED = 3         # ring pops diverted into a per-kind lane
TS_PREFETCH = 4       # descriptors whose operands came from a prefetch
TS_FULL_ROUNDS = 5    # batch rounds at full width
TS_SPILLED = 6        # lane entries spilled back to the ring at sched exit
TS_OFFERED = 7        # batch slots offered (sum of widths over fired rounds)
TS_AGE_FIRES = 8      # batch rounds fired by the age trigger (jumped the
                      # ring-drain-first policy; zero when lane_max_age off)
TS_MAX_AGE = 9        # max starved-round age any lane reached (rounds a
                      # lane held entries without firing; written only
                      # when lane_max_age is on - the device-side gauge
                      # the age-trigger acceptance bounds)
TS_BUCKET_FIRES = 10  # batch rounds fired from a NONZERO priority bucket
                      # (priority_buckets builds only; zero otherwise) -
                      # how much of the dispatch actually used the
                      # ordered-retirement structure
TS_INVERSIONS = 11    # bucket-order inversions: age-guard fires that
                      # jumped a LOWER non-empty bucket (the only legal
                      # way a higher bucket fires first; bounded noise
                      # is healthy, a large count means the age knob is
                      # fighting the priority order)
TS_BECAME = 12        # dispatches that ended RE-ARMED (ctx.become: the row
                      # stayed, as its own continuation); written by every
                      # build, batch-routed or not
TS_WALKED = 13        # retirements that walked more than F_SUCC0 (a second
                      # inline successor or a CSR list: retire()'s slow
                      # region); written by every build. The fast path's
                      # share is 1 - walked / (executed - became)
TS_DIRECT = 14        # rows a device-side spawn pushed straight onto their
                      # kind's lane (``_DirectLanes``): direct + routed is
                      # what routed alone was
TS_WORDS = 15

# Re-arm words (SMEM scratch of RA_MARK + widest-batch words, the third of
# ``core_scratch``): what ``KernelContext.become`` leaves for the
# ``complete()`` of the same dispatch, at static offsets. A mark is set and
# cleared inside one dispatch, so no round boundary, export or checkpoint
# cut ever sees one set.
RA_BECAME = 0  # re-armed dispatches since stage() (rides out as TS_BECAME)
RA_WALKED = 1  # retirements since stage() that took retire()'s slow
               # region, a second inline successor or a CSR list (rides
               # out as TS_WALKED)
RA_MARK = 2    # + the dispatch's slot (0 on the scalar tier): re-armed

# Priority-bucket dispatch tier (ISSUE 15): ``priority_buckets=B`` layers
# B bucket rings over every per-kind batch lane - pop lowest-nonempty-
# bucket-first at ring-drain time. The bucket id is a pure function of
# the descriptor's OWN arg words (BatchSpec.priority reads them at
# routing time), so a bucket id always rides the descriptor: residue
# spilled to the ready ring, stolen rows, and checkpoint/reshard exports
# re-bucket on the next routing pop by construction - no extra transport
# word, no re-bucketing pass. BK_MAX bounds the static set (SMEM lane
# scratch scales linearly with B).
BK_MAX = 8

# Per-lane scheduler state words (SMEM (nbatch, LS_WORDS) scratch): the
# lane's FIFO cursors plus the cross-round prefetch handshake.
LS_HEAD = 0     # pop cursor (monotonic; ring-indexed by ring_slot)
LS_TAIL = 1     # push cursor
LS_PF_BASE = 2  # head-at-issue + 1 of the outstanding prefetch (0 = none)
LS_PF_N = 3     # descriptors the outstanding prefetch covers
LS_PF_BUF = 4   # operand-buffer half the prefetch was written into
LS_AGE = 5      # consecutive rounds the lane held entries without firing
                # (the age-trigger clock; written only when lane_max_age
                # is on - see the firing-policy site in sched())
LS_WORDS = 8

# Quiesce control words (the checkpoint/restore subsystem,
# runtime/checkpoint.py). ``qctl`` is an 8-word int32 row in HBM that the
# scheduler RE-READS by DMA inside its round loop when the megakernel was
# built with ``checkpoint=True`` - the checkpoint twin of the abort word
# (device/inject.py ctl[3], device/resident.py's abort input): a host with
# in-place device-buffer write access stops a resident kernel mid-run by
# writing the word; through this driver the word is uploaded at entry.
# On observing (flag set AND at least ``after`` tasks executed since
# entry), workers stop popping at the next round boundary, per-kind lanes
# spill back to the ready ring (the fuel-exit path), and the kernel
# returns with its live scheduler state in the aliased outputs instead of
# discarding it.
QC_FLAG = 0    # nonzero = quiesce requested
QC_AFTER = 1   # honor the flag only once this many tasks ran this entry
# ``qstat`` (8-word SMEM output, appended; present only when
# checkpoint=True) reports the observation back to the host:
QS_QUIESCED = 0  # 1 = the round loop observed the quiesce word
QS_AT = 1        # tasks executed since entry at observation
QS_POLLS = 2     # scheduling rounds ticked (the quiesce_stride counter)

# counts[] slots
C_HEAD = 0
C_TAIL = 1
C_ALLOC = 2
C_PENDING = 3
C_VALLOC = 4
C_EXECUTED = 5
C_OVERFLOW = 6
# Slot 7 is time-shared: during a kernel entry it is C_VBASE (first value
# slot above the host-preset range, set by stage()); AFTER a multi-device
# steal loop finishes, the runners (device/sharded.py, device/resident.py)
# overwrite it with their round count for the host to read.
C_ROUNDS = 7
C_VBASE = 7


def _lane_push(lanes, lstate, li, t) -> None:
    """Append row ``t`` to lane-state row ``li``'s ring."""
    tail = lstate[li, LS_TAIL]
    lanes[li, ring_slot(tail, lanes.shape[1])] = t
    lstate[li, LS_TAIL] = tail + 1


class _DirectLanes(NamedTuple):
    """The lane scratch of one scheduler core and the kinds a device-side
    ``spawn`` may push straight onto it: ``rows`` maps the F_FN of every
    batch-routed kind whose lane pops FIFO off a single ring
    (``spec.prefetch``, no priority buckets) to its lane-state row. Such a
    round moves only ``LS_HEAD``, so a push at the tail is safe at any
    time, from inside the kind's own batch body too; a LIFO round rewrites
    ``LS_TAIL`` after its body and a bucketed build chooses the ring at the
    routing pop, so those kinds keep the ready ring."""

    lanes: Any
    lstate: Any
    tstats: Any
    rows: Dict[int, int]


class _Rearm:
    """One scheduler core's re-arm words (``RA_*``) and, while the core is
    traced, whether a handler traced so far calls ``become``: each
    dispatch site traces its bodies before their ``complete()``, so a table
    in which nothing re-arms compiles the completion it always had."""

    def __init__(self, ref) -> None:
        self.ref = ref
        self.used = False


class KernelContext:
    """Facilities exposed to device task kernels (the device analogue of the
    worker-state + spawn API the reference hands to tasks)."""

    def __init__(self, idx, tasks, succ, ready, counts, ivalues, data,
                 scratch, capacity, free, num_values, vfree,
                 uses_row_values=False, tracks_home=False,
                 rearm=None, slot=0, direct=None):
        self.idx = idx  # this task's descriptor index
        self._tasks = tasks
        self._succ = succ
        self._ready = ready
        self._counts = counts
        self.ivalues = ivalues
        self.data = data  # name -> ref (HBM/VMEM tensor buffers)
        self.scratch = scratch  # name -> scratch ref (VMEM buffers, DMA sems)
        self._capacity = capacity
        self._num_values = num_values
        # Free-stack of recycled descriptor rows: free[0] is the count,
        # free[1..] the stack (completed rows are reclaimed, so a bounded
        # table runs unbounded dynamic graphs whose *live* set fits).
        self._free = free
        # Free-stack of recycled VBLOCK-word value blocks, same layout.
        self._vfree = vfree
        self._uses_row_values = uses_row_values
        # Whether this kernel composition can host migrated (homed) rows:
        # only then does spawn maintain the F_HOME words
        # (ResidentKernel sets Megakernel.tracks_home; plain megakernels
        # skip the dead scalar writes - the cost unit on this tier).
        self._tracks_home = tracks_home
        # The core's re-arm words (a ``_Rearm``) and this dispatch's slot
        # in them: 0 under scalar dispatch, the batch slot under
        # ``BatchContext.slot_ctx`` (``become``).
        self._rearm = rearm
        self._slot = slot
        # The core's ``_DirectLanes`` (None where no kind qualifies):
        # ``spawn`` of a kind it names skips the ready ring.
        self._direct = direct

    # -- descriptor access --

    def arg(self, i: int):
        return self._tasks[self.idx, F_A0 + i]

    def set_arg(self, idx, i: int, v) -> None:
        """Write argument word i of descriptor ``idx`` (e.g. to point a
        just-spawned join task at values whose location depends on its own
        row, which is only known after the spawn)."""
        self._tasks[idx, F_A0 + i] = v

    @property
    def out_slot(self):
        return self._tasks[self.idx, F_OUT]

    def value(self, slot):
        return self.ivalues[slot]

    def set_value(self, slot, v) -> None:
        self.ivalues[slot] = v

    def set_out(self, v) -> None:
        self.ivalues[self.out_slot] = v

    # -- on-device promises (the serving-loop wait surface) --

    def satisfy(self, slot, v=1) -> None:
        """Satisfy the promise flag at value slot ``slot``: one scalar
        SMEM write of a NONZERO word (``v``) - the SURVEY north star's
        "promise satisfaction becomes on-device flag writes". The
        matching ``wait_value`` observes it; the wait-graph analysis
        (hclib_tpu.analysis.waits) proves at construction that every
        waiter has a satisfier that can run first."""
        self.ivalues[slot] = v

    def wait_value(self, slot, spin_cap: int = 4096):
        """Block this task in place until the promise flag at value slot
        ``slot`` is nonzero (bounded spin; returns the observed value).

        This is an IN-BODY wait - unlike dependency edges (a task with
        deps simply isn't ready; the scheduler never blocks), a spinning
        wait occupies the core, so on a single scheduler it can only
        succeed if the satisfier already ran. That is exactly why kinds
        using it are GATED at construction: ``Megakernel(verify=True)``
        runs the wait-graph deadlock analysis over every kind's recorded
        wait/satisfy/spawn ops and refuses cycles (analysis/waits.py,
        rule ``wait-cycle``) - the safety floor under the completion-
        promise serving loop. ``spin_cap`` bounds the spin (static);
        exhaustion sets ``OVF_PROMISE`` so the host raises a diagnostic
        instead of the kernel wedging the core."""

        def cond(c):
            i, seen = c
            return (i < jnp.int32(spin_cap)) & jnp.logical_not(seen)

        def body(c):
            i, _ = c
            return (i + 1, self.ivalues[slot] != 0)

        _, seen = jax.lax.while_loop(
            cond, body, (jnp.int32(0), self.ivalues[slot] != 0)
        )
        self._counts[C_OVERFLOW] = jnp.where(
            seen, self._counts[C_OVERFLOW],
            self._counts[C_OVERFLOW] | OVF_PROMISE,
        )
        return self.ivalues[slot]

    # -- dynamic task creation --

    def alloc_values(self, k: int):
        """Reserve k consecutive scalar value slots; returns the base slot.

        k <= VBLOCK allocations consume one VBLOCK-word block, preferring a
        recycled block from the free stack (see ``free_values``) over the
        bump allocator - so graphs whose *live* value set fits run
        unbounded, like descriptor rows. k > VBLOCK falls back to exact-size
        bump allocation and is never recycled. Exhaustion sets the overflow
        flag and clamps so writes stay in bounds - the host raises after
        the kernel returns.

        Re-entrant callers (the sharded steal round loop): the value-block
        free stack is scratch, reset on every kernel entry, so blocks freed
        in an earlier round are NOT reusable later - the bump cursor holds
        its high-water mark and exhaustion is reported as overflow, never
        corruption. (Descriptor rows don't have this limit: stage()
        rebuilds their free stack from completion tombstones.) Long-lived
        recycling under re-entry wants row-owned blocks (``row_values``),
        which recycle with the rows."""
        if self._uses_row_values:
            # Trace-time guard: the bump region starts exactly at the
            # row-block base (C_VBASE == initial C_VALLOC), so any bump
            # allocation would silently alias row 0's block.
            raise ValueError(
                "alloc_values cannot be mixed with row_values "
                "(uses_row_values=True): the bump region overlaps the "
                "row-owned blocks"
            )
        # Branch-free (unconditional SMEM read-modify-writes + selects):
        # scalar-core conditionals cost more than the handful of extra SMEM
        # ops they would save, and this runs on every dynamic spawn.
        if k > VBLOCK:
            base = self._counts[C_VALLOC]
            ok = base + k <= self._num_values
            self._counts[C_VALLOC] = jnp.where(ok, base + k, base)
            self._counts[C_OVERFLOW] = jnp.where(
                ok, self._counts[C_OVERFLOW],
                self._counts[C_OVERFLOW] | OVF_VALUES,
            )
            return jnp.where(ok, base, jnp.maximum(self._num_values - k, 0))
        nfree = self._vfree[0]
        use_free = nfree > 0
        b_free = self._vfree[jnp.maximum(nfree, 1)]
        b_new = self._counts[C_VALLOC]
        ok = use_free | (b_new + VBLOCK <= self._num_values)
        self._vfree[0] = nfree - use_free.astype(jnp.int32)
        self._counts[C_VALLOC] = jnp.where(
            jnp.logical_not(use_free) & ok, b_new + VBLOCK, b_new
        )
        self._counts[C_OVERFLOW] = jnp.where(
            ok, self._counts[C_OVERFLOW],
            self._counts[C_OVERFLOW] | OVF_VALUES,
        )
        return jnp.where(
            use_free,
            b_free,
            jnp.where(
                ok, b_new, jnp.maximum(self._num_values - VBLOCK, 0)
            ),
        )

    def row_values(self, idx):
        """Base of the VBLOCK-word value block *owned by descriptor row*
        ``idx`` - the zero-overhead alternative to alloc/free_values for
        spawn/join patterns: the block's lifetime IS the row's lifetime
        (rows recycle on completion, so the block recycles with them, no
        allocator on the hot path). A join task derives its block from its
        own row (``ctx.row_values(ctx.idx)``); its spawner points children's
        out slots into it. Requires ``num_values >= host-preset slots +
        VBLOCK * capacity`` (sized by the host; see Megakernel docs) and
        must not be mixed with bump-side ``alloc_values`` in the same
        megakernel (the bump region overlaps the row blocks)."""
        return self._counts[C_VBASE] + idx * VBLOCK

    def free_values(self, base) -> None:
        """Return the VBLOCK-word block at ``base`` (from a k <= VBLOCK
        ``alloc_values``) to the free stack. Call from the kernel that
        consumes the block's values - after this, the slots may be handed to
        any later allocation (the analogue of the reference freeing a task's
        promise cells once its continuation has read them). Never free
        host-preset slots or k > VBLOCK allocations.

        A full stack means more frees than blocks exist (double-free or a
        host-preset base): the push is clamped inside the stack and
        C_OVERFLOW is set so the host raises instead of silently corrupting
        SMEM past the scratch window."""
        vcap = self._num_values // VBLOCK  # stack slots available
        nf = self._vfree[0] + 1
        ok = nf <= vcap
        nf_c = jnp.minimum(nf, vcap)
        self._vfree[0] = nf_c
        # On overflow this rewrites the top element with itself (one block
        # leaks; no corruption).
        self._vfree[nf_c] = jnp.where(ok, base, self._vfree[nf_c])
        self._counts[C_OVERFLOW] = jnp.where(
            ok, self._counts[C_OVERFLOW],
            self._counts[C_OVERFLOW] | OVF_VALUES,
        )

    def push_ready(self, t) -> None:
        tail = self._counts[C_TAIL]
        self._ready[ring_slot(tail, self._ready.shape[0])] = t
        self._counts[C_TAIL] = tail + 1

    def add_executed(self, n) -> None:
        """Credit ``n`` extra executed tasks (the vector tier reports its
        expanded node count here so 'executed' means tasks across both
        tiers, and fuel accounting sees vector work)."""
        self._counts[C_EXECUTED] = self._counts[C_EXECUTED] + n

    def flag_overflow(self, cond) -> None:
        """Raise the overflow flag where ``cond`` (host raises after the
        kernel returns)."""
        self._counts[C_OVERFLOW] = jnp.where(
            cond, self._counts[C_OVERFLOW] | OVF_ENGINE,
            self._counts[C_OVERFLOW],
        )

    def become(self, fn: int, dep_count) -> None:
        """Re-arm this task's own row as its continuation: the row turns
        into a ``fn`` task waiting on ``dep_count`` predecessors (children
        spawned with ``succ0=ctx.idx``), and keeps its successors, its out
        slot, its args, its value block and a migrated copy's home-link
        where they lie - what the reference does when the blocked task
        itself becomes the continuation (_help_finish_ctx,
        src/hclib-runtime.c:1032-1065: nothing allocated, no waiter list
        moved). Two dynamic writes (F_FN, F_DEP) and one static mark; this
        dispatch's ``complete()`` sees the mark, counts the task executed
        and leaves the row pending: no hook, no successor walk, no
        tombstone. ``dep_count`` must be positive: a continuation that is
        ready at once is a plain ``spawn``."""
        if isinstance(dep_count, (int, np.integer)) and dep_count <= 0:
            raise ValueError(
                f"become() needs dep_count > 0, got {dep_count}: a "
                "continuation that is ready at once is a plain spawn"
            )
        if self._rearm is None:
            raise ValueError(
                "this KernelContext was built without the core's re-arm "
                "words (core_scratch()'s third); become() needs them"
            )
        self._tasks[self.idx, F_FN] = jnp.int32(fn)
        self._tasks[self.idx, F_DEP] = jnp.int32(dep_count)
        self._rearm.used = True
        self._rearm.ref[RA_MARK + self._slot] = 1

    def spawn(
        self,
        fn: int,
        args: Sequence = (),
        dep_count=0,
        succ0=NO_TASK,
        succ1=NO_TASK,
        out=0,
        nargs: Optional[int] = None,
    ):
        """Allocate + enqueue a new task descriptor; returns its index.

        A ready child lands on the ready ring, from where the scheduler
        pops it, or - spawn-time routing - straight on its kind's batch
        lane where ``fn`` is a Python int that the core's ``_DirectLanes``
        names (a FIFO single-ring batch kind): the scheduler round that
        would pop the row only to read its ``F_FN`` and push the lane is
        decided here, at trace time (90 bundles an EXPAND of a search on
        the v5e, PR 50). A traced ``fn`` keeps the ring; so does a child
        with ``dep_count > 0``, which ``retire()`` releases onto it.

        On table overflow the task is dropped and counts[C_OVERFLOW] is set
        (the reference asserts on deque overflow, src/hclib-runtime.c:520-524;
        here the host checks the flag after the kernel returns).

        ``nargs`` (static) bounds how many arg words the new task will ever
        read (default: all 6 are zeroed). Scalar SMEM writes are the unit
        of cost on this tier (~1 cycle each), so a spawn-heavy kernel that
        declares its arity skips up to 6 dead writes per spawn - recycled
        rows may hold stale words beyond nargs, which a conforming kernel
        never reads (the same contract C lets the reference's task structs
        rely on, inc/hclib-task.h:32-44).

        Dynamically indexed touches a dispatch (by a row, a ring position
        or a stack top; ``counts[C_*]`` is static), as ISSUE 41 counted
        them: a fib fork 25 (48 before ``become``: pop, F_FN, arg 3;
        ``become`` 2; ``set_arg`` x2; two spawns 18; ``complete()`` 0), a
        leaf 14.5, a SUM 18.5; one ``spawn(nargs=1)`` is 9 (the free
        stack, six row words, the arg, the ring). A task's time on the
        v5e did NOT follow that count (PR 41): it follows the bundles of
        straight-line code its path runs, about 30 a spawn since the
        rings are masked (PR 45), the five ``pl.when``s below predicated
        into them.

        What the new row's links cost when it RETIRES (PR 46, the v5e
        listing of the fib kernel): one successor in ``succ0`` is the
        cheap shape; a ``succ1`` (with or without a ``succ0``) or a CSR
        list (host-built rows only) takes ``retire()``'s slow region:
        one branch, 13 bundles more for the second inline slot, then 17
        a listed successor. A fork-join child names its parent in
        ``succ0``.
        """
        if nargs is None:
            nargs = 6
        if len(args) > nargs:
            raise ValueError(f"{len(args)} args exceed declared nargs={nargs}")
        nfree = self._free[0]
        use_free = nfree > 0
        a_free = self._free[jnp.maximum(nfree, 1)]
        a_new = self._counts[C_ALLOC]
        ok = use_free | (a_new < self._capacity)
        a_clamped = jnp.where(
            use_free, a_free, jnp.minimum(a_new, self._capacity - 1)
        )

        @pl.when(use_free)
        def _():
            self._free[0] = nfree - 1

        @pl.when(jnp.logical_not(use_free) & (a_new < self._capacity))
        def _():
            self._counts[C_ALLOC] = a_new + 1

        @pl.when(ok)
        def _():
            self._counts[C_PENDING] = self._counts[C_PENDING] + 1
            self._tasks[a_clamped, F_FN] = jnp.int32(fn)
            self._tasks[a_clamped, F_DEP] = jnp.int32(dep_count)
            self._tasks[a_clamped, F_SUCC0] = jnp.int32(succ0)
            self._tasks[a_clamped, F_SUCC1] = jnp.int32(succ1)
            # F_CSR_OFF is only ever read under F_CSR_N > 0, so a stale
            # offset in a recycled row is dead - no write needed.
            self._tasks[a_clamped, F_CSR_N] = 0
            for i in range(nargs):
                self._tasks[a_clamped, F_A0 + i] = (
                    jnp.int32(args[i]) if i < len(args) else 0
                )
            self._tasks[a_clamped, F_OUT] = jnp.int32(out)
            if self._tracks_home:
                # Recycled rows may carry a stale home-link from a
                # previously migrated occupant; fresh spawns are local
                # tasks. (F_VMASK needs no clear: it is only set on wire
                # copies, and the import path zeroes it after
                # rehydration.)
                self._tasks[a_clamped, F_HOME] = jnp.int32(NO_TASK)

        d = self._direct
        li = None
        if d is not None and isinstance(fn, (int, np.integer)):
            li = d.rows.get(int(fn))

        @pl.when(ok & (jnp.int32(dep_count) == 0))
        def _():
            if li is None:
                self.push_ready(a_clamped)
            else:
                _lane_push(d.lanes, d.lstate, li, a_clamped)
                d.tstats[TS_DIRECT] = d.tstats[TS_DIRECT] + 1

        @pl.when(jnp.logical_not(ok))
        def _():
            self._counts[C_OVERFLOW] = self._counts[C_OVERFLOW] | OVF_ROWS

        return a_clamped


class BatchSpec:
    """Describes the batched-dispatch form of one kernel-table entry.

    A kind routed through a BatchSpec is never dispatched through the
    ``lax.switch`` table: its ready descriptors wait in a per-kind SMEM
    lane - diverted there off the ready ring at the pop, or, where the lane
    pops FIFO off a single ring (``prefetch=True``, no priority buckets),
    pushed there by the device-side ``spawn`` that made them
    (``KernelContext.spawn``'s spawn-time routing) - and, each batch round,
    the scheduler pops up to ``width`` of them and invokes
    ``body(ctx: BatchContext)`` ONCE for the whole group - one tiled
    kernel body instead of ``width`` sequential switch dispatches.
    Ready descriptors of one kind are mutually independent by construction
    (neither's completion has run, so neither can be the other's
    predecessor), which is what makes same-kind group execution safe for
    arbitrary DAGs; the body remains responsible for its slots writing
    disjoint data.

    Lane pop order: non-prefetch specs pop the NEWEST queued descriptors
    each round (LIFO, the scalar tier's owner-side discipline) - recursive
    spawn-heavy families stay depth-first (bounded live set) and the
    oldest entries stay cold for the multi-device steal exchanges.
    ``prefetch=True`` switches the lane to FIFO pops, which the prefetch
    pipeline requires (see below); the static tile DAGs that use prefetch
    are order-insensitive. A FIFO round moves only the lane's head, so
    rows may join at the tail at any time: such a kind's children, spawned
    under a constant kind with no predecessor to wait for, skip the ring
    and run in the order they were spawned.

    ``priority`` opts the kind into the priority-bucket tier (armed only
    when the megakernel is built with ``priority_buckets=B``): a callable
    ``priority(arg) -> traced int32`` where ``arg(i)`` reads the popped
    descriptor's arg word ``i`` - the bucket id is a pure function of the
    descriptor's own words, clipped into ``[0, B)`` by the scheduler.
    Routing diverts the descriptor into its kind's bucket ring; at
    ring-drain time the LOWEST non-empty bucket fires first, so ordered-
    retirement workloads (delta-stepping relaxation, best-first search)
    retire cheap/urgent work before speculative work. Priorities are a
    performance hint ONLY: results must be schedule-independent (the
    ``si_claim`` certification gate), and with ``priority_buckets``
    off/unset the callable is never consulted - the build is
    byte-identical to one without it.

    ``prefetch=True`` opts into the cross-round double-buffer protocol:
    the tier tells the body how many descriptors of the NEXT prospective
    batch to prefetch (``ctx.prefetch_count``) and, the round after, how
    many of its own slots were already prefetched (``ctx.prefetched``, into
    operand-buffer half ``ctx.buf``). A lane entry's inputs are fully
    written before it is pushed (its predecessors completed in earlier
    rounds and batch bodies drain their stores before completion runs), so
    prefetching a queued descriptor's operands during the current batch's
    compute is always safe. A body that opts in MUST issue exactly the
    starts the tier announces and MUST provide ``drain(ctx)`` to wait the
    in-flight prefetch of ``ctx.prefetched`` descriptors - the scheduler
    calls it before spilling unrun lane entries at exit so no DMA outlives
    its consumer.

    ``fire_at=N`` (N >= width) is for a kind whose descriptors are MADE on
    the device by scalar tasks that keep the ready ring hot (forasync's
    RECURSIVE splitter): the lane fires as soon as it holds N entries,
    whatever the ring holds, instead of only at a drained ring - the
    producer is paced by its consumer, so the live set stays N rows plus
    the producer's own depth however many descriptors the loop makes.
    ``2 * width`` fires full batches with a full batch queued behind each
    for the prefetch. None compiles nothing: ring-drain-first, as before.
    """

    def __init__(self, body, width: int = 8, prefetch: bool = False,
                 drain=None, priority=None,
                 verify_suppress: Sequence[str] = (),
                 fire_at: Optional[int] = None) -> None:
        if width < 1:
            raise ValueError(f"batch width must be >= 1, got {width}")
        if fire_at is not None and fire_at < width:
            raise ValueError(
                f"fire_at must be >= width ({width}), got {fire_at}"
            )
        if prefetch and drain is None:
            raise ValueError(
                "prefetch=True requires a drain(ctx) callback: the "
                "scheduler must be able to retire in-flight prefetch DMAs "
                "when it exits with lane entries unrun"
            )
        if priority is not None and not callable(priority):
            raise ValueError(
                "priority must be a callable priority(arg) -> bucket "
                "(arg(i) reads the descriptor's arg word i)"
            )
        self.body = body
        self.width = int(width)
        self.prefetch = bool(prefetch)
        self.drain = drain
        self.priority = priority
        self.fire_at = None if fire_at is None else int(fire_at)
        # Per-rule opt-outs for the build-time verifier (hclib_tpu.
        # analysis): a spec whose body DELIBERATELY violates a checked
        # contract (e.g. intentionally-shared value slots) annotates the
        # rule here - the suppression rides the spec, next to the code
        # it excuses, and the finding still appears (marked suppressed)
        # in hclint reports.
        self.verify_suppress = tuple(verify_suppress)


class BatchContext:
    """Facilities exposed to batched-dispatch bodies: per-slot descriptor
    access for the current (and prospective next) batch, plus the underlying
    KernelContext facilities (``data``/``scratch``/value slots/overflow).

    Slot liveness is a prefix: slots ``[0, count)`` are live, and a live
    slot's descriptor row is ``idx(s)``. ``count`` is traced (1..width);
    ``width`` is static - bodies unroll ``range(width)`` under
    ``pl.when(s < count)``.
    """

    def __init__(self, kctx, lanes, li, head, count, width,
                 prefetched, buf, prefetch_count, capacity,
                 ctx_hook=None):
        self.k = kctx
        self._lanes = lanes
        self._li = li
        self._head = head
        self.count = count
        self.width = width
        # Prefetch protocol (zeros unless the spec opted in):
        self.prefetched = prefetched      # slots already loaded last round
        self.buf = buf                    # 0/1 operand half holding them
        self.prefetch_count = prefetch_count  # next-batch slots to issue
        self._capacity = capacity
        # The embedding runner's per-task context hook (attaches ctx.pgas
        # on the resident/pgas runners): applied to every slot_ctx so a
        # batch body's per-slot contexts carry the same facilities the
        # scalar dispatch path would have handed the task.
        self._ctx_hook = ctx_hook

    # -- current batch --

    def _row(self, pos):
        """Lane entry at FIFO position ``pos``, clamped into the descriptor
        table: dead-slot reads (callers guard semantics with ``live``) must
        still be IN-BOUNDS SMEM accesses, and uninitialized lane words must
        never index past the task table."""
        row = self._lanes[self._li, ring_slot(pos, self._lanes.shape[1])]
        return jnp.clip(row, 0, self._capacity - 1)

    def idx(self, s):
        """Descriptor row of slot ``s`` (meaningful for s < count; clamped
        but arbitrary otherwise)."""
        return self._row(self._head + jnp.minimum(s, self.count - 1))

    def live(self, s):
        return jnp.int32(s) < self.count

    def arg(self, s, i: int):
        return self.k._tasks[self.idx(s), F_A0 + i]

    def out_slot(self, s):
        return self.k._tasks[self.idx(s), F_OUT]

    def set_out(self, s, v) -> None:
        """Write slot ``s``'s output value (callers guard liveness)."""
        self.k.ivalues[self.out_slot(s)] = v

    def slot_ctx(self, s):
        """A KernelContext focused on slot ``s``'s descriptor row - for
        batch bodies whose per-slot work is scalar-shaped (dynamic spawns,
        re-arming in place) rather than one fused tile op. The returned
        context shares every underlying ref with this batch, so
        ``spawn``/``become``/``set_arg``/``row_values`` behave exactly as
        they would under scalar dispatch of the same row (a re-arm marks
        slot ``s``'s own word, which the round reads when it completes
        that slot after all the bodies); a
        body that unrolls ``range(width)`` under ``pl.when(live(s))`` and
        runs the scalar kernel per live slot computes bit-identical
        results while skipping the per-descriptor ring pop + lax.switch
        overhead (the batched spelling of spawn-heavy families like fib)."""
        k = self.k
        ctx = KernelContext(
            self.idx(s), k._tasks, k._succ, k._ready, k._counts, k.ivalues,
            k.data, k.scratch, k._capacity, k._free, k._num_values,
            k._vfree, k._uses_row_values, k._tracks_home,
            rearm=k._rearm, slot=s, direct=k._direct,
        )
        if self._ctx_hook is not None:
            self._ctx_hook(ctx)
        return ctx

    # -- prospective next batch (prefetch targets) --

    def next_idx(self, s):
        """Descriptor row of slot ``s`` of the NEXT batch (meaningful for
        s < prefetch_count): lane pops are FIFO, so the entries behind the
        current batch are exactly what the next batch round will pop."""
        return self._row(
            self._head + self.count
            + jnp.minimum(s, self.prefetch_count - 1)
        )

    def next_arg(self, s, i: int):
        return self.k._tasks[self.next_idx(s), F_A0 + i]

    # -- KernelContext delegation --

    @property
    def data(self):
        return self.k.data

    @property
    def scratch(self):
        return self.k.scratch

    def value(self, slot):
        return self.k.value(slot)

    def set_value(self, slot, v) -> None:
        self.k.set_value(slot, v)

    def satisfy(self, slot, v=1) -> None:
        self.k.satisfy(slot, v)

    def wait_value(self, slot, spin_cap: int = 4096):
        return self.k.wait_value(slot, spin_cap)

    def add_executed(self, n) -> None:
        self.k.add_executed(n)

    def flag_overflow(self, cond) -> None:
        self.k.flag_overflow(cond)


def _is_vector_spec(fn) -> bool:
    from .vector_engine import VectorTaskSpec

    return isinstance(fn, VectorTaskSpec)


def _is_batch_spec(fn) -> bool:
    return isinstance(fn, BatchSpec)


def _batch_stub(ctx: "KernelContext") -> None:
    """Switch-table placeholder for a batch-routed kind. Unreachable by
    construction: the scalar pop path diverts these F_FNs into their lane
    before dispatch, so the branch only exists to keep the table dense."""
    return None


def _wrap_vector_spec(spec, interpret: bool):
    """Bridge a VectorTaskSpec into the scalar kernel table: popping a task
    of this F_FN dispatches its whole subtree across VPU lanes (the batch-
    dispatch tier, device/vector_engine.py). The seed task's 6 arg words
    feed ``spec.seed``; the ``out_acc`` accumulator lands in the task's
    F_OUT value slot; expanded-node count is credited to C_EXECUTED so
    'executed' counts tasks across both tiers."""
    from .vector_engine import make_subtree_runner

    runner = make_subtree_runner(spec, use_pltpu_roll=not interpret)

    def body(ctx: "KernelContext") -> None:
        args = tuple(ctx.arg(i) for i in range(6))
        seed_frame, seed_count = spec.seed(args)
        nodes, accs, over = runner(seed_frame, seed_count)
        if spec.root_contrib is not None:
            # The vector steps only ever expand *children*; a seed that is
            # itself a leaf contributes here (its execution is already
            # counted by the scalar tier's complete()).
            rc = spec.root_contrib(args)
            root_leaf = jnp.int32(seed_count) == 0
            accs = {
                name: accs[name] + jnp.where(root_leaf, rc.get(name, 0), 0)
                for name in accs
            }
        if spec.out_acc is not None:
            ctx.set_out(accs[spec.out_acc])
        ctx.add_executed(nodes)
        ctx.flag_overflow(over)

    # The verifier's classification pass must not abstractly interpret
    # the subtree runner (it embeds whole-engine sweeps); the marker
    # routes this kind straight to the 'vector' class.
    body._hclib_vector_wrapped = True
    return body


class Megakernel:
    """Builds and runs the single-core scheduler kernel over a task DAG.

    ``kernels`` is an ordered list of ``(name, fn)`` where ``fn(ctx)`` emits
    the device code for that kernel-table entry; a task's F_FN word indexes
    this table. ``data_specs`` declares named tensor buffers (passed to
    ``run`` and updated in place); ``scratch_specs`` declares named VMEM /
    semaphore scratch allocations available to kernels via ``ctx.scratch``.
    """

    def __init__(
        self,
        kernels: Sequence[Tuple[str, Callable[[KernelContext], None]]],
        data_specs: Optional[Dict[str, jax.ShapeDtypeStruct]] = None,
        scratch_specs: Optional[Dict[str, Any]] = None,
        capacity: int = 4096,
        num_values: int = 4096,
        succ_capacity: int = 4096,
        interpret: Optional[bool] = None,
        uses_row_values: bool = False,
        vmem_limit_bytes: Optional[int] = None,
        route: Optional[Dict[str, Any]] = None,
        trace: Optional[Any] = None,
        checkpoint: Optional[bool] = None,
        quiesce_stride: Optional[int] = None,
        lane_max_age: Optional[int] = None,
        priority_buckets: Optional[int] = None,
        verify: Optional[bool] = None,
        verify_suppress: Sequence[str] = (),
        read_only: Sequence[str] = (),
    ) -> None:
        interpret = resolve_interpret(interpret)
        # Device flight recorder (device/tracebuf.py): ``trace`` is None
        # (off: zero compiled cost, no extra outputs - bit-identical to a
        # build that predates tracing), a record capacity, or a TraceRing.
        # When on, run() appends one SMEM ring output the scheduler writes
        # round/dispatch/prefetch records into, decoded as info['trace'].
        # HCLIB_TPU_TRACE=1 (default capacity) or =N turns it on
        # process-wide without touching call sites. Env-derived tracing is
        # marked so runners that cannot trace (ShardedMegakernel) degrade
        # to untraced instead of failing a run the env owner never wrote.
        self.trace_from_env = False
        if trace is None:
            env = env_raw("HCLIB_TPU_TRACE", "")
            if env and env != "0":
                try:
                    n = int(env)
                except ValueError:
                    n = 1
                # n <= 0 stays off (a negative typo in a process-wide env
                # must not abort runs that never asked for tracing).
                if n > 0:
                    trace = True if n == 1 else n
                    self.trace_from_env = True
        self.trace = TraceRing.of(trace)
        # Checkpoint/restore (runtime/checkpoint.py): ``checkpoint=True``
        # compiles the quiesce protocol into the scheduler - a qctl HBM
        # input re-read inside the round loop plus a qstat output (QC_*/
        # QS_* above). DeviceFaultPlan discipline: False compiles none of
        # it (no extra refs, no per-round DMA - bit-identical to a build
        # that predates checkpointing). HCLIB_TPU_CHECKPOINT=1 turns it on
        # process-wide; env-derived enablement is marked so runners that
        # cannot export state (ShardedMegakernel) degrade instead of
        # failing a run the env owner never wrote.
        self.checkpoint_from_env = False
        if checkpoint is None:
            checkpoint = env_bool("HCLIB_TPU_CHECKPOINT")
            self.checkpoint_from_env = checkpoint
        self.checkpoint = bool(checkpoint)
        # Quiesce poll stride (checkpoint builds only): the scheduler
        # re-reads the qctl word from HBM every scheduling round by
        # default, which is what the checkpoint-overhead guard prices
        # (~1.2x enabled-idle). ``quiesce_stride=N`` polls every Nth
        # round instead - the DMA cost amortizes N-fold and a quiesce
        # request lands at most N-1 rounds later than it would have (the
        # bounded-latency trade ROADMAP's open item asked to expose).
        # HCLIB_TPU_QUIESCE_STRIDE sets it process-wide; a malformed or
        # nonpositive value degrades to 1 (poll every round), never off.
        if quiesce_stride is None:
            quiesce_stride = env_int(
                "HCLIB_TPU_QUIESCE_STRIDE", None, malformed=1
            )
        self.quiesce_stride = max(1, int(quiesce_stride or 1))
        # Lane firing-policy age trigger (the ROADMAP lane-policy fix):
        # ``lane_max_age=N`` lets a batch lane that has held entries for N
        # consecutive scheduling rounds without firing JUMP the
        # ring-drain-first policy and fire its (possibly partial) batch -
        # see the firing-policy site in _make_core's sched(). 0/None = off:
        # no age words are written and the round loop is the pre-knob
        # ring-drain-first policy, byte-for-byte. HCLIB_TPU_LANE_MAX_AGE
        # sets it process-wide; malformed or negative values RAISE (the
        # PR 8 env convention - a typo must not silently change the
        # firing policy).
        if lane_max_age is None:
            lane_max_age = env_int("HCLIB_TPU_LANE_MAX_AGE", None)
        lane_max_age = int(lane_max_age or 0)
        if lane_max_age < 0:
            raise ValueError(
                f"lane_max_age must be >= 0 (0 = off), got {lane_max_age}"
            )
        self.lane_max_age = lane_max_age
        # Priority-bucket dispatch tier (ISSUE 15): ``priority_buckets=B``
        # layers B bucket rings over every per-kind batch lane and makes
        # ring-drain firing pop the LOWEST non-empty bucket first (see
        # the firing-policy site in sched()). The bucket id is computed
        # at routing time by the kind's BatchSpec.priority callable - a
        # pure function of the popped descriptor's arg words, so residue
        # re-buckets on resume/reshard by construction. 0/None = off: no
        # bucket rings, priorities never consulted - byte-identical to a
        # build whose specs carry no priority at all (asserted).
        # HCLIB_TPU_PRIORITY_BUCKETS sets it process-wide; malformed or
        # out-of-range values RAISE (the PR 8 env convention).
        if priority_buckets is None:
            priority_buckets = env_int("HCLIB_TPU_PRIORITY_BUCKETS", None)
        priority_buckets = int(priority_buckets or 0)
        if priority_buckets and not 2 <= priority_buckets <= BK_MAX:
            raise ValueError(
                f"priority_buckets must be 0 (off) or 2..{BK_MAX} "
                f"(the static bucket-ring set), got {priority_buckets}"
            )
        self.priority_buckets = priority_buckets
        if priority_buckets and any(
            _is_batch_spec(s) and s.fire_at for s in (route or {}).values()
        ):
            raise ValueError(
                "BatchSpec(fire_at=) has no spelling under priority_buckets: "
                "a bucketed kind fires its lowest non-empty bucket at a "
                "drained ring"
            )
        # Dispatch-tier routing: ``route`` maps a kernel NAME to the spec
        # of a non-scalar dispatch tier for that task family. Two tiers:
        #
        # - VectorTaskSpec (the subtree tier, device/vector_engine.py): a
        #   recursive + reduction-shaped family whose tasks are dispatched
        #   as whole subtrees across the VPU lanes - one descriptor pop
        #   expands thousands of frame-tasks.
        # - BatchSpec (the batched same-kind tier): ready descriptors of
        #   this kind are diverted into a per-kind SMEM lane; each batch
        #   round pops up to ``width`` of them and runs ONE tiled body over
        #   the group (with optional cross-round operand prefetch) instead
        #   of ``width`` sequential ``lax.switch`` dispatches.
        #
        # Either way the routed entry is a drop-in at the DAG level: its
        # result lands where the scalar kernel's would and its successors
        # fire on completion, so irregular DAGs mix routed and scalar
        # tasks freely. A spec must compute the same values as the scalar
        # kernel it replaces.
        self.route = dict(route or {})
        unknown = set(self.route) - {name for name, _ in kernels}
        if unknown:
            raise ValueError(
                f"route names unknown kernels: {sorted(unknown)}"
            )
        not_specs = [
            n for n, s in self.route.items()
            if not (_is_vector_spec(s) or _is_batch_spec(s))
        ]
        if not_specs:
            raise ValueError(
                f"route values must be VectorTaskSpecs or BatchSpecs; "
                f"{sorted(not_specs)} are not"
            )
        self.kernel_names = [name for name, _ in kernels]
        self.fn_id = {name: i for i, name in enumerate(self.kernel_names)}
        # Batch-routed kinds never reach the switch table (the scheduler
        # pops them into lanes): their branch is a no-op stub, so their
        # batched body is the only trace (a scalar twin would force both
        # bodies' scratch into every build).
        self.batch_specs = sorted(
            (
                (self.fn_id[name], spec)
                for name, spec in self.route.items()
                if _is_batch_spec(spec)
            ),
            key=lambda kv: kv[0],
        )
        batched_ids = {fid for fid, _ in self.batch_specs}
        routed = [
            (name, self.route.get(name, fn)) for name, fn in kernels
        ]
        self.kernel_fns = [
            _wrap_vector_spec(fn, interpret) if _is_vector_spec(fn)
            else (_batch_stub if i in batched_ids else fn)
            for i, (_, fn) in enumerate(routed)
        ]
        self.data_specs = dict(data_specs or {})
        # Data buffers no kernel of this table writes: ``run`` / ``resume``
        # hand them to the kernel as plain inputs, read where they lie
        # (no alias, no output, no copy), and the caller keeps them. Every
        # other buffer is an output: aliased in and out, and donated
        # where the caller handed it in on the device (the ownership rule
        # in ``run``'s docstring). The embedders that wrap
        # ``_build_raw`` themselves (sharded, resident) alias every buffer
        # as before.
        self.read_only = tuple(k for k in self.data_specs if k in read_only)
        if set(read_only) - set(self.data_specs):
            raise ValueError(
                f"read_only names undeclared buffers: "
                f"{sorted(set(read_only) - set(self.data_specs))}"
            )
        self.scratch_specs = dict(scratch_specs or {})
        self.capacity = capacity
        self.num_values = num_values
        self.succ_capacity = succ_capacity
        # Declare when any kernel calls ctx.row_values: run() then verifies
        # every row's block fits below num_values (the region starts at the
        # runtime value_alloc, which out-slots and presets can push up).
        self.uses_row_values = uses_row_values
        self.interpret = interpret
        # Kernels whose scratch exceeds the compiler's default 16 MiB
        # scoped-vmem budget (e.g. 1024x1024 f32 tile pipelines) raise it
        # here; real VMEM is 128 MiB on v5e.
        self.vmem_limit_bytes = vmem_limit_bytes
        # Set by ResidentKernel when homed migration is configured: the
        # scheduler then maintains descriptor home-link words on spawn and
        # continuation transfer (dead writes otherwise - skipped).
        self.tracks_home = False
        # (fuel, stage_all_values, the data buffers that ride the slab)
        # -> the program _build_exec made for them
        self._jitted: Dict[Any, Any] = {}
        # shared_build's stats of this instance's most recent program
        # build, filled by progcache.building at the program's first
        # call - surfaced as info['program_cache'] so every run reports
        # what its program cost to obtain.
        self._pc_stats: Optional[Dict[str, Any]] = None
        # Last run()'s info dict (incl. the batched-tier counters), for
        # stats_dict() consumers that don't thread the return value.
        self._last_info: Optional[Dict[str, Any]] = None
        if not self.interpret:
            # A compiled build must fit the chip's SMEM; refuse here,
            # naming the capacity that fits, not inside XLA.
            self.check_smem(_target_row())
        # Build-time static verifier (hclib_tpu.analysis - the hclint
        # station): pure host analysis over the objects assembled above,
        # so it cannot change the compiled program in ANY mode - it can
        # only raise here with a witness. verify=None resolves through
        # HCLIB_TPU_VERIFY, defaulting ON under pytest and off
        # elsewhere; error findings raise AnalysisError unless listed in
        # ``verify_suppress`` (see analysis.findings for the syntax).
        self.verify_suppress = tuple(verify_suppress)
        # Schedule-independence claim (analysis/model.py): builders whose
        # exactness story IS schedule-independence (frontier traversals,
        # forasync tile loops) stamp their claim here; describe() and
        # hclint surface the certificate (or the refusal) lazily.
        self.si_claim = None
        if verify is None:
            from ..analysis.findings import verify_default

            verify = verify_default()
        self.verify = bool(verify)
        self.analysis = None
        if self.verify:
            from ..analysis import verify_megakernel

            self.analysis = verify_megakernel(
                self, suppress=self.verify_suppress
            )

    def smem_footprint(self, capacity: Optional[int] = None) -> int:
        """Padded SMEM bytes of the scheduler state ``_build_raw``
        allocates at ``capacity`` rows (default: this build's). Every
        aliased SMEM operand costs an input AND an output window."""
        cap = self.capacity if capacity is None else int(capacity)
        io = [  # windows in + out
            (cap, DESC_WORDS), (ring_len(cap),), (8,), (self.num_values,),
        ]
        one = [(self.succ_capacity,)]  # succ is input-only
        one += [s.shape for s in self.core_scratch(cap)]
        one.append((TS_WORDS,))
        if self.checkpoint:
            one += [(8,), (8,)]  # qstat out, qbuf
        if self.trace is not None:
            one.append(self.trace.out_shape().shape)
        for spec in self.scratch_specs.values():
            if getattr(spec, "memory_space", None) == pltpu.SMEM:
                one.append(spec.shape)
        return 2 * sum(map(smem_bytes, io)) + sum(map(smem_bytes, one))

    def check_smem(
        self,
        row: Dict[str, float],
        extra: Callable[[int], int] = lambda capacity: 0,
    ) -> None:
        """Raise unless the scheduler state fits ``row['smem_bytes']`` (a
        ``DEVICE_TABLE`` row), naming the largest capacity that would.
        ``extra(capacity)`` is what an embedder (the streaming kernel's
        tenant/egress/telemetry blocks) adds in padded bytes."""
        # The compiler keeps a little SMEM for itself that its dumps do
        # not list (it refused, by 144 B, a stream build this sum had
        # admitted); 4 KiB of headroom covers that.
        budget = int(row["smem_bytes"]) - 4096
        need = self.smem_footprint() + extra(self.capacity)
        if need <= budget:
            return
        lo, hi = 0, self.capacity  # largest cap whose footprint fits
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if self.smem_footprint(mid) + extra(mid) <= budget:
                lo = mid
            else:
                hi = mid - 1
        raise SmemError(
            f"Megakernel(capacity={self.capacity}) needs {need} B of SMEM "
            f"and the chip offers {budget} B: every [capacity, {DESC_WORDS}] "
            f"task-table row pads to 128 lanes (512 B) in an input and an "
            f"output window. The largest capacity that fits beside "
            f"num_values={self.num_values} and the rest of this build is "
            f"{lo}; rows recycle, so size capacity to the LIVE task set."
        )

    @property
    def ring_len(self) -> int:
        """Words of this build's ready ring and of each lane ring:
        ``descriptor.ring_len(capacity)``, the next power of two. What
        ``state['ready']`` is long, and what every runner that reads or
        writes a ring from outside (steal, export, checkpoint) wraps by."""
        return ring_len(self.capacity)

    @property
    def lane_scratch_rows(self) -> int:
        """Rows of the batched-tier lane/lstate SMEM scratch: one ring
        per routed kind, times ``priority_buckets`` bucket rings per
        kind when the priority tier is armed. ``core_scratch`` sizes the
        scratch from here for every embedder of the core (this class's
        _build_raw, which ShardedMegakernel re-enters, and
        ResidentKernel), so the bucket layout cannot drift per runner."""
        return len(self.batch_specs) * (self.priority_buckets or 1)

    def core_scratch(self, capacity: Optional[int] = None) -> list:
        """The scheduler core's own SMEM scratch at ``capacity`` rows
        (default: this build's), in ``_make_core``'s order: the two free
        stacks (``free``, ``vfree``), the re-arm words (``RA_*``: a mark
        a dispatch slot), then - for a batch-routed build - the batched
        tier's ``lanes`` and ``lstate``. The embedders
        (_build_raw, StreamingMegakernel._build, ResidentKernel._build)
        splice these into their scratch lists and ``smem_footprint``
        charges the same shapes, so the core's layout is declared once."""
        cap = self.capacity if capacity is None else int(capacity)
        widest = max([spec.width for _, spec in self.batch_specs] + [1])
        shapes = [
            (cap + 1,), (self.num_values // VBLOCK + 1,),
            (RA_MARK + widest,),
        ]
        if self.batch_specs:
            shapes += [
                (self.lane_scratch_rows, ring_len(cap)),
                (self.lane_scratch_rows, LS_WORDS),
            ]
        return [pltpu.SMEM(s, jnp.int32) for s in shapes]

    def describe(self) -> Dict[str, Any]:
        """Whole-program description of this megakernel's kernel table:
        per-kind dispatch tier and migratability classification (the
        reshard-class analysis), plus the build knobs - what hclint
        prints and what checkpoint bundles carry for upfront reshard
        diagnostics. Classification runs on demand (one recording-shim
        pass, memoized) even when verification is off."""
        from ..analysis import classify_megakernel

        classes = classify_megakernel(self)
        batched = {fid: spec for fid, spec in self.batch_specs}
        kinds = {}
        for i, name in enumerate(self.kernel_names):
            spec = batched.get(i)
            kinds[name] = {
                "id": i,
                "dispatch": (
                    "batch" if spec is not None
                    else ("vector" if classes.get(name) == "vector"
                          else "scalar")
                ),
                "classification": classes.get(name, "unknown"),
                **(
                    {"width": spec.width, "prefetch": spec.prefetch,
                     "priority": spec.priority is not None}
                    if spec is not None else {}
                ),
            }
        cert = None
        if self.si_claim is not None:
            from ..analysis.model import certify_claim

            cert = certify_claim(self, raise_on_error=False)
        return {
            "kinds": kinds,
            "capacity": self.capacity,
            "num_values": self.num_values,
            "checkpoint": self.checkpoint,
            "priority_buckets": self.priority_buckets,
            "verify": self.verify,
            # The schedule-independence certificate (analysis/model.py),
            # beside the reshard classification: None when the builder
            # made no claim; a dict with status "certified" (K permuted
            # pop orders, identical fixpoint) or "refused" (with the two
            # divergent schedules) otherwise.
            "schedule_independence": cert,
            "findings": (
                self.analysis.to_jsonable() if self.analysis else []
            ),
        }

    # -- the kernel body --

    def _make_core(
        self,
        succ,
        tasks,
        ready,
        counts,
        ivalues,
        data,
        scratch,
        free,
        vfree,
        rearm,
        tasks_in,
        ready_in,
        counts_in,
        ivalues_in,
        stage_all_values: bool,
        ctx_hook: Optional[Callable[["KernelContext"], None]] = None,
        complete_hook=None,
        value_limit: Optional[int] = None,
        lanes=None,
        lstate=None,
        tstats=None,
        tracer=None,
        quiesce_hook=None,
        fire_hook=None,
        round_hook=None,
    ):
        """Builds the scheduler core closures over a concrete set of refs:
        ``stage()`` (copy host state into the mutable windows), and
        ``sched(fuel)`` (pop/dispatch/complete until the ready ring drains
        or ``fuel`` tasks have run since this call). Used by this class's
        own kernel body and by the two kernels that embed the scheduler
        next to other phases: the streaming front door, device/inject.py,
        and the resident mesh runner, device/resident.py - whose
        ``ctx_hook`` attaches its put/am/wait-until ops to each task's
        KernelContext before dispatch, whose ``complete_hook(idx)`` runs at
        the top of every completion to forward migrated tasks' results
        home, and whose ``value_limit`` caps dynamic value allocation below
        the region it reserves for migration result slots.

        ``quiesce_hook(executed_since_entry)`` - when given - is evaluated
        once per scheduling round and returns a traced bool; a True makes
        sched() stop popping at that round boundary and exit through the
        normal fuel-exhaustion path (lanes spill to the ring, prefetches
        drain), leaving the live scheduler state in the output windows.
        The hook owns observation bookkeeping (qstat, TR_QUIESCE). None
        compiles nothing - the checkpoint-off path is byte-identical.

        ``fire_hook(idx)`` / ``round_hook()`` are the telemetry seams
        (ISSUE 19, device/telemetry.py): round_hook() runs once per
        scheduling round right after the trace tick (it owns the
        cumulative round counter and the live gauges), fire_hook(idx)
        runs at every dispatch site - scalar pop and each batch slot -
        BEFORE the task body/complete, so the fire-round stamp is
        visible to the egress fold inside complete_hook. None compiles
        nothing - the telemetry-off path is byte-identical.
        """
        capacity = self.capacity
        # The ready ring and every lane ring are ``ring_len`` words, a
        # power of two: an index is a mask, not a divide (ring_slot).
        ring = self.ring_len
        assert ready.shape == (ring,), (ready.shape, ring)
        num_values = value_limit if value_limit is not None else self.num_values
        # Batched same-kind dispatch tier: requires the per-kind lane
        # scratch. Megakernel's own build (which the sharded steal loop
        # re-enters) and the resident runner allocate it (core_scratch)
        # and pass it; the lane discipline is steal-round-RE-ENTRANT - sched()
        # unconditionally spills unrun lane entries back to the ready ring
        # at every exit (the fuel/quiesce path below), so between sched
        # calls the ring is the ONLY live structure and the steal/export/
        # checkpoint sides never see a lane-resident descriptor. A direct
        # embedder that forgot the scratch would dispatch batch-routed
        # kinds into their no-op switch stub and silently drop work, so
        # refuse at trace time instead.
        if self.batch_specs and lanes is None:
            routed = sorted(
                self.kernel_names[fid] for fid, _ in self.batch_specs
            )
            raise ValueError(
                f"batch-routed kernels ({routed}) "
                "need the batched dispatch tier's lane scratch "
                "(lanes/lstate/tstats): pass it through _make_core like "
                "Megakernel._build_raw and ResidentKernel do, or drop the "
                "BatchSpec routes for this embedding"
            )
        use_batch = lanes is not None and len(self.batch_specs) > 0
        nbatch = len(self.batch_specs) if use_batch else 0
        # Priority-bucket tier: each kind's lane becomes ``nbk`` bucket
        # rings (rows ``li*nbk .. li*nbk+nbk-1`` of the lanes/lstate
        # scratch; bucket 0 pops first at drain time). nbk == 1 is the
        # bucket-free tier - every row mapping below degenerates to the
        # pre-knob lane indexing, so the off path compiles byte-for-byte
        # identically.
        nbk = self.priority_buckets if (
            use_batch and self.priority_buckets
        ) else 1
        nrows = nbatch * nbk
        # Static (row, fid, spec) enumeration of every lane-state row,
        # in row order - the spill/stage iteration set. (Drain PRIORITY
        # is not encoded here: the firing policy below derives the
        # lowest-nonempty-bucket choice dynamically via kind_lowb/
        # best_b so each kind keeps one batch-body instantiation.)
        lane_rows = [
            (li * nbk + bk, fid, spec)
            for li, (fid, spec) in enumerate(self.batch_specs)
            for bk in range(nbk)
        ]
        # Flight recorder: a NullTracer's methods are no-ops, so every
        # emit site below compiles to nothing when tracing is off (the
        # DeviceFaultPlan zero-cost-when-disabled pattern).
        tr = tracer if tracer is not None else NullTracer()

        # On TPU, SMEM output windows do NOT start with the aliased input's
        # contents (unlike interpret mode) - stage the initial scheduler
        # state into the mutable output windows explicitly. Only live rows
        # are copied: host-built descriptors ([0, alloc)), the initial ready
        # ring ([0, tail)), and host-preset value slots ([0, value_alloc)) -
        # scalar SMEM stores are expensive enough that staging the whole
        # capacity would dominate small dynamic graphs.
        def stage() -> None:
            free[0] = 0
            vfree[0] = 0
            for w in range(rearm.shape[0]):
                rearm[w] = 0
            # Trace header resets per entry - the same per-graph
            # semantics tstats has.
            tr.reset()
            if use_batch:
                # Lanes/prefetch state are per-entry scratch (sched() spills
                # unrun entries back to the ready ring before returning, so
                # nothing lives in a lane across entries).
                for li in range(nrows):
                    for w in range(LS_WORDS):
                        lstate[li, w] = 0
            if tstats is not None:
                # The tier's output window - zeroed per entry. TS_DIRECT,
                # the last word, is the batch tier's alone: a build
                # without lanes neither writes nor reads it.
                for w in range(TS_WORDS if use_batch else TS_DIRECT):
                    tstats[w] = 0
            for i in range(8):
                counts[i] = counts_in[i]
            # Row-owned value blocks sit directly above the host range.
            counts[C_VBASE] = counts_in[C_VALLOC]

            def copy_task(i, _):
                for w in range(DESC_WORDS):
                    tasks[i, w] = tasks_in[i, w]
                # Rebuild the row free stack from completion tombstones so
                # rows freed in earlier entries (sharded steal rounds) are
                # reusable - the stack itself is scratch and resets here.
                tomb = tasks_in[i, F_DEP] == -1
                nf = free[0] + tomb.astype(jnp.int32)
                free[jnp.where(tomb, nf, 0)] = jnp.where(tomb, i, free[0])
                free[0] = nf
                return 0

            jax.lax.fori_loop(0, counts_in[C_ALLOC], copy_task, 0)

            def copy_ready(i, _):
                ready[i] = ready_in[i]
                return 0

            # C_TAIL is the all-time push counter; once it passes the
            # ring's length the whole ring may be live (entries wrap), and
            # raw C_TAIL as a bound would walk out of the ring. A NEGATIVE
            # head (lane spills insert at the cold end, walking head below
            # zero) also wraps the live window - positions [ring+head,
            # ring) hold live entries a [0, tail) copy would drop.
            jax.lax.fori_loop(
                0,
                jnp.where(
                    counts_in[C_HEAD] < 0,
                    ring,
                    jnp.minimum(counts_in[C_TAIL], ring),
                ),
                copy_ready,
                0,
            )

            def copy_vals(i, _):
                ivalues[i] = ivalues_in[i]
                return 0

            # stage_all_values=True (re-entrant callers like the sharded
            # steal loop, where slots above value_alloc carry live results
            # between kernel entries) copies every slot. Single-shot run()
            # copies host slots only ([0, value_alloc), widened over any
            # nonzero presets): slots above are device-owned temporaries
            # nobody reads back, and staging all num_values slots cost ~3
            # scalar copies per task on fib-sized graphs once row-owned
            # blocks grew the buffer.
            jax.lax.fori_loop(
                0,
                self.num_values if stage_all_values else counts_in[C_VALLOC],
                copy_vals,
                0,
            )

        def push_ready(t) -> None:
            tail = counts[C_TAIL]
            ready[ring_slot(tail, ring)] = t
            counts[C_TAIL] = tail + 1

        ra = _Rearm(rearm)
        # Spawn-time routing (``KernelContext.spawn``): the kinds a spawn
        # may push straight onto their lane. None where none qualifies, and
        # such a build traces what it always traced.
        direct = None
        if use_batch and nbk == 1:
            fifo_rows = {
                fid: li for li, (fid, spec) in enumerate(self.batch_specs)
                if spec.prefetch
            }
            if fifo_rows:
                direct = _DirectLanes(lanes, lstate, tstats, fifo_rows)

        def complete(idx, slot: int = 0) -> None:
            """Decrement successors' dep counters; push newly-ready tasks
            (device analogue of hclib_promise_put waking the waiter list,
            src/hclib-promise.c:203-245). A dispatch whose body re-armed
            its row (``KernelContext.become`` marked ``slot``) is counted
            executed and nothing else: the row has not retired, so the
            hook, the successor walk, the tombstone and the free-stack
            push all wait for the continuation, on the same row."""
            if not ra.used:
                retire(idx)
                return
            mark = RA_MARK + slot

            def stay() -> None:
                rearm[mark] = 0
                rearm[RA_BECAME] = rearm[RA_BECAME] + 1
                counts[C_EXECUTED] = counts[C_EXECUTED] + 1

            # A branch, not arithmetic folded into the walk: the v5e
            # compiler predicates what it can, and every task would then
            # pay for the whole walk (140.9 ns a task against 131.3, my
            # chip runs, PR 41).
            jax.lax.cond(rearm[mark] != 0, stay, lambda: retire(idx))

        def retire(idx) -> None:
            if complete_hook is not None:
                complete_hook(idx)

            def dec(s) -> None:
                @pl.when(s != NO_TASK)
                def _():
                    d = tasks[s, F_DEP] - 1
                    tasks[s, F_DEP] = d

                    @pl.when(d == 0)
                    def _():
                        push_ready(s)

            # The row's three link words are read together, before the
            # first dec's chain, so the test below does not wait behind it.
            s0 = tasks[idx, F_SUCC0]
            s1 = tasks[idx, F_SUCC1]
            n = tasks[idx, F_CSR_N]
            dec(s0)

            def walk() -> None:
                rearm[RA_WALKED] = rearm[RA_WALKED] + 1
                dec(s1)
                off = tasks[idx, F_CSR_OFF]

                def body(i, _):
                    dec(succ[off + i])
                    return 0

                jax.lax.fori_loop(0, n, body, 0)

            # One successor in F_SUCC0 is the shape a fork-join task has:
            # the second inline successor and the CSR list sit behind ONE
            # branch that such a row jumps (the loop inside keeps the v5e
            # compiler from predicating the region; PR 46: 21 bundles of
            # every retiring task went to links that were NO_TASK). The
            # order of pushes is F_SUCC0, F_SUCC1, the list, as it was.
            jax.lax.cond((s1 != NO_TASK) | (n > 0), walk, lambda: None)
            counts[C_PENDING] = counts[C_PENDING] - 1
            counts[C_EXECUTED] = counts[C_EXECUTED] + 1
            # Reclaim the completed row: nothing references it anymore
            # (predecessors completed earlier; successor lists only point
            # forward), so it can back future spawns - a bounded table runs
            # unbounded dynamic graphs whose live set fits (the reference
            # frees tasks after execution, src/hclib-runtime.c:448-478).
            # The F_DEP=-1 tombstone lets stage() rediscover freed rows on
            # re-entry (the free stack itself is scratch): spawn overwrites
            # it on reuse, and completed rows are never re-examined
            # otherwise.
            tasks[idx, F_DEP] = -1
            nf = free[0] + 1
            free[0] = nf
            free[nf] = idx

        def step(idx) -> None:
            ctx = KernelContext(
                idx, tasks, succ, ready, counts, ivalues, data, scratch,
                capacity, free, num_values, vfree,
                self.uses_row_values, self.tracks_home, rearm=ra,
                direct=direct,
            )
            if ctx_hook is not None:
                ctx_hook(ctx)
            branches = [functools.partial(fn, ctx) for fn in self.kernel_fns]
            jax.lax.switch(tasks[idx, F_FN], branches)
            complete(idx)

        def _make_bctx(li, spec, head, take, pre, buf, nxt):
            kctx = KernelContext(
                lanes[li, ring_slot(head, ring)], tasks, succ, ready, counts,
                ivalues, data, scratch, capacity, free, num_values, vfree,
                self.uses_row_values, self.tracks_home, rearm=ra,
                direct=direct,
            )
            if ctx_hook is not None:
                ctx_hook(kctx)
            return BatchContext(
                kctx, lanes, li, head, take, spec.width, pre, buf, nxt,
                capacity, ctx_hook=ctx_hook,
            )

        def sched(fuel) -> None:
            """Pop/dispatch/complete until the ready ring (and the per-kind
            lanes, when the batched tier is on) drain, `fuel` tasks have run
            since this call, or everything empties with work still pending
            (a dependency cycle, a lost wakeup, or - sharded - tasks parked
            on another device's queue; the caller rebalances or inspects).

            With batch-routed kinds, each round dispatches EITHER one batch
            (up to ``width`` same-kind descriptors through one tiled body)
            or one scalar descriptor; a batch round may overshoot ``fuel``
            by width-1 tasks."""

            def batch_round(li, fid, spec, e0, rt) -> None:
                """Fire one batch off lane-state row ``li`` (a (kind,
                bucket) ring under the priority tier; the kind's only
                ring otherwise)."""
                B = spec.width
                head = lstate[li, LS_HEAD]
                tail = lstate[li, LS_TAIL]
                avail = tail - head
                take = jnp.minimum(avail, B)
                # Pop side of the lane. Prefetch specs pop FIFO (oldest
                # first): the cross-round operand pipeline targets "the
                # entries behind the current batch", which is only stable
                # when pops and pushes use opposite ends. Bucket rings
                # (nbk > 1) pop FIFO too - stable oldest-first within a
                # bucket is the order the schedule-independence
                # certification's bucketed schedule models, and the
                # depth-first rationale below doesn't apply (the bucket
                # structure, not the pop end, bounds the live set).
                # Remaining non-prefetch specs pop LIFO (the NEWEST
                # `take` as one contiguous block): that is the scalar
                # tier's owner-side discipline - newest-first keeps
                # recursive families depth-first (live set ~ width *
                # depth, not a breadth frontier; a FIFO fib lane
                # measured ~40% of the WHOLE tree live) and leaves the
                # oldest entries cold in the lane, which is exactly what
                # the multi-device steal exchanges expect to find
                # spilled at the ring's cold end.
                fifo = spec.prefetch or nbk > 1
                base = head if fifo else tail - take
                # Cross-round prefetch handshake: an outstanding prefetch
                # is ours iff it was issued for exactly this head (a spill
                # or lane restage invalidates by clearing LS_PF_BASE).
                pf_ok = lstate[li, LS_PF_BASE] == head + 1
                pre = jnp.where(
                    pf_ok, jnp.minimum(lstate[li, LS_PF_N], take), 0
                )
                buf = lstate[li, LS_PF_BUF]
                if spec.prefetch and nbk == 1:
                    # Announce next-batch prefetch only when the lane keeps
                    # entries AND fuel admits another round - the round
                    # that consumes (or drains) the prefetch is then
                    # guaranteed to run before sched() exits.
                    may = ((avail - take) > 0) & (
                        counts[C_EXECUTED] - e0 + take < fuel
                    )
                    nxt = jnp.where(may, jnp.minimum(avail - take, B), 0)
                else:
                    # Priority-bucketed builds never announce: the NEXT
                    # firing ring is chosen at fire time (lowest
                    # non-empty bucket then), so "the entries behind
                    # this batch" are not the next batch, and the VMEM
                    # operand halves are shared across a kind's bucket
                    # rings - a cross-round prefetch from ring A would
                    # be overwritten (and its semaphores consumed) by
                    # ring B's on-demand loads. Ordered retirement
                    # trades the prefetch away; the asymptotic EXPAND
                    # reduction is the workload's whole point.
                    nxt = jnp.int32(0)
                # Flight-recorder: one record per batch round, lane id and
                # occupancy packed ((fid << 16) | take), prefetched count
                # in b - the triple tests/test_tracebuf.py reconciles
                # against tstats (rounds / tasks / prefetch hits) exactly.
                tr.emit(
                    TR_FIRE_BATCH, rt, (jnp.int32(fid) << 16) | take, pre
                )
                if spec.prefetch:
                    @pl.when(nxt > 0)
                    def _():
                        tr.emit(TR_PREFETCH_ISSUE, rt, fid, nxt)
                bctx = _make_bctx(li, spec, base, take, pre, buf, nxt)
                spec.body(bctx)
                for s in range(B):
                    @pl.when(jnp.int32(s) < take)
                    def _(s=s):
                        if fire_hook is not None:
                            fire_hook(lanes[li, ring_slot(base + s, ring)])
                        complete(lanes[li, ring_slot(base + s, ring)], s)
                if fifo:
                    lstate[li, LS_HEAD] = head + take
                    if spec.prefetch:
                        lstate[li, LS_PF_BASE] = jnp.where(
                            nxt > 0, head + take + 1, 0
                        )
                        lstate[li, LS_PF_N] = nxt
                        # The half a prefetch targets is always 1 - buf;
                        # the next round consumes (or on-demand-fills)
                        # that half, so the parity alternates every
                        # round.
                        lstate[li, LS_PF_BUF] = 1 - buf
                else:
                    # LIFO pop: the block came off the tail; head (and the
                    # dormant prefetch words) stay put.
                    lstate[li, LS_TAIL] = base
                tstats[TS_BATCH_ROUNDS] = tstats[TS_BATCH_ROUNDS] + 1
                tstats[TS_BATCH_TASKS] = tstats[TS_BATCH_TASKS] + take
                tstats[TS_OFFERED] = tstats[TS_OFFERED] + B
                tstats[TS_PREFETCH] = tstats[TS_PREFETCH] + pre
                tstats[TS_FULL_ROUNDS] = tstats[TS_FULL_ROUNDS] + (
                    take == B
                ).astype(jnp.int32)

            def cond(carry):
                # `fuel` budgets *this call*: compare against tasks executed
                # since entry, not the all-time counter (which persists
                # across steal rounds re-entering the scheduler).
                pending, executed, e0, stuck = carry
                return (
                    (pending > 0)
                    & (executed - e0 < fuel)
                    & jnp.logical_not(stuck)
                )

            def body(carry):
                _, _, e0, _ = carry
                head = counts[C_HEAD]
                tail = counts[C_TAIL]
                ring_work = head < tail
                # Entry-relative round index: the trace timebase of every
                # record this iteration emits (no device wall clock; the
                # host epoch brackets the launch and timeline.py
                # interpolates).
                rt = tr.tick()
                if round_hook is not None:
                    round_hook()
                # Quiesce poll (checkpoint builds only): a True stops this
                # round's pop - the round boundary the export contract
                # promises - and exits the loop below.
                if quiesce_hook is not None:
                    qz = quiesce_hook(counts[C_EXECUTED] - e0)
                else:
                    qz = jnp.bool_(False)
                if not use_batch:
                    @pl.when(ring_work & jnp.logical_not(qz))
                    def _():
                        # LIFO on the owner side (newest first, depth-first,
                        # small live sets); the head side is the
                        # steal/export side (device/sharded.py,
                        # device/resident.py) - the Chase-Lev split of the
                        # reference deque (src/hclib-deque.c).
                        idx = ready[ring_slot(tail - 1, ring)]
                        counts[C_TAIL] = tail - 1
                        tr.emit(TR_FIRE_SCALAR, rt, tasks[idx, F_FN], idx)
                        if fire_hook is not None:
                            fire_hook(idx)
                        step(idx)

                    return (
                        counts[C_PENDING],
                        counts[C_EXECUTED],
                        e0,
                        jnp.logical_not(ring_work) | qz,
                    )
                avails = [
                    lstate[li, LS_TAIL] - lstate[li, LS_HEAD]
                    for li in range(nrows)
                ]
                lane_work = functools.reduce(
                    jnp.logical_or, [a > 0 for a in avails]
                )
                # Lane firing policy: lanes fire only once the ring drains.
                # Ring pops cost ~10 SMEM ops each and keep routing more
                # same-kind descriptors into the lanes, so waiting them out
                # maximizes batch occupancy AND leaves entries queued behind
                # each batch - which is what engages the cross-round
                # prefetch. Ready kinds that are all batch-routed reach
                # their lane within a handful of rounds, so the added
                # latency is noise against one kernel body. One dispatch
                # per round; among eligible lanes the lowest F_FN wins.
                # KNOWN TRADE (the ROADMAP lane-policy watch item, FIXED
                # here by ISSUE 10): a dynamic spawner that keeps the ring
                # hot - a chained producer, or a graph frontier whose
                # every batch deposits a fan-out of same-kind children on
                # the ring - starves the lanes: under pure ring-drain-
                # first a lane fires only at full drains, so entries sit
                # for the whole routing run (latency unbounded; partial
                # fires pile up once drains become momentary). The
                # DETECTOR is the ``lane_partial_age`` gauge (trace a run
                # and read info['tiers']; tracebuf.lane_partial_age off
                # the TR_FIRE_BATCH records, exported by
                # MetricsRegistry.add_run_info). The FIX is the age
                # trigger below: ``Megakernel(lane_max_age=N)`` /
                # HCLIB_TPU_LANE_MAX_AGE arms a per-lane starved-round
                # clock (LS_AGE: rounds the lane held entries without
                # firing); at age >= N the lane JUMPS ring-drain-first
                # and fires whatever it holds - a full batch when >= width
                # entries accumulated during routing (the frontier case:
                # occupancy AND latency improve), a partial one otherwise
                # (bounded latency is the point). Each jump emits a
                # TR_FIRE_AGE reason record beside the round's
                # TR_FIRE_BATCH and counts in tstats[TS_AGE_FIRES];
                # tstats[TS_MAX_AGE] carries the worst age any lane
                # reached. N=0/off compiles none of this - the pre-knob
                # ring-drain-first policy, byte-for-byte. Knob trail for
                # a starving workload: (1) set lane_max_age (>= the lane
                # width keeps age-fires full under a steady spawner);
                # (2) widen the spawner's fan-out so each drain deposits
                # >= width same-kind entries; (3) shrink the BatchSpec
                # width toward the workload's actual same-kind
                # concurrency.
                # (``fired`` starts at the quiesce flag: an observed
                # quiesce suppresses both the batch fire and the scalar
                # pop, so the exit below sees an untouched round.)
                max_age = self.lane_max_age
                fired = qz
                lane_fires = [jnp.bool_(False)] * nrows
                # Two eligibility passes: STARVED rows (age >= N) first,
                # then the ordinary drained-ring scan - so a starved row
                # beats the drain priority and the age bound holds with
                # several routed kinds/buckets (simultaneously starved
                # rows fire on consecutive rounds, so the worst observed
                # age is N + nrows - 1, not unbounded). Under the
                # priority tier the SAME guard is what keeps high
                # buckets live: drain pops retire the LOWEST non-empty
                # bucket first (globally - a kind is drain-eligible only
                # when its lowest non-empty bucket ties the mesh-wide
                # minimum), so a high bucket behind a continuously
                # refilled low bucket would starve without it; its
                # age-guard fire is the one legal bucket-order
                # inversion, counted in tstats[TS_INVERSIONS].
                #
                # The bucket CHOICE within a kind is a traced row index
                # (a where-fold over the kind's nbk cursor pairs), NOT a
                # per-bucket unroll: batch bodies are the largest code
                # objects in the program (a frontier body carries
                # width x EBLOCK relax loops), so each kind must keep
                # exactly ONE instantiation per phase - the pre-bucket
                # program size - with only the handful of scalar
                # selection ops scaling in nbk.
                if nbk > 1:
                    # Per kind: lowest non-empty bucket (nbk = empty),
                    # then the global minimum across kinds.
                    kind_lowb = []
                    kind_work = []
                    for li in range(nbatch):
                        has = [
                            avails[li * nbk + b] > 0 for b in range(nbk)
                        ]
                        lb = jnp.int32(nbk)
                        for b in reversed(range(nbk)):
                            lb = jnp.where(has[b], jnp.int32(b), lb)
                        kind_lowb.append(lb)
                        kind_work.append(
                            functools.reduce(jnp.logical_or, has)
                        )
                    best_b = functools.reduce(jnp.minimum, kind_lowb)
                phases = (["starved"] if max_age else []) + ["drain"]
                for phase in phases:
                    for li, (fid, spec) in enumerate(self.batch_specs):
                        base = li * nbk
                        if nbk == 1:
                            row = base
                            bk_sel = jnp.int32(0)
                            if phase == "starved":
                                eligible = (avails[base] > 0) & (
                                    lstate[base, LS_AGE]
                                    >= jnp.int32(max_age)
                                )
                            else:
                                eligible = (
                                    avails[base] > 0
                                ) & jnp.logical_not(ring_work)
                                if spec.fire_at:
                                    # A lane its producer filled fires
                                    # over a hot ring (BatchSpec.fire_at).
                                    eligible = eligible | (
                                        avails[base]
                                        >= jnp.int32(spec.fire_at)
                                    )
                        elif phase == "starved":
                            # Lowest-bucket starved ring of this kind
                            # (deterministic; any starved ring fires
                            # within nrows rounds either way).
                            sflags = [
                                (avails[base + b] > 0)
                                & (lstate[base + b, LS_AGE]
                                   >= jnp.int32(max_age))
                                for b in range(nbk)
                            ]
                            bk_sel = jnp.int32(nbk - 1)
                            for b in reversed(range(nbk)):
                                bk_sel = jnp.where(
                                    sflags[b], jnp.int32(b), bk_sel
                                )
                            eligible = functools.reduce(
                                jnp.logical_or, sflags
                            )
                            row = base + bk_sel
                        else:
                            # Drain: this kind offers its lowest
                            # non-empty bucket, and fires only when
                            # that bucket ties the global minimum
                            # (lowest-nonempty-bucket-first across
                            # kinds; ties break to the lower F_FN via
                            # the fired latch below).
                            bk_sel = jnp.minimum(
                                kind_lowb[li], jnp.int32(nbk - 1)
                            )
                            eligible = (
                                kind_work[li]
                                & (kind_lowb[li] == best_b)
                                & jnp.logical_not(ring_work)
                            )
                            row = base + bk_sel
                        fire_now = eligible & jnp.logical_not(fired)
                        avail_sel = (
                            lstate[row, LS_TAIL] - lstate[row, LS_HEAD]
                        )
                        take = jnp.minimum(avail_sel, spec.width)
                        if phase == "starved":
                            # Reason record + counter for a fire that
                            # jumped the ring (emitted before batch_round
                            # so LS_AGE still holds the pre-fire age;
                            # take mirrors batch_round's min(avail,
                            # width) exactly). A starved fire with the
                            # ring already empty is an ordinary drain
                            # fire - no jump, no record.
                            @pl.when(fire_now & ring_work)
                            def _(row=row, fid=fid, take=take):
                                tr.emit(
                                    TR_FIRE_AGE, rt,
                                    (jnp.int32(fid) << 16) | take,
                                    lstate[row, LS_AGE],
                                )
                                tstats[TS_AGE_FIRES] = (
                                    tstats[TS_AGE_FIRES] + 1
                                )
                            if nbk > 1:
                                # Bucket-order inversion: this age-guard
                                # fire retires bucket ``bk_sel`` while a
                                # LOWER bucket still holds entries - the
                                # only path a higher bucket beats a
                                # lower one (drain pops are bucket-
                                # ordered by construction).
                                lower = functools.reduce(
                                    jnp.logical_or,
                                    [
                                        (jnp.int32(r2 % nbk) < bk_sel)
                                        & (avails[r2] > 0)
                                        for r2 in range(nrows)
                                    ],
                                )

                                @pl.when(fire_now & lower)
                                def _():
                                    tstats[TS_INVERSIONS] = (
                                        tstats[TS_INVERSIONS] + 1
                                    )
                        if nbk > 1:
                            # Bucketed fire record: which bucket ring
                            # retired, at what occupancy - the
                            # per-bucket occupancy gauge decodes from
                            # these (tracebuf.bucket_occupancy).
                            @pl.when(fire_now)
                            def _(bk_sel=bk_sel, fid=fid, take=take):
                                tr.emit(
                                    TR_FIRE_BUCKET, rt,
                                    (bk_sel << 16) | take,
                                    fid,
                                )

                            @pl.when(fire_now & (bk_sel > 0))
                            def _():
                                tstats[TS_BUCKET_FIRES] = (
                                    tstats[TS_BUCKET_FIRES] + 1
                                )

                        @pl.when(fire_now)
                        def _(row=row, fid=fid, spec=spec, e0=e0):
                            batch_round(row, fid, spec, e0, rt)

                        if nbk == 1:
                            lane_fires[base] = lane_fires[base] | fire_now
                        else:
                            for r in range(base, base + nbk):
                                lane_fires[r] = lane_fires[r] | (
                                    fire_now & (row == jnp.int32(r))
                                )
                        fired = fired | eligible

                @pl.when(jnp.logical_not(fired) & ring_work)
                def _():
                    idx = ready[ring_slot(tail - 1, ring)]
                    counts[C_TAIL] = tail - 1
                    # Pop-time partitioning: batch-routed kinds divert into
                    # their lane (one compare per routed kind) whoever
                    # pushed them - stage, install_descriptor, completion,
                    # and every spawn that spawn-time routing does not
                    # take (``direct``) funnel through the ring, so the
                    # ring stays the single persistent structure and the
                    # lanes never survive a kernel exit.
                    fn = tasks[idx, F_FN]
                    routed = jnp.bool_(False)
                    for li, (fid, spec) in enumerate(self.batch_specs):
                        hit = fn == jnp.int32(fid)

                        @pl.when(hit)
                        def _(li=li, idx=idx, spec=spec):
                            if nbk > 1 and spec.priority is not None:
                                # Priority tier: the bucket id is a pure
                                # function of the descriptor's own arg
                                # words (clipped into the static set), so
                                # spilled/stolen/resharded residue
                                # re-buckets right here on its next
                                # routing pop - the bucket rides the
                                # descriptor, not the ring row.
                                bk = jnp.clip(
                                    spec.priority(
                                        lambda i: tasks[idx, F_A0 + i]
                                    ),
                                    0, nbk - 1,
                                ).astype(jnp.int32)
                                _lane_push(
                                    lanes, lstate,
                                    jnp.int32(li * nbk) + bk, idx,
                                )
                            else:
                                _lane_push(lanes, lstate, li * nbk, idx)

                        routed = routed | hit

                    @pl.when(jnp.logical_not(routed))
                    def _():
                        tr.emit(TR_FIRE_SCALAR, rt, fn, idx)
                        step(idx)
                        tstats[TS_SCALAR_ROUNDS] = (
                            tstats[TS_SCALAR_ROUNDS] + 1
                        )

                    @pl.when(routed)
                    def _():
                        tstats[TS_ROUTED] = tstats[TS_ROUTED] + 1

                if max_age:
                    # Advance the starved-round clocks AFTER dispatch: a
                    # row that holds entries now (including one a scalar
                    # pop just routed into) and did not fire this round
                    # ages by one; a fire or an empty row resets. The
                    # worst age any row reaches rides out in tstats -
                    # the bounded-age gauge the acceptance pins (under
                    # the priority tier the clock is per bucket ring, so
                    # the guard bounds HIGH-bucket latency too).
                    for li in range(nrows):
                        has_now = (
                            lstate[li, LS_TAIL] - lstate[li, LS_HEAD]
                        ) > 0
                        age = jnp.where(
                            lane_fires[li] | jnp.logical_not(has_now),
                            0,
                            lstate[li, LS_AGE] + 1,
                        )
                        lstate[li, LS_AGE] = age
                        tstats[TS_MAX_AGE] = jnp.maximum(
                            tstats[TS_MAX_AGE], age
                        )

                return (
                    counts[C_PENDING],
                    counts[C_EXECUTED],
                    e0,
                    jnp.logical_not(ring_work | lane_work) | qz,
                )

            e0 = counts[C_EXECUTED]
            tr.emit(
                TR_ROUND_BEGIN, tr.tick(),
                counts[C_TAIL] - counts[C_HEAD], counts[C_PENDING],
            )
            jax.lax.while_loop(
                cond,
                body,
                (counts[C_PENDING], counts[C_EXECUTED], e0, jnp.bool_(False)),
            )
            if use_batch:
                # Exit with unrun lane entries (fuel exhaustion, quiesce):
                # retire any in-flight prefetch, then spill the entries
                # back to the ready ring - the ring is the only structure
                # whose contents survive this call (outputs/readback,
                # restage, steal/export scans, checkpoint export, host
                # stall diagnosis). Entries spill to the HEAD side (the
                # cold, steal-facing end of the Chase-Lev split): a lane
                # holds the OLDEST ready descriptors of its kind (routing
                # pops drained them off the ring before execution), so
                # under the multi-device runners they are exactly the
                # cold work a thief's head-side scan window must see -
                # spilling to the tail would hide every lane-resident
                # candidate behind the hot end and starve the steal
                # exchange (observed: a batch-routed forest never
                # spread). C_HEAD may go negative; every reader indexes
                # the ring by ring_slot, and stage() widens its copy to
                # the whole ring when the window wraps below zero.
                rt_x = tr.now()
                for li, fid, spec in lane_rows:
                    h = lstate[li, LS_HEAD]
                    t = lstate[li, LS_TAIL]
                    if spec.prefetch:
                        pf_ok = lstate[li, LS_PF_BASE] == h + 1
                        pre = jnp.where(pf_ok, lstate[li, LS_PF_N], 0)

                        @pl.when(pre > 0)
                        def _(li=li, spec=spec, h=h, pre=pre, fid=fid):
                            tr.emit(TR_PREFETCH_DRAIN, rt_x, fid, pre)
                            spec.drain(_make_bctx(
                                li, spec, h, pre, pre,
                                lstate[li, LS_PF_BUF], jnp.int32(0),
                            ))

                    head0 = counts[C_HEAD]

                    def spill(s, _, li=li, h=h, head0=head0):
                        ready[ring_slot(head0 - 1 - s, ring)] = lanes[
                            li, ring_slot(h + s, ring)
                        ]
                        return 0

                    jax.lax.fori_loop(0, t - h, spill, 0)
                    counts[C_HEAD] = head0 - (t - h)

                    @pl.when(t > h)
                    def _(fid=fid, h=h, t=t):
                        tr.emit(TR_SPILL, rt_x, fid, t - h)

                    lstate[li, LS_HEAD] = t
                    lstate[li, LS_PF_BASE] = 0
                    tstats[TS_SPILLED] = tstats[TS_SPILLED] + (t - h)
            if tstats is not None:
                tstats[TS_BECAME] = rearm[RA_BECAME]
                tstats[TS_WALKED] = rearm[RA_WALKED]
            tr.emit(
                TR_ROUND_END, tr.tick(),
                counts[C_EXECUTED] - e0, counts[C_PENDING],
            )

        def install_descriptor(read_word):
            """Adopt one externally-produced descriptor row (a stolen row
            arriving over ICI, an injected stream row): allocate a row
            through the same path spawns use (freed rows first, then the
            bump cursor), copy the ABI words via ``read_word(w)``, count it
            pending, and push it ready only when its dep counter is zero -
            a dependent row waits for its predecessors like any other.
            Returns the installed row index (meaningful only when no
            overflow was flagged) so callers can apply post-install fixups
            (device/resident.py rewrites migrated rows' out slots)."""
            nf = free[0]
            use_free = nf > 0
            row_free = free[jnp.maximum(nf, 1)]
            a = counts[C_ALLOC]
            ok = use_free | (a < capacity)
            row = jnp.where(use_free, row_free, jnp.minimum(a, capacity - 1))

            @pl.when(use_free)
            def _():
                free[0] = nf - 1

            @pl.when(jnp.logical_not(use_free) & (a < capacity))
            def _():
                counts[C_ALLOC] = a + 1

            @pl.when(ok)
            def _():
                for w in range(DESC_WORDS):
                    tasks[row, w] = read_word(w)
                counts[C_PENDING] = counts[C_PENDING] + 1

                @pl.when(tasks[row, F_DEP] == 0)
                def _():
                    push_ready(row)

            @pl.when(jnp.logical_not(ok))
            def _():
                counts[C_OVERFLOW] = counts[C_OVERFLOW] | OVF_ROWS

            return row

        def headroom():
            """Task-table slots available to adopt EXTERNAL rows right
            now: tombstone-recycled rows on the free stack plus the unbump
            tail of the table. The inject-ring poll hook for traffic
            shaping (device/inject.py tenant lanes): a poll that consumes
            at most ``headroom()`` rows can never trip OVF_ROWS - rows it
            leaves on the ring are *backpressure* the host observes
            through the consumed-cursor echo, instead of an overflow that
            aborts the stream. (Spawning kernels still flag OVF_ROWS as
            before; the hook only shapes externally-injected load.)"""
            return free[0] + (capacity - counts[C_ALLOC])

        return types.SimpleNamespace(
            stage=stage, sched=sched, push_ready=push_ready,
            complete=complete, install_descriptor=install_descriptor,
            headroom=headroom,
        )

    def _kernel(
        self, fuel: int, stage_all_values: bool, trace, ckpt, qstride,
        inputs, *refs
    ) -> None:
        # ``trace``/``ckpt``/``qstride``/``inputs`` are the TraceRing /
        # checkpoint flag / quiesce poll stride / input-only data buffers
        # captured when _build_raw fixed the output tree - NOT self.trace:
        # pallas kernels trace lazily (first call), so reading mutable
        # instance state here could disagree with the already-built
        # out_shape and shift every ref slice.
        ndata = len(self.data_specs)
        written = [k for k in self.data_specs if k not in inputs]
        nbatch = len(self.batch_specs)
        ntrace = 1 if trace is not None else 0
        n_in = 5 + ndata + (1 if ckpt else 0)  # qctl rides last
        n_out = 5 + len(written) + (1 if ckpt else 0) + ntrace
        in_refs = refs[:n_in]
        out_refs = refs[n_in : n_in + n_out]
        tail = list(refs[n_in + n_out :])
        scratch_refs = tail[: len(self.scratch_specs)]
        tail = tail[len(self.scratch_specs) :]
        free = tail.pop(0)  # internal free-stack: [0]=count, [1..]=rows
        vfree = tail.pop(0)  # value-block free-stack, same layout
        rearm = tail.pop(0)  # re-arm words (RA_*)
        lanes = tail.pop(0) if nbatch else None  # per-kind ready lanes
        lstate = tail.pop(0) if nbatch else None  # lane cursors + prefetch
        qbuf = tail.pop(0) if ckpt else None  # quiesce-word staging
        qsem = tail.pop(0) if ckpt else None  # its DMA semaphore
        assert not tail, f"{len(tail)} unconsumed scratch refs"
        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        qctl = in_refs[5 + ndata] if ckpt else None
        tasks, ready, counts, ivalues = out_refs[:4]
        # An input-only buffer is its input ref; a written one its
        # (aliased) output window.
        data = dict(zip(self.data_specs, in_refs[5 : 5 + ndata]))
        data.update(zip(written, out_refs[4 : 4 + len(written)]))
        after = 4 + len(written)  # the appended outputs start here
        tstats = out_refs[after]
        qstat = out_refs[after + 1] if ckpt else None
        tracer = (
            Tracer(out_refs[n_out - 1], trace.capacity)
            if ntrace
            else None
        )
        scratch = dict(zip(self.scratch_specs.keys(), scratch_refs))
        tr = tracer if tracer is not None else NullTracer()

        quiesce_hook = None
        if ckpt:
            for w in range(8):
                qstat[w] = 0

            def quiesce_hook(executed_since):
                # Acquire-read the quiesce word from HBM - the same
                # re-read-every-round discipline as the abort words, so a
                # host with in-place buffer write access (pinned-host
                # production) lands a quiesce mid-entry; this driver
                # uploads qctl at entry, which bounds latency at one
                # round past the QC_AFTER threshold. ``quiesce_stride``
                # > 1 skips the DMA on all but every Nth round (round 0
                # always polls, so qbuf is never read uninitialized); a
                # stale qbuf between polls is safe because the host only
                # ever raises the flag monotonically within an entry -
                # observation latency grows by at most stride-1 rounds.
                if qstride > 1:
                    cnt = qstat[QS_POLLS]
                    qstat[QS_POLLS] = cnt + 1

                    @pl.when(cnt % qstride == 0)
                    def _():
                        cp = pltpu.make_async_copy(qctl, qbuf, qsem.at[0])
                        cp.start()
                        cp.wait()
                else:
                    qstat[QS_POLLS] = qstat[QS_POLLS] + 1
                    cp = pltpu.make_async_copy(qctl, qbuf, qsem.at[0])
                    cp.start()
                    cp.wait()
                q = (qbuf[QC_FLAG] != 0) & (executed_since >= qbuf[QC_AFTER])

                @pl.when(q & (qstat[QS_QUIESCED] == 0))
                def _():
                    qstat[QS_QUIESCED] = 1
                    qstat[QS_AT] = executed_since
                    tr.emit(TR_QUIESCE, tr.now(), executed_since)

                return q

        core = self._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            rearm, tasks_in, ready_in, counts_in, ivalues_in,
            stage_all_values, lanes=lanes, lstate=lstate, tstats=tstats,
            tracer=tracer,
            quiesce_hook=quiesce_hook,
        )

        core.stage()
        core.sched(fuel)
        if ckpt:
            # State-export record: one TR_CKPT at exit when this entry
            # quiesced (pending rows exported, ready backlog) - the device
            # half of the checkpoint bracket tools/timeline.py renders.
            @pl.when(qstat[QS_QUIESCED] != 0)
            def _():
                tr.emit(
                    TR_CKPT, tr.now(), counts[C_PENDING],
                    counts[C_TAIL] - counts[C_HEAD],
                )

    # -- host entry --

    @staticmethod
    def widen_value_alloc(counts_row, ivalues_row) -> None:
        """Widen counts_row[C_VALLOC] over the highest nonzero preset in
        ivalues_row (in place): presets are host slots, so staging must
        cover them and the device bump/row-block regions must sit above.
        Deliberate ZERO presets above value_alloc can't be detected here -
        declare them with TaskGraphBuilder.reserve_values instead."""
        nz = np.flatnonzero(np.asarray(ivalues_row))
        if len(nz):
            counts_row[C_VALLOC] = max(
                counts_row[C_VALLOC], int(nz[-1]) + 1
            )

    def check_row_values(self, value_alloc: int) -> None:
        """For uses_row_values kernels: every row's block ([value_alloc,
        value_alloc + VBLOCK*capacity)) must fit in the value buffer, else
        row_values writes would clamp and silently corrupt the top slots."""
        if not self.uses_row_values:
            return
        need = value_alloc + VBLOCK * self.capacity
        if need > self.num_values:
            raise ValueError(
                f"row-owned value blocks need num_values >= value_alloc"
                f"({value_alloc}) + VBLOCK*capacity({VBLOCK * self.capacity})"
                f" = {need}, got {self.num_values}; shrink out slots/presets "
                "or grow num_values"
            )

    def _build_raw(
        self, fuel: int, stage_all_values: bool = False,
        inputs: Sequence[str] = (),
    ):
        """The bare pallas_call (for embedding under shard_map; re-entrant
        callers must pass stage_all_values=True so value slots above
        value_alloc survive between entries). The data buffers named in
        ``inputs`` are arguments only: no output, no alias (``_build_exec``
        passes ``read_only``; the embedders alias every buffer)."""
        ndata = len(self.data_specs)
        written = [k for k in self.data_specs if k not in inputs]
        ckpt = self.checkpoint
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        anyspace = functools.partial(pl.BlockSpec, memory_space=pl.ANY)
        in_specs = (
            [smem(), smem(), smem(), smem(), smem()]
            + [anyspace() for _ in range(ndata)]
            # The quiesce ctl rides last in ANY (HBM): the scheduler
            # re-reads it by DMA every round (checkpoint builds only).
            + ([anyspace()] if ckpt else [])
        )
        out_specs = tuple(
            [smem(), smem(), smem(), smem()]
            + [anyspace() for _ in written]
            # The tier counters (TS_* words; a build with no batch route
            # writes TS_BECAME and TS_WALKED alone) ride out as one extra
            # SMEM word row APPENDED after the data outputs, so every
            # existing consumer's positional indexing is untouched.
            + [smem()]
            # Quiesce status (QS_* words), same appended discipline.
            + ([smem()] if ckpt else [])
            # The flight-recorder ring rides last, same appended-output
            # discipline (absent entirely when tracing is off).
            + ([smem()] if self.trace is not None else [])
        )
        data_shapes = [
            jax.ShapeDtypeStruct(s.shape, s.dtype)
            for s in map(self.data_specs.get, written)
        ]
        out_shape = tuple(
            [
                jax.ShapeDtypeStruct((self.capacity, DESC_WORDS), jnp.int32),
                jax.ShapeDtypeStruct((self.ring_len,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((self.num_values,), jnp.int32),
            ]
            + data_shapes
            + [jax.ShapeDtypeStruct((TS_WORDS,), jnp.int32)]
            + ([jax.ShapeDtypeStruct((8,), jnp.int32)] if ckpt else [])
            + ([self.trace.out_shape()] if self.trace is not None else [])
        )
        # inputs: tasks(0) succ(1) ready(2) counts(3) ivalues(4) data(5..)
        # outputs: tasks(0) ready(1) counts(2) ivalues(3) data(4..) tstats
        aliases = {0: 0, 2: 1, 3: 2, 4: 3}
        for o, k in enumerate(written):
            aliases[5 + list(self.data_specs).index(k)] = 4 + o
        return pl.pallas_call(
            functools.partial(
                self._kernel, fuel, stage_all_values, self.trace, ckpt,
                self.quiesce_stride, tuple(inputs),
            ),
            out_shape=out_shape,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=list(self.scratch_specs.values())
            + self.core_scratch()
            + (
                [
                    pltpu.SMEM((8,), jnp.int32),  # qbuf (quiesce staging)
                    pltpu.SemaphoreType.DMA((1,)),  # qsem
                ]
                if ckpt
                else []
            ),
            input_output_aliases=aliases,
            # Plain bool on purpose: True selects the fast XLA-backed
            # pallas interpreter. interpret_mode()'s InterpretParams
            # would select the far slower thread-per-device Mosaic
            # interpreter, which only kernels simulating remote DMA +
            # semaphores need (device/resident.py and friends).
            interpret=self.interpret,
            compiler_params=(
                pltpu.CompilerParams(
                    vmem_limit_bytes=self.vmem_limit_bytes
                )
                if self.vmem_limit_bytes and not self.interpret
                else None
            ),
        )

    def _exec_layout(self, riding: Sequence[str] = (),
                     given: Sequence[str] = ()) -> "_ExecLayout":
        """How each block of ``_build_raw``'s signature crosses in a
        ``run`` / ``resume`` (``ins`` / ``outs`` name its arguments and
        results in order; a data buffer ``k`` is ``data:k`` both sides):

        - ``up``: name -> shape of what goes up as ONE int32 slab, in
          this order: the scheduler's own blocks, the quiesce words of a
          checkpoint build, and the data buffers named in ``riding``.
        - ``alone``: the other data buffers, arguments of their own.
        - ``down``: name -> shape of what the host reads after every
          run (counts, the values, and the tier, quiesce and trace rows
          of the builds that have them); comes back as ONE array.
        - ``stays``: what the program returns that stays on the chip:
          the table, the ring, every written data buffer, and a
          ``read_only`` one that rode the slab (its block of it).
        - ``donated``: the program's argument numbers it consumes: the
          written data buffers named in ``given``, the ones the caller
          handed in as ``jax.Array``s."""
        data = ["data:" + k for k in self.data_specs]
        kept = ["data:" + k for k in self.read_only]
        ins = ["tasks", "succ", "ready", "counts", "ivalues"] + data
        outs = ["tasks", "ready", "counts", "ivalues"] + [
            n for n in data if n not in kept
        ]
        up = {
            "tasks": (self.capacity, DESC_WORDS),
            "succ": (self.succ_capacity,),
            "ready": (self.ring_len,),
            "counts": (8,),
            "ivalues": (self.num_values,),
        }
        down = {"counts": (8,), "ivalues": (self.num_values,),
                "tstats": (TS_WORDS,)}
        outs.append("tstats")
        if self.checkpoint:
            ins.append("qctl")
            up["qctl"] = (8,)
            outs.append("qstat")
            down["qstat"] = (8,)
        if self.trace is not None:
            outs.append("trace")
            down["trace"] = self.trace.out_shape().shape
        up.update(
            (n, tuple(self.data_specs[n[5:]].shape))
            for n in data if n in riding
        )
        alone = [n for n in ins if n not in up]
        return _ExecLayout(
            ins, outs, up, alone, down,
            ["tasks", "ready"]
            + [n for n in data if n not in kept or n in up],
            tuple(1 + i for i, n in enumerate(alone)
                  if n in given and n not in kept),
        )

    def _build_exec(self, fuel: int, stage_all_values: bool, lay):
        """The program one ``run`` / ``resume`` runs: ``_build_raw``'s
        kernel inside a jitted wrapper that splits the host's slab into
        the blocks of ``lay.up`` and joins those of ``lay.down`` into
        the one array the host reads, so a call is one transfer each way
        beside the buffers that cross alone. Called as
        ``program(slab, *alone)``; returns ``(packed, *stays)``."""
        kernel = self._build_raw(
            fuel, stage_all_values=stage_all_values, inputs=self.read_only
        )

        # Named for the trace's sake: a Pallas kernel shows under the
        # outermost jit's name, and the benchmark's kernel metrics find
        # ``%tpu_custom_call.N``, what a bare ``jit(pallas_call)`` gave
        # (inject.py:_build_entry, the same).
        def tpu_custom_call(slab, *alone):
            blocks = {**dict(zip(lay.alone, alone)), **_split(slab, lay.up)}
            res = dict(zip(lay.outs, kernel(*[blocks[n] for n in lay.ins])))
            res = {**blocks, **res}  # a read-only block is its input
            return (
                jnp.concatenate([res[n].reshape(-1) for n in lay.down]),
                *[res[n] for n in lay.stays],
            )

        # Donated, a written buffer is updated where it lies; kept, XLA
        # copies it whole in front of the kernel to honour the alias (and
        # may keep a small one in faster memory meanwhile, which is why a
        # buffer this call uploaded itself is left to XLA, as it was).
        return jax.jit(tpu_custom_call, donate_argnums=lay.donated)

    def decode_tier_stats(self, tstats) -> Dict[str, Any]:
        """Decode the raw TS_WORDS counter row into the per-tier stats dict
        (``info['tiers']``). Occupancy is batch tasks over the slots the
        fired rounds offered (TS_OFFERED accumulates each firing lane's own
        width, so the ratio stays exact with mixed-width routes) - the
        number perf tracking watches: low occupancy means the DAG isn't
        exposing same-kind parallelism (or the firing policy is
        dispatching partial batches too eagerly)."""
        t = np.asarray(tstats)
        rounds = int(t[TS_BATCH_ROUNDS])
        tasks = int(t[TS_BATCH_TASKS])
        offered = int(t[TS_OFFERED])
        width = max(spec.width for _, spec in self.batch_specs)
        return {
            "batch_rounds": rounds,
            "batch_tasks": tasks,
            "batch_occupancy": tasks / offered if offered else 0.0,
            "batch_width": width,
            "full_rounds": int(t[TS_FULL_ROUNDS]),
            "scalar_tasks": int(t[TS_SCALAR_ROUNDS]),
            "routed": int(t[TS_ROUTED]),
            # Rows a device-side spawn pushed straight onto their lane
            # (spawn-time routing): direct + routed reached a lane.
            "direct": int(t[TS_DIRECT]),
            "prefetch_hits": int(t[TS_PREFETCH]),
            "spilled": int(t[TS_SPILLED]),
            # Dispatches that ended re-armed (KernelContext.become), on
            # either tier.
            "became": int(t[TS_BECAME]),
            # Retirements that walked more than F_SUCC0 (retire()'s slow
            # region), on either tier.
            "walked": int(t[TS_WALKED]),
            # Age-trigger firing policy (lane_max_age; zeros when off):
            # rounds that jumped ring-drain-first, and the worst
            # starved-round age any lane reached - the device-side gauge
            # the bounded-age acceptance pins (lane_partial_age, the
            # trace-derived partial-fire streak, rides separately on
            # traced runs).
            "age_fires": int(t[TS_AGE_FIRES]),
            "max_starved_age": int(t[TS_MAX_AGE]),
            # Priority-bucket tier (priority_buckets; zeros when off):
            # rounds fired from a nonzero bucket ring, and age-guard
            # fires that jumped a lower non-empty bucket - the only
            # legal bucket-order inversion (per-bucket occupancy rides
            # separately on traced runs, off the TR_FIRE_BUCKET
            # records).
            "bucket_fires": int(t[TS_BUCKET_FIRES]),
            "bucket_inversions": int(t[TS_INVERSIONS]),
        }

    def stats_dict(self) -> Dict[str, Any]:
        """Stats snapshot of the most recent ``run()`` (per-tier dispatch
        counters included when batch-routed); {} before any run. The
        benches and tools/perf_regression.py read this so tier occupancy
        never floats free of a harness."""
        return dict(self._last_info or {})

    @staticmethod
    def quiesce_words(quiesce) -> np.ndarray:
        """Normalize a ``quiesce=`` argument into the 8-word qctl row:
        None/False = off (zeros - a caller plumbing a boolean flag must
        get 'no quiesce', not 'quiesce now'), True = quiesce at the first
        round boundary, an int k = quiesce once >= k tasks have executed
        this entry (the deterministic checkpoint-at-round-k spelling;
        batch rounds may overshoot by width-1 like fuel does)."""
        q = np.zeros(8, np.int32)
        if quiesce is None or quiesce is False:
            return q
        q[QC_FLAG] = 1
        if quiesce is not True:
            q[QC_AFTER] = int(quiesce)
        return q

    def run(
        self,
        builder: TaskGraphBuilder,
        data: Optional[Dict[str, Any]] = None,
        ivalues: Optional[np.ndarray] = None,
        fuel: int = 1 << 22,
        quiesce=None,
    ):
        """Execute the task graph to completion; returns
        (ivalues, data_dict, info_dict).

        Value-slot readback contract: only slots below the staged
        ``value_alloc`` (host presets + declared out slots, widened over any
        nonzero entries of ``ivalues``) round-trip host -> kernel -> host.
        Slots above it are device temporaries (row-owned blocks, bump
        allocations): their returned contents are whatever the last kernel
        entry left there and must not be relied on. A deliberate ZERO preset
        above the out-slot range is invisible to the widening scan - declare
        it with ``TaskGraphBuilder.reserve_values`` so staging covers it.

        ``quiesce`` (checkpoint builds only; see ``quiesce_words``) makes
        the scheduler stop popping at a round boundary and return its live
        state: the run comes back with ``info['quiesced']=True`` and
        ``info['state']`` (the resumable scheduler snapshot - feed it to
        ``resume()`` or ``runtime.checkpoint.snapshot_megakernel``)
        instead of raising StallError on the pending remainder.

        What crosses the host link (``resume`` alike): ONE int32 slab up
        (the task table, successors, ready ring, counts, values, the
        quiesce words of a checkpoint build, and every ``data`` buffer
        that is on the host, int32 and smaller than ``SLAB_RIDE_BYTES``),
        one upload more for each other host buffer (a large or a
        non-int32 one), nothing for a buffer that is a ``jax.Array``
        already; ONE packed int32 array down (counts, values, and the
        tier, quiesce and trace rows of the builds that have them), its
        host copy started behind the launch. The data outputs stay on
        the chip as ``jax.Array``s until the caller reads them. The rule
        reads only the buffer (host or device, dtype, bytes); a layout
        seen for the first time builds its own program.

        Who owns a ``data`` buffer that arrives as a ``jax.Array``
        (``resume`` alike): a buffer the build declared ``read_only`` is
        read where it lies, is still the caller's afterwards, and comes
        back in the data dict as the same array. Every other one is
        CONSUMED: it is donated to the program, the kernel writes it in
        place (no whole-buffer copy on the way in), the array the caller
        handed in is deleted, and its contents come back under the same
        name in the data dict. A host (numpy) buffer is uploaded as
        before, the caller's array never touched and the upload not
        donated: host callers run the program they always ran.
        ``info['staging']`` counts it: ``uploads`` (the slab counted
        once), ``slab_words``, ``slab_blocks`` (the names that rode it,
        a data buffer ``k`` as ``data:k``), ``downloads`` (1; 2 where a
        quiesced run also pulled its state)."""
        with span("mk.finalize"):
            tasks, succ, ring, counts = builder.finalize(
                capacity=self.capacity, succ_capacity=self.succ_capacity
            )
            if ivalues is None:
                ivalues = np.zeros(self.num_values, dtype=np.int32)
            else:
                counts = counts.copy()
                self.widen_value_alloc(counts, ivalues)
            self.check_row_values(int(counts[C_VALLOC]))
        data = dict(data or {})
        if set(data.keys()) != set(self.data_specs.keys()):
            raise ValueError(
                f"data buffers {sorted(data)} != declared {sorted(self.data_specs)}"
            )
        return self._execute(
            tasks, succ, ring, counts, ivalues, data, fuel, quiesce,
            stage_all_values=False,
        )

    def resume(self, state: Dict[str, Any], fuel: int = 1 << 22,
               quiesce=None):
        """Re-enter mid-graph from a quiesced run's exported state (the
        ``info['state']`` dict of a quiesced ``run()``/``resume()``, or a
        restored CheckpointBundle's) and continue to completion - the
        restart half of the checkpoint protocol. Stages ALL value slots
        (live row-owned blocks / bump allocations survive the re-entry,
        the sharded steal loop's re-entrant discipline) and rebuilds the
        row free stack from completion tombstones. Chains: a resumed run
        may itself be quiesced again."""
        data = dict(state.get("data") or {})
        if set(data.keys()) != set(self.data_specs.keys()):
            raise ValueError(
                f"state data buffers {sorted(data)} != declared "
                f"{sorted(self.data_specs)}"
            )
        return self._execute(
            state["tasks"], state["succ"], state["ready"], state["counts"],
            state["ivalues"], data, fuel, quiesce, stage_all_values=True,
        )

    def _execute(
        self, tasks, succ, ring, counts, ivalues, data, fuel, quiesce,
        stage_all_values: bool,
    ):
        if quiesce is False:  # falsy boolean plumbing = off, everywhere
            quiesce = None
        if quiesce is not None and not self.checkpoint:
            raise ValueError(
                "quiesce= needs Megakernel(checkpoint=True): the quiesce "
                "word is compiled into the round loop only then"
            )
        # What crosses, crosses once (ISSUE 39). The scheduler's blocks
        # and the small int32 buffers the caller holds on the host go up
        # as ONE slab; a buffer already on the chip is passed through, a
        # large or non-int32 host buffer gets an upload of its own
        # (``_rides`` reads the rule off each buffer).
        host = {
            "tasks": tasks, "succ": succ,
            # A snapshot written while the ring was ``capacity`` long is
            # told by its shape and its live window re-laid.
            "ready": relay_ring(ring, counts, self.ring_len),
            "counts": counts, "ivalues": ivalues,
        }
        if self.checkpoint:
            host["qctl"] = self.quiesce_words(quiesce)
        alone = {
            "data:" + k: data[k]
            for k in self.data_specs if not _rides(data[k])
        }
        riding = tuple(
            "data:" + k for k in self.data_specs if "data:" + k not in alone
        )
        host.update((n, data[n[5:]]) for n in riding)
        given = tuple(
            n for n, d in alone.items()
            if isinstance(d, jax.Array) and n[5:] not in self.read_only
        )
        # A caller may hand the same buffer from the host once and from
        # the chip the next time: the layout is part of the program.
        key = (fuel, bool(stage_all_values), riding, given)
        first_build = key not in self._jitted
        if first_build:
            # Process-wide program cache (runtime/progcache.py): a
            # content-identical program built by ANY instance this
            # process is reused here - the returned callable is the
            # same jitted object, so its first call skips trace/lower
            # entirely. The per-instance dict stays as the L1 (repeat
            # runs on one instance never pay fingerprinting).
            from ..runtime.progcache import first_call, shared_build

            lay = self._exec_layout(riding, given)
            fn, self._pc_stats = shared_build(
                self, ("megakernel-exec",) + key,
                lambda: self._build_exec(fuel, stage_all_values, lay),
            )
            self._jitted[key] = fn, lay
        jitted, lay = self._jitted[key]
        import contextlib

        # An interpret-mode kernel is plain JAX ops plus host callbacks.
        # Where the default device is a TPU (interpret=True stated on a
        # machine with a chip), those ops would be compiled FOR the chip
        # and the run would no longer be the interpreter the caller asked
        # for; pin them to the host CPU.
        cm = (
            jax.default_device(jax.devices("cpu")[0])
            if self.interpret
            else contextlib.nullcontext()
        )
        import time as _time

        with cm:
            with span("mk.upload"):
                slab = _join(host, lay.up)
                args = [jnp.asarray(slab)] + [
                    d if isinstance(d, jax.Array) else jnp.asarray(d)
                    for d in alone.values()
                ]
            # Epoch bracket for the flight recorder (the clockprobe
            # trick): monotonic_ns before launch and after readback are
            # the host wall clock the trace's round-indexed records
            # interpolate into - the same clock runtime/instrument.py
            # stamps host events with, so device rounds and host spans
            # share one Perfetto timeline.
            t0_ns = _time.monotonic_ns()
            # jax.jit is lazy: the trace, the lowering and the compile
            # the program cache exists to skip are paid inside a
            # program's first call, so that call, launch to readback, is
            # the build the ledger's bracket times (and the cache's
            # eviction weight).
            with building(
                "megakernel", jitted,
                self._pc_stats if first_build else None,
            ):
                with span("mk.launch"):
                    launch = jitted
                    if first_build:  # this call traces: see first_call
                        launch = functools.partial(first_call, jitted)
                    packed_dev, tasks_out, ready_out, *rest = launch(*args)
                    # The host's copy of what it reads starts behind the
                    # launch; mk.wait only waits for it.
                    packed_dev.copy_to_host_async()
                with span("mk.wait"):  # the kernel runs inside this span
                    packed = np.asarray(packed_dev)
            t1_ns = _time.monotonic_ns()
        # A read-only buffer that crossed alone is the array that went
        # in; every other one is what the program returned.
        held = {**dict(zip(lay.alone, args[1:])),
                **dict(zip(lay.stays[2:], rest))}
        data_out = {k: held["data:" + k] for k in self.data_specs}
        staging = {
            "uploads": 1 + sum(
                not isinstance(d, jax.Array) for d in alone.values()
            ),
            "slab_words": int(slab.size),
            "slab_blocks": list(lay.up),
            "downloads": 1,
        }
        counts_np = packed[:8]
        ivalues_np = packed[8 : 8 + self.num_values]
        off = 8 + self.num_values
        tstats_np = packed[off : off + TS_WORDS]
        off += TS_WORDS
        info = {
            "executed": int(counts_np[C_EXECUTED]),
            # Of those, the dispatches that ended re-armed (ctx.become):
            # the row stayed pending, as its own continuation.
            "became": int(tstats_np[TS_BECAME]),
            # Retirements that took retire()'s slow region: the row held a
            # second inline successor or a CSR list. The fast path's
            # share is 1 - walked / (executed - became).
            "walked": int(tstats_np[TS_WALKED]),
            "pending": int(counts_np[C_PENDING]),
            "allocated": int(counts_np[C_ALLOC]),
            "value_alloc": int(counts_np[C_VALLOC]),
            "overflow": bool(counts_np[C_OVERFLOW]),
            "staging": staging,
            **ran_on(packed_dev, self.interpret),
        }
        if self._pc_stats is not None:
            # How this run's program was obtained (the build that
            # produced the executable, not this entry): the ledger's
            # row of it, cache hit flag and build_s vs cache_lookup_s -
            # the trade the program cache exists to win.
            info["program_cache"] = dict(self._pc_stats)
        if self.batch_specs:
            info["tiers"] = self.decode_tier_stats(tstats_np)
        quiesced = False
        if self.checkpoint:
            qstat = packed[off : off + 8]
            off += 8
            quiesced = bool(qstat[QS_QUIESCED])
            info["quiesced"] = quiesced
            if quiesced:
                info["quiesce"] = {"executed_at": int(qstat[QS_AT])}
        if self.trace is not None:
            info["trace"] = trace_info(
                [packed[off : off + self.trace.words]], t0_ns, t1_ns,
                self.trace.capacity,
            )
            if self.batch_specs and "tiers" in info:
                # Partial-batch starvation gauge (the lane-policy watch
                # item): longest consecutive-partial-fire streak per
                # lane, in rounds, off the TR_FIRE_BATCH records; the
                # max rides info['tiers'] so MetricsRegistry.add_run_info
                # exports it beside lane_occupancy.
                from .tracebuf import lane_partial_age

                ages = lane_partial_age(
                    info["trace"],
                    {fid: spec.width for fid, spec in self.batch_specs},
                )
                info["tiers"]["lane_partial_ages"] = ages
                info["tiers"]["lane_partial_age"] = max(
                    ages.values(), default=0
                )
                if self.priority_buckets:
                    # Per-bucket occupancy gauge (the priority tier's
                    # structural health read): retired descriptors over
                    # offered slots per bucket ring, off TR_FIRE_BUCKET.
                    from .tracebuf import bucket_occupancy

                    info["tiers"]["bucket_occupancy"] = bucket_occupancy(
                        info["trace"],
                        {fid: spec.width for fid, spec in
                         self.batch_specs},
                        self.priority_buckets,
                    )
        if quiesced:
            # The exported scheduler snapshot: everything resume() (and
            # CheckpointBundle) needs to relaunch mid-graph. succ is
            # input-only (never mutated on device), so the input array IS
            # its live value.
            staging["downloads"] += 1
            tasks_np, ready_np, data_np = jax.device_get(
                (tasks_out, ready_out, data_out)
            )
            info["state"] = {
                "tasks": tasks_np,
                "succ": np.asarray(succ),
                "ready": ready_np,
                "counts": counts_np.copy(),
                "ivalues": ivalues_np.copy(),
                "data": data_np,
            }
        self._last_info = info
        if info["overflow"]:
            raise RuntimeError(
                f"megakernel overflow: "
                f"{decode_overflow(int(counts_np[C_OVERFLOW]))} exhausted "
                f"(capacity={self.capacity}, num_values={self.num_values}); "
                "raise the limits, coarsen tasks, or audit frees"
            )
        if info["pending"] != 0 and not quiesced:
            from ..runtime.resilience import StallError

            raise StallError(
                f"megakernel stalled with {info['pending']} pending tasks "
                f"after {info['executed']} executed (dependency cycle or fuel "
                f"{fuel} exhausted)",
                stats=info,
            )
        return ivalues_np, data_out, info
