"""Recording abstract interpreter for device kernel bodies.

Kernel bodies are plain Python that *emits* device code through a small
surface: ``jnp`` math, ``pl.when`` predication, ``pltpu.make_async_copy``
DMA, loop combinators, and the ``KernelContext``/``BatchContext``
facilities. That surface is narrow enough to run a body ONCE, host-only,
over **concrete synthetic descriptors and fake buffers**, recording the
effects the static analyses need:

- every DMA start/wait as a (src, dst, sem) triple of buffer *windows*
  (concrete index boxes - synthetic descriptor args are plain ints, so
  the windows a body computes from them evaluate to numbers),
- every value-slot write, tagged with the batch slot that made it
  (``slot_ctx``/``set_out`` attribution),
- every dynamic spawn / continuation transfer, with its (static) link
  words - the migratability classification input.

No Pallas trace happens and no Mosaic is imported: ``pl.when`` /
``make_async_copy`` / the loop combinators are patched to host
equivalents for the duration of one body evaluation, math runs eagerly
on concrete values, and loops are truncated at ``LOOP_CAP`` iterations
(structure discovery, not value computation). A body using machinery
outside this surface raises ``ShimUnsupported`` - the caller reports a
``shim-unsupported`` info finding and verifies nothing (soundness over
false alarms).

Synthetic descriptor args are ``(slot+1) * ARG_STRIDE + word*7``: large
and slot-distinct, so store windows computed from a slot's own args land
far apart and windows that *coincide* across slots mean the body ignored
its descriptor - the classic copy-paste batch race.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..device.descriptor import (
    DESC_WORDS, F_A0, F_DEP, F_FN, F_HOME, F_OUT, F_SUCC0, F_SUCC1,
    NO_TASK,
)

__all__ = [
    "ShimUnsupported",
    "BodyTrace",
    "run_batch_body",
    "run_drain",
    "run_scalar_kernel",
    "ARG_STRIDE",
    "OUT_BASE",
    "OUT_STRIDE",
]

# Synthetic-descriptor layout (see module docstring).
ARG_STRIDE = 1 << 16
OUT_BASE = 1000
OUT_STRIDE = 17
LOOP_CAP = 128          # fori/while truncation (structure, not values)
SHIM_BUDGET_S = 5.0     # per-body wall ceiling (tier-1 safety valve)
_CAPACITY = 512         # synthetic task-table rows

_lock = threading.Lock()  # the patches touch module globals
# Thread-transparency for the module-global patches: only the thread
# that entered _patched() sees the host-loop/recording behavior; any
# OTHER thread (a streaming megakernel's device threads, a concurrent
# trace) that calls jax.lax.fori_loop / pl.when / make_async_copy while
# a shim run is active is routed to the saved originals.
_tls = threading.local()


class ShimUnsupported(RuntimeError):
    """The body used machinery outside the shim's surface; nothing was
    verified (the caller downgrades to an info finding)."""


# ------------------------------------------------------------- fake refs


def _as_int(x) -> int:
    return int(np.asarray(x))


def _norm_box(shape, idx) -> Tuple[Tuple[int, int], ...]:
    """Normalize an indexer (ints / slices / pl.ds / Ellipsis) into a
    per-axis (start, stop) box over ``shape`` (None dims = unbounded)."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    dims = list(shape) if shape is not None else [1 << 30] * len(idx)
    # Expand Ellipsis.
    if any(x is Ellipsis for x in idx):
        k = idx.index(Ellipsis)
        pad = len(dims) - (len(idx) - 1)
        idx = idx[:k] + (slice(None),) * pad + idx[k + 1:]
    box = []
    for ax, x in enumerate(idx):
        d = dims[ax] if ax < len(dims) else 1 << 30
        if isinstance(x, slice):
            lo = 0 if x.start is None else _as_int(x.start)
            hi = d if x.stop is None else _as_int(x.stop)
            box.append((lo, hi))
        elif hasattr(x, "start") and hasattr(x, "size"):  # pl.ds
            lo = _as_int(x.start)
            box.append((lo, lo + _as_int(x.size)))
        else:
            i = _as_int(x)
            box.append((i, i + 1))
    for d in dims[len(idx):]:
        box.append((0, d))
    return tuple(box)


class Window:
    """A window of a fake ref: the DMA-endpoint representation."""

    def __init__(self, ref: "FakeRef", box) -> None:
        self.ref = ref
        self.box = box

    @property
    def key(self):
        return self.ref.name


class _AtHelper:
    def __init__(self, ref: "FakeRef") -> None:
        self._ref = ref

    def __getitem__(self, idx) -> Window:
        return Window(self._ref, _norm_box(self._ref.shape, idx))


class FakeRef:
    """Concrete stand-in for a device memory ref: numpy backing for
    reads, recorded writes, ``.at[...]`` windows for DMA endpoints."""

    def __init__(self, name: str, kind: str, shape=None, dtype=np.int32,
                 backing: Optional[np.ndarray] = None) -> None:
        self.name = name
        self.kind = kind  # data | scratch | smem | sem
        self.shape = tuple(shape) if shape is not None else None
        self.writes: List[Tuple[Tuple[Tuple[int, int], ...], Any]] = []
        if backing is not None:
            self.backing = backing
            self.shape = backing.shape
        elif self.shape is not None and kind != "sem":
            self.backing = np.zeros(self.shape, dtype)
        else:
            self.backing = None

    @property
    def at(self) -> _AtHelper:
        return _AtHelper(self)

    def _np_idx(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        out = []
        for x in idx:
            if isinstance(x, slice) or x is Ellipsis:
                out.append(x)
            elif hasattr(x, "start") and hasattr(x, "size"):  # pl.ds
                lo = _as_int(x.start)
                out.append(slice(lo, lo + _as_int(x.size)))
            else:
                out.append(_as_int(x))
        return tuple(out)

    def __getitem__(self, idx):
        if self.backing is None:
            raise ShimUnsupported(f"read of value-less ref {self.name}")
        try:
            return self.backing[self._np_idx(idx)]
        except (IndexError, TypeError) as e:
            raise ShimUnsupported(f"unmodelled read {self.name}[{idx}]: {e}")

    def __setitem__(self, idx, val) -> None:
        self.writes.append((_norm_box(self.shape, idx), val))
        if self.backing is None:
            return
        try:
            self.backing[self._np_idx(idx)] = np.asarray(val)
        except (IndexError, TypeError, ValueError):
            pass  # out-of-range synthetic index: structure recorded above


# ------------------------------------------------------------ the trace


@dataclass
class DMAEvent:
    op: str  # start | wait
    src: Tuple[str, Any]
    dst: Tuple[str, Any]
    dst_kind: str
    sem: Tuple[str, Any]
    seq: int

    def triple(self):
        return (self.src, self.dst, self.sem)


@dataclass
class BodyTrace:
    dma: List[DMAEvent] = field(default_factory=list)
    # (slot-or-None, value-slot index, seq)
    value_writes: List[Tuple[Optional[int], int, int]] = field(
        default_factory=list
    )
    value_reads: List[Tuple[Optional[int], int, int]] = field(
        default_factory=list
    )
    # (slot-or-None, {dep_count, succ0, succ1, out, fn})
    spawns: List[Tuple[Optional[int], Dict[str, int]]] = field(
        default_factory=list
    )
    # On-device promise ops (the direction-1 serving surface): every
    # ``ctx.wait_value(slot)`` / ``ctx.satisfy(slot)`` a body performs,
    # as (slot-or-None, value-slot index, seq). The wait-graph analysis
    # (analysis/waits.py) matches waiters to satisfiers across kinds.
    waits: List[Tuple[Optional[int], int, int]] = field(
        default_factory=list
    )
    satisfies: List[Tuple[Optional[int], int, int]] = field(
        default_factory=list
    )
    continuations: int = 0
    next_reads: List[Tuple[int, int]] = field(default_factory=list)
    # Loops whose bounds were truncated at LOOP_CAP or derived from the
    # synthetic descriptor args (>= ARG_STRIDE): the trace is then an
    # UNDER-approximation. ``approx_marks`` holds the seq position of
    # each truncation - the point where the skipped iterations WOULD
    # have emitted their events - so protocol findings demote only when
    # their witness's missing half could sit inside a skipped window
    # (an unmatched wait before every mark, or an unmatched start after
    # every mark, is an EXACT-window finding and stays an error).
    approx_loops: int = 0
    approx_marks: List[int] = field(default_factory=list)
    seq: int = 0

    def tick(self) -> int:
        self.seq += 1
        return self.seq

    def starts(self) -> List[DMAEvent]:
        return [e for e in self.dma if e.op == "start"]

    def unmatched_starts(self) -> List[DMAEvent]:
        """Starts with no later wait on the same (src, dst, sem) triple
        (each wait retires the earliest open start of its triple)."""
        open_: List[DMAEvent] = []
        for e in self.dma:
            if e.op == "start":
                open_.append(e)
            else:
                for s in open_:
                    if s.triple() == e.triple():
                        open_.remove(s)
                        break
        return open_

    def unmatched_waits(self) -> List[DMAEvent]:
        open_: List[DMAEvent] = []
        bad: List[DMAEvent] = []
        for e in self.dma:
            if e.op == "start":
                open_.append(e)
            else:
                for s in open_:
                    if s.triple() == e.triple():
                        open_.remove(s)
                        break
                else:
                    bad.append(e)
        return bad


class _RecCopy:
    def __init__(self, trace: BodyTrace, src, dst, sem) -> None:
        self._trace = trace
        self._src = self._end(src)
        self._dst = self._end(dst)
        self._dst_kind = self._kind(dst)
        self._sem = self._end(sem)

    @staticmethod
    def _end(x):
        if isinstance(x, Window):
            return (x.ref.name, x.box)
        if isinstance(x, FakeRef):
            full = (
                tuple((0, d) for d in x.shape)
                if x.shape is not None else ()
            )
            return (x.name, full)
        raise ShimUnsupported(f"DMA endpoint {type(x).__name__} unmodelled")

    @staticmethod
    def _kind(x):
        return x.ref.kind if isinstance(x, Window) else getattr(
            x, "kind", "?"
        )

    def _emit(self, op: str) -> None:
        self._trace.dma.append(DMAEvent(
            op, self._src, self._dst, self._dst_kind, self._sem,
            self._trace.tick(),
        ))

    def start(self) -> None:
        self._emit("start")

    def wait(self) -> None:
        self._emit("wait")


# ------------------------------------------------------------- patching


@contextlib.contextmanager
def _patched(trace: BodyTrace):
    """Swap pl.when / pltpu.make_async_copy / pltpu.roll / lax loop
    combinators for host equivalents while one body runs (module-global
    patch, guarded by a lock; construction-time only)."""
    import time

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    deadline = time.monotonic() + SHIM_BUDGET_S

    def _tick():
        if time.monotonic() > deadline:
            raise ShimUnsupported(
                f"body evaluation exceeded the {SHIM_BUDGET_S:.0f}s "
                "shim budget"
            )

    def _mine() -> bool:
        return getattr(_tls, "active", False)

    def _when(cond):
        if not _mine():
            return saved_when(cond)
        live = bool(np.asarray(cond))

        def deco(fn):
            if live:
                fn()
            return fn

        return deco

    def _fori(lo, hi, body, init, **kw):
        if not _mine():
            return saved_fori(lo, hi, body, init, **kw)
        val = init
        lo, hi = _as_int(lo), _as_int(hi)
        # A well-formed static loop is a small forward range; anything
        # else (reversed/empty-by-arithmetic bounds, ranges past the
        # cap) is taken as arg-dependent and marks the trace
        # approximate - the synthetic descriptor args make such bounds
        # meaningless (cholesky's nj = i - k goes negative).
        approx = not (0 <= lo <= hi <= lo + LOOP_CAP)
        if approx:
            trace.approx_loops += 1
        for i in range(lo, min(hi, lo + LOOP_CAP)):
            _tick()
            val = body(i, val)
        if approx:
            # Skipped iterations run (conceptually) HERE, after the
            # executed prefix - the mark the demotion window keys on.
            trace.approx_marks.append(trace.tick())
        return val

    def _while(cond, body, init):
        if not _mine():
            return saved_while(cond, body, init)
        val = init
        for i in range(LOOP_CAP + 1):
            if not bool(np.asarray(cond(val))):
                break
            if i == LOOP_CAP:
                trace.approx_loops += 1
                trace.approx_marks.append(trace.tick())
                break
            _tick()
            val = body(val)
        return val

    def _roll(x, shift, axis=None, **kw):
        if not _mine():
            return saved_roll(x, shift, axis=axis, **kw)
        import jax.numpy as jnp

        return jnp.roll(x, _as_int(shift), axis=axis)

    def _copy(src, dst, sem, **kw):
        if not _mine():
            return saved_copy(src, dst, sem, **kw)
        return _RecCopy(trace, src, dst, sem)

    saved_when = pl.when
    saved_copy = pltpu.make_async_copy
    saved_fori = jax.lax.fori_loop
    saved_while = jax.lax.while_loop
    saved_roll = getattr(pltpu, "roll", None)
    saved = [
        (pl, "when", saved_when),
        (pltpu, "make_async_copy", saved_copy),
        (jax.lax, "fori_loop", saved_fori),
        (jax.lax, "while_loop", saved_while),
    ]
    if saved_roll is not None:
        saved.append((pltpu, "roll", saved_roll))
    with _lock:
        try:
            _tls.active = True
            pl.when = _when
            pltpu.make_async_copy = _copy
            jax.lax.fori_loop = _fori
            jax.lax.while_loop = _while
            if saved_roll is not None:
                pltpu.roll = _roll
            yield
        finally:
            _tls.active = False
            for mod, attr, fn in saved:
                setattr(mod, attr, fn)


# ------------------------------------------------- recording contexts


_ctx_classes = None


def _make_recording_contexts():
    """Subclass the real contexts lazily (import cycle: megakernel
    imports nothing from analysis; analysis subclasses megakernel) and
    once (class creation is measurable at per-construction frequency)."""
    global _ctx_classes
    if _ctx_classes is not None:
        return _ctx_classes
    from ..device.megakernel import BatchContext, KernelContext

    class RecordingKernelContext(KernelContext):
        _shim_trace: BodyTrace = None  # set per instance
        _shim_slot: Optional[int] = None

        def value(self, slot):
            self._shim_trace.value_reads.append(
                (self._shim_slot, _as_int(slot), self._shim_trace.tick())
            )
            return super().value(slot)

        def set_value(self, slot, v) -> None:
            self._shim_trace.value_writes.append(
                (self._shim_slot, _as_int(slot), self._shim_trace.tick())
            )
            super().set_value(slot, v)

        def set_out(self, v) -> None:
            self._shim_trace.value_writes.append(
                (self._shim_slot, _as_int(self.out_slot),
                 self._shim_trace.tick())
            )
            super().set_out(v)

        def wait_value(self, slot, spin_cap=None):
            # Record the promise-wait; never spin (the synthetic flag is
            # unset, and the wait-graph analysis - not execution order -
            # decides whether a satisfier exists). Return the flag word
            # like the real op so bodies that COMPUTE with the waited
            # value keep interpreting past the wait.
            self._shim_trace.waits.append(
                (self._shim_slot, _as_int(slot), self._shim_trace.tick())
            )
            return self.ivalues[slot]

        def satisfy(self, slot, v=1) -> None:
            self._shim_trace.satisfies.append(
                (self._shim_slot, _as_int(slot), self._shim_trace.tick())
            )
            super().satisfy(slot, v)

        def spawn(self, fn, args=(), dep_count=0, succ0=NO_TASK,
                  succ1=NO_TASK, out=0, nargs=None):
            row = super().spawn(
                fn, args, dep_count=dep_count, succ0=succ0, succ1=succ1,
                out=out, nargs=nargs,
            )
            self._shim_trace.spawns.append((self._shim_slot, {
                "fn": _as_int(fn), "dep_count": _as_int(dep_count),
                "succ0": _as_int(succ0), "succ1": _as_int(succ1),
                "out": _as_int(out),
            }))
            return row

        def become(self, fn, dep_count) -> None:
            # A fork-join on the task's own row: its links stay behind
            # for the continuation.
            self._shim_trace.continuations += 1
            super().become(fn, dep_count)

    class RecordingBatchContext(BatchContext):
        _shim_trace: BodyTrace = None

        def value(self, slot):
            self._shim_trace.value_reads.append(
                (None, _as_int(slot), self._shim_trace.tick())
            )
            return super().value(slot)

        def set_value(self, slot, v) -> None:
            self._shim_trace.value_writes.append(
                (None, _as_int(slot), self._shim_trace.tick())
            )
            super().set_value(slot, v)

        def set_out(self, s, v) -> None:
            self._shim_trace.value_writes.append(
                (int(s), _as_int(self.out_slot(s)), self._shim_trace.tick())
            )
            super().set_out(s, v)

        def wait_value(self, slot, spin_cap=None):
            self._shim_trace.waits.append(
                (None, _as_int(slot), self._shim_trace.tick())
            )
            return self.k.ivalues[slot]

        def satisfy(self, slot, v=1) -> None:
            self._shim_trace.satisfies.append(
                (None, _as_int(slot), self._shim_trace.tick())
            )
            super().satisfy(slot, v)

        def next_idx(self, s):
            self._shim_trace.next_reads.append(
                (int(s), _as_int(self.prefetch_count))
            )
            return super().next_idx(s)

        def slot_ctx(self, s):
            ctx = super().slot_ctx(s)
            rec = RecordingKernelContext(
                ctx.idx, ctx._tasks, ctx._succ, ctx._ready, ctx._counts,
                ctx.ivalues, ctx.data, ctx.scratch, ctx._capacity,
                ctx._free, ctx._num_values, ctx._vfree,
                ctx._uses_row_values, ctx._tracks_home,
                rearm=ctx._rearm, slot=ctx._slot, direct=ctx._direct,
            )
            rec._shim_trace = self._shim_trace
            rec._shim_slot = int(s)
            return rec

    _ctx_classes = (RecordingKernelContext, RecordingBatchContext)
    return _ctx_classes


# --------------------------------------------------------- environments


def _spec_shape_dtype(spec):
    shape = getattr(spec, "shape", None)
    dtype = getattr(spec, "dtype", None)
    try:
        dtype = np.dtype(dtype) if dtype is not None else np.int32
    except TypeError:
        dtype = np.int32
    return shape, dtype


def _fake_env(data_specs: Dict[str, Any], scratch_specs: Dict[str, Any]):
    data = {}
    for name, s in (data_specs or {}).items():
        shape, dtype = _spec_shape_dtype(s)
        data[name] = FakeRef(f"data:{name}", "data", shape, dtype)
    scratch = {}
    for name, s in (scratch_specs or {}).items():
        shape, dtype = _spec_shape_dtype(s)
        kind = "sem" if "Semaphore" in type(s).__name__ else "scratch"
        if kind == "sem":
            scratch[name] = FakeRef(f"scratch:{name}", "sem", shape)
        else:
            scratch[name] = FakeRef(f"scratch:{name}", "scratch", shape,
                                    dtype)
    return data, scratch


def synth_arg(slot: int, word: int) -> int:
    """The synthetic descriptor arg of batch slot ``slot``, word ``word``
    (slot-distinct, far apart - see module docstring)."""
    return (slot + 1) * ARG_STRIDE + word * 7


def _synth_tasks(fid: int, width: int, nxt: int) -> np.ndarray:
    tasks = np.zeros((_CAPACITY, DESC_WORDS), np.int64)
    for r in range(width + nxt):
        tasks[r, F_FN] = fid
        tasks[r, F_DEP] = 0
        tasks[r, F_SUCC0] = NO_TASK
        tasks[r, F_SUCC1] = NO_TASK
        tasks[r, F_HOME] = NO_TASK
        for i in range(6):
            tasks[r, F_A0 + i] = synth_arg(r, i)
        tasks[r, F_OUT] = OUT_BASE + r * OUT_STRIDE
    return tasks


def _core_refs(tasks: np.ndarray):
    from ..device.megakernel import (
        C_ALLOC, C_PENDING, C_VALLOC, C_VBASE, RA_MARK, _Rearm,
    )

    t = FakeRef("smem:tasks", "smem", backing=tasks)
    succ = FakeRef("smem:succ", "smem", (64,))
    ready = FakeRef("smem:ready", "smem", (_CAPACITY,))
    counts = FakeRef("smem:counts", "smem", (8,))
    n = _CAPACITY // 2
    counts.backing[C_ALLOC] = n
    counts.backing[C_PENDING] = n
    counts.backing[C_VALLOC] = OUT_BASE + _CAPACITY * OUT_STRIDE
    counts.backing[C_VBASE] = 1 << 20  # row-owned blocks far above outs
    ivalues = FakeRef("smem:ivalues", "smem", (64,))
    free = FakeRef("smem:free", "smem", (_CAPACITY + 1,))
    vfree = FakeRef("smem:vfree", "smem", (_CAPACITY + 1,))
    rearm = _Rearm(FakeRef("smem:rearm", "smem", (RA_MARK + _CAPACITY,)))
    return t, succ, ready, counts, ivalues, free, vfree, rearm


class _BigValues:
    """ivalues stand-in: reads return 0 for ANY slot (synthetic out
    slots range far), writes recorded by the recording contexts."""

    def __init__(self) -> None:
        self.name = "smem:ivalues"
        self.kind = "smem"
        self.shape = None

    def __getitem__(self, idx):
        return np.int32(0)

    def __setitem__(self, idx, val) -> None:
        pass


def _run(fn, trace: BodyTrace):
    try:
        with _patched(trace):
            fn()
    except ShimUnsupported as e:
        # The partial trace rides the exception: events recorded BEFORE
        # the unmodelled construct (a promise wait, say) are real, and
        # the wait-graph gate must still see them - otherwise any
        # unmodelled tail would silently evade the deadlock analysis.
        e.trace = trace
        raise
    except Exception as e:  # noqa: BLE001 - any body failure = unmodelled
        exc = ShimUnsupported(f"{type(e).__name__}: {e}")
        exc.trace = trace
        raise exc from e
    return trace


def run_batch_body(spec, fid: int, data_specs, scratch_specs, *,
                   prefetch_count: int = 0, ctx_hook=None) -> BodyTrace:
    """Evaluate ``spec.body`` once over a full-width synthetic batch
    (``prefetch_count`` next-batch descriptors announced, none
    pre-loaded); returns the recorded trace."""
    RecordingKernelContext, RecordingBatchContext = (
        _make_recording_contexts()
    )
    trace = BodyTrace()
    tasks, succ, ready, counts, ivalues, free, vfree, rearm = (
        _core_refs(_synth_tasks(fid, spec.width, prefetch_count))
    )
    data, scratch = _fake_env(data_specs, scratch_specs)
    lanes = FakeRef(
        "smem:lanes", "smem",
        backing=np.tile(np.arange(_CAPACITY, dtype=np.int64), (1, 1)),
    )
    kctx = RecordingKernelContext(
        0, tasks, succ, ready, counts, _BigValues(), data, scratch,
        _CAPACITY, free, 1 << 22, vfree, False, False, rearm=rearm,
    )
    kctx._shim_trace = trace
    bctx = RecordingBatchContext(
        kctx, lanes, 0, 0, np.int32(spec.width), spec.width,
        np.int32(0), np.int32(0), np.int32(prefetch_count), _CAPACITY,
        ctx_hook=ctx_hook,
    )
    bctx._shim_trace = trace
    return _run(lambda: spec.body(bctx), trace)


def run_drain(spec, fid: int, data_specs, scratch_specs, *,
              prefetched: int, buf: int) -> BodyTrace:
    """Evaluate ``spec.drain`` as the scheduler's exit path would: the
    in-flight prefetch covers ``prefetched`` descriptors (the rows the
    body's prefetch pass targeted) in operand half ``buf``."""
    RecordingKernelContext, RecordingBatchContext = (
        _make_recording_contexts()
    )
    trace = BodyTrace()
    tasks, succ, ready, counts, ivalues, free, vfree, rearm = (
        _core_refs(_synth_tasks(fid, spec.width, prefetched))
    )
    data, scratch = _fake_env(data_specs, scratch_specs)
    lanes = FakeRef(
        "smem:lanes", "smem",
        backing=np.tile(np.arange(_CAPACITY, dtype=np.int64), (1, 1)),
    )
    kctx = RecordingKernelContext(
        spec.width, tasks, succ, ready, counts, _BigValues(), data,
        scratch, _CAPACITY, free, 1 << 22, vfree, False, False,
        rearm=rearm,
    )
    kctx._shim_trace = trace
    # head = width: the drained prefetch targets the rows BEHIND the
    # batch the body just ran - exactly what its next_arg reads saw.
    bctx = RecordingBatchContext(
        kctx, lanes, 0, spec.width, np.int32(prefetched), spec.width,
        np.int32(prefetched), np.int32(buf), np.int32(0), _CAPACITY,
    )
    bctx._shim_trace = trace
    return _run(lambda: spec.drain(bctx), trace)


def run_scalar_kernel(fn, data_specs, scratch_specs,
                      args=None) -> BodyTrace:
    """Evaluate a scalar kernel-table entry once over one synthetic
    descriptor (row 0, the same ``synth_arg`` scheme batch bodies get:
    arg-derived values land ``>= ARG_STRIDE``, which is how the
    wait-graph analysis tells an arg-carried promise slot from a static
    one; arg-bounded loops truncate and mark the trace approximate);
    the trace's spawns/continuations drive classification."""
    RecordingKernelContext, _ = _make_recording_contexts()
    trace = BodyTrace()
    tasks, succ, ready, counts, ivalues, free, vfree, rearm = (
        _core_refs(_synth_tasks(0, 1, 0))
    )
    for i in range(6):
        tasks.backing[0, F_A0 + i] = (
            args[i] if args is not None and i < len(args)
            else synth_arg(0, i)
        )
    data, scratch = _fake_env(data_specs, scratch_specs)
    ctx = RecordingKernelContext(
        0, tasks, succ, ready, counts, _BigValues(), data, scratch,
        _CAPACITY, free, 1 << 22, vfree, False, False, rearm=rearm,
    )
    ctx._shim_trace = trace
    ctx._shim_slot = 0
    return _run(lambda: fn(ctx), trace)
