"""Host -> resident-kernel task injection: streaming graphs over an HBM ring.

The reference can hand new work to a running runtime from outside: an AM
handler materializes a task on a remote PE mid-execution
(modules/openshmem-am/src/hclib_openshmem-am.cpp:64-123), and hclib_async
may be called while workers run. The megakernel's task table, by contrast,
was sealed at launch. This module adds the missing channel: an **injection
ring** in HBM that the scheduler polls *from inside the kernel*:

- ring[R, 256] int32: descriptor rows padded to 1024 B so any row offset is
  a legal dynamic DMA offset (Mosaic wants coarse alignment); row words
  0..15 are the standard descriptor ABI (device/descriptor.py).
- ctl[8] int32: [0]=tail (total rows ever appended), [1]=close flag,
  [2]=device-consumed cursor (echoed back), [3]=host abort word - polled
  by the kernel INSIDE its round loop, [4] echoes the round the abort
  was observed, [5]=host quiesce word + [6]=its executed-count threshold
  (checkpoint builds only, see ``quiesce()``; the output's [5] echoes
  the round the quiesce was observed, -1 = never). This driver uploads
  a fresh ctl copy per entry, so an abort
  lands at the next ENTRY boundary and the in-kernel poll then bounds the
  final entry to about one round; the per-round ctl re-read is the device
  half a zero-copy pinned-host producer would need for true mid-quantum
  aborts (same status as the ring's pinned-production mode above).
- Write ordering (the fence contract): the producer writes descriptor rows
  FIRST, then bumps tail - release semantics. The kernel reads tail, then
  DMAs only rows below it - acquire semantics; a row is never read before
  the tail that published it.
- The kernel interleaves scheduler quanta with ring polls, installing new
  rows through the same row-allocation path spawns use, and reports its
  consumed count back through the aliased ctl output.

Multi-tenant mode (``tenants=``, device/tenants.py): the ring is
partitioned into per-tenant contiguous regions, each with its own
tail/consumed cursors in a per-tenant ``tctl[T, 8]`` control block, and
the in-kernel poll becomes a **weighted round-robin** over the lanes -
at most ``weight`` rows per lane per poll, start lane rotating every
round, rows host-marked expired dropped with a counted TR_TENANT record,
and total installs bounded by the scheduler's live ``headroom()`` so a
full task table turns into ring backpressure instead of an overflow.
Admission (quotas, token buckets, deadlines, poison quarantine) is the
host half, in device/tenants.py; ``submit()`` below is its entry point.
A ``tenants=None`` build compiles none of this - no extra inputs,
outputs, or scratch - and is bit-identical to the single-firehose path
(the perf_regression ``ingress-overhead`` guard pins it).

Execution model: ``StreamingMegakernel.run_stream`` re-enters the kernel in
bounded quanta; each entry drains everything available (including rows that
appear mid-entry: the poll runs between quanta INSIDE the kernel) and
returns when there is nothing left and the stream is not yet closed. Host
threads may call ``inject()`` at any time; ``close()`` lets the final entry
drain and exit. The same ring layout admits zero-copy pinned-host
production (host writes rows then tail over PCIe; the in-kernel poll is
the consumer side already). This driver does not do that: it uploads the
ring per entry, so delivery lands at entry boundaries while the in-kernel
poll/drain path is exercised by pre-published rows discovered mid-entry
(tests/test_inject.py).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import resilience
from ..runtime.resilience import CancelledError, StallError
from ..runtime.clockprobe import EpochBracket
from ..runtime.env import env_bool
from .descriptor import (
    DESC_WORDS,
    F_FN,
    F_OUT,
    NO_TASK,
    RING_ROW,
    TEN_ADMIT_ROUND,
    TEN_EXPIRED,
    TEN_ID,
    TEN_TOKEN,
    TaskGraphBuilder,
)
from .egress import (
    EC_CONSUMED,
    EC_INFLIGHT,
    EC_PARK_COUNT,
    EC_PARK_HEAD,
    EC_PARKED,
    EC_WRITE,
    EGR_FN,
    EGR_OK,
    EGR_SLOT,
    EGR_STATUS,
    EGR_T_ADMIT,
    EGR_T_SPANS,
    EGR_TEN,
    EGR_TOKEN,
    EGR_VALUE,
    EGR_WORDS,
    TOKEN_LIMIT,
    EgressProtocolError,
)
from .megakernel import (
    C_EXECUTED,
    C_HEAD,
    C_OVERFLOW,
    C_PENDING,
    C_TAIL,
    C_VALLOC,
    Megakernel,
    _target_row,
    ran_on,
    smem_bytes,
)
from .telemetry import (
    LAT_ADMIT,
    LAT_BUCKETS,
    LAT_FIRE,
    LAT_INSTALL,
    LAT_WORDS,
    TG_BACKLOG,
    TG_ENTRIES,
    TG_INSTALLS,
    TG_PARKED,
    TG_RETIRES,
    TG_ROUNDS,
    unpack_spans,
)
from .tenants import (
    TC_CONSUMED,
    TC_DROPPED,
    TC_EXPIRED,
    TC_INSTALLED,
    TC_PAUSE,
    TC_TAIL,
    TC_WEIGHT,
    Admission,
    TenantTable,
    build_row,
    normalize_tenants,
)
from .tracebuf import (
    NullTracer,
    TR_ABORT,
    TR_CKPT,
    TR_EGRESS,
    TR_INJECT,
    TR_LATENCY,
    TR_QUIESCE,
    TR_TENANT,
    Tracer,
    trace_info,
)

__all__ = ["StreamingMegakernel", "RING_ROW"]


class StreamingMegakernel:
    """Megakernel + injection ring: a resident scheduler whose task supply
    is open-ended (the streaming/AM substrate).

    Relationship to the unified runner: ``ResidentKernel(inject=True)``
    subsumes this capability on device meshes (injection composes there
    with stealing and PGAS in one kernel, and ``dryrun_multichip``
    exercises exactly that). This class remains the single-device,
    no-mesh specialization whose host loop supports LIVE re-entrant
    production (inject()/close() from any thread between entries).

    ``mk`` supplies kernels/capacities; the injection ring holds
    ``ring_capacity`` rows. The ring is a linear (non-wrapping) append log
    per stream: capacity bounds TOTAL injected tasks per run_stream (keeps
    the producer/consumer index algebra trivial; streams needing more roll
    over to a fresh run_stream).

    ``tenants=`` (the multi-tenant front door, device/tenants.py): an int
    N, a sequence of TenantSpec/str/dict lane specs, or a prebuilt
    TenantTable (deterministic-clock tests build their own). None reads
    the ``HCLIB_TPU_TENANTS*`` env spelling; False forces single-firehose
    mode regardless of env. With lanes enabled the ring splits into
    per-tenant regions of ``ring_capacity // N`` rows (rounded up to
    8-row DMA chunks), producers go through ``submit()`` for a typed
    ``Admission`` verdict, and the in-kernel poll runs weighted
    round-robin over the lanes.
    """

    def __init__(self, mk: Megakernel, ring_capacity: int = 1024,
                 tenants=None, telemetry=None) -> None:
        self.mk = mk
        # Rounded up to a whole 8-row chunk: the kernel fetches the ring in
        # 8-row DMAs, and the final chunk must not run off the array.
        self.ring_capacity = -(-int(ring_capacity) // 8) * 8
        if isinstance(tenants, TenantTable):
            self.tenants: Optional[TenantTable] = tenants
        else:
            specs = normalize_tenants(tenants)
            if specs is None:
                self.tenants = None
            else:
                region = -(-self.ring_capacity // (8 * len(specs))) * 8
                self.tenants = TenantTable(specs, region)
        if self.tenants is not None:
            # The ring is exactly the concatenation of the lane regions.
            self.ring_capacity = (
                len(self.tenants) * self.tenants.region_rows
            )
        # Completion-mailbox egress (device/egress.py): compiled into
        # the kernel only when the tenant table is egress-enabled - a
        # mailbox ring + park buffer + ectl cursor block + per-task-row
        # token table ride as four extra SMEM in/out pairs, retirements
        # publish EGR rows through the complete_hook seam, and the
        # driver drains both regions (resolving futures) after every
        # entry. Egress-off builds compile ZERO of it - no extra
        # operands, no extra words - and stay bit-identical to the
        # pre-egress kernel (tests/test_serving.py pins the lowered
        # text).
        self._egress = (
            self.tenants.egress if self.tenants is not None else None
        )
        # Live telemetry plane (ISSUE 19, device/telemetry.py):
        # per-row lifecycle stamps + per-tenant on-device latency
        # histograms + a live-gauge row, riding two extra host-seeded/
        # echoed SMEM pairs (the ctl-echo discipline) so the host can
        # scrape them MID-STREAM (telemetry_snapshot / TelemetryPoller).
        # Requires an egress-enabled tenant stream: the latency fold
        # runs at the egress publish hook, keyed by the retiring row's
        # tenant. None reads HCLIB_TPU_TELEMETRY; False forces off.
        # Off compiles ZERO of it - no extra operands, no hooks - and
        # stays bit-identical to the pre-telemetry kernel
        # (tests/test_telemetry.py pins the lowered text).
        if telemetry is None:
            telemetry = env_bool("HCLIB_TPU_TELEMETRY")
        self.telemetry = bool(telemetry)
        if self.telemetry and self._egress is None:
            raise ValueError(
                "telemetry needs an egress-enabled tenant stream (the "
                "latency histograms are per-tenant and fold at the "
                "egress publish hook): build with tenants= plus an "
                "EgressSpec, or set HCLIB_TPU_EGRESS_DEPTH"
            )
        # Last entry's echoed telemetry block + conversion state, under
        # self._lock (written by the driver thread, read by pollers).
        self._tele_seq = 0
        self._tele_snapshot: Optional[Dict[str, Any]] = None
        self._spans: Dict[int, Tuple[int, int, int]] = {}
        self._jitted: Dict[Any, Any] = {}
        self._pc_stats: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()
        self._pending_rows: List[np.ndarray] = []
        self._closed = False
        # Distinguishes a quiesce-induced close (undone by a same-object
        # resume) from an explicit close()/abort() (sticky).
        self._closed_by_quiesce = False
        self._abort_reason: Optional[str] = None
        self._abort_t: Optional[float] = None
        # Checkpoint quiesce (mk must be built with checkpoint=True):
        # requested threshold + the wall clock of the request, for the
        # quiesce-latency stat.
        self._quiesce_after: Optional[int] = None
        self._quiesce_t: Optional[float] = None
        # Abort-latency accounting (surfaced by stats_dict): filled by the
        # run_stream driver when the abort entry returns.
        self._stats: Dict[str, Any] = {
            "aborts": 0,
            "abort_reason": None,
            "abort_observed_round": None,
            "abort_latency_s": None,
            "abort_drain_executed": None,
            # Preempt-storm accounting (ISSUE 6): how many quiesce cuts
            # this stream object has taken and resumed through, so a
            # storm soak can assert every injected preemption actually
            # cut (and the MetricsRegistry can rate() the churn).
            "quiesces": 0,
            "resumes": 0,
            "last_quiesce_latency_s": None,
        }

    def _smem_extra(self, capacity: int) -> int:
        """Padded SMEM bytes ``_build`` adds to the scheduler's own at
        ``capacity`` task rows (the shapes below are ``_build``'s)."""
        one = [(8,), (8,), (8, RING_ROW)]  # ctl out, ctl + row staging
        io = []  # host-seeded, echoed: an input and an output window
        if self.tenants is not None:
            io.append((len(self.tenants), 8))
        if self._egress is not None:
            depth = self._egress.depth
            io += [(depth, EGR_WORDS), (depth, EGR_WORDS), (8,),
                   (capacity,)]
        if self.telemetry:
            io += [(1 + len(self.tenants), LAT_BUCKETS),
                   (capacity, LAT_WORDS)]
        return sum(map(smem_bytes, one)) + 2 * sum(map(smem_bytes, io))

    # ---- lifecycle (resilience: the ring must never stay open) ----

    def __enter__(self) -> "StreamingMegakernel":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # Guarantee close() even when the producer body raised: an open
        # ring would leave run_stream (on any thread) re-entering forever
        # waiting for a close that never comes.
        self.close()
        return False

    def abort(self, reason: str = "aborted") -> None:
        """Host-side abort: stop accepting injections and stop the running
        stream. At its next entry boundary the driving run_stream
        publishes the ctl abort word and runs ONE final kernel entry - the
        round loop polls the word and exits within a bounded number of
        inner iterations, remaining rows dropped - then raises
        ``CancelledError``. Abort latency (wall time, observed round,
        tasks drained after the abort) is surfaced by ``stats_dict()``.
        (The in-kernel per-round poll is what a zero-copy pinned-host
        producer would need to land an abort mid-entry; this driver's
        per-entry ctl upload bounds latency at one entry + one round.)"""
        with self._lock:
            if self._abort_reason is None:
                self._abort_reason = str(reason)
                self._abort_t = time.monotonic()
            self._closed = True
            self._closed_by_quiesce = False

    def quiesce(self, after_executed: int = 0) -> None:
        """Host-side checkpoint request (``mk`` must be built with
        ``checkpoint=True``): at its next entry boundary the driving
        run_stream publishes the ctl quiesce word; the kernel observes it
        inside its round loop - once at least ``after_executed`` tasks
        have run (0: immediately; a positive k is the deterministic
        checkpoint-at-k spelling) - stops popping at that round boundary,
        and exits with its live scheduler state. run_stream then returns
        with ``info['quiesced']=True`` and ``info['state']`` (feed it to
        ``runtime.checkpoint.snapshot_stream`` / ``run_stream(
        resume_state=...)``), the ring closed so producers fail fast -
        preemption semantics: checkpoint, then stop."""
        if not self.mk.checkpoint:
            raise ValueError(
                "quiesce() needs Megakernel(checkpoint=True): the quiesce "
                "word is compiled into the round loop only then"
            )
        with self._lock:
            if self._quiesce_after is None:
                self._quiesce_after = max(0, int(after_executed))
                self._quiesce_t = time.monotonic()

    def stats_dict(self) -> dict:
        """Resilience counters for this stream (abort latency included).
        With tenant lanes enabled the snapshot folds in the per-tenant
        admission counters (``tenants.<id>.backlog/accepted/rejected``
        ...), so a StallError carrying these stats names the tenant that
        wedged the stream, not just "the stream"."""
        with self._lock:
            d = dict(self._stats)
        if self.tenants is not None:
            d["tenants"] = self.tenants.stats()
            if self.tenants.futures is not None:
                d["egress"] = self.tenants.futures.stats_dict()
        return d

    # ---- producer side (host; any thread) ----

    def inject(
        self,
        fn: int,
        args: Sequence[int] = (),
        out: int = 0,
        dep_count: int = 0,
        succ0: int = NO_TASK,
        succ1: int = NO_TASK,
    ) -> None:
        """Queue one descriptor for the stream (thread-safe; rows reach the
        device ring at the next entry boundary, or immediately on attached
        hosts writing the pinned ring directly). On a tenant-enabled
        stream this is sugar for ``submit()`` on the first (default)
        lane, raising if that lane rejects - quota-aware producers call
        ``submit`` directly and handle the Admission verdict."""
        if dep_count != 0:
            # A dependent injected row would wait on predecessors, but the
            # host has no way to wire successor edges INTO a row whose
            # device id is unknown until installation - nothing could ever
            # decrement it. (Successor edges OUT of injected rows, succ0/1
            # naming static-graph rows, are fine.)
            raise ValueError("injected tasks must have dep_count == 0")
        if self.tenants is not None:
            adm = self.submit(
                self.tenants.ids[0], fn, args=args, out=out,
                succ0=succ0, succ1=succ1,
            )
            if not adm:
                raise RuntimeError(
                    f"inject rejected by tenant lane "
                    f"{self.tenants.ids[0]!r}: {adm.reason}"
                )
            return
        row = build_row(fn, args, out, succ0, succ1)
        with self._lock:
            if self._closed:
                reason = self._abort_reason
                raise RuntimeError(
                    "stream closed" + (f" ({reason})" if reason else "")
                )
            self._pending_rows.append(row)

    def submit(
        self,
        tenant,
        fn: int,
        args: Sequence[int] = (),
        out: int = 0,
        succ0: int = NO_TASK,
        succ1: int = NO_TASK,
        deadline_s: Optional[float] = None,
        cancel_scope=None,
        wait: bool = False,
        wait_timeout_s: float = 30.0,
    ) -> Admission:
        """Admit one task into a tenant lane (thread-safe; needs a
        tenant-enabled stream). Returns the typed ``Admission`` verdict:
        ACCEPTED (inside the lane's in-flight budget; publishes at the
        next entry), QUEUED (over budget, host backlog has room), or
        REJECTED(reason) - the explicit backpressure signal.

        ``deadline_s``/``cancel_scope`` feed deadline-aware admission
        (device/tenants.py): explicit deadline wins, else the scope
        chain's nearest ``CancelScope.set_deadline``, else the lane's
        default. Expired-at-admission rejects on the spot; later
        expiries drop lazily (host pump or device poll, counted).

        ``wait=True`` converts the *transient* rejections - "rate" (the
        token bucket refills) and "backlog" (the pump drains the host
        queue) - into a blocking wait with bounded exponential backoff,
        up to ``wait_timeout_s`` or the submission's own deadline.
        Terminal rejections (ring budget, quarantine, cancellation,
        expiry, closed stream) return immediately either way."""
        if self.tenants is None:
            raise ValueError(
                "submit() needs tenant lanes: build the stream with "
                "tenants= (or set HCLIB_TPU_TENANTS)"
            )
        table = self.tenants
        table._lane(tenant)  # unknown tenants raise KeyError up front
        row = build_row(fn, args, out, succ0, succ1)
        deadline_at = table.resolve_deadline(
            tenant, deadline_s, cancel_scope
        )
        with self._lock:
            closed = self._closed
        if closed:
            return table.record_reject(tenant, "closed")
        if not wait:
            return table.admit(tenant, row, deadline_at, cancel_scope)
        # The timeout is a WALL-clock bound: an injected table clock
        # (deterministic tests) governs admission/deadline semantics but
        # must not be able to make "bounded wait" unbounded - a frozen
        # fake clock would otherwise never reach t_end while time.sleep
        # burns real time forever.
        t_end = time.monotonic() + float(wait_timeout_s)
        backoff = 0.0005
        while True:
            adm = table.admit(
                tenant, row, deadline_at, cancel_scope,
                record_reject=False,
            )
            if adm:
                return adm
            if adm.reason not in ("rate", "backlog"):
                return table.record_reject(tenant, adm.reason)
            if deadline_at is not None and table.clock() >= deadline_at:
                return table.record_reject(tenant, "expired")
            if time.monotonic() >= t_end:
                return table.record_reject(tenant, adm.reason)
            with self._lock:
                if self._closed:
                    return table.record_reject(tenant, "closed")
            time.sleep(backoff)
            backoff = min(backoff * 2, 0.05)
        assert False, "unreachable"

    def close(self) -> None:
        """No more injections: the stream drains and run_stream returns."""
        with self._lock:
            self._closed = True
            self._closed_by_quiesce = False

    # ---- kernel ----

    def _kernel(self, quantum: int, max_rounds: int, trace, *refs) -> None:
        # ``trace`` captured at _build time (pallas traces lazily; see
        # Megakernel._kernel).
        mk = self.mk
        ndata = len(mk.data_specs)
        ntrace = 1 if trace is not None else 0
        nten = 1 if self.tenants is not None else 0
        negr = 1 if (nten and self._egress is not None) else 0
        ntele = 1 if self.telemetry else 0
        depth = self._egress.depth if negr else 0
        park_cap = depth  # bounds tokened in-flight work (credit gate)
        # + ring, ctl (+ tctl, tenant lanes) (+ egr/park/ectl/etok,
        # egress) (+ tele/tlat, telemetry)
        n_in = 7 + ndata + nten + 4 * negr + 2 * ntele
        in_refs = refs[:n_in]
        # + ctl out (+ tctl echo) (+ egress echoes) (+ telemetry echoes)
        n_out = 5 + ndata + ntrace + nten + 4 * negr + 2 * ntele
        out_refs = refs[n_in : n_in + n_out]
        rest = refs[n_in + n_out :]
        nscratch = len(mk.scratch_specs)
        scratch_refs = rest[:nscratch]
        free, vfree, ctlbuf, rowbuf, isem = rest[nscratch:]
        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        ring, ctl_in = in_refs[5], in_refs[6]
        tctl_in = in_refs[7 + ndata] if nten else None
        if negr:
            egr_in, park_in, ectl_in, etok_in = in_refs[
                8 + ndata : 12 + ndata
            ]
        if ntele:
            tele_in, tlat_in = in_refs[12 + ndata : 14 + ndata]
        tasks, ready, counts, ivalues = out_refs[:4]
        ctl_out = out_refs[4]
        data = dict(zip(mk.data_specs.keys(), out_refs[5 : 5 + ndata]))
        tr = (
            Tracer(out_refs[5 + ndata], trace.capacity)
            if ntrace
            else NullTracer()
        )
        tctl_out = out_refs[5 + ndata + ntrace] if nten else None
        if negr:
            egr_out, park_out, ectl_out, etok_out = out_refs[
                6 + ndata + ntrace : 10 + ndata + ntrace
            ]
        if ntele:
            tele_out, tlat_out = out_refs[
                10 + ndata + ntrace : 12 + ndata + ntrace
            ]
        scratch = dict(zip(mk.scratch_specs.keys(), scratch_refs))

        def egress_complete(idx):
            """Completion-mailbox publish, run at task retirement (the
            complete_hook seam fires FIRST inside complete(), while the
            row's words are intact). Tokened rows (etok != 0) publish an
            EGR row into the mailbox; a full mailbox PARKS the row in
            the park ring instead - counted (EC_PARKED cumulative,
            EC_PARK_COUNT current), traced as TR_EGRESS, never dropped,
            never an OVF abort. The install-side credit gate bounds
            parked + in-flight below park_cap, so the park append here
            cannot overflow by construction. egress_reference is the
            executable spec - change one, change both."""
            packed = etok_out[idx]

            @pl.when(packed != 0)
            def _():
                token = jax.lax.rem(packed, jnp.int32(TOKEN_LIMIT))
                ten = packed // jnp.int32(TOKEN_LIMIT)
                slot = tasks[idx, F_OUT]
                write = ectl_out[EC_WRITE]
                room = depth - (write - ectl_out[EC_CONSUMED])
                if ntele:
                    # Lifecycle span (telemetry builds only): retire
                    # round == fire round (dispatch and completion are
                    # atomic within one inner round), so the EGR span
                    # word packs only (fire - install, install - admit)
                    # and the fold below uses the live round gauge as
                    # the retire stamp. unpack_spans / bucket_of /
                    # hist_fold_reference (device/telemetry.py) are the
                    # host spec of these three computations.
                    now = tele_out[0, TG_ROUNDS]
                    admit = tlat_out[idx, LAT_ADMIT]
                    spans = (
                        jnp.clip(
                            now - tlat_out[idx, LAT_INSTALL], 0, 0xFFFF
                        ) << 16
                    ) | jnp.clip(
                        tlat_out[idx, LAT_INSTALL] - admit, 0, 0xFFFF
                    )

                @pl.when(room > 0)
                def _():
                    s = jax.lax.rem(write, depth)
                    egr_out[s, EGR_STATUS] = jnp.int32(EGR_OK)
                    egr_out[s, EGR_TOKEN] = token
                    egr_out[s, EGR_TEN] = ten
                    egr_out[s, EGR_FN] = tasks[idx, F_FN]
                    egr_out[s, EGR_SLOT] = slot
                    egr_out[s, EGR_VALUE] = ivalues[slot]
                    if ntele:
                        egr_out[s, EGR_T_ADMIT] = admit
                        egr_out[s, EGR_T_SPANS] = spans
                    ectl_out[EC_WRITE] = write + 1

                @pl.when(room <= 0)
                def _():
                    n = ectl_out[EC_PARK_COUNT]
                    p = jax.lax.rem(
                        ectl_out[EC_PARK_HEAD] + n, park_cap
                    )
                    park_out[p, EGR_STATUS] = jnp.int32(EGR_OK)
                    park_out[p, EGR_TOKEN] = token
                    park_out[p, EGR_TEN] = ten
                    park_out[p, EGR_FN] = tasks[idx, F_FN]
                    park_out[p, EGR_SLOT] = slot
                    park_out[p, EGR_VALUE] = ivalues[slot]
                    if ntele:
                        park_out[p, EGR_T_ADMIT] = admit
                        park_out[p, EGR_T_SPANS] = spans
                    ectl_out[EC_PARK_COUNT] = n + 1
                    ectl_out[EC_PARKED] = ectl_out[EC_PARKED] + 1
                    tr.emit(TR_EGRESS, tr.now(), token, n + 1)

                if ntele:
                    # Histogram fold: log2 bucket of (retire - admit),
                    # branch-free (b = sum of threshold crossings; the
                    # last bucket is the counted overflow bucket). One
                    # event, two views: the per-tenant counter bump the
                    # poller scrapes, and the TR_LATENCY trace record.
                    d = jnp.maximum(now - admit, 0)
                    b = jnp.int32(0)
                    for k in range(1, LAT_BUCKETS):
                        b = b + (d >= (1 << k)).astype(jnp.int32)
                    tele_out[1 + ten, b] = tele_out[1 + ten, b] + 1
                    tele_out[0, TG_RETIRES] = (
                        tele_out[0, TG_RETIRES] + 1
                    )
                    tr.emit(TR_LATENCY, tr.now(), (ten << 16) | b, d)
                etok_out[idx] = jnp.int32(0)
                ectl_out[EC_INFLIGHT] = ectl_out[EC_INFLIGHT] - 1

        def tele_fire(idx):
            """Telemetry fire stamp (the _make_core fire_hook seam):
            runs at every dispatch site before the task body, so the
            egress fold inside complete_hook sees it."""
            tlat_out[idx, LAT_FIRE] = tele_out[0, TG_ROUNDS]

        def tele_round():
            """Telemetry round tick (the _make_core round_hook seam):
            advances the cumulative round gauge - the stream's
            timebase - and refreshes the point-in-time gauges."""
            tele_out[0, TG_ROUNDS] = tele_out[0, TG_ROUNDS] + 1
            tele_out[0, TG_BACKLOG] = counts[C_TAIL] - counts[C_HEAD]
            tele_out[0, TG_PARKED] = ectl_out[EC_PARK_COUNT]

        core = mk._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            tasks_in, ready_in, counts_in, ivalues_in, True,
            tracer=tr if tr.enabled else None,
            complete_hook=egress_complete if negr else None,
            fire_hook=tele_fire if ntele else None,
            round_hook=tele_round if ntele else None,
        )
        cap = mk.capacity

        core.stage()

        def install(row_slot) -> None:
            idx = core.install_descriptor(lambda w: rowbuf[row_slot, w])
            if ntele:
                # Lifecycle stamps: the ring row's host-stamped admit
                # round rides into the per-row table (0 = unstamped),
                # the install round is the live gauge, and installs
                # count - tracked and untracked alike.
                tlat_out[idx, LAT_ADMIT] = rowbuf[
                    row_slot, TEN_ADMIT_ROUND
                ]
                tlat_out[idx, LAT_INSTALL] = tele_out[0, TG_ROUNDS]
                tele_out[0, TG_INSTALLS] = tele_out[0, TG_INSTALLS] + 1
            if negr:
                # Stamp the submit token (packed token | tenant << 24)
                # onto the allocated task-table row so retirement knows
                # where to publish; count it in-flight for the credit
                # gate.
                token = rowbuf[row_slot, TEN_TOKEN]

                @pl.when(token != 0)
                def _():
                    etok_out[idx] = token + (
                        rowbuf[row_slot, TEN_ID] * jnp.int32(TOKEN_LIMIT)
                    )
                    ectl_out[EC_INFLIGHT] = ectl_out[EC_INFLIGHT] + 1

        def poll(consumed):
            """Acquire-read the ring: ctl first (tail publishes rows), then
            the rows below tail, fetched in 8-row chunks (Mosaic dynamic
            slices along the sublane-tiled dim must be 8-aligned).
            Returns (consumed', close_flag)."""
            cp = pltpu.make_async_copy(ctl_in, ctlbuf, isem.at[0])
            cp.start()
            cp.wait()
            tail = ctlbuf[0]
            close = ctlbuf[1]

            def chunk(c):
                base = (c // 8) * 8
                rp = pltpu.make_async_copy(
                    ring.at[pl.ds(base, 8)], rowbuf, isem.at[1]
                )
                rp.start()
                rp.wait()
                n = jnp.minimum(tail - c, 8 - (c - base))

                def ins(i, _):
                    install(c - base + i)
                    return 0

                jax.lax.fori_loop(0, n, ins, 0)
                return c + n

            consumed = jax.lax.while_loop(
                lambda c: c < tail, chunk, consumed
            )
            return consumed, close

        T = len(self.tenants) if nten else 0
        region = self.tenants.region_rows if nten else 0

        def tpoll(r):
            """The tenant-lane poll: weighted round-robin over the lane
            regions, start lane rotating with the round index. Per lane
            visit it installs at most ``weight`` rows, never more than
            the scheduler's live ``headroom()`` (a full task table turns
            into ring backpressure the host reads off the cursor echo,
            not an OVF_ROWS abort), drops rows the host marked expired
            (counted, a TR_TENANT record), and sweeps paused lanes -
            quarantine/cancel drains their published residue without
            installing. Cursors and cumulative counters live in the
            tctl echo (host-seeded, so they survive entries). Returns
            rows installed this poll. The global ctl acquire DMA
            (close/abort/quiesce words) stays with the caller.

            This scan IS the mesh-tenancy poll too: ``ResidentKernel``
            (tenants=) compiles the same semantics per device against
            its per-device tctl block (plus a quiesce freeze), and
            ``tenants.wrr_poll_reference`` is the shared executable
            spec both are tested against - change one, change all
            three."""
            newly = jnp.int32(0)
            for k in range(T):
                lane = jax.lax.rem(r + k, T)
                tail = tctl_out[lane, TC_TAIL]
                cons = tctl_out[lane, TC_CONSUMED]
                paused = tctl_out[lane, TC_PAUSE] != 0
                avail = tail - cons
                weight = tctl_out[lane, TC_WEIGHT]
                take = jnp.where(
                    paused,
                    0,
                    jnp.minimum(
                        jnp.minimum(weight, avail), core.headroom()
                    ),
                )
                if negr:
                    # Egress credit gate: tokened rows currently parked
                    # or in-flight never exceed park_cap, so a retiring
                    # row ALWAYS has a mailbox slot or a park slot - a
                    # full mailbox is ring backpressure (rows wait on
                    # their lanes, cursors stop advancing), never loss.
                    take = jnp.minimum(
                        take,
                        jnp.maximum(
                            jnp.int32(park_cap)
                            - ectl_out[EC_PARK_COUNT]
                            - ectl_out[EC_INFLIGHT],
                            0,
                        ),
                    )
                target = cons + take

                def chunk(carry, lane=lane, target=target):
                    c, inst, exp = carry
                    base = (c // 8) * 8
                    rp = pltpu.make_async_copy(
                        ring.at[pl.ds(lane * region + base, 8)], rowbuf,
                        isem.at[1],
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
                            install(slot)

                        one = jnp.int32(1)
                        return (
                            inst0 + jnp.where(expired, 0, one),
                            exp0 + jnp.where(expired, one, 0),
                        )

                    inst, exp = jax.lax.fori_loop(0, n, ins, (inst, exp))
                    return c + n, inst, exp

                c, inst, exp = jax.lax.while_loop(
                    lambda cr, target=target: cr[0] < target,
                    chunk,
                    (cons, jnp.int32(0), jnp.int32(0)),
                )
                tctl_out[lane, TC_CONSUMED] = jnp.where(paused, tail, c)
                tctl_out[lane, TC_DROPPED] = (
                    tctl_out[lane, TC_DROPPED]
                    + jnp.where(paused, avail, 0)
                )
                tctl_out[lane, TC_INSTALLED] = (
                    tctl_out[lane, TC_INSTALLED] + inst
                )
                tctl_out[lane, TC_EXPIRED] = (
                    tctl_out[lane, TC_EXPIRED] + exp
                )

                @pl.when((inst > 0) | (exp > 0))
                def _(lane=lane, inst=inst, exp=exp):
                    tr.emit(
                        TR_TENANT, tr.now(), (lane << 16) | inst, exp
                    )

                newly = newly + inst
            return newly

        def lanes_drained():
            d = jnp.bool_(True)
            for i in range(T):
                d = d & (
                    tctl_out[i, TC_CONSUMED] == tctl_out[i, TC_TAIL]
                )
            return d

        ckpt = mk.checkpoint

        def cond(carry):
            r, consumed, done, abr, qr = carry
            return jnp.logical_not(done) & (r < max_rounds)

        def body(carry):
            r, consumed, _, abr, qr = carry
            core.sched(quantum)
            if nten:
                # Tenant lanes: the global ctl acquire DMA still lands
                # every round (abort/close/quiesce words), but rows come
                # off the per-lane regions through the WRR poll; lane
                # cursors live in the tctl echo, not the loop carry.
                cp = pltpu.make_async_copy(ctl_in, ctlbuf, isem.at[0])
                cp.start()
                cp.wait()
                newly = tpoll(r)

                @pl.when(newly > 0)
                def _():
                    tr.emit(TR_INJECT, tr.now(), newly)

            else:
                c0 = consumed
                consumed, close = poll(consumed)

                @pl.when(consumed > c0)
                def _():
                    tr.emit(TR_INJECT, tr.now(), consumed - c0)

            # Host abort word (ctl[3]): re-read by the same acquire DMA as
            # the ring tail, so the abort lands INSIDE the round loop - a
            # running stream stops within one quantum + poll of the write,
            # pending work and unconsumed rows abandoned where they stand.
            aborted = ctlbuf[3] != 0

            @pl.when(aborted & (abr < 0))
            def _():
                tr.emit(TR_ABORT, tr.now(), r)

            abr = jnp.where(aborted & (abr < 0), r, abr)
            # Host quiesce word (ctl[5], checkpoint builds only; same
            # acquire DMA): observed once the cumulative executed count
            # passes ctl[6], the round loop stops popping at this round
            # boundary and exits WITH its state - unlike abort, nothing
            # is abandoned (pending rows, unconsumed ring rows, and the
            # consumed cursor all survive into the exported snapshot).
            if ckpt:
                qz = (ctlbuf[5] != 0) & (counts[C_EXECUTED] >= ctlbuf[6])

                @pl.when(qz & (qr < 0))
                def _():
                    tr.emit(TR_QUIESCE, tr.now(), r)

                qr = jnp.where(qz & (qr < 0), r, qr)
            else:
                qz = jnp.bool_(False)
            # Nothing runnable and nothing new: exit. The host re-enters
            # while the stream is open; a closed, drained stream is final.
            idle = counts[C_PENDING] == 0
            drained = lanes_drained() if nten else (consumed == ctlbuf[0])
            done = (idle & drained) | aborted | qz
            return r + 1, consumed, done, abr, qr

        if nten:
            # Lane cursors + cumulative counters: host-seeded per entry,
            # mutated in place by the WRR poll, echoed back at exit.
            for i in range(T):
                for w in range(8):
                    tctl_out[i, w] = tctl_in[i, w]
        if negr:
            # Mailbox/park/token staging: host-seeded per entry (the
            # tctl pattern - no aliasing), mutated in place by the
            # publish path, echoed back at exit for the host drain.
            for w in range(8):
                ectl_out[w] = ectl_in[w]

            def _cp_egr(i, _):
                for w in range(EGR_WORDS):
                    egr_out[i, w] = egr_in[i, w]
                return 0

            jax.lax.fori_loop(0, depth, _cp_egr, 0)

            def _cp_park(i, _):
                for w in range(EGR_WORDS):
                    park_out[i, w] = park_in[i, w]
                return 0

            jax.lax.fori_loop(0, park_cap, _cp_park, 0)

            def _cp_tok(i, _):
                etok_out[i] = etok_in[i]
                return 0

            jax.lax.fori_loop(0, cap, _cp_tok, 0)

            # Entry-start parked retry: the host consumed between
            # entries, so mailbox room may have opened - move parked
            # rows (FIFO, off EC_PARK_HEAD) into the mailbox while room
            # lasts. flush_parked_reference is the executable spec.
            def _flush(i, _):
                cnt = ectl_out[EC_PARK_COUNT]
                room = depth - (
                    ectl_out[EC_WRITE] - ectl_out[EC_CONSUMED]
                )

                @pl.when((cnt > 0) & (room > 0))
                def _():
                    h = ectl_out[EC_PARK_HEAD]
                    s = jax.lax.rem(ectl_out[EC_WRITE], depth)
                    for w in range(EGR_WORDS):
                        egr_out[s, w] = park_out[h, w]
                    for w in range(EGR_WORDS):
                        park_out[h, w] = jnp.int32(0)
                    ectl_out[EC_PARK_HEAD] = jax.lax.rem(
                        h + 1, park_cap
                    )
                    ectl_out[EC_PARK_COUNT] = cnt - 1
                    ectl_out[EC_WRITE] = ectl_out[EC_WRITE] + 1
                return 0

            jax.lax.fori_loop(0, park_cap, _flush, 0)
        if ntele:
            # Telemetry echo staging (the tctl pattern): host-seeded
            # per entry, mutated by the hooks and the egress fold,
            # echoed back at exit - the block the mid-run poller and
            # the checkpoint cut both read.
            def _cp_tele(i, _):
                for w in range(LAT_BUCKETS):
                    tele_out[i, w] = tele_in[i, w]
                return 0

            jax.lax.fori_loop(0, 1 + T, _cp_tele, 0)

            def _cp_tlat(i, _):
                for w in range(LAT_WORDS):
                    tlat_out[i, w] = tlat_in[i, w]
                return 0

            jax.lax.fori_loop(0, cap, _cp_tlat, 0)
        # Initial ctl fetch: the consumed cursor (slot 2) persists across
        # entries through the host-echoed ctl.
        cp0 = pltpu.make_async_copy(ctl_in, ctlbuf, isem.at[0])
        cp0.start()
        cp0.wait()
        _, consumed, _, abr, qr = jax.lax.while_loop(
            cond, body, (jnp.int32(0), ctlbuf[2], jnp.bool_(False),
                         jnp.int32(-1), jnp.int32(-1))
        )
        # Report progress: consumed count rides the aliased ctl output
        # (slot 2); tail/close/abort echo through; slot 4 reports the round
        # the abort word was first observed, slot 5 the round the quiesce
        # word was (-1: never).
        ctl_out[0] = ctlbuf[0]
        ctl_out[1] = ctlbuf[1]
        ctl_out[2] = consumed
        ctl_out[3] = ctlbuf[3]
        ctl_out[4] = abr
        ctl_out[5] = qr if ckpt else 0
        for i in range(6, 8):
            ctl_out[i] = 0
        if ckpt:
            @pl.when(qr >= 0)
            def _():
                tr.emit(
                    TR_CKPT, tr.now(), counts[C_PENDING],
                    ctlbuf[0] - consumed,
                )

    def _build(self, quantum: int, max_rounds: int):
        mk = self.mk
        if not mk.interpret:
            # The stream's own SMEM blocks ride beside the scheduler's:
            # refuse a compiled build that cannot fit the chip here, by
            # name, not inside XLA.
            mk.check_smem(_target_row(), extra=self._smem_extra)
        ndata = len(mk.data_specs)
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        anyspace = functools.partial(pl.BlockSpec, memory_space=pl.ANY)
        # ring AND ctl live in ANY (HBM): the kernel re-reads them by DMA
        # on every poll - the consumer side of the pinned-host production
        # path - instead of snapshotting them into SMEM at entry. The
        # tenant tctl block (host-published per entry, tiny) rides SMEM;
        # a tenants=None build compiles none of it.
        nten = 1 if self.tenants is not None else 0
        negr = 1 if (nten and self._egress is not None) else 0
        ntele = 1 if self.telemetry else 0
        depth = self._egress.depth if negr else 0
        T = len(self.tenants) if nten else 0
        in_specs = (
            [smem()] * 5 + [anyspace(), anyspace()] + [anyspace()] * ndata
            + [smem()] * nten + [smem()] * (4 * negr)
            + [smem()] * (2 * ntele)
        )
        data_shapes = [
            jax.ShapeDtypeStruct(s.shape, s.dtype)
            for s in mk.data_specs.values()
        ]
        ntrace = 1 if mk.trace is not None else 0
        out_shape = tuple(
            [
                jax.ShapeDtypeStruct((mk.capacity, DESC_WORDS), jnp.int32),
                jax.ShapeDtypeStruct((mk.capacity,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((mk.num_values,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),  # ctl out
            ]
            + data_shapes
            + ([mk.trace.out_shape()] if ntrace else [])
            + ([jax.ShapeDtypeStruct((T, 8), jnp.int32)] if nten else [])
            + ([
                # mailbox ring, park ring, ectl cursor block, per-row
                # token table - host-seeded, echoed (the tctl pattern).
                jax.ShapeDtypeStruct((depth, EGR_WORDS), jnp.int32),
                jax.ShapeDtypeStruct((depth, EGR_WORDS), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((mk.capacity,), jnp.int32),
            ] if negr else [])
            + ([
                # Telemetry: gauge row + per-tenant histograms, and the
                # per-row lifecycle stamp table - host-seeded, echoed
                # (the tctl pattern; device/telemetry.py).
                jax.ShapeDtypeStruct((1 + T, LAT_BUCKETS), jnp.int32),
                jax.ShapeDtypeStruct((mk.capacity, LAT_WORDS), jnp.int32),
            ] if ntele else [])
        )
        out_specs = tuple(
            [smem()] * 4 + [smem()] + [anyspace()] * ndata
            + [smem()] * ntrace + [smem()] * nten + [smem()] * (4 * negr)
            + [smem()] * (2 * ntele)
        )
        aliases = {0: 0, 2: 1, 3: 2, 4: 3}
        for i in range(ndata):
            aliases[7 + i] = 5 + i
        return jax.jit(pl.pallas_call(
            functools.partial(self._kernel, quantum, max_rounds, mk.trace),
            out_shape=out_shape,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=list(mk.scratch_specs.values())
            # free, vfree: the stream kernel embeds the core without the
            # batched tier (_make_core refuses a batch-routed mk).
            + mk.core_scratch()[:2]
            + [
                pltpu.SMEM((8,), jnp.int32),  # ctl staging
                pltpu.SMEM((8, RING_ROW), jnp.int32),  # row staging (8-row chunks)
                pltpu.SemaphoreType.DMA((2,)),
            ],
            input_output_aliases=aliases,
            interpret=mk.interpret,
        ))

    # ---- the stream driver ----

    def run_stream(
        self,
        builder: Optional[TaskGraphBuilder] = None,
        ivalues: Optional[np.ndarray] = None,
        data: Optional[Dict[str, Any]] = None,
        quantum: int = 1 << 10,
        max_rounds: int = 64,
        poll_interval_s: float = 0.001,
        deadline_s: Optional[float] = None,
        cancel_scope=None,
        resume_state: Optional[Dict[str, Any]] = None,
    ) -> Tuple[np.ndarray, dict]:
        """Run the stream to completion: entries re-enter the resident
        scheduler while the host (any thread) injects; returns after
        close() once everything drained. Returns (ivalues, info).

        Resilience: ``deadline_s`` bounds the whole stream - past it the
        ring is closed and a structured ``StallError`` raises instead of
        re-entering forever (e.g. a producer that never calls close()).
        ``abort()`` from any thread stops the stream mid-quantum via the
        ctl abort word (see ``abort``) and raises ``CancelledError``;
        ``cancel_scope`` ties the stream to a host finish scope - the
        scope cancelling (e.g. root-finish cancellation, the watchdog's
        last rung) aborts the stream the same way, through a registered
        abort hook, so a device stream never outlives its cancelled scope.
        ANY exception escaping this driver closes the ring, so concurrent
        producers fail fast on their next inject() instead of queueing
        rows nobody will ever drain.

        Checkpoint/restore (``mk`` built with ``checkpoint=True``):
        ``quiesce()`` from any thread stops the stream at its next round
        boundary WITH its state - run_stream returns (ivalues, info) where
        ``info['quiesced']=True`` and ``info['state']`` is the resumable
        snapshot (tables, values, unconsumed ring rows). A later
        ``run_stream(resume_state=...)`` - on this object or a freshly
        built equivalent one - re-publishes the residue and continues the
        stream mid-graph (``builder`` and ``resume_state`` are mutually
        exclusive)."""
        if (builder is None) == (resume_state is None):
            raise ValueError(
                "run_stream wants exactly one of builder= (a fresh "
                "stream) or resume_state= (a checkpointed one)"
            )
        if resume_state is not None and (
            data is not None or ivalues is not None
        ):
            raise ValueError(
                "resume_state= carries its own data/ivalues; passing "
                "them too would be silently ignored"
            )
        unregister = None
        if cancel_scope is not None:
            # Register-then-replay (the one implementation, in
            # runtime/resilience.py): a cancel() racing this registration
            # still aborts the stream.
            unregister = resilience.bind_abort_to_scope(
                self.abort, cancel_scope
            )
        try:
            return self._run_stream(
                builder, ivalues, data, quantum, max_rounds,
                poll_interval_s, deadline_s, resume_state,
            )
        except BaseException:
            with self._lock:
                self._closed = True
            raise
        finally:
            if unregister is not None:
                unregister()

    @staticmethod
    def _drain_egress(table, egr, park, ectl, spans=None) -> int:
        """Consume the completion mailbox AND the park ring at an entry
        boundary (this driver IS the poller), resolving each row's
        future exactly once. Mutates the arrays in place: consumed
        mailbox slots re-zero and EC_CONSUMED catches up to EC_WRITE;
        parked rows resolve directly (they never occupied a mailbox
        slot) and the park ring empties. Draining both regions here is
        what makes a full mailbox unable to wedge quiesce or the
        drained exit. ``spans`` (telemetry builds): a dict collecting
        ``token -> (admit, install, fire)`` absolute rounds decoded off
        the EGR span words. Returns rows consumed."""
        futures = table.futures

        def _one(row):
            if spans is not None:
                spans[int(row[EGR_TOKEN])] = unpack_spans(
                    row[EGR_T_ADMIT], row[EGR_T_SPANS]
                )[:3]
            futures.resolve(int(row[EGR_TOKEN]), int(row[EGR_VALUE]))
            row[:] = 0

        depth = egr.shape[0]
        n = 0
        consumed = int(ectl[EC_CONSUMED])
        while consumed < int(ectl[EC_WRITE]):
            row = egr[consumed % depth]
            if int(row[EGR_STATUS]) != EGR_OK:
                raise EgressProtocolError(
                    f"mailbox slot {consumed % depth} consumed twice or "
                    f"never published (status {int(row[EGR_STATUS])})"
                )
            _one(row)
            consumed += 1
            n += 1
        ectl[EC_CONSUMED] = consumed
        head, cnt = int(ectl[EC_PARK_HEAD]), int(ectl[EC_PARK_COUNT])
        cap = park.shape[0]
        for k in range(cnt):
            row = park[(head + k) % cap]
            if int(row[EGR_STATUS]) != EGR_OK:
                raise EgressProtocolError(
                    f"park slot {(head + k) % cap} empty but counted "
                    f"(status {int(row[EGR_STATUS])})"
                )
            _one(row)
            n += 1
        ectl[EC_PARK_HEAD] = 0
        ectl[EC_PARK_COUNT] = 0
        return n

    # ---- live telemetry (ISSUE 19) ----

    def telemetry_snapshot(self) -> Optional[Dict[str, Any]]:
        """Thread-safe copy of the LAST entry's echoed telemetry block
        (None before the first telemetry entry completes): ``seq``
        (monotone snapshot counter), ``tele`` (the (1+T, LAT_BUCKETS)
        gauge+histogram block), ``rounds``/``entries`` (cumulative),
        and ``ns_per_round`` (rounds->wall conversion from the entry
        epoch brackets; None until a bracket with round progress
        lands). This is the :class:`~..device.telemetry.TelemetryPoller`
        source - call it from any thread while the stream runs."""
        with self._lock:
            if self._tele_snapshot is None:
                return None
            snap = dict(self._tele_snapshot)
        snap["tele"] = np.array(snap["tele"])
        return snap

    def telemetry_spans(self) -> Dict[int, Tuple[int, int, int]]:
        """``token -> (admit, install, fire)`` absolute rounds for every
        retirement drained so far (telemetry builds; retire == fire).
        tools/timeline.py joins these with Future submit/done wall
        stamps into Perfetto flow events."""
        with self._lock:
            return dict(self._spans)

    @staticmethod
    def _adopt_etok(table, etok, tasks) -> None:
        """Re-adopt installed-but-unretired submit tokens off a resumed
        snapshot's etok table: each packed word (token | tenant << 24)
        re-enters the futures ledger so the resumed stream's
        retirements resolve - and preempted clients reattach - instead
        of raising on an unknown token."""
        tasks = np.asarray(tasks)
        for idx in np.flatnonzero(etok):
            packed = int(etok[idx])
            table.futures.adopt_row_token(
                packed % TOKEN_LIMIT,
                table.ids[packed // TOKEN_LIMIT],
                int(tasks[idx, F_FN]),
                int(tasks[idx, F_OUT]),
            )

    def _run_stream(
        self, builder, ivalues, data, quantum, max_rounds,
        poll_interval_s, deadline_s, resume_state=None,
    ) -> Tuple[np.ndarray, dict]:
        deadline = (
            None if deadline_s is None else time.monotonic() + deadline_s
        )
        mk = self.mk
        table = self.tenants
        ring = np.zeros((self.ring_capacity, RING_ROW), np.int32)
        ctl = np.zeros(8, np.int32)  # [tail, close, consumed, abort, ...]
        egspec = self._egress if table is not None else None
        if egspec is not None:
            # Completion-mailbox host halves: mailbox + park rings,
            # cursor block, per-task-row token table. Host-seeded every
            # entry, mutated by the kernel's publish path, drained (and
            # futures resolved) right after every entry - so quiesce and
            # the drained exit always run against an EMPTY mailbox and
            # an empty park ring: a slow poller cannot wedge either.
            depth = egspec.depth
            egr_np = np.zeros((depth, EGR_WORDS), np.int32)
            park_np = np.zeros((depth, EGR_WORDS), np.int32)
            ectl_np = np.zeros(8, np.int32)
            etok_np = np.zeros(mk.capacity, np.int32)
        if self.telemetry:
            # Telemetry host halves: the gauge+histogram block and the
            # per-row stamp table (host-seeded every entry, mutated by
            # the kernel, snapshotted after) plus the epoch bracket
            # that converts cumulative rounds to wall time.
            tele_np = np.zeros((1 + len(table), LAT_BUCKETS), np.int32)
            tlat_np = np.zeros((mk.capacity, LAT_WORDS), np.int32)
            bracket = EpochBracket()
            prev_rounds = 0
            if resume_state is None:
                with self._lock:
                    self._spans = {}
                    self._tele_snapshot = None
                    self._tele_seq = 0
        injected = 0
        if resume_state is not None:
            # Same-object resume must behave like a fresh stream: clear
            # the quiesce request and undo the QUIESCE-induced close (the
            # snapshot already captured everything producers queued). An
            # explicit close()/abort() stays sticky - drain-and-exit
            # semantics survive the resume.
            with self._lock:
                self._quiesce_after = None
                self._quiesce_t = None
                self._stats["resumes"] += 1
                if self._closed_by_quiesce:
                    self._closed = False
                    self._closed_by_quiesce = False
            st = resume_state
            succ = np.asarray(st["succ"])
            state = [
                np.asarray(st["tasks"]), np.asarray(st["ready"]),
                np.asarray(st["counts"]), np.asarray(st["ivalues"]),
            ]
            data = dict(st.get("data") or {})
            # Residue: rows published-but-unconsumed at quiesce (plus any
            # host-queued rows the snapshot captured) re-publish from ring
            # slot 0 with a reset consumed cursor - installed rows already
            # live in the task table. Tenant-tagged residue instead
            # re-enters its lanes' host backlogs (counters restored from
            # the snapshot's tctl/tstats blocks) and the next pump
            # re-publishes it per region - per-tenant counts conserved.
            if table is not None:
                table.resume_from(st)
                if egspec is not None:
                    et = np.asarray(
                        st.get("etok", np.zeros(mk.capacity, np.int32)),
                        np.int32,
                    ).reshape(-1)
                    if et.shape[0] != mk.capacity:
                        raise ValueError(
                            f"resume etok table has {et.shape[0]} rows; "
                            f"this kernel's task table has {mk.capacity}"
                        )
                    etok_np = et.copy()
                    self._adopt_etok(table, etok_np, state[0])
                    # The cut exported no ectl block (the mailbox and
                    # park ring drained before export) - but the
                    # adopted tokens ARE in flight, and the install
                    # credit gate reads EC_INFLIGHT. Seeding it zero
                    # would let each adopted retirement drive it
                    # negative, inflating the gate until the park ring
                    # overwraps its counted rows.
                    ectl_np[EC_INFLIGHT] = int(np.count_nonzero(etok_np))
                if self.telemetry:
                    # The telemetry block rides the cut: the round
                    # gauge is cumulative, so resumed rows' measured
                    # latencies span the preemption. Absent keys mean
                    # the snapshot came from a telemetry-off stream -
                    # start the plane fresh from zero.
                    for name, cur in (
                        ("tele", tele_np), ("tlat", tlat_np),
                    ):
                        blk = st.get(name)
                        if blk is None:
                            continue
                        blk = np.asarray(blk, np.int32)
                        if blk.shape != cur.shape:
                            raise ValueError(
                                f"resume {name} block has shape "
                                f"{blk.shape}; this stream expects "
                                f"{cur.shape}"
                            )
                        cur[:] = blk
                    prev_rounds = int(tele_np[0, TG_ROUNDS])
            elif "tctl" in st or "tstats" in st:
                # The mirror of TenantTable.resume_from's guard: a
                # tenant-tagged snapshot resumed on a plain stream would
                # silently strip every row's tenant identity (and its
                # counters) instead of conserving them.
                raise ValueError(
                    "resume state carries per-tenant lane blocks "
                    "(tctl/tstats): it was exported from a tenant-enabled "
                    "stream and cannot resume on a plain one"
                )
            else:
                residue = np.asarray(
                    st.get("ring_rows",
                           np.zeros((0, RING_ROW), np.int32))
                ).reshape(-1, RING_ROW)
                if len(residue) > self.ring_capacity:
                    raise ValueError(
                        f"resume residue ({len(residue)} rows) exceeds "
                        f"this stream's ring_capacity "
                        f"{self.ring_capacity}"
                    )
                ring[: len(residue)] = residue
                injected = len(residue)
        else:
            tasks, succ, ring0, counts = builder.finalize(
                capacity=mk.capacity, succ_capacity=mk.succ_capacity
            )
            if ivalues is None:
                ivalues = np.zeros(mk.num_values, np.int32)
            else:
                counts = counts.copy()
                mk.widen_value_alloc(counts, ivalues)
            mk.check_row_values(int(counts[C_VALLOC]))
            data = dict(data or {})
            state = [tasks, ring0, counts, ivalues]
        if set(data.keys()) != set(mk.data_specs.keys()):
            raise ValueError("data buffers != declared data_specs")
        key = (quantum, max_rounds)
        if key not in self._jitted:
            from ..runtime.progcache import shared_build

            # Only facts the stream compiles into the program key the
            # variant: tenant count / region rows / egress ring depth.
            # WRR weights and rate limits ride tctl at runtime.
            variant = (
                "stream", self.ring_capacity,
                None if self.tenants is None
                else (len(self.tenants), self.tenants.region_rows),
                None if self._egress is None else self._egress.depth,
                bool(self.telemetry),
            ) + key
            self._jitted[key], self._pc_stats = shared_build(
                mk, variant, lambda: self._build(quantum, max_rounds),
            )
        jitted = self._jitted[key]

        data_np = [np.asarray(data[k]) for k in mk.data_specs.keys()]
        ndata = len(mk.data_specs)
        # Flight recorder: each entry resets the ring, so the LAST entry's
        # records surface in info - bracketed by THAT entry's own epoch
        # (a whole-stream bracket would stretch the final entry's rounds
        # across every earlier entry's wall time in the Perfetto view).
        trace_row = None
        entry_t0_ns = entry_t1_ns = time.monotonic_ns()
        while True:
            # Publish queued rows: rows first, then tail (release order;
            # both are uploaded with the next entry's arguments).
            with self._lock:
                rows, self._pending_rows = self._pending_rows, []
                closed = self._closed
                abort_reason = self._abort_reason
                quiesce_after = self._quiesce_after
            if abort_reason is not None:
                # Publish the ctl abort word and run ONE final entry: the
                # kernel polls the word inside its round loop and exits
                # within one quantum's worth of inner iterations, pending
                # work abandoned where it stands and queued rows dropped.
                # Then surface latency and raise. Tenant lanes get a
                # frozen all-paused tctl: nothing publishes, nothing
                # installs, remaining rows abandoned like the plain path.
                e0 = int(state[2][C_EXECUTED])
                ctl[0] = injected
                ctl[1] = 1
                ctl[3] = 1
                extra = []
                if table is not None:
                    frozen = np.zeros((len(table), 8), np.int32)
                    frozen[:, TC_PAUSE] = 1
                    extra = [jnp.asarray(frozen)]
                if egspec is not None:
                    extra += [
                        jnp.asarray(egr_np), jnp.asarray(park_np),
                        jnp.asarray(ectl_np), jnp.asarray(etok_np),
                    ]
                if self.telemetry:
                    extra += [
                        jnp.asarray(tele_np), jnp.asarray(tlat_np),
                    ]
                outs = jitted(
                    jnp.asarray(state[0]), jnp.asarray(succ),
                    jnp.asarray(state[1]), jnp.asarray(state[2]),
                    jnp.asarray(state[3]), jnp.asarray(ring),
                    jnp.asarray(ctl), *[jnp.asarray(d) for d in data_np],
                    *extra,
                )
                counts_ab = np.asarray(outs[2])
                ctl_ab = np.asarray(outs[4])
                if egspec is not None:
                    # Degradation ladder, abort rung: results that made
                    # it into the mailbox/park before the stop still
                    # resolve RESULT; every other outstanding future
                    # poisons - clients get a typed terminal state, not
                    # a hang.
                    nt_ab = 1 if mk.trace is not None else 0
                    base = 6 + len(mk.data_specs) + nt_ab
                    egr_np, park_np, ectl_np, etok_np = (
                        np.array(outs[base + i]) for i in range(4)
                    )
                    sp = {} if self.telemetry else None
                    self._drain_egress(
                        table, egr_np, park_np, ectl_np, spans=sp
                    )
                    if self.telemetry:
                        tele_np = np.array(outs[base + 4])
                        tlat_np = np.array(outs[base + 5])
                        with self._lock:
                            self._spans.update(sp)
                    table.futures.poison_all(
                        f"stream aborted: {abort_reason}"
                    )
                with self._lock:
                    t0 = self._abort_t
                    self._stats.update({
                        "aborts": self._stats["aborts"] + 1,
                        "abort_reason": abort_reason,
                        "abort_observed_round": int(ctl_ab[4]),
                        "abort_latency_s": (
                            None if t0 is None
                            else round(time.monotonic() - t0, 6)
                        ),
                        "abort_drain_executed": (
                            int(counts_ab[C_EXECUTED]) - e0
                        ),
                    })
                raise CancelledError(f"stream aborted: {abort_reason}")
            if deadline is not None and time.monotonic() >= deadline:
                raise StallError(
                    f"run_stream deadline of {deadline_s}s exceeded "
                    f"(injected={injected}, closed={closed})",
                    stats=self.stats_dict(),
                )
            for row in rows:
                if injected >= self.ring_capacity:
                    raise RuntimeError(
                        f"injection ring exhausted ({self.ring_capacity} "
                        "rows per stream)"
                    )
                ring[injected] = row
                injected += 1
            if table is not None:
                # Tenant lanes: the pump expires/publishes the host
                # backlogs into the per-lane ring regions and builds the
                # tctl block this entry uploads; the plain tail is unused.
                if self.telemetry:
                    # Admit-round feedback: rows published by THIS pump
                    # are stamped with the round gauge the last entry
                    # echoed - ring-wait time is inside the measured
                    # admission->retire span.
                    table.set_admit_round(int(tele_np[0, TG_ROUNDS]))
                tctl_np = table.pump(ring)
                injected = table.total_published()
                ctl[0] = 0
            ctl[1] = 1 if closed else 0
            if quiesce_after is not None:
                # Publish the quiesce word + threshold: the kernel
                # observes it inside its round loop once the executed
                # count passes the threshold and exits with its state.
                ctl[5] = 1
                ctl[6] = quiesce_after
            if table is None:
                ctl[0] = injected
            entry_t0_ns = time.monotonic_ns()
            outs = jitted(
                jnp.asarray(state[0]), jnp.asarray(succ),
                jnp.asarray(state[1]), jnp.asarray(state[2]),
                jnp.asarray(state[3]), jnp.asarray(ring),
                jnp.asarray(ctl), *[jnp.asarray(d) for d in data_np],
                *([jnp.asarray(tctl_np)] if table is not None else []),
                *([
                    jnp.asarray(egr_np), jnp.asarray(park_np),
                    jnp.asarray(ectl_np), jnp.asarray(etok_np),
                ] if egspec is not None else []),
                *([
                    jnp.asarray(tele_np), jnp.asarray(tlat_np),
                ] if self.telemetry else []),
            )
            state = [np.asarray(o) for o in outs[:4]]
            ctl_o = np.asarray(outs[4])
            data_np = [np.asarray(o) for o in outs[5 : 5 + ndata]]
            ntrace = 1 if mk.trace is not None else 0
            if mk.trace is not None:
                trace_row = np.asarray(outs[5 + ndata])
                entry_t1_ns = time.monotonic_ns()
            if table is not None:
                # Fold the lane-cursor echo back: consume cursors advance
                # (freeing in-flight budget), cumulative install/expire/
                # sweep counters refresh, admission latencies record.
                table.absorb(np.asarray(outs[5 + ndata + ntrace]))
            if egspec is not None:
                # Drain the mailbox AND the park ring at the entry
                # boundary, resolving futures - both always empty when
                # the loop reaches the quiesce/drained-exit checks
                # below, so a full mailbox can never wedge either.
                base = 6 + ndata + ntrace
                egr_np, park_np, ectl_np, etok_np = (
                    np.array(outs[base + i]) for i in range(4)
                )
                sp = {} if self.telemetry else None
                self._drain_egress(
                    table, egr_np, park_np, ectl_np, spans=sp
                )
                if sp:
                    with self._lock:
                        self._spans.update(sp)
            if self.telemetry:
                # Absorb the echoed histogram/gauge + stamp blocks and
                # publish a coherent snapshot for mid-run scrapers. The
                # epoch bracket pairs this entry's host wall clock with
                # the round-gauge delta so rounds convert to ns without
                # any on-device clock.
                tbase = 10 + ndata + ntrace
                tele_np = np.array(outs[tbase])
                tlat_np = np.array(outs[tbase + 1])
                tele_np[0, TG_ENTRIES] += 1
                t1_ns = time.monotonic_ns()
                rounds = int(tele_np[0, TG_ROUNDS])
                bracket.accumulate(
                    entry_t0_ns, t1_ns, rounds - prev_rounds
                )
                prev_rounds = rounds
                with self._lock:
                    self._tele_seq += 1
                    self._tele_snapshot = {
                        "seq": self._tele_seq,
                        "tele": tele_np.copy(),
                        "rounds": rounds,
                        "entries": int(tele_np[0, TG_ENTRIES]),
                        "ns_per_round": bracket.ns_per_round(),
                        "t0_ns": entry_t0_ns,
                        "t1_ns": t1_ns,
                    }
            counts_np = state[2]
            ctl[2] = ctl_o[2]  # device-consumed cursor persists
            if bool(counts_np[C_OVERFLOW]):
                raise RuntimeError("streaming megakernel overflow")
            observed_round = int(ctl_o[5]) if quiesce_after is not None else -1
            # A threshold the workload never reaches must not spin this
            # loop forever: once the stream is fully drained, the entry
            # boundary IS a round boundary - export host-side (observed
            # round -1) instead of waiting on a quiesce the kernel can
            # never observe.
            drained_cut = (
                quiesce_after is not None
                and int(counts_np[C_PENDING]) == 0
                and (
                    table.drained() if table is not None
                    else int(ctl_o[2]) == injected
                )
            )
            if observed_round >= 0 or drained_cut:
                # The quiesce point: export the live stream state and
                # stop. The ring closes (preemption semantics:
                # checkpoint, then stop) so concurrent producers fail
                # fast; rows they queued before the close ride along as
                # unpublished residue.
                consumed = int(ctl_o[2])
                with self._lock:
                    late, self._pending_rows = self._pending_rows, []
                    if not self._closed:
                        self._closed = True
                        self._closed_by_quiesce = True
                    t0 = self._quiesce_t
                    self._stats["quiesces"] += 1
                    self._stats["last_quiesce_latency_s"] = (
                        None if t0 is None
                        else round(time.monotonic() - t0, 6)
                    )
                info = {
                    "executed": int(counts_np[C_EXECUTED]),
                    "pending": int(counts_np[C_PENDING]),
                    "injected": injected,
                    "quiesced": True,
                    "quiesce_observed_round": observed_round,
                    "quiesce_latency_s": (
                        None if t0 is None
                        else round(time.monotonic() - t0, 6)
                    ),
                    "state": {
                        "tasks": state[0],
                        "succ": np.asarray(succ),
                        "ready": state[1],
                        "counts": state[2],
                        "ivalues": state[3],
                        "data": dict(zip(mk.data_specs.keys(), data_np)),
                    },
                }
                if self._pc_stats is not None:
                    info["program_cache"] = dict(self._pc_stats)
                if table is not None:
                    # Per-tenant residue (tenant-tagged rows) + the
                    # cumulative tctl/tstats counter blocks: resume_from
                    # re-seeds the lanes so per-tenant accepted/installed/
                    # expired counts are conserved exactly across the cut.
                    # (inject() on a tenant stream routes through
                    # submit(), so _pending_rows holds no untagged rows.)
                    assert not late, "tenant stream held untagged rows"
                    if egspec is not None:
                        # Installed-but-unretired tokens ride the cut
                        # (mailbox/park already drained above); their
                        # futures go PREEMPTED inside export_state and
                        # reattach via resume tokens after resume_from
                        # re-adopts this table.
                        info["state"]["etok"] = etok_np.copy()
                    if self.telemetry:
                        # Histogram/gauge + stamp blocks ride the cut so
                        # the resumed stream's round gauge and per-tenant
                        # latency totals stay cumulative across it.
                        info["state"]["tele"] = tele_np.copy()
                        info["state"]["tlat"] = tlat_np.copy()
                    info["state"].update(table.export_state(ring))
                else:
                    residue = (
                        list(ring[consumed:injected]) + list(late)
                    )
                    info["state"]["ring_rows"] = np.asarray(
                        residue, np.int32
                    ).reshape(-1, RING_ROW)
                if mk.trace is not None and trace_row is not None:
                    info["trace"] = trace_info(
                        [trace_row], entry_t0_ns, entry_t1_ns,
                        mk.trace.capacity,
                    )
                return state[3], info
            if (
                closed
                and int(counts_np[C_PENDING]) == 0
                and (
                    # Atomically drained-check AND close the front door:
                    # a submit racing this exit gets "closed", never an
                    # ACCEPTED row the returned stream will not run.
                    table.close_if_drained() if table is not None
                    else int(ctl_o[2]) == injected
                )
                and not self._pending_rows
            ):
                info = {
                    "executed": int(counts_np[C_EXECUTED]),
                    "pending": int(counts_np[C_PENDING]),
                    "injected": (
                        table.total_published() if table is not None
                        else injected
                    ),
                    **ran_on(outs[2], mk.interpret),
                }
                if self._pc_stats is not None:
                    info["program_cache"] = dict(self._pc_stats)
                if table is not None:
                    info["tenants"] = table.stats()
                if self.telemetry:
                    info["telemetry"] = {
                        "tele": tele_np.copy(),
                        "ns_per_round": bracket.ns_per_round(),
                        "rounds": int(tele_np[0, TG_ROUNDS]),
                    }
                if mk.trace is not None and trace_row is not None:
                    info["trace"] = trace_info(
                        [trace_row], entry_t0_ns, entry_t1_ns,
                        mk.trace.capacity,
                    )
                return state[3], info
            time.sleep(poll_interval_s)
