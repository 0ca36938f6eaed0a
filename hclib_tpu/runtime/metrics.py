"""Unified telemetry: one registry over every observability source.

The runtime grew five independent sources - ``Runtime.stats_dict()``
(worker counters + resilience retry/quarantine), the megakernel's
``info['tiers']`` dispatch counters, the resident mesh's
``info['fault_stats']``, the device flight recorder
(``info['trace']``, device/tracebuf.py), and ad-hoc run infos - each with
its own shape and no common export. ``MetricsRegistry`` folds them into
ONE snapshot/delta API with JSON and Prometheus-text export, so a
dashboard, the watchdog's stats-dump rung, or a bench artifact all read
the same numbers the same way.

Model:

- ``register(name, source)`` attaches a LIVE source (a zero-arg callable
  returning a mapping, e.g. ``rt.stats_dict``) polled at snapshot time.
- ``record(name, mapping)`` stores a STATIC snapshot (e.g. a device
  run's ``info``); the latest record under a name wins.
- ``add_run_info(name, info)`` is the device-run convenience: it keeps
  the numeric core of an info dict and summarizes ``fault_stats`` and
  the trace ring (per-tag record counts) instead of carrying raw rows.
- ``snapshot()`` flattens everything to ``{dotted.key: number}`` plus a
  timestamp; ``delta(a, b)`` subtracts two snapshots key-wise (counters
  become rates when divided by the timestamp delta).
- ``to_json()`` / ``to_prometheus()`` render a snapshot; the Prometheus
  form sanitizes keys into ``<namespace>_<key>`` gauges.
- ``watch(name, source)`` (ISSUE 19) is the live-refresh face: a
  daemon thread polls the source every interval and records the latest
  mapping, so a scrape endpoint (tools/metrics_serve.py) serves fresh
  numbers without snapshotting on the request path - and the last
  value survives the source going away. ``record_latency(block)``
  stores a scraped ``TelemetryBlock`` whose per-tenant histograms
  export in the native Prometheus histogram form
  (``hclib_latency_bucket{tenant=...,le=...}``, cumulative, ``+Inf``
  capped, plus ``hclib_latency_count``; ``le`` is in scheduler rounds,
  with ``hclib_latency_ns_per_round`` alongside for conversion).

Enable runtime-side via ``Runtime(metrics=True)`` or
``HCLIB_TPU_METRICS=1``: the runtime registers its own ``stats_dict``
and the watchdog's stats-dump rung (strike 2) logs the registry snapshot
alongside ``format_stats()``, so a stalled run's post-mortem carries
device counters too when the program recorded them.
"""

from __future__ import annotations

import json
import numbers
import threading
import time
from typing import Any, Callable, Dict, Mapping, Optional

__all__ = ["MetricsRegistry", "CHECKPOINT_EVENTS", "LATENCY_FAMILY"]

# The latency-histogram family name is a dashboard ABI (ISSUE 19's
# acceptance scrapes it literally), pinned independently of the
# registry namespace.
LATENCY_FAMILY = "hclib_latency"

# The canonical durable-store event series (runtime/checkpoint.py's
# BundleStore records one ``record_event`` per store action, so each
# exports as ``<name>.count`` plus ``<name>.last.*``): saves published,
# generations validated+loaded, restores that fell back past a bad
# generation, and generations quarantined. Dashboards alert on
# ``checkpoint.quarantined.count`` rising - a quarantine is never
# silent - and rate() the save/load pair for store traffic.
CHECKPOINT_EVENTS = (
    "checkpoint.save",
    "checkpoint.load",
    "checkpoint.fallback",
    "checkpoint.quarantined",
)


def _is_num(v: Any) -> bool:
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


def _flatten(prefix: str, obj: Any, out: Dict[str, float]) -> None:
    """Numeric leaves only: strings/None are dropped (Prometheus carries
    numbers; string context belongs in the JSON info files next to it),
    bools coerce to 0/1, lists index as ``.<i>``."""
    if isinstance(obj, Mapping):
        for k, v in obj.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            _flatten(key, v, out)
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _flatten(f"{prefix}.{i}", v, out)
    elif isinstance(obj, bool):
        out[prefix] = 1.0 if obj else 0.0
    elif _is_num(obj):
        out[prefix] = float(obj)
    # numpy scalars quack like numbers.Real; arrays do not - summarize
    # them before recording (add_run_info does for the known shapes).


class MetricsRegistry:
    """Aggregates live sources and recorded run infos into flat numeric
    snapshots with JSON / Prometheus export. Thread-safe: the watchdog
    thread snapshots while workers record."""

    def __init__(self, namespace: str = "hclib_tpu") -> None:
        self.namespace = namespace
        self._lock = threading.Lock()
        self._sources: Dict[str, Callable[[], Mapping]] = {}
        self._records: Dict[str, Mapping] = {}
        self._watches: Dict[str, threading.Event] = {}
        self._latency = None  # (TelemetryBlock, {index: label})

    # -- wiring --

    def register(self, name: str, source: Callable[[], Mapping]) -> None:
        """Attach a live source polled at every snapshot."""
        if not callable(source):
            raise TypeError(f"source {name!r} must be callable")
        with self._lock:
            self._sources[name] = source

    def unregister(self, name: str) -> None:
        with self._lock:
            self._sources.pop(name, None)

    def record(self, name: str, mapping: Mapping) -> None:
        """Store a static snapshot under ``name`` (latest wins)."""
        with self._lock:
            self._records[name] = dict(mapping)

    def watch(
        self,
        name: str,
        source: Callable[[], Optional[Mapping]],
        interval_s: Optional[float] = None,
        on_update: Optional[Callable[[Mapping], None]] = None,
    ) -> None:
        """Live refresh (ISSUE 19): poll ``source`` on a daemon thread
        every ``interval_s`` (default HCLIB_TPU_TELEMETRY_POLL_S) and
        ``record`` the latest mapping under ``name`` - scrapes then
        read fresh values off the record table without touching the
        source on the request path, and the last value outlives the
        source. ``None`` returns skip (a stream before its first
        entry); a raising source records ``<name>.error = 1`` once and
        keeps polling. ``unwatch(name)`` stops the thread; re-watching
        a name replaces the old watch."""
        if interval_s is None:
            from .env import env_float

            interval_s = env_float("HCLIB_TPU_TELEMETRY_POLL_S", 0.05)
        interval_s = float(interval_s)
        if interval_s <= 0:
            raise ValueError(
                f"watch interval must be > 0 seconds, got {interval_s}"
            )
        stop = threading.Event()
        with self._lock:
            old = self._watches.pop(name, None)
            self._watches[name] = stop
        if old is not None:
            old.set()

        def _loop() -> None:
            while not stop.is_set():
                try:
                    m = source()
                except Exception:
                    m = {"error": 1}
                if m is not None:
                    self.record(name, m)
                    if on_update is not None:
                        on_update(m)
                stop.wait(interval_s)

        threading.Thread(
            target=_loop, name=f"hclib-metrics-watch-{name}", daemon=True
        ).start()

    def unwatch(self, name: str) -> None:
        """Stop a ``watch`` thread; its last recorded value remains."""
        with self._lock:
            stop = self._watches.pop(name, None)
        if stop is not None:
            stop.set()

    def record_latency(self, block, labels: Optional[Mapping] = None):
        """Store a scraped ``TelemetryBlock`` (device/telemetry.py) for
        native histogram exposition. ``labels`` maps tenant INDEX ->
        label text (defaults to the index)."""
        with self._lock:
            self._latency = (
                block,
                None if labels is None else dict(labels),
            )

    def record_event(self, name: str, mapping: Mapping) -> None:
        """Record one occurrence of a recurring event (an autoscaler
        decision, a checkpoint cut): keeps ``<name>.count`` (monotonic)
        plus ``<name>.last.*`` (the latest event's numeric fields) - the
        counter/last-value pair a dashboard rate()s and inspects, without
        the registry ever holding an unbounded event list."""
        with self._lock:
            prev = self._records.get(name)
            count = (
                int(prev.get("count", 0)) + 1
                if isinstance(prev, dict) else 1
            )
            self._records[name] = {"count": count, "last": dict(mapping)}

    def add_run_info(self, name: str, info: Mapping) -> None:
        """Record a device run's ``info`` dict: numeric scalars plus
        ``tiers``/``fault_stats`` pass through; the flight-recorder trace
        is summarized (per-tag counts, written/dropped) rather than
        carried raw; array-valued entries (per_device_counts) reduce to
        per-device executed/rounds. A batch-routed run additionally gets
        the ``lane_occupancy`` gauge - one value per device (mesh runs
        return ``tiers`` as a per-device list; single-device runs read
        as a one-entry list), exported as
        ``<name>.lane_occupancy.<d>`` - the ROADMAP lane-firing-policy
        detector a dashboard watches without digging through tiers. A
        tenant-enabled stream's ``info['tenants']`` additionally mirrors
        under the canonical ``tenant.<id>.*`` prefix."""
        keep: Dict[str, Any] = {}
        for k, v in info.items():
            if k == "trace":
                from ..device.tracebuf import summarize

                keep["trace"] = summarize(v)
            elif k == "per_device_counts":
                import numpy as np

                from ..device.megakernel import C_EXECUTED

                c = np.asarray(v)
                keep["per_device_executed"] = c[:, C_EXECUTED].tolist()
            elif k == "extra_outputs":
                continue
            else:
                keep[k] = v
        tiers = keep.get("tiers")
        if isinstance(tiers, Mapping):
            tiers = [tiers]
        if isinstance(tiers, (list, tuple)) and tiers:
            try:
                keep["lane_occupancy"] = [
                    float(t["batch_occupancy"]) for t in tiers
                ]
            except (KeyError, TypeError):
                pass
            # Partial-batch starvation gauge (the lane-policy watch
            # item): present only on traced batch-routed runs (the
            # detector needs TR_FIRE_BATCH records), exported per device
            # like lane_occupancy so a dashboard alerts on starvation
            # without digging through trace rings.
            ages = [
                t.get("lane_partial_age")
                for t in tiers
                if isinstance(t, Mapping)
            ]
            if any(a is not None for a in ages):
                keep["lane_partial_age"] = [
                    float(a) for a in ages if a is not None
                ]
            # Device-side starved-age gauge (the ISSUE 10 age-triggered
            # firing policy, tstats TS_MAX_AGE): worst consecutive
            # starved-round count any lane reached, per device - the
            # number the lane_max_age knob bounds. Exported beside the
            # trace-derived lane_partial_age so a dashboard alert works
            # on untraced runs too.
            sages = [
                t.get("max_starved_age")
                for t in tiers
                if isinstance(t, Mapping)
            ]
            if any(a is not None for a in sages):
                keep["lane_max_starved_age"] = [
                    float(a) for a in sages if a is not None
                ]
            # Priority-bucket tier gauges (ISSUE 15): bucket-order
            # inversions (age-guard fires that jumped a lower
            # non-empty bucket - a rising rate means the age knob is
            # fighting the priority order) per device, and per-bucket
            # occupancy (traced runs only; <name>.bucket_occupancy.<b>)
            # so a dashboard sees the ordered-retirement structure
            # without digging through trace rings.
            invs = [
                t.get("bucket_inversions")
                for t in tiers
                if isinstance(t, Mapping)
            ]
            if any(i is not None for i in invs):
                keep["bucket_inversions"] = [
                    float(i) for i in invs if i is not None
                ]
            boccs = [
                t.get("bucket_occupancy")
                for t in tiers
                if isinstance(t, Mapping)
            ]
            if any(isinstance(b, Mapping) for b in boccs):
                # One dict per device (mesh runs return tiers as a
                # per-device list), flattened as
                # <name>.bucket_occupancy.<device>.<bucket> - same
                # per-device discipline as lane_occupancy.
                keep["bucket_occupancy"] = [
                    {str(k): float(v) for k, v in b.items()}
                    for b in boccs
                    if isinstance(b, Mapping)
                ]
        # Edge-rate gauge (graph-analytics runs, device/frontier.py):
        # a run info carrying traversed edges and a wall time exports
        # traversed-edges/s directly - the TEPS headline as a metric.
        if "edges" in keep and keep.get("elapsed_s"):
            try:
                keep["teps"] = float(keep["edges"]) / float(
                    keep["elapsed_s"]
                )
            except (TypeError, ZeroDivisionError):
                pass
        tenants = keep.get("tenants")
        if isinstance(tenants, Mapping):
            # Multi-tenant ingress: mirror the per-tenant admission
            # counters under the canonical ``tenant.<id>.*`` prefix
            # (accepted/rejected/expired/completed/backlog ...), the
            # series dashboards and the fairness tests key on -
            # regardless of what ``name`` the run info landed under.
            # (Records flatten after live sources at snapshot time, so
            # this end-of-run mirror wins over a still-registered live
            # ``tenant`` source's stale overlap.)
            self.record(
                "tenant",
                {str(tid): s for tid, s in tenants.items()},
            )
            # One canonical series only: drop the copy that would
            # otherwise also flatten as <name>.tenants.<id>.* and
            # double every tenant counter's scrape cardinality.
            keep.pop("tenants")
        if "program_cache" in keep:
            # Process-wide program-cache gauges (runtime/progcache.py):
            # the run info's per-build hit/miss record stays under
            # <name>.program_cache.*, while the canonical
            # program_cache.{hits,misses,evictions,entries} series
            # reflects the whole process cache - one series regardless
            # of which run name the build landed under. Beside them the
            # build ledger's sums over every jit of the process:
            # program_cache.{trace_s,lower_s,compile_s,
            # cache_retrieval_s,traces,programs}.
            from .progcache import build_totals, cache_stats

            self.record("program_cache", {**cache_stats(), **build_totals()})
        self.record(name, keep)

    # -- snapshots --

    def snapshot(self) -> Dict[str, Any]:
        """``{'t': epoch_seconds, 'metrics': {dotted.key: float}}``. A
        live source that raises is reported as ``<name>.error = 1``
        instead of sinking the snapshot (the watchdog must be able to
        snapshot a half-dead runtime)."""
        with self._lock:
            sources = dict(self._sources)
            records = dict(self._records)
        metrics: Dict[str, float] = {}
        for name, fn in sources.items():
            try:
                _flatten(name, fn(), metrics)
            except Exception:
                metrics[f"{name}.error"] = 1.0
        for name, rec in records.items():
            _flatten(name, rec, metrics)
        return {"t": time.time(), "metrics": metrics}

    @staticmethod
    def delta(
        a: Mapping[str, Any], b: Mapping[str, Any]
    ) -> Dict[str, Any]:
        """Key-wise ``b - a`` over two snapshots (missing keys read 0, so
        a source that appeared mid-interval deltas from zero); ``t`` is
        the interval seconds."""
        am: Mapping[str, float] = a.get("metrics", a)
        bm: Mapping[str, float] = b.get("metrics", b)
        keys = set(am) | set(bm)
        return {
            "t": float(b.get("t", 0.0)) - float(a.get("t", 0.0)),
            "metrics": {
                k: float(bm.get(k, 0.0)) - float(am.get(k, 0.0))
                for k in sorted(keys)
            },
        }

    # -- export --

    def to_json(self, snapshot: Optional[Mapping] = None) -> str:
        return json.dumps(snapshot or self.snapshot(), sort_keys=True)

    @staticmethod
    def _sanitize(key: str) -> str:
        out = []
        for ch in key:
            out.append(ch if (ch.isalnum() or ch == "_") else "_")
        name = "".join(out)
        if name and name[0].isdigit():
            name = "_" + name
        return name

    def to_prometheus(self, snapshot: Optional[Mapping] = None) -> str:
        """Prometheus text exposition: one gauge per flattened key,
        ``<namespace>_<sanitized key>``. Values render via repr(float)
        (Prometheus accepts scientific notation)."""
        snap = snapshot or self.snapshot()
        lines = []
        for k in sorted(snap["metrics"]):
            name = f"{self.namespace}_{self._sanitize(k)}"
            v = snap["metrics"][k]
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {float(v)!r}")
        lines.extend(self._latency_lines())
        lines.append("")
        return "\n".join(lines)

    def _latency_lines(self) -> list:
        """Native Prometheus histogram exposition of the recorded
        TelemetryBlock: per tenant, CUMULATIVE bucket counts with
        ``le`` = the bucket's upper edge in scheduler rounds (the
        overflow bucket folds into ``+Inf``), plus ``_count``; and the
        rounds->ns factor as a gauge when the block carries one."""
        with self._lock:
            rec = self._latency
        if rec is None:
            return []
        from ..device.telemetry import bucket_edges

        block, labels = rec
        fam = LATENCY_FAMILY
        edges = bucket_edges()
        lines = [f"# TYPE {fam} histogram"]
        for t in range(block.tenants):
            label = str(t if labels is None else labels.get(t, t))
            counts = block.hist(t)
            cum = 0
            for (_, hi), c in zip(edges, counts.tolist()):
                cum += int(c)
                if hi is None:
                    continue  # the overflow mass lands in +Inf below
                lines.append(
                    f'{fam}_bucket{{tenant="{label}",le="{hi}"}} {cum}'
                )
            total = int(counts.sum())
            lines.append(
                f'{fam}_bucket{{tenant="{label}",le="+Inf"}} {total}'
            )
            lines.append(f'{fam}_count{{tenant="{label}"}} {total}')
        if block.ns_per_round is not None:
            lines.append(f"# TYPE {fam}_ns_per_round gauge")
            lines.append(
                f"{fam}_ns_per_round {float(block.ns_per_round)!r}"
            )
        return lines
