"""From what a run recorded to the value of one metric. A metric's file
(metrics/<name>.json) names one reducer and its arguments; a reducer that
finds nothing to read returns None and the metric is left out of the line.

Records are the dicts a driver's ``operation`` returns, one per operation
of the window (``wall_s``, ``work``, ...). End-to-end reducers read
only those and the window's length; per-layer reducers read the trace of
the traced run (trace.read) through the three functions of trace.py.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import statistics
from typing import Callable, Dict, List, Optional

from . import ops, trace


@dataclasses.dataclass
class Run:
    """What one run recorded: all a reducer may read."""
    cfg: dict
    records: List[dict]
    window_s: float
    peaks: dict
    trace: Optional[dict] = None  # trace.read()'s result, traced runs only

    def spans(self, name: str):
        return [e for e in self.trace["host"] if e[0] == name]

    def device(self):
        """The first chip's events (every cell so far has one chip)."""
        return self.trace["device"].get(0, [])


# ------------------------------------------------- end to end (records)


def rate(run: Run, field: str) -> Optional[float]:
    """All the work of the window over all its time."""
    if not run.records or run.window_s <= 0:
        return None
    return sum(r[field] for r in run.records) / run.window_s


def median_ms(run: Run, field: str = "wall_s") -> Optional[float]:
    vals = [r[field] for r in run.records]
    return statistics.median(vals) * 1e3 if vals else None


def percentile_ms(run: Run, field: str, q: float) -> Optional[float]:
    """The q-th percentile (nearest rank) over every request of the window.
    ``field`` is a list per record; a request that failed is in it as
    ``inf`` and so misses any limit."""
    vals = sorted(v for r in run.records for v in r[field])
    if not vals:
        return None
    v = vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]
    return v * 1e3 if math.isfinite(v) else None  # too many failed


# ---------------------------------------------------- per layer (trace)


def span_mean(run: Run, span: str, scale: float) -> Optional[float]:
    """Mean length of the host spans of that name, ns times ``scale``."""
    sp = run.spans(span)
    if not sp:
        return None
    return sum(e - s for _, s, e in sp) / len(sp) * scale


def span_minus_device(run: Run, span: str, scale: float) -> Optional[float]:
    """Mean over the spans of: span length minus the time the device was
    busy inside it. What the caller waits for while the chip waits too."""
    sp = run.spans(span)
    if not sp or not run.device():
        return None
    dev = run.device()
    return sum(
        (e - s) - trace.busy_ns(dev, s, e) for _, s, e in sp
    ) / len(sp) * scale


def _per(run: Run, per: str, sp) -> float:
    """The divisor: the number of spans, or a field of the records summed
    (``work``: the program's own exact counter)."""
    if per == "span":
        return len(sp)
    return sum(r[per] for r in run.records)


def device_time_per_count(run: Run, span: str, pattern: str, per: str,
                          scale: float) -> Optional[float]:
    """Device time of the events matching ``pattern`` inside the spans,
    over ``per``."""
    sp = run.spans(span)
    if not sp:
        return None
    total, count = trace.inside_spans(run.device(), sp, pattern)
    div = _per(run, per, sp)
    if not count or not div:
        return None
    return total / div * scale


def event_count_per_span(run: Run, span: str, pattern: str
                         ) -> Optional[float]:
    sp = run.spans(span)
    if not sp:
        return None
    _, count = trace.inside_spans(run.device(), sp, pattern)
    return count / len(sp) if count else None


def roofline_share(run: Run, span: str, pattern: str, ops_fn: str,
                   peak: str) -> Optional[float]:
    """The least time the chip could take for one operation (its
    operations over the published peak) over the kernel time measured for
    one, in percent."""
    sp = run.spans(span)
    if not sp:
        return None
    total, count = trace.inside_spans(run.device(), sp, pattern)
    if not count:
        return None
    least_s = getattr(ops, ops_fn)(run.cfg) / run.peaks[peak]
    return 100.0 * least_s / (total / len(sp) / 1e9)


REDUCERS: Dict[str, Callable] = {
    f.__name__: f for f in (
        rate, median_ms, percentile_ms, span_mean, span_minus_device,
        device_time_per_count, event_count_per_span, roofline_share,
    )
}


def reducer(name: str) -> Callable:
    """One of the fixed set above, or ``reducers/<name>.py``'s ``reduce``
    for a metric that brings its own."""
    if name in REDUCERS:
        return REDUCERS[name]
    return importlib.import_module(
        f"{__package__}.reducers.{name}"
    ).reduce
