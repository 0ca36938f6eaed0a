"""The whole reduction from a profiler trace to numbers: three functions
over plain ``(name, start_ns, end_ns)`` events, and the reader that gets
those events out of an ``.xplane.pb`` with nothing but JAX.

Which events count as the device was settled by looking at a real trace
of the v5e (tests/data holds two): each chip is a plane named
``/device:TPU:<i>``, and its line ``XLA Ops`` holds one event for every
operation the chip ran, the Pallas kernel among them; the other lines of
that plane (modules, steps) are envelopes around those and would count
the gaps inside them as busy. Host spans are the ``bench:*`` events the
benchmark itself writes with ``jax.profiler.TraceAnnotation``. Both are
on one clock in the file.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"


def read(path: str) -> Dict[str, object]:
    """``{"device": {chip: [Event]}, "host": [Event]}`` from one
    ``.xplane.pb`` file, or from the newest one under a profiler log
    directory. Events are sorted by start."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")
        ))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == DEVICE_LINE:
                device.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                )
            elif not m:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)
                )
    for evs in device.values():
        evs.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host}


def _merged(events: Sequence[Event], t0: float, t1: float):
    """Disjoint busy intervals of ``events`` clipped to [t0, t1]."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: Sequence[Event], t0: float, t1: float) -> float:
    """(a) Length of the union of the events' intervals inside [t0, t1]:
    the time in which some operation ran on that chip."""
    return sum(e - s for s, e in _merged(events, t0, t1))


def inside_spans(events: Sequence[Event], spans: Sequence[Event],
                 pattern: str) -> Tuple[float, int]:
    """(b) Summed duration and count of the events whose name matches
    ``pattern`` and that start inside one of ``spans``."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for name, s, e in events:
        if rx.search(name) and any(a <= s < b for _, a, b in spans):
            total += e - s
            count += 1
    return total, count


def idle_by_span(events: Sequence[Event], spans: Sequence[Event],
                 t0: float, t1: float, threshold_ns: float
                 ) -> Dict[str, float]:
    """(c) The idle time of [t0, t1] that lies in gaps longer than
    ``threshold_ns``, by the name of the host span that covers it: every
    instant goes to the innermost span open at it (the spans of one thread
    nest), ``"(no span)"`` where none is."""
    gaps, edge = [], t0
    for s, e in _merged(events, t0, t1) + [[t1, t1]]:
        if s - edge > threshold_ns:
            gaps.append((edge, s))
        edge = max(edge, e)
    starts = [g[0] for g in gaps]
    before = [0.0]  # idle time before each gap's start
    for a, b in gaps:
        before.append(before[-1] + (b - a))

    def idle_until(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        return 0.0 if i < 0 else before[i] + min(t, gaps[i][1]) - starts[i]

    marks = sorted(
        [(s, 1, i) for i, (_, s, e) in enumerate(spans) if e > s]
        + [(e, 0, i) for i, (_, s, e) in enumerate(spans) if e > s]
    )  # at one instant, ends come before starts
    out: Dict[str, float] = {}
    stack: List[int] = []
    cur = t0
    for t, is_start, i in marks + [(t1, 0, -1)]:
        t = min(max(t, t0), t1)
        if t > cur:
            name = spans[stack[-1]][0] if stack else "(no span)"
            idle = idle_until(t) - idle_until(cur)
            if idle > 0:
                out[name] = out.get(name, 0.0) + idle
            cur = t
        if is_start:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return out
