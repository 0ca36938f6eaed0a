"""The arrival schedule of an open-loop stream: when each request is due,
whose it is and what it asks, from the mix's parameters and the seed
alone. ``traffic.py`` has no arrival process and may not be edited, so
the open loop's generator lives beside its driver. Imports nothing of the
program."""

from __future__ import annotations

import numpy as np


def schedule(mix: dict, seed: int, stream: int):
    """Stream number ``stream`` of the run: ``(due_s, lane, x)``, one
    entry a request in arrival order. ``due_s`` is seconds from the
    stream's start: exponential gaps, scaled so that the stream's
    ``requests_per_stream`` arrivals take ``requests_per_stream /
    rate_per_s`` seconds (a Poisson process of that rate given its count:
    every stream offers the file's rate exactly, and lasts as long as
    every other). ``lane`` is the tenant's index, drawn by ``shares``;
    ``x`` the argument, uniform in [arg_low, arg_high). The same seed and
    stream give the same schedule."""
    n = int(mix["requests_per_stream"])
    rng = np.random.default_rng([seed, stream])
    gaps = rng.exponential(1.0, n + 1)  # the last one ends the stream
    due_s = np.cumsum(gaps[:n]) * (n / float(mix["rate_per_s"]) / gaps.sum())
    shares = np.asarray(mix["shares"], np.float64)
    lane = rng.choice(len(shares), n, p=shares / shares.sum())
    x = rng.integers(mix["arg_low"], mix["arg_high"], n)
    return due_s, lane, x


def realised_rate(due_s: np.ndarray) -> float:
    """Requests a second the schedule offers, by its own arithmetic: the
    count over the time from the stream's start to its last arrival."""
    return len(due_s) / float(due_s[-1])
