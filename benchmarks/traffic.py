"""The one traffic generator: reads a mix's file of parameters and makes,
from the seed, what each operation of a closed loop sends. A new mix is a
new file under traffic/, never new code."""

from __future__ import annotations

import json
import os

import numpy as np


def load(root: str, name: str) -> dict:
    """The mix ``name`` of the checkout at ``root``."""
    path = os.path.join(root, "benchmarks", "traffic", name + ".json")
    with open(path) as f:
        return json.load(f)


def burst_args(mix: dict, seed: int, burst: int, lanes: int) -> np.ndarray:
    """Arguments of burst number ``burst``: ``[lanes, requests_per_tenant]``
    whole numbers in [arg_low, arg_high). Every seed sends the same number
    of requests to the same lanes; only the values differ."""
    rng = np.random.default_rng([seed, burst])
    return rng.integers(
        mix["arg_low"], mix["arg_high"],
        (lanes, mix["requests_per_tenant"]),
    )
