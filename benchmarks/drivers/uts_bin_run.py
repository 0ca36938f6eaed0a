"""Driver of the binomial UTS deployments: one operation is one whole
``uts_pallas`` call on the configuration's tree, from the root (the root's
children hashed on the host, upload, ONE launch of the kernel in which the
lanes feed each other through an exchange buffer in VMEM that overflows
to a pool in HBM, readback), as a caller of upstream's ``./uts`` waits for
it. ``uts_run``'s shape; the record carries its fields and the pool's six
counters.

``check`` counts the same tree once with the plain reference
(``reference/uts_bin.py``: one jitted loop over levels in ``jax.numpy`` on
the chip, numpy under the interpreter) and holds every call of the window
to it, and the reference to the configuration's published numbers. All
integers, all limits 0: any precision, any skipped or doubled subtree, a
frame lost between two lanes, fails.
"""

from __future__ import annotations

import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import uts_bin as ref_uts

TREE_TYPES = {"BIN (-t 0)": 0}  # uts.h: enum uts_trees_e { BIN = 0, GEO, ... }
COUNTERS = ("donated", "claimed", "pool_max", "spills", "stack_max",
            "balance_rounds")


def setup(cfg, mix, seed, interpret):
    from hclib_tpu.models import uts as model

    tree = cfg["tree"]
    # A program without binomial trees has no such field: it stops here,
    # before anything is seeded or launched.
    params = model.UTSParams(
        tree=TREE_TYPES[tree["type"]], b0=float(tree["b0"]), q=tree["q"],
        m=tree["m"], root_seed=tree["root_seed"])
    kw = {"lanes": tuple(cfg["lanes"]), "stack_size": cfg["stack_size"],
          "interpret": interpret}
    if "max_steps" in cfg:  # only a control has it
        kw["max_steps"] = cfg["max_steps"]
    return {"cfg": cfg, "interpret": interpret, "kw": kw, "params": params}


def operation(st):
    from hclib_tpu.device.uts_pallas import uts_pallas

    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        r = uts_pallas(st["params"], **st["kw"])
    t1 = time.monotonic()
    return {"wall_s": t1 - t0, "attempted": 1, "work": r["nodes"],
            **{k: r[k] for k in (
                "nodes", "leaves", "max_depth", "host_seed_nodes",
                "device_nodes", "steps", "refills", "roots", "stack_size",
                "pool_capacity", "interpret", "platform") + COUNTERS}}


def check(st, records):
    cfg = st["cfg"]
    if st["interpret"]:
        xp = np
    else:
        import jax.numpy as xp
    t0 = time.monotonic()
    ref = ref_uts.count_tree(cfg["tree"], xp)
    print(json.dumps({"reference": {
        "seconds": time.monotonic() - t0, "array_module": xp.__name__,
        **ref}}))
    nlanes = cfg["lanes"][0] * cfg["lanes"][1]
    published = {**cfg["guarantees"], "hashed_nodes": cfg["hashed_nodes"]}
    ref_err = {k: abs(ref[k] - published[k])
               for k in ("nodes", "leaves", "depth", "hashed_nodes")}

    def errs(r):
        return {
            "nodes_abs_err": abs(r["nodes"] - ref["nodes"]),
            "leaves_abs_err": abs(r["leaves"] - ref["leaves"]),
            "depth_abs_err": abs(r["max_depth"] - ref["depth"]),
            "host_plus_device_minus_nodes": abs(
                r["host_seed_nodes"] + r["device_nodes"] - r["nodes"]),
            "device_nodes_over_lane_steps": max(
                0, r["device_nodes"] - r["steps"] * nlanes),
            "donated_plus_roots_minus_claimed": abs(
                r["donated"] + r["roots"] - r["claimed"]),
            "pool_max_over_capacity": max(
                0, r["pool_max"] - r["pool_capacity"]),
            "stack_max_over_ring": max(0, r["stack_max"] - r["stack_size"]),
            "calls_nothing_donated": int(r["donated"] == 0),
        }

    per_call = [errs(r) for r in records]
    bad = sum(any(e.values()) for e in per_call)
    if any(ref_err.values()):  # a wrong reference judges no call sound
        bad = len(records)
    compared = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared += [(f"reference_{k}_abs_err", v, 0)
                 for k, v in ref_err.items()]
    return bad, compared
