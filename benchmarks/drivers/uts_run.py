"""Driver of the UTS deployments: one operation is one whole
``uts_pallas`` call on the configuration's tree, from the root (host
seeding, upload, one launch of the kernel, readback), as a caller of
upstream's ``./uts`` waits for it. The call sequence is
``chip_smoke.phase_uts``'s, proven on the chip.

``check`` counts the same tree once with the plain reference
(``reference/uts.py``; its block hash runs in ``jax.numpy`` on the chip and
in numpy under the interpreter) and holds every call of the window to it,
and the reference to the configuration's published numbers. All integers,
all limits 0: any precision and any skipped subtree fails.
"""

from __future__ import annotations

import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import uts as ref_uts



def setup(cfg, mix, seed, interpret):
    from hclib_tpu.models import uts as model

    tree = cfg["tree"]
    kw = {"target_roots": cfg["target_roots"], "lanes": tuple(cfg["lanes"]),
          "min_idle_div": cfg["min_idle_div"], "interpret": interpret}
    if "max_steps" in cfg:  # only a control has it
        kw["max_steps"] = cfg["max_steps"]
    return {
        "cfg": cfg, "interpret": interpret, "kw": kw,
        "params": model.UTSParams(
            shape=getattr(model, tree["shape"]), gen_mx=tree["gen_mx"],
            b0=float(tree["b0"]), root_seed=tree["root_seed"]),
    }


def operation(st):
    from hclib_tpu.device.uts_pallas import uts_pallas

    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        r = uts_pallas(st["params"], **st["kw"])
    t1 = time.monotonic()
    return {"wall_s": t1 - t0, "attempted": 1, "work": r["nodes"],
            "refills": r.get("refills"),  # the parent of PR 29 has none
            **{k: r[k] for k in (
                "nodes", "leaves", "max_depth", "host_seed_nodes",
                "device_nodes", "steps", "interpret", "platform")}}


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
        }

    per_call = [errs(r) for r in records]
    bad = sum(any(e.values()) for e in per_call)
    if any(ref_err.values()):  # a wrong reference judges no call sound
        bad = len(records)
    compared = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared += [(f"reference_{k}_abs_err", v, 0)
                 for k, v in ref_err.items()]
    return bad, compared
