"""Driver of the deployments that span the host's chips: one resident
scheduler a chip on a 1-D mesh (``ResidentKernel``), a fib forest spawned
on device 0 alone and stolen by the others over the in-kernel ICI exchange.
One operation is one whole ``rk.run`` from the caller's side: fresh
builders, partition, upload, one launch across the mesh, readback, the out
slots summed. The call sequence is ``stress.forest_resident``'s and
``chip_smoke.phase_four_chips``'s, proven on the chips.

Set-up also builds the one-device twin (the same roots on one builder
through ``Megakernel.run``, as ``phase_four_chips`` does), warms it and
times whole calls of it: their median, ``twin_wall_s``, rides in every
record, for the metric that says what share of ``chips`` times one chip
the mesh delivers.

``check`` holds every call to the plain reference
(``reference/fib_forest.py``) and that reference's closed form to its own
direct count. All integers, all limits 0. Two of the configuration's
guarantees (nothing lost in flight, something stolen) stand on the
program's counters of migrated rows, ``info["steal"]``: a program without
them cannot run this deployment, and ``setup`` says so at once.
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import fib_forest as ref

TWIN_CALLS = 5  # warmed whole calls of the twin; their median is its time


def build(st):
    """Fresh builders for one call: every root on device 0; every device
    reserves the roots' out slots (a stolen root writes its slot on the
    thief's value buffer)."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    builders = [TaskGraphBuilder() for _ in range(st["chips"])]
    for r in range(st["roots"]):
        builders[0].add(st["kind"], args=[st["n"]], out=r)
    for b in builders:
        b.reserve_values(st["roots"])
    return builders


def _twin(st):
    """The same forest on one device: one builder, ``Megakernel.run``."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    t0 = time.monotonic()
    b = TaskGraphBuilder()
    for r in range(st["roots"]):
        b.add(st["kind"], args=[st["n"]], out=r)
    iv, _, info = st["mk"].run(b)
    value = int(np.asarray(iv)[:st["roots"]].sum(dtype=np.int64))
    wall_s = time.monotonic() - t0
    want = ref.closed_form(st["roots"], st["n"])
    got = {"value": value, "descriptors": info["executed"]}
    if got != want or info["pending"] or info["overflow"]:
        raise RuntimeError(f"the one-device twin gave {got}, pending "
                           f"{info['pending']}, not {want}")
    return wall_s, info["executed"]


def setup(cfg, mix, seed, interpret):
    import jax
    from jax.sharding import Mesh

    from hclib_tpu.device import resident
    from hclib_tpu.device.megakernel import VBLOCK
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    if not hasattr(resident, "FS_IMPORTED"):
        raise RuntimeError(
            "this program counts no rows migrated by the steal exchange "
            "(info['steal']): the guarantees of " + cfg["name"]
            + " cannot be held, so it cannot run this deployment")
    chips, roots, cap = cfg["chips"], cfg["roots"], cfg["capacity"]
    devs = jax.devices()[:chips]
    if len(devs) < chips:
        raise RuntimeError(f"the deployment needs {chips} devices, JAX has "
                           f"{len(jax.devices())}")
    mk = make_fib_megakernel(
        cap, interpret=interpret,
        num_values=VBLOCK * cap + max(64, roots),
    )
    st = {
        "cfg": cfg, "interpret": interpret, "chips": chips, "roots": roots,
        "n": cfg["n"], "kind": FIB, "mk": mk,
        "rk": ResidentKernel(
            mk, Mesh(np.array(devs), ("q",)), migratable_fns=[FIB],
            homed=cfg["homed"], window=cfg["window"],
        ),
        "kw": {"quantum": cfg["quantum"]},
    }
    if "max_rounds" in cfg:  # only a control has it
        st["kw"]["max_rounds"] = cfg["max_rounds"]
    _twin(st)  # the call that compiles
    calls = [_twin(st) for _ in range(TWIN_CALLS)]
    st["twin_wall_s"] = statistics.median(w for w, _ in calls)
    st["twin_work"] = calls[0][1]
    return st


def operation(st):
    from hclib_tpu.device.megakernel import C_EXECUTED

    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        with TraceAnnotation("bench:build"):
            builders = build(st)
        iv, _, info = st["rk"].run(builders, **st["kw"])
        value = int(np.asarray(iv)[:, :st["roots"]].sum(dtype=np.int64))
    t1 = time.monotonic()
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": info["executed"],
        "value": value, "rounds": info["rounds"],
        "per_device_executed": [
            int(c[C_EXECUTED]) for c in info["per_device_counts"]
        ],
        "exported": info["steal"]["exported"],
        "imported": info["steal"]["imported"],
        "twin_wall_s": st["twin_wall_s"], "twin_work": st["twin_work"],
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "input_devices", "interpret", "platform")},
    }


def check(st, records):
    roots, n, chips = st["roots"], st["n"], st["chips"]
    want, one = ref.closed_form(roots, n), ref.direct_count(n)
    form = ref.closed_form(1, n)
    ref_err = {k: abs(form[k] - one[k]) for k in ("value", "descriptors")}
    print(json.dumps({"reference": {
        "roots": roots, "n": n, **want, "one_root_closed_form": form,
        "one_root_direct_count": one}}))
    print(json.dumps({"calls": [
        {k: r[k] for k in ("rounds", "per_device_executed", "exported",
                           "imported", "input_devices", "wall_s")}
        for r in records[:3]], "of": len(records),
        "twin_wall_s": st["twin_wall_s"], "twin_work": st["twin_work"]}))

    def errs(r):
        per = r["per_device_executed"]
        return {
            "value_abs_err": abs(r["value"] - want["value"]),
            "executed_abs_err": abs(r["executed"] - want["descriptors"]),
            "per_device_sum_minus_executed": abs(sum(per) - r["executed"]),
            "pending": r["pending"],
            "overflowed": int(bool(r["overflow"])),
            "devices_that_executed_nothing": sum(x <= 0 for x in per),
            "devices_without_inputs": abs(chips - r["input_devices"]),
            "exported_minus_imported": abs(
                sum(r["exported"]) - sum(r["imported"])),
            "calls_with_nothing_stolen": int(sum(r["imported"]) <= 0),
        }

    per_call = [errs(r) for r in records]
    bad = sum(any(e.values()) for e in per_call)
    if any(ref_err.values()):  # a wrong reference judges no call sound
        bad = len(records)
    compared = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared += [(f"reference_{k}_closed_form_minus_direct_count", v, 0)
                 for k, v in ref_err.items()]
    return bad, compared
