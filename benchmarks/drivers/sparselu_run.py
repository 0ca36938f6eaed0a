"""Driver of the SparseLU deployment: one operation is one whole
``device_sparselu`` call on one prebuilt ``Megakernel``, from the root
descriptor to the factor's blocks on the chip and ``info`` in hand, as a
solver that refactors one block-sparse matrix resident on the accelerator
waits for it. The matrix (``reference/sparselu.py``: ``genmat``'s pattern,
values from the seed, made on the chip) is handed in as a ``jax.Array``
and is not consumed; every call factors it again.

Set-up asks the program first: one without ``device_sparselu`` fails
there, within seconds. The factor a call returns stays on the chip; the
check reads the newest and a reservoir sample of the earlier ones drawn
from the seed (``check.keep_results``; a factor at ``n`` 128 is 545 MB), and
a factor that leaves the sample is the next call's output buffer, so a
call that counted on what a slot held before shows in the residual.

``check`` holds every call's counters to the reference's symbolic counts
(the four kinds, the fill blocks, one descriptor a task of the source),
the program's slot map to the reference's final pattern, and each kept
factor to the componentwise backward error ``max |L U - A| / (|L| |U|)``
at HIGHEST on the device (``reference/sparselu.py:readings``): every
entry at its own scale, a fill block's at that of the products that made
it.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import sparselu as ref

KINDS = ("lu0", "fwd", "bdiv", "bmod")


def _the_programs_call(cfg, interpret):
    try:
        from hclib_tpu.device.sparselu import (
            device_sparselu, make_sparselu_megakernel,
        )
    except ImportError as e:
        raise RuntimeError(
            f"this program has no device_sparselu ({e}): it cannot run "
            "this deployment") from e
    if cfg["dtype"] != "float32":
        raise RuntimeError(
            f"the program factors float32 blocks, the configuration "
            f"states {cfg['dtype']}")
    mk = make_sparselu_megakernel(cfg["n"], cfg["m"], interpret=interpret)
    return device_sparselu, mk


def setup(cfg, mix, seed, interpret):
    call, mk = _the_programs_call(cfg, interpret)
    present = ref.genmat_pattern(cfg["n"])
    sym = ref.symbolic(present)
    a = ref.make_blocks(seed, present, cfg["m"])
    a.block_until_ready()
    return {
        "cfg": cfg, "interpret": interpret, "call": call, "mk": mk,
        "present": present, "sym": sym, "a": a,
        "keep": max(1, cfg["check"]["keep_results"]),
        "rng": np.random.default_rng(seed),
        "newest": None, "sample": [], "spare": None, "calls": 0,
    }


def _keep(st, factor):
    """The newest factor and a reservoir sample of the earlier ones; the
    one that drops out is the next call's output buffer."""
    old = st["newest"]
    if old is not None:
        if len(st["sample"]) < st["keep"] - 1:
            st["sample"].append(old)
        else:
            j = int(st["rng"].integers(0, old[0] + 1))
            if j < st["keep"] - 1:
                st["sample"][j], old = old, st["sample"][j]
            st["spare"] = old[1]
    st["newest"] = (st["calls"], factor)
    st["calls"] += 1


def operation(st):
    out, st["spare"] = st["spare"], None
    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        factor, info = st["call"](st["a"], mk=st["mk"], out=out)
    t1 = time.monotonic()
    _keep(st, factor)
    slu, tiers = info["sparselu"], info["tiers"]
    width = tiers["batch_width"]  # the widest lane's: the bmod lane's
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": 1,
        "call": st["calls"] - 1,
        "tasks": sum(slu[k] for k in KINDS),
        "bmod_offered": slu["bmod_rounds"] * width,
        "slot_rows": slu["rows"], "slot_cols": slu["cols"],
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "interpret", "platform")},
        **{k: slu[k] for k in KINDS + (
            "fill_blocks", "scans", "released", "releases", "panel_rounds",
            "panel_tasks", "bmod_rounds", "bmod_tasks", "live_rows_max",
            "capacity")},
        **{k: tiers[k] for k in ("batch_rounds", "batch_tasks", "direct",
                                 "scalar_tasks")},
    }


def check(st, records):
    cfg, sym = st["cfg"], st["sym"]
    stated = cfg["guarantees"]
    limit = stated["residual_limit"]
    want = dict(sym["counts"], fill_blocks=sym["fill_blocks"])
    for k, v in want.items():  # the configuration states what it runs
        if stated[k] != v:
            raise RuntimeError(f"the configuration states {k} "
                               f"{stated[k]}, the pattern gives {v}")
    rows, cols = ref.slots(st["present"], sym["final"])
    mine = {r["call"] for r in records}
    read = {i: ref.readings(f, st["a"], st["present"], sym["final"])
            for i, f in st["sample"] + [st["newest"]] if i in mine}
    st["sample"], st["newest"], st["spare"] = [], None, None

    def errs(r):
        e = {f"{k}_abs_err": abs(r[k] - v) for k, v in want.items()}
        e.update(
            released_abs_err=abs(r["released"] - (r["tasks"] - 1)),
            executed_abs_err=abs(
                r["executed"] - sym["descriptors"] - r["scans"]),
            structure_differs=int(
                not (np.array_equal(r["slot_rows"], rows)
                     and np.array_equal(r["slot_cols"], cols))),
            pending=r["pending"], overflowed=int(bool(r["overflow"])),
            table_filled=int(r["live_rows_max"] >= r["capacity"]),
        )
        return e

    per_call = [errs(r) for r in records]
    bad = {r["call"] for r, e in zip(records, per_call) if any(e.values())}
    bad |= {i for i, x in read.items()
            if not (x["finite"] and x["residual"] < limit)}
    compared = [
        ("residual_max", max(x["residual"] for x in read.values()), limit),
        ("nonfinite", sum(not x["finite"] for x in read.values()), 0),
        ("growth_max", max(x["growth"] for x in read.values()),
         stated["growth_limit"]),
    ]
    if compared[2][1] >= stated["growth_limit"]:
        bad |= mine
    compared += [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared.append(("factors_compared", len(read), 1))
    return len(bad), compared
