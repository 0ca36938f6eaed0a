"""Driver of the Smith-Waterman wavefront deployments: one operation is one
whole ``device_sw_wave`` call on the configuration's pair, on one prebuilt
``Megakernel`` (graph build, host buffers, upload, one launch whose batch
tier walks the wavefront, readback), as a caller of upstream's
``test/smithwaterman`` waits for the score. The call sequence is
``chip_smoke.phase_sw``'s, proven on the chip.

``check`` holds every call of the window to the plain reference
(``reference/sw.py``, computed once in set-up): the best score, H's last
row and H's last column, and the scheduler's own counters to the counts of
the wavefront. All integers, all limits 0. The reference is held to its
own cell-by-cell recurrence on a corner of the pair, and its counts to the
configuration's, so a wrong reference fails as loudly as a wrong program.

A control (``band_tiles`` at the configuration's top level, where the
configuration of record does not have it) runs a banded alignment: the
same descriptors through the same ``Megakernel``, but only the tiles
within that many tiles of the diagonal.
"""

from __future__ import annotations

import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import sw as ref_sw

CORNER = 256  # the reference checks itself on the pair's CORNER x CORNER


def _band_graph(nt_i: int, nt_j: int, chunk: int, band: int):
    """``build_sw_wave_graph``'s ``[w, lo, count]`` descriptors and
    wave-to-wave dependencies, over the tiles with |ti - tj| <= band
    only (contiguous on every anti-diagonal)."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.smithwaterman import WAVE_FN

    builder = TaskGraphBuilder()
    prev_wave: list = []
    for w in range(nt_i + nt_j - 1):
        lo = max(0, w - (nt_j - 1), -(-(w - band) // 2))
        hi = min(nt_i - 1, w, (w + band) // 2)
        this_wave = [
            builder.add(WAVE_FN, deps=prev_wave,
                        args=[w, base, min(chunk, hi + 1 - base)])
            for base in range(lo, hi + 1, chunk)
        ]
        prev_wave = this_wave or prev_wave
    return builder


def setup(cfg, mix, seed, interpret):
    from hclib_tpu.device import smithwaterman as sw

    engine = {"tile": sw.T, "chunk": sw.WAVE_R, "width": sw.WAVE_B}
    stated = {k: cfg[k] for k in engine}
    if stated != engine:  # device_sw_wave takes no such arguments
        raise RuntimeError(f"the configuration states {stated}, the engine "
                           f"runs {engine}")
    n, m = cfg["n"], cfg["m"]
    scoring = {k: cfg[k] for k in ("match", "mismatch", "gap")}
    a, b = ref_sw.make_pair(seed, n, m, cfg["alphabet"])
    nt_i, nt_j = n // sw.T, m // sw.T
    mk = sw.make_sw_wave_megakernel(
        nt_i, nt_j, interpret=interpret, with_h=cfg["with_h"])
    t0 = time.monotonic()
    want = ref_sw.sw_last(a, b, **scoring)
    return {
        "cfg": cfg, "interpret": interpret, "a": a, "b": b, "mk": mk,
        "nt": (nt_i, nt_j), "scoring": scoring, "want": want,
        "reference_s": time.monotonic() - t0,
        "band": cfg.get("band_tiles"),  # only a control has it
    }


def _banded(st):
    """The control's call: ``device_sw_wave``'s steps with the band's
    graph in the full graph's place."""
    from hclib_tpu.device.smithwaterman import WAVE_R, sw_wave_buffers

    nt_i, nt_j = st["nt"]
    builder = _band_graph(nt_i, nt_j, WAVE_R, st["band"])
    ivalues, out, info = st["mk"].run(
        builder, data=sw_wave_buffers(st["a"], st["b"]))
    info = dict(info)
    info["last_row"] = np.asarray(out["bot"])[nt_i - 1].reshape(-1)
    info["last_col"] = np.asarray(out["right"])[:, nt_j - 1].reshape(-1)
    return int(ivalues[0]), None, info


def _differing(got, want) -> int:
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def operation(st):
    from hclib_tpu.device.smithwaterman import device_sw_wave

    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        if st["band"] is None:
            score, _, info = device_sw_wave(
                st["a"], st["b"], interpret=st["interpret"], mk=st["mk"],
                with_h=st["cfg"]["with_h"])
        else:
            score, _, info = _banded(st)
    t1 = time.monotonic()
    if "last_row" not in info or "last_col" not in info:
        raise RuntimeError(
            "this program's device_sw_wave keeps no info['last_row'] / "
            "info['last_col'] (H's last row and column): the guarantees of "
            + st["cfg"]["name"] + " cannot be held, so it cannot run this "
            "deployment")
    tiers, want = info["tiers"], st["want"]
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": 1, "score": score,
        "last_row_differing": _differing(info["last_row"],
                                         want["last_row"]),
        "last_col_differing": _differing(info["last_col"],
                                         want["last_col"]),
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "interpret", "platform")},
        **{k: tiers[k] for k in ("batch_rounds", "batch_tasks",
                                 "batch_occupancy", "prefetch_hits",
                                 "spilled", "scalar_tasks")},
    }


def check(st, records):
    cfg, want = st["cfg"], st["want"]
    nt_i, nt_j = st["nt"]
    counts = ref_sw.wave_counts(nt_i, nt_j, cfg["chunk"])
    t0 = time.monotonic()
    c = min(CORNER, cfg["n"], cfg["m"])
    a, b = st["a"][:c], st["b"][:c]
    quick = ref_sw.sw_last(a, b, **st["scoring"])
    h = ref_sw.sw_naive(a, b, **st["scoring"])
    print(json.dumps({"reference": {
        "seconds": st["reference_s"], "score": want["score"],
        "corner": c, "corner_seconds": time.monotonic() - t0,
        "corner_score": int(h.max()), **counts}}))
    ref_err = {
        "corner_score": abs(quick["score"] - int(h.max())),
        "corner_last_row": _differing(quick["last_row"], h[-1]),
        "corner_last_col": _differing(quick["last_col"], h[:, -1]),
        **{k: abs(counts[k] - cfg["guarantees"][k])
           for k in ("tiles", "descriptors", "waves", "csr_words")},
    }

    def errs(r):
        return {
            "score_abs_err": abs(r["score"] - want["score"]),
            "last_row_differing": r["last_row_differing"],
            "last_col_differing": r["last_col_differing"],
            "executed_abs_err": abs(r["executed"] - counts["tiles"]),
            "batch_tasks_abs_err": abs(
                r["batch_tasks"] - counts["descriptors"]),
            "pending": r["pending"],
            "overflowed": int(bool(r["overflow"])),
            "scalar_tasks": r["scalar_tasks"],
        }

    per_call = [errs(r) for r in records]
    bad = sum(any(e.values()) for e in per_call)
    if any(ref_err.values()):  # a wrong reference judges no call sound
        bad = len(records)
    compared = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared += [(f"reference_{k}_abs_err", v, 0)
                 for k, v in ref_err.items()]
    return bad, compared
