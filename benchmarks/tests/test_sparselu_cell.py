"""The cell ``sparselu-dep-128`` (PR 58) at 6 x 6 blocks of 128 x 128
through the Pallas interpreter, on the CPU, run by hand with the other
benchmark tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite.
"""

import contextlib
import io
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.reducers import slu_roofline  # noqa: E402
from benchmarks.reference import sparselu as ref  # noqa: E402

CELL = "sparselu-dep-128"
CONFIG = "benchmarks/configs/sparselu-taskdep.json"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 58
TINY = {"n": 6, "check": {"keep_results": 1}}
TINY_COUNTS = {"lu0": 6, "fwd": 9, "bdiv": 9, "bmod": 19, "fill_blocks": 4}
MINE = {"slu_kernel_ms", "slu_roofline", "slu_task_ns", "slu_round_us",
        "slu_occupancy", "slu_live_rows", "slu_fill_blocks", "stage_ms.slu"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False):
    full = run.load_json(CONFIG)
    over = {**TINY, "guarantees": {**full["guarantees"], **TINY_COUNTS}}
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = run.run_cell(bench, CELL, SEED, 0.1, traced, CPU,
                           interpret=True, cfg_over=over)
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


def compared_of(lines):
    return {x["compared"]: (x["value"], x["limit"])
            for x in lines if "compared" in x}


def test_the_configuration_states_what_the_reference_counts():
    cfg = run.load_json(CONFIG)
    sym = ref.symbolic(ref.genmat_pattern(cfg["n"]))
    g = cfg["guarantees"]
    assert {k: g[k] for k in sym["counts"]} == sym["counts"]
    assert g["fill_blocks"] == sym["fill_blocks"] == 6552
    assert cfg["descriptors"] == sym["descriptors"] == 183104
    assert abs(slu_roofline.least_flops(128, 128) - 0.750456e12) < 1e6
    assert abs(ref.diag_shift(ref.genmat_pattern(128), 128) - 421.3) < 0.05
    assert 0 < g["residual_limit"] < 1e-3


def test_cell_is_correct_on_the_cpu(bench):
    out, lines = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, (out, lines)
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    got = compared_of(lines)
    value, limit = got.pop("residual_max")
    assert 0 < value < limit == run.load_json(CONFIG)["guarantees"][
        "residual_limit"]
    assert got.pop("growth_max")[0] < 1.1
    assert got.pop("factors_compared") == (1, 1)
    assert len(got) == 12 and all(v == (0, 0) for v in got.values()), got


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the spans and the counters are read.
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True
    mine = set(out["metrics"]) & MINE
    assert mine == {"slu_occupancy", "slu_live_rows", "slu_fill_blocks"}
    assert out["metrics"]["slu_fill_blocks"]["value"] == 4
    assert 0 < out["metrics"]["slu_occupancy"]["value"] <= 100
    assert 0 < out["metrics"]["slu_live_rows"]["value"] < 64
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:slu.run", "bench:mk.wait"}


CONTROLS = {
    # the reference's factorisation as it stands: the limit separates it
    "float32": ({}, True),
    "bfloat16": ({"precision": "bfloat16"}, False),
    # a bmod whose operands were both present, drawn from the seed
    "dropped": ({"drop": ref.control_bmod(ref.genmat_pattern(6), SEED)},
                False),
    # a product of two FILL blocks, (4, 1) and (1, 4), onto a present one
    "dropped_fill_fill": ({"drop": (1, 4, 4)}, False),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_controls_through_the_check_itself(bench, monkeypatch, control):
    """The reference's own blocked factorisation standing in the program's
    place, through the driver's ``check``: the program runs (its counters
    are a sound call's) and its factor is replaced. Correct in float32 at
    HIGHEST; not correct, by ten times the limit and more, with one bf16
    pass in the trailing products or with one bmod left out. (At the
    cell's size: PERF.md section 2.)"""
    from hclib_tpu.device import sparselu as slu

    kw, sound = CONTROLS[control]
    p = ref.genmat_pattern(6)
    final = ref.symbolic(p)["final"]
    assert {tuple(r[:3]) for r in ref.bmods(p)} >= {
        c[0]["drop"] for c in CONTROLS.values() if "drop" in c[0]}
    real = slu.device_sparselu

    def in_place(blocks, mk, out=None):
        _, info = real(blocks, mk=mk, out=out)
        return ref.blocked_lu(blocks, p, final, **kw), info

    monkeypatch.setattr(slu, "device_sparselu", in_place)
    out, lines = tiny(bench)
    got = compared_of(lines)
    value, limit = got.pop("residual_max")
    assert out["correct"] is sound, (control, value, limit)
    if not sound:
        assert value > 10 * limit and out["failed"] >= 1
    assert set(run.load_json(CONFIG)["control"]) == {"precision", "dropped"}
    assert all(got[k] == (0, 0) for k in got
               if k.endswith("_abs_err") or k == "pending")


def test_a_bmod_dropped_in_the_program_is_not_correct(bench, monkeypatch):
    """A timed path kept broken: the first slot of every bmod round
    subtracts nothing. Every task runs once and every counter is right."""
    from hclib_tpu.device import sparselu as slu

    real, seen = slu.mm_nn, []

    def first_slot_dropped(a, b):
        # the body multiplies once a slot, sixteen times a trace (the
        # verifier's shim traces it too): every sixteenth is slot 0
        seen.append(1)
        return real(a, b) * (0.0 if len(seen) % 16 == 1 else 1.0)

    monkeypatch.setattr(slu, "mm_nn", first_slot_dropped)
    # the program cache keys a build by its code and closures, which a
    # patched module global is not in
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", "0")
    out, lines = tiny(bench)
    assert seen and out["correct"] is False and out["failed"] >= 1
    got = compared_of(lines)
    assert got["residual_max"][0] > 10 * got["residual_max"][1]
    assert all(got[k] == (0, 0) for k in got
               if k.endswith("_abs_err") or k == "pending")


def test_a_fill_block_left_stale_is_not_correct(bench, monkeypatch):
    """The other: a fill block is not made clean by its first bmod but
    read from the output buffer, which holds the factor of the call
    before (the driver hands a factor that left its sample back as the
    next call's output). The first call, into zeros, is sound."""
    from hclib_tpu.device import sparselu as slu

    monkeypatch.setattr(slu, "_made", lambda word: word == word)
    monkeypatch.setattr(slu, "_in_output",
                        lambda word: (word & slu.B_FRESH) == 0)
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", "0")
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] >= 1
    got = compared_of(lines)
    assert got["residual_max"][0] > 10 * got["residual_max"][1]
    assert got["fill_blocks_abs_err"] == (0, 0)
