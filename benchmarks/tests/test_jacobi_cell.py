"""The cell ``jacobi-dep-hbm`` (PR 51) at 128 x 256 in (8, 128) tiles, three
steps, through the Pallas interpreter, on the CPU, run by hand with the
other benchmark tests:

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

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from test_uts_cell import _git, _only_gained  # noqa: E402

from benchmarks import reduce, run  # noqa: E402
from benchmarks.reducers import jd_roofline  # noqa: E402
from benchmarks.reference import jacobi as ref  # noqa: E402

CELL = "jacobi-dep-hbm"
CONFIG = "benchmarks/configs/jacobi-taskdep.json"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 51 started from: what the benchmark had.
BASE = "e0c8b99627cd990d6c904fddd19546c24fcd1ec4"
SEED = 2**31 + 51
# 16 x 2 tiles of (8, 128), three steps: tall enough for steps to overlap.
TINY = {"H": 128, "W": 256, "tile": [8, 128], "width": 2, "steps": 3}
TINY_COUNTS = {"tiles": 96, "splits": 31, "released": 64,
               "decrements": 248, "executed": 127}
MINE = {"jd_kernel_ms", "jd_step_ms", "jd_round_us", "jd_roofline",
        "jd_occupancy", "jd_prefetch_share", "jd_mixed_share",
        "jd_live_rows", "stage_ms.jd"}
MK = {"mk_finalize_ms", "mk_upload_ms", "mk_launch_ms", "mk_tail_ms"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    over = {**TINY, **(cfg or {})}
    full = run.load_json(CONFIG)
    over["guarantees"] = {**full["guarantees"], **TINY_COUNTS,
                          **over.get("guarantees", {})}
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = run.run_cell(bench, CELL, SEED, 0.1, traced, CPU,
                           interpret=True, cfg_over=over)
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


def compared_of(lines):
    return {x["compared"]: x["value"] for x in lines if "compared" in x}


def test_cell_is_correct_and_every_compared_number_is_zero(bench):
    out, lines = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    compared = [x for x in lines if "compared" in x]
    # thirteen a call, two after, six of the reference
    assert len(compared) == 21
    assert all(x["value"] == 0 and x["limit"] == 0 for x in compared)
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert reference["corner"] == 128
    assert {k: reference[k] for k in TINY_COUNTS} == TINY_COUNTS


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the spans and the counters are read.
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "jd_occupancy", "jd_prefetch_share", "jd_mixed_share",
        "jd_live_rows", "mk_finalize_ms", "mk_upload_ms", "mk_launch_ms"}
    assert out["metrics"]["jd_occupancy"]["value"] == 100.0
    assert 0 < out["metrics"]["jd_mixed_share"]["value"] < 100
    assert 0 < out["metrics"]["jd_live_rows"]["value"] < 64
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:fa.seed", "bench:fa.run", "bench:mk.wait"}


def test_control_a_scheduler_that_stops_early_raises(bench):
    from hclib_tpu.runtime.resilience import StallError

    full = run.load_json(CONFIG)
    assert full["control"] == {"fuel": 16384} and "fuel" not in full
    assert full["control"]["fuel"] < full["guarantees"]["executed"]
    with pytest.raises(StallError, match="pending"):
        tiny(bench, cfg={"fuel": 64})


def test_a_neighbour_not_awaited_is_not_correct(bench, monkeypatch):
    """A timed path kept broken: the tiles above and below are neither
    awaited nor checked for (the verifier would refuse the loop), so a
    tile of step 1 reads a row that step 0 has not stored. Every tile
    runs once and every counter but the decrements is right."""
    from hclib_tpu.device import workloads

    real = workloads.jacobi_loop

    def rows_not_awaited(H, W, th, tw, steps, awaits):
        return real(H, W, th, tw, steps,
                    awaits=[o for o in awaits if not o[0]])

    monkeypatch.setattr(workloads, "jacobi_loop", rows_not_awaited)
    monkeypatch.setenv("HCLIB_TPU_VERIFY", "0")
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["digest_plain_differs"] == c["digest_weighted_differs"] == 1
    assert c["grid_differing"] > 0
    assert c["executed_abs_err"] == c["batch_tasks_abs_err"] == 0
    assert c["released_abs_err"] == c["pending"] == c["overflowed"] == 0
    assert c["decrements_abs_err"] == 2 * 2 * 15 * 2  # the vertical ones


def test_a_step_short_is_not_correct(bench, monkeypatch):
    """The other: a program that advances the grid one step less than the
    configuration states. The plane the last step should have written
    holds step 1's grid, and the program's own count of steps says so."""
    from hclib_tpu.device import workloads

    real = workloads.jacobi_loop
    monkeypatch.setattr(
        workloads, "jacobi_loop",
        lambda H, W, th, tw, steps, awaits: real(
            H, W, th, tw, steps - 1, awaits))
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["digest_plain_differs"] == c["digest_weighted_differs"] == 1
    assert c["grid_differing"] > 128 * 256 * 0.9
    assert c["steps_abs_err"] == 1 and c["batch_tasks_abs_err"] == 32
    assert c["released_abs_err"] == 32 and c["pending"] == 0


def test_a_program_without_the_loop_is_refused(bench, monkeypatch):
    """The parent of PR 51: the driver raises before anything is made."""
    from benchmarks.drivers import jacobi_run
    from hclib_tpu.device import workloads

    monkeypatch.delattr(workloads, "jacobi_loop")
    monkeypatch.setattr(jacobi_run, "_chip_functions", None)  # not reached
    with pytest.raises(RuntimeError, match="cannot run this deployment"):
        tiny(bench)


def test_a_wrong_reference_fails_as_loudly(bench, monkeypatch):
    out, lines = tiny(bench, cfg={"guarantees": {"decrements": 249}})
    assert out["correct"] is False and out["failed"] == out["attempted"]
    assert compared_of(lines)["reference_decrements_abs_err"] == 1
    real = ref._band

    def one_off(interior, H, W, steps, r0, n):
        block = real(interior, H, W, steps, r0, n)
        block[0, 7] += r0 == 0
        return block

    monkeypatch.setattr(ref, "_band", one_off)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["reference_corner_differing"] == 1
    assert c["grid_differing"] == 1 and c["digest_plain_differs"] == 1


def test_a_configuration_of_another_kind_is_refused(bench):
    with pytest.raises(RuntimeError, match="int32 grids resident"):
        tiny(bench, cfg={"resident": False})
    with pytest.raises(RuntimeError, match="four edge"):
        tiny(bench, cfg={"awaits": [[0, 0], [0, 1], [0, -1]]})


def test_reference_against_the_cell_by_cell_loop():
    rng = np.random.default_rng(SEED)
    for H, W, steps, band in ((17, 23, 2, 4), (40, 9, 5, 64),
                              (8, 128, 8, 8), (30, 30, 1, 7)):
        g = rng.integers(0, 1 << 31, (H, W), dtype=np.int32)  # wraps
        quick = np.concatenate(
            [b.copy() for _, b in ref.sweeps(g, H, W, steps, band)])
        c = min(H, W)
        assert np.array_equal(quick[:c, :c],
                              ref.sweeps_naive(g, H, W, steps, c))
        # one step is forasync's reference
        from benchmarks.reference import forasync as fa
        padded = np.zeros((H + 8, W + 128), np.int32)
        padded[1:H + 1, 1:W + 1] = g
        one = np.concatenate([b.copy() for _, b in fa.sweep(padded, H, W)])
        assert np.array_equal(
            one, np.concatenate(
                [b.copy() for _, b in ref.sweeps(g, H, W, 1, band)]))
    assert ref.loop_counts(32768, 32768, [256, 1024], 8) == {
        "tiles": 32768, "splits": 4095, "released": 28672,
        "decrements": 141120, "executed": 36863}
    assert ref.loop_counts(128, 256, [8, 128], 3) == TINY_COUNTS


def test_configuration_counts_against_the_programs_plan():
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import JAC_AWAITS, jacobi_loop

    cfg = run.load_json(CONFIG)
    g = cfg["guarantees"]
    assert {k: g[k] for k in TINY_COUNTS} == ref.loop_counts(
        cfg["H"], cfg["W"], cfg["tile"], cfg["steps"])
    assert g["executed"] == g["tiles"] + g["splits"] == 36863
    assert cfg["bytes_moved"] == jd_roofline.least_bytes(cfg) == 2 ** 36
    assert cfg["reduced"] == [] and set(cfg["assumed"]) >= {
        "source", "recurrence", "H", "W", "steps", "awaits", "tile",
        "width", "mode", "values"}
    assert sorted(map(tuple, cfg["awaits"])) == sorted(JAC_AWAITS)
    tk, bounds, tile = jacobi_loop(cfg["H"], cfg["W"], *cfg["tile"],
                                   steps=cfg["steps"])
    assert tk.data_specs["grid"].shape == (2, 32784, 33024)
    assert 4 * 2 * 32784 * 33024 == 8_661_270_528  # 54 % of 16 GB
    mk = make_forasync_megakernel(tk, width=cfg["width"], interpret=True,
                                  space=(bounds, tile), verify=False)
    sim = mk.fa_plan.simulate(cfg["width"])
    assert {k: sim[k] for k in ("released", "decrements")} == {
        k: g[k] for k in ("released", "decrements")}
    # the table holds the skewed front, not a row a tile
    assert sim["live_rows_max"] < mk.capacity < 128 < g["tiles"]
    assert sim["mixed_rounds"] >= g["mixed_rounds_min"]
    assert cfg["control"]["fuel"] < g["executed"]


def test_each_reducer_on_a_synthetic_run(bench):
    cfg = run.load_json(CONFIG)
    peaks = run.load_json("benchmarks/peaks.json")["TPU v5 lite"]
    # two calls: a 110 ms kernel in each, the grid remade before it and
    # the digests taken after it inside the window's span
    tr = {"host": [("bench:window", -60e6, 130e6), ("bench:call", 0.0, 112e6),
                   ("bench:window", 140e6, 330e6),
                   ("bench:call", 200e6, 313e6)],
          "device": {0: [("%fusion.1 = x", -50e6, -1e6),
                         ("%tpu_custom_call.1 = x", 1e6, 111e6),
                         ("%reduce_fusion = x", 115e6, 125e6),
                         ("%tpu_custom_call.1 = x", 202e6, 312e6)]}}
    recs = [{"batch_rounds": 4096, "batch_tasks": 32768, "steps": 8,
             "prefetch_hits": 32000, "batch_occupancy": 1.0,
             "mixed_rounds": 3248, "live_rows_max": 88}] * 2
    run_ = reduce.Run(cfg=cfg, records=recs, window_s=1, peaks=peaks,
                      trace=tr)

    def read(name):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        assert set(spec) == {"name", "what", "reducer", "args"}
        return reduce.reducer(spec["reducer"])(run_, **spec["args"])

    assert read("jd_kernel_ms") == pytest.approx(110.0)
    assert read("jd_step_ms") == pytest.approx(110.0 / 8)
    assert read("jd_round_us") == pytest.approx(110e3 / 4096)
    assert read("stage_ms.jd") == pytest.approx((2.0 + 3.0) / 2)
    assert read("jd_occupancy") == pytest.approx(100.0)
    assert read("jd_prefetch_share") == pytest.approx(100 * 32000 / 32768)
    assert read("jd_mixed_share") == pytest.approx(100 * 3248 / 4096)
    assert read("jd_live_rows") == pytest.approx(88.0)
    # 68.7 GB at 819 GB/s is 83.9 ms: 76.3 % of 110 ms
    assert read("jd_roofline") == pytest.approx(
        100 * 2 ** 36 / 819e9 / 110e-3)
    assert 76 < read("jd_roofline") < 77
    # a kernel under another name, or records without the counters (a
    # program that has none), are nothing to read
    tr["device"][0] = [("%uts_dfs.1 = x", 2e6, 15e6)]
    assert [read(k) for k in ("jd_kernel_ms", "jd_step_ms", "jd_round_us",
                              "jd_roofline")] == [None] * 4
    run_.records = [{}]
    assert [read(k) for k in ("jd_occupancy", "jd_prefetch_share",
                              "jd_mixed_share", "jd_live_rows")] == [None] * 4


# ----------------------- what the benchmark had is as it was (PR 51)


def test_every_file_the_benchmark_had_is_byte_identical(bench):
    """Files are added, none edited; ``BENCHMARK.json`` only gained."""
    had = _git("ls-tree", "-r", "--name-only", BASE, "benchmarks").decode()
    assert had.split()
    for path in had.split():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == _git("show", f"{BASE}:{path}"), path
    old = json.loads(_git("show", f"{BASE}:BENCHMARK.json"))
    _only_gained(old, bench)
    cell = run.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jacobi-taskdep", "back-to-back", 1)
    assert len(bench["workloads"]) == len(old["workloads"]) + 1
    assert len(bench["configs"]) == len(old["configs"]) + 1
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == MINE
    assert len(bench["per_layer"]) == len(old["per_layer"]) + len(MINE)
    assert all(m["moves"] == "solve_ms" for m in mine.values())
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]} == MINE | MK
    assert CELL in run.find(bench["end_to_end"], "solve_ms", "metric")[
        "workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
