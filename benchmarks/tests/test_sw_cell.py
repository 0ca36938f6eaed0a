"""The cell ``sw-wave-8192`` (PR 35) at 256 x 256 through the Pallas
interpreter, on the CPU, run by hand with the other benchmark tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite. An interpreter build of the
wave megakernel costs about half a minute, so the sound runs are made
once for the module.
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
from benchmarks.drivers import sw_run  # noqa: E402
from benchmarks.reducers import sw_roofline  # noqa: E402
from benchmarks.reference import sw as ref  # noqa: E402

CELL = "sw-wave-8192"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 35 started from: what the benchmark had.
BASE = "3703b4eea8c7b6fb2b0b10c79715247dce69a5fd"
SEED = 2**31 + 35
# 2 x 2 tiles: 3 waves of 1, 2 and 1 tiles, one descriptor each.
TINY = {"n": 256, "m": 256}
TINY_COUNTS = {"tiles": 4, "descriptors": 3, "waves": 3, "csr_words": 0}
MINE = {"stage_ms.sw", "sw_kernel_ms", "sw_round_us", "sw_occupancy",
        "sw_prefetch_share", "sw_build_ms", "sw_roofline"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    over = {**TINY, **(cfg or {})}
    full = run.load_json("benchmarks/configs/sw-wave.json")["guarantees"]
    over["guarantees"] = {**full, **TINY_COUNTS,
                          **over.get("guarantees", {})}
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = run.run_cell(bench, CELL, SEED, 0.1, traced, CPU,
                           interpret=True, cfg_over=over)
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


@pytest.fixture(scope="module")
def sound(bench):
    return tiny(bench)


def test_cell_is_correct_and_every_compared_number_is_zero(sound):
    out, lines = sound
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    compared = {x["compared"]: x for x in lines if "compared" in x}
    assert len(compared) == 15  # eight a call, seven of the reference
    assert all(x["value"] == 0 and x["limit"] == 0
               for x in compared.values())
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert reference["corner"] == 256 and reference["tiles"] == 4
    assert reference["score"] == reference["corner_score"] > 0


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the span and the counters are read.
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"sw_occupancy", "sw_prefetch_share",
                                   "sw_build_ms"}
    assert 0 < out["metrics"]["sw_occupancy"]["value"] <= 100
    assert 0 <= out["metrics"]["sw_prefetch_share"]["value"] <= 100
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:sw.build", "bench:sw.stage", "bench:sw.run",
                     "bench:sw.readback"}


def test_one_tile_with_its_bottom_row_zeroed_is_not_correct(
        bench, monkeypatch):
    """The timed path broken where no score shows it: the bottom row of
    the last tile row's first tile."""
    from hclib_tpu.device import smithwaterman as sw

    real = sw._sw_result

    def zeroed(n, m, ivalues, out, info, dt):
        out = {**out, "bot": np.asarray(out["bot"]).copy()}
        out["bot"][-1, 0] = 0
        return real(n, m, ivalues, out, info, dt)

    monkeypatch.setattr(sw, "_sw_result", zeroed)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    compared = {x["compared"]: x["value"] for x in lines if "compared" in x}
    assert compared["score_abs_err"] == 0
    assert 0 < compared["last_row_differing"] <= 128
    assert compared["last_col_differing"] == 0


def test_a_program_without_the_boundary_vectors_is_refused(
        bench, monkeypatch):
    """The parent of PR 35: the driver raises from the warm call."""
    from hclib_tpu.device import smithwaterman as sw

    real = sw._sw_result

    def dropped(*args):
        score, h, info = real(*args)
        del info["last_row"], info["last_col"]
        return score, h, info

    monkeypatch.setattr(sw, "_sw_result", dropped)
    with pytest.raises(RuntimeError, match="cannot run this deployment"):
        tiny(bench)


def test_a_wrong_reference_fails_as_loudly(bench, monkeypatch):
    out, _ = tiny(bench, cfg={"guarantees": {"descriptors": 4}})
    assert out["correct"] is False and out["failed"] == out["attempted"]
    real = ref.sw_last

    def one_off(a, b, **kw):
        r = real(a, b, **kw)
        return {**r, "last_col": r["last_col"] + (np.arange(len(a)) == 7)}

    # the driver's set-up and its corner check both read the module
    monkeypatch.setattr(ref, "sw_last", one_off)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    compared = {x["compared"]: x["value"] for x in lines if "compared" in x}
    assert compared["reference_corner_last_col_abs_err"] == 1


def test_a_configuration_that_states_another_engine_is_refused(bench):
    with pytest.raises(RuntimeError, match="the engine runs"):
        tiny(bench, cfg={"chunk": 4})


def test_control_a_banded_alignment(bench):
    """The configuration's control at the tiny size: a band of 0 tiles
    runs the two diagonal tiles of four. The driver takes ``band_tiles``
    only from the configuration's top level."""
    full = run.load_json("benchmarks/configs/sw-wave.json")
    assert set(full["control"]) == {"band_tiles"}
    assert "band_tiles" not in full
    out, lines = tiny(bench, cfg={"band_tiles": 0})
    assert out["correct"] is False and out["failed"] == out["attempted"]
    compared = {x["compared"]: x["value"] for x in lines if "compared" in x}
    assert compared["executed_abs_err"] == 2
    assert compared["batch_tasks_abs_err"] == 1
    assert compared["last_row_differing"] > 0
    assert compared["last_col_differing"] > 0
    assert compared["pending"] == 0 and compared["scalar_tasks"] == 0


def _args(builder):
    """``[w, lo, count]`` of every descriptor of a finalized graph."""
    from hclib_tpu.device.descriptor import F_A0

    tasks = builder.finalize()[0]
    return [tuple(int(x) for x in row[F_A0:F_A0 + 3])
            for row in tasks[:builder.num_tasks]]


def test_the_band_graph_at_the_cells_size():
    b = sw_run._band_graph(64, 64, 8, 4)
    tiles = {(lo + s, w - lo - s) for w, lo, cnt in _args(b)
             for s in range(cnt)}
    assert tiles == {(i, j) for i in range(64) for j in range(64)
                     if abs(i - j) <= 4}
    assert len(tiles) == 556 and b.num_tasks == 127


def test_configuration_counts_against_the_programs_graph():
    from hclib_tpu.device.smithwaterman import build_sw_wave_graph

    cfg = run.load_json("benchmarks/configs/sw-wave.json")
    nt = cfg["n"] // cfg["tile"], cfg["m"] // cfg["tile"]
    assert nt == (64, 64) and cfg["cell_updates"] == cfg["n"] * cfg["m"]
    b = build_sw_wave_graph(*nt)
    g = cfg["guarantees"]
    assert b.num_tasks == g["descriptors"] == 568
    assert sum(a[2] for a in _args(b)) == g["tiles"] == 4096
    assert len({a[0] for a in _args(b)}) == g["waves"] == 127
    assert sum(max(0, len(s) - 2) for s in b._succs) == g[
        "csr_words"] == 2074
    assert ref.wave_counts(*nt, cfg["chunk"]) == {
        k: g[k] for k in ("tiles", "descriptors", "waves", "csr_words")}
    # the rows of the table the larger pairs would take (PERF.md section 4)
    assert [ref.wave_counts(k, k, 8)["descriptors"]
            for k in (80, 96, 128)] == [870, 1236, 2160]


def test_reference_against_the_naive_recurrence():
    rng = np.random.default_rng(SEED)
    pairs = [ref.make_pair(SEED + k, 70 + k, 90 - k) for k in range(3)]
    pairs.append((np.zeros(40, np.int32), np.zeros(50, np.int32)))
    pairs.append((np.zeros(40, np.int32), np.ones(50, np.int32)))
    pairs.append((rng.integers(0, 2, 33, dtype=np.int32),) * 2)
    for a, b in pairs:
        h, r = ref.sw_naive(a, b), ref.sw_last(a, b)
        assert r["score"] == h.max()
        assert np.array_equal(r["last_row"], h[-1])
        assert np.array_equal(r["last_col"], h[:, -1])
    assert ref.sw_last(*pairs[3])["score"] == 2 * 40  # all matches
    assert ref.sw_last(*pairs[4])["score"] == 0  # none
    a, b = ref.make_pair(SEED, 8192, 8192)
    assert a.shape == b.shape == (8192,) and a.dtype == np.int32
    assert set(np.unique(a)) == {0, 1, 2, 3} and not np.array_equal(a, b)


def test_each_reducer_on_a_synthetic_run(bench):
    cfg = run.load_json("benchmarks/configs/sw-wave.json")
    # two calls: 50 ms spans, a 35 ms kernel event and a 1 ms copy in each
    tr = {"host": [("bench:call", 0.0, 50e6), ("bench:call", 60e6, 110e6),
                   ("bench:sw.build", 1e6, 2e6),
                   ("bench:sw.build", 61e6, 63e6)],
          "device": {0: [("%tpu_custom_call.1 = x", 5e6, 40e6),
                         ("%copy.1 = x", 41e6, 42e6),
                         ("%tpu_custom_call.1 = x", 65e6, 100e6),
                         ("%copy.1 = x", 101e6, 102e6)]}}
    recs = [{"batch_rounds": 316, "batch_tasks": 568, "prefetch_hits": 330,
             "batch_occupancy": 568 / 632}] * 2
    run_ = reduce.Run(cfg=cfg, records=recs, window_s=1, peaks={}, trace=tr)

    def read(name):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        return reduce.reducer(spec["reducer"])(run_, **spec["args"])

    assert read("stage_ms.sw") == pytest.approx(14.0)
    assert read("sw_kernel_ms") == pytest.approx(35.0)
    assert read("sw_round_us") == pytest.approx(35e3 / 316)
    assert read("sw_occupancy") == pytest.approx(100 * 568 / 632)
    assert read("sw_prefetch_share") == pytest.approx(100 * 330 / 568)
    assert read("sw_build_ms") == pytest.approx(1.5)
    # a device kind the table lacks is an error, not a default
    with pytest.raises(RuntimeError, match="no row"):
        read("sw_roofline")
    # a kernel under another name, or records without the counters, are
    # nothing to read
    tr["device"][0] = [("%uts_dfs.1 = x", 5e6, 40e6)]
    assert [read(k) for k in ("sw_kernel_ms", "sw_round_us",
                              "sw_roofline")] == [None] * 3
    run_.records = [{}]
    assert read("sw_occupancy") is None
    assert read("sw_prefetch_share") is None


def test_roofline_is_the_recurrence_as_written_over_the_vpu_peak():
    # compare, select, add, two subtractions, three maxima, running best
    assert sw_roofline.cell_update_ops() == 1 + 1 + 1 + 2 + 3 + 1 == 9
    cfg = run.load_json("benchmarks/configs/sw-wave.json")
    spec = run.load_json("benchmarks/metrics/sw_roofline.json")
    least = sw_roofline.least_seconds(cfg, "TPU v5 lite",
                                      spec["args"]["peak"])
    assert least == 8192 * 8192 * 9 / 6.144e12
    assert 0.09e-3 < least < 0.1e-3
    assert 100 * least / 35e-3 < 1  # the chain, not the VPU, is the ceiling


# ----------------------- what the benchmark had is as it was (PR 35)


def test_every_file_the_benchmark_had_is_byte_identical(bench):
    """Files are added, none edited; ``BENCHMARK.json`` only gained."""
    had = _git("ls-tree", "-r", "--name-only", BASE, "benchmarks").decode()
    assert had.split()
    for path in had.split():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == _git("show", f"{BASE}:{path}"), path
    _only_gained(json.loads(_git("show", f"{BASE}:BENCHMARK.json")), bench)
    cell = run.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sw-wave", "back-to-back", 1)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == MINE
    assert all(m["moves"] == "solve_ms" for m in mine.values())
    assert run.find(bench["end_to_end"], "solve_ms", "metric")[
        "workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == len(bench["configs"]) == 6
