"""The cell ``g500-bfs-search`` (PR 48) at Graph500's scale 8 through the
Pallas interpreter, on the CPU, run by hand with the other benchmark
tests:

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
from benchmarks.reducers import g5_roofline  # noqa: E402
from benchmarks.reference import graph500 as ref  # noqa: E402

CELL = "g500-bfs-search"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 48 started from: what the benchmark had.
BASE = "90363c8ed323a7d1cd0534139491401f7d0fce84"
SEED = 2**31 + 48
TINY = {"scale": 8, "capacity": 32, "width": 4}
MINE = {"g5_kernel_ms", "g5_round_us", "g5_edge_ns", "g5_teps",
        "g5_rework", "g5_occupancy", "g5_live_rows", "g5_frontier_max",
        "stage_ms.g5", "g5_roofline"}
MK = {"mk_finalize_ms", "mk_upload_ms", "mk_launch_ms", "mk_tail_ms"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = run.run_cell(bench, CELL, SEED, 0.1, traced, CPU,
                           interpret=True, cfg_over={**TINY, **(cfg or {})})
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


def compared_of(lines):
    return {x["compared"]: x["value"] for x in lines if "compared" in x}


def test_cell_is_correct_and_every_compared_number_is_zero(bench):
    out, lines = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    compared = [x for x in lines if "compared" in x]
    assert len(compared) == 14  # six a search, eight of the reference
    assert all(x["value"] == 0 and x["limit"] == 0 for x in compared)
    assert {x["compared"] for x in compared} >= set(ref.RULES) | {
        "levels_differ", "pending", "table_filled"}
    (k1,) = [x["kernel1"] for x in lines if "kernel1" in x]
    assert k1["vertices"] == 256 and k1["tuples"] == 16 * 256
    (held,) = [x["reference"] for x in lines if "reference" in x]
    assert held["searches_held"] == 4  # the newest and a reservoir of 3


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the spans and the counters are read.
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True and out["attempted"] == 2
    assert set(out["metrics"]) == {
        "g5_teps", "g5_rework", "g5_occupancy", "g5_live_rows",
        "g5_frontier_max", "mk_finalize_ms", "mk_upload_ms", "mk_launch_ms"}
    assert out["metrics"]["g5_rework"]["value"] == 1.0
    assert 0 < out["metrics"]["g5_live_rows"]["value"] < 32
    assert out["metrics"]["g5_frontier_max"]["value"] > 32
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:g500.seed", "bench:g500.search",
                     "bench:g500.readback", "bench:mk.wait"}


def test_control_a_task_budget_below_a_search_raises(bench):
    from hclib_tpu.runtime.resilience import StallError

    full = run.load_json("benchmarks/configs/graph500-bfs.json")
    assert full["control"] == {"fuel": 65536} and "fuel" not in full
    with pytest.raises(StallError, match="pending"):
        tiny(bench, cfg={"fuel": 16})


def test_a_search_that_stops_a_level_early_is_not_correct(
        bench, monkeypatch):
    from hclib_tpu.device import frontier

    real = frontier.GraphSearch.bfs

    def a_level_short(self, key):
        parent, info = real(self, key)
        depth, _ = ref.levels_of_tree(parent, key)
        parent[depth == depth.max()] = -1
        return parent, info

    monkeypatch.setattr(frontier.GraphSearch, "bfs", a_level_short)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["rule4_span"] > 0 and c["levels_differ"] > 0
    assert c["reached_abs_err"] > 0 and c["levels_abs_err"] == 1
    assert c["rule1_tree"] == c["rule3_level_gap"] == 0
    assert c["rule5_not_an_edge"] == c["pending"] == 0


def test_a_parent_that_is_no_neighbour_is_not_correct(bench, monkeypatch):
    from hclib_tpu.device import frontier

    real = frontier.GraphSearch.bfs

    def one_wrong_parent(self, key):
        parent, info = real(self, key)
        depth, _ = ref.levels_of_tree(parent, key)
        kids = set(parent[parent >= 0].tolist())
        for x in np.flatnonzero(depth >= 2):  # a leaf, so no depth moves
            joined = set(self.graph.adj[x].tolist())
            for y in np.flatnonzero(depth == depth[x] - 1):
                if int(x) not in kids and int(y) not in joined:
                    parent[x] = y
                    return parent, info
        raise AssertionError("no leaf with a stranger a level up")

    monkeypatch.setattr(frontier.GraphSearch, "bfs", one_wrong_parent)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["rule5_not_an_edge"] == 1
    assert not any(v for k, v in c.items() if k != "rule5_not_an_edge")


def test_a_program_without_the_search_is_refused(bench, monkeypatch):
    """The parent of PR 48: the driver raises before any data is made."""
    from hclib_tpu.device import frontier

    monkeypatch.delattr(frontier, "GraphSearch")
    monkeypatch.setattr(ref, "edge_list", None)  # never reached
    with pytest.raises(RuntimeError, match="cannot run this deployment"):
        tiny(bench)


def test_a_configuration_of_another_kind_is_refused(bench):
    with pytest.raises(RuntimeError, match="undirected graphs"):
        tiny(bench, cfg={"undirected": False})


def test_configuration_states_the_specification_and_the_cut():
    cfg = run.load_json("benchmarks/configs/graph500-bfs.json")
    assert cfg["edgefactor"] == 16 and cfg["search_keys"] == 64
    assert cfg["initiator"] == [0.57, 0.19, 0.19, 0.05]
    assert cfg["reduced"] == ["scale"] and 20 <= cfg["scale"] <= 22
    assert set(cfg["published"]) == {"classes", "step", "rule"}
    assert set(cfg["assumed"]) >= {"specification", "draws", "shuffle",
                                   "component_edges", "capacity", "control"}
    assert len(cfg["source"]) <= 200
    assert set(cfg["guarantees"]) == {"rules", "levels", "books", "answer"}


def test_each_reducer_on_a_synthetic_run(bench):
    cfg = run.load_json("benchmarks/configs/graph500-bfs.json")
    peaks = run.load_json("benchmarks/peaks.json")["TPU v5 lite"]
    # two searches: 4.1 s calls with a 4 s kernel event in each
    tr = {"host": [("bench:window", 0.0, 4.2e9), ("bench:call", 0.0, 4.1e9),
                   ("bench:window", 5e9, 9.2e9), ("bench:call", 5e9, 9.1e9)],
          "device": {0: [("%tpu_custom_call.1 = x", 0.05e9, 4.05e9),
                         ("%tpu_custom_call.1 = x", 5.05e9, 9.05e9)]}}
    rec = {"wall_s": 4.1, "edges": 2 * 67_000_000, "batch_rounds": 400_000,
           "batch_occupancy": 0.5, "live_rows_max": 121,
           "frontier_max": 1_500_000, "component_edges": 67_000_000,
           "component_entries": 2 * 67_000_000,
           "reached_by_reference": 2_400_000}
    run_ = reduce.Run(cfg=cfg, records=[rec, {**rec, "wall_s": 8.2}],
                      window_s=1, peaks=peaks, trace=tr)

    def read(name):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        assert set(spec) == {"name", "what", "reducer", "args"}
        return reduce.reducer(spec["reducer"])(run_, **spec["args"])

    assert read("g5_kernel_ms") == pytest.approx(4000.0)
    assert read("g5_round_us") == pytest.approx(4e6 / 400_000)
    assert read("g5_edge_ns") == pytest.approx(4e9 / 134e6)
    assert read("stage_ms.g5") == pytest.approx(100.0)
    assert read("g5_occupancy") == pytest.approx(50.0)
    assert read("g5_live_rows") == pytest.approx(121.0)
    assert read("g5_frontier_max") == pytest.approx(1.5e6)
    assert read("g5_rework") == pytest.approx(1.0)
    # the harmonic mean of 67 M / 4.1 s and 67 M / 8.2 s
    assert read("g5_teps") == pytest.approx(2 * 67e6 / (4.1 + 8.2))
    # 8 B a tuple + 4 B a vertex = 545.6 MB at 819 GB/s is 0.666 ms
    assert g5_roofline.least_bytes(67_000_000, 2_400_000) == 545_600_000
    assert read("g5_roofline") == pytest.approx(
        100 * 545.6e6 / 819e9 / 4.0)
    assert 0 < read("g5_roofline") < 100
    # a kernel under another name, or records without the counters or the
    # reference's counts (the parent's), are nothing to read
    tr["device"][0] = [("%uts_dfs.1 = x", 2e6, 15e6)]
    assert [read(k) for k in ("g5_kernel_ms", "g5_round_us", "g5_edge_ns",
                              "g5_roofline")] == [None] * 4
    run_.records = [{"wall_s": 1.0}]
    assert [read(k) for k in ("g5_occupancy", "g5_live_rows", "g5_teps",
                              "g5_frontier_max", "g5_rework")] == [None] * 5


# ----------------------- what the benchmark had is as it was (PR 48)


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
        "graph500-bfs", "search-keys-64", 1)
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
