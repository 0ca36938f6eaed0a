"""The cell ``uts-t1l`` (PR 29) at a tiny tree through the Pallas
interpreter, on the CPU, run by hand with the other benchmark tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite.
"""

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmarks import reduce, run  # noqa: E402
from benchmarks.reducers import uts_roofline  # noqa: E402

CELL = "uts-t1l"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 29 started from: what the benchmark had.
BASE = "f642427b476cf361b1b61e961891a16197109750"
# Upstream's small sample T3 of the tests (-t 1 -a 3 -d 5 -b 4 -r 42); its
# counts are tests/test_uts_reference.py's, from a hashlib traversal.
TINY = {
    "tree": {"shape": "FIXED", "gen_mx": 5, "b0": 4, "root_seed": 42},
    "target_roots": 64, "lanes": [8, 128], "min_idle_div": 8,
    "guarantees": {"nodes": 1279, "leaves": 1018, "depth": 5},
    "hashed_nodes": 317,
}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    return run.run_cell(bench, CELL, 2**31 + 29, 0.2, traced, CPU,
                        interpret=True, cfg_over={**TINY, **(cfg or {})})


def test_cell_is_correct_and_reports_solve_ms_and_setup_s(bench, capsys):
    out = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    compared = {x["compared"]: x for x in lines if "compared" in x}
    assert len(compared) == 9  # five a call, four of the reference
    assert all(x["value"] == 0 and x["limit"] == 0
               for x in compared.values())
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert reference["nodes"] == 1279 and reference["seconds"] > 0


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the span and the counters are read.
    out = tiny(bench, traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"uts_seed_ms", "uts_lane_share"}
    assert 0 < out["metrics"]["uts_lane_share"]["value"] < 100
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:uts.seed", "bench:uts.stage", "bench:uts.run",
                     "bench:uts.readback"}


def test_one_lane_counting_one_node_more_is_not_correct(bench, monkeypatch):
    """The timed path broken where the answer is produced."""
    from hclib_tpu.device import uts_pallas as up

    real = up._uts_dfs_pallas

    def one_more(*args, **kw):
        nodes, *rest = real(*args, **kw)
        return (nodes.at[0, 0].add(1), *rest)

    monkeypatch.setattr(up, "_uts_dfs_pallas", one_more)
    out = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_a_wrong_reference_fails_as_loudly(bench):
    out = tiny(bench, cfg={"hashed_nodes": 318})
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_control_traversal_that_stops_early(bench):
    """The configuration's control: a step budget below the steps the
    traversal takes. The driver passes ``max_steps`` only when the
    configuration has the key. It raises or comes out false."""
    control = run.load_json("benchmarks/configs/uts-t1l.json")["control"]
    assert set(control) == {"max_steps"}
    try:
        out = tiny(bench, cfg={"max_steps": 2})
    except RuntimeError as e:  # a control that raises has failed
        assert "ran out of steps" in str(e)
        return
    assert out["correct"] is False


def test_roofline_is_the_unavoidable_hashes_over_the_vpu_peak(bench):
    assert uts_roofline.sha1_compression_ops() == 1449
    cfg = run.load_json("benchmarks/configs/uts-t1l.json")
    # a kernel event of 45 ms inside one call span
    tr = {"host": [("bench:call", 0.0, 1e9)],
          "device": {0: [("%uts_dfs.1 = x", 100.0, 100.0 + 45e6)]}}
    run_ = reduce.Run(cfg=cfg, records=[], window_s=1, peaks={}, trace=tr)
    spec = run.load_json("benchmarks/metrics/uts_roofline.json")
    least = uts_roofline.least_seconds(cfg, "TPU v5 lite",
                                       spec["args"]["peak"])
    assert least == cfg["hashed_nodes"] * 1449 / 6.144e12
    assert 100 * least / 45e-3 < 100
    # every hash of the tree but the root's, at that kernel time
    all_hashed = {**cfg, "hashed_nodes": cfg["guarantees"]["nodes"] - 1}
    assert uts_roofline.least_seconds(
        all_hashed, "TPU v5 lite", spec["args"]["peak"]) < 45e-3
    # a device kind the table lacks is an error, not a default
    with pytest.raises(RuntimeError, match="no row"):
        reduce.reducer(spec["reducer"])(run_, **spec["args"])
    # and a kernel under another name is nothing to read
    tr["device"][0] = [("%tpu_custom_call.1 = x", 100.0, 200.0)]
    assert reduce.reducer(spec["reducer"])(run_, **spec["args"]) is None


# ----------------------- what the benchmark had is as it was (PR 29)


def _git(*args) -> bytes:
    try:
        return subprocess.run(
            ["git", "-C", ROOT, *args], check=True, capture_output=True,
        ).stdout
    except (OSError, subprocess.CalledProcessError) as e:
        pytest.skip(f"no git history to compare with: {e}")


def _only_gained(old, new, where="BENCHMARK.json"):
    """``new`` is ``old`` with entries appended to lists and nothing else."""
    if isinstance(old, dict):
        assert isinstance(new, dict) and set(old) == set(new), where
        for k in old:
            _only_gained(old[k], new[k], f"{where}.{k}")
    elif isinstance(old, list):
        assert isinstance(new, list) and len(new) >= len(old), where
        for i, x in enumerate(old):
            _only_gained(x, new[i], f"{where}[{i}]")
    else:
        assert old == new, where


def test_every_file_the_benchmark_had_is_byte_identical(bench):
    """A later ``benchmark`` PR that edits a file moves BASE with it."""
    had = _git("ls-tree", "-r", "--name-only", BASE, "benchmarks").decode()
    assert had.split()
    for path in had.split():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == _git("show", f"{BASE}:{path}"), path
    _only_gained(json.loads(_git("show", f"{BASE}:BENCHMARK.json")), bench)
    cell = run.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "back-to-back", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"uts_seed_ms", "stage_ms.uts", "uts_kernel_ms",
                    "uts_node_ns", "uts_lane_share", "uts_roofline"}
    assert all(m["moves"] == "solve_ms" for m in bench["per_layer"]
               if m["name"] in mine)
