"""The program build layer's five metrics (ISSUE 53) on the line of one
traced run: ``fib30-scalar`` at a tiny size through the interpreter, on
the CPU, down ``--trace 1``'s path. Run by hand with the rest of
``benchmarks/tests``; not tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmarks import reduce, run  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
FIVE = ["build_trace_s", "build_lower_s", "build_compile_s", "build_traces",
        "window_builds"]


@pytest.fixture(scope="module")
def line():
    bench = run.load_json("BENCHMARK.json")
    return bench, run.run_cell(
        bench, "fib30-scalar", 2**31 + 53, 0.2, True, CPU, interpret=True,
        cfg_over={"n": 12, "fuel": 1 << 16})


def test_the_traced_line_carries_the_five(line):
    bench, out = line
    assert out["correct"] is True
    got = out["metrics"]
    assert set(FIVE) <= set(got)
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert all(got[n]["unit"] == units[n] for n in FIVE)
    # the warm call built the kernel; the window built nothing
    assert got["window_builds"]["value"] == 0
    assert got["build_traces"]["value"] >= 1
    assert min(got[n]["value"] for n in FIVE[:3]) > 0


def test_every_cell_reports_the_five_and_they_move_set_up(line):
    bench, _ = line
    cells = [w["name"] for w in bench["workloads"]]
    for name in FIVE:
        m = run.find(bench["per_layer"], name, "metric")
        assert (m["layer"], m["moves"]) == ("program build", "setup_s")
        assert all(run.reports(m, c, bench) for c in cells)


def test_a_program_without_a_ledger_is_nothing_to_read(line, monkeypatch):
    """The benchmark's files are laid over the parent's checkout too:
    there ``progcache`` has no ledger, and the reader must not raise."""
    from hclib_tpu.runtime import progcache

    monkeypatch.delattr(progcache, "build_ledger")
    run_ = reduce.Run(cfg={}, records=[], window_s=1.0, peaks={})
    assert reduce.reducer("build_ledger")(run_, field="trace_s") is None
