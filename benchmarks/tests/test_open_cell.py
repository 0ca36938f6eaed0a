"""The cell ``serve-open-steady`` (PR 44) at a tiny size through the
Pallas interpreter, on the CPU, run by hand with the other benchmark
tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite (tests/test_ring_recycle.py
holds the program's half there).
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

from benchmarks import reduce, run, traffic  # noqa: E402
from benchmarks.drivers import open_schedule  # noqa: E402

CELL = "serve-open-steady"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 44 started from: what the benchmark had.
BASE = "43b22321c7ebd6cbe5b10d51b73a89e335fce96c"
SEED = 2**31 + 44
# 8-row regions under 448 requests a stream: gold wraps 32 times, bronze 8.
TINY = {"region_rows": 8, "capacity": 64, "egress_depth": 8,
        "gc_freeze": False}
TINY_MIX = {"requests_per_stream": 448, "rate_per_s": 400}
MINE = {"open_rows_per_entry", "ring_rows_up_per_row", "open_queue_ms",
        "open_sleep_share", "open_late_us", "open_entry_us"}
JOINED = {"settle_us", "pump_us", "launch_us", "wait_us",
          "uploads_per_burst", "submit_us", "entries_per_burst"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None, mix=None):
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = run.run_cell(bench, CELL, SEED, 0.1, traced, CPU,
                           interpret=True, cfg_over={**TINY, **(cfg or {})},
                           mix_over={**TINY_MIX, **(mix or {})})
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


def compared_of(lines):
    return {x["compared"]: (x["value"], x["limit"])
            for x in lines if "compared" in x}


def test_the_cell_tiny_is_correct_and_reports_its_metrics(bench):
    out, lines = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"req_per_s", "p95_ms", "setup_s"}
    assert out["attempted"] >= 3 * 448
    got = compared_of(lines)
    limited = {k: v for k, v in got.items() if v[1] is not None}
    assert all(v <= lim for v, lim in limited.values()), limited
    assert set(limited) == {
        "requests_wrong", "running_sum_abs_err",
        "tenant_lanes_off_contract", "ledgers_not_conserved",
        "streams_not_drained", "executed_minus_requests_minus_1",
        "lanes_wrapped_under_4_times",
    }
    assert {k for k, v in got.items() if v[1] is None} == {
        "schedule_rate_rel_err", "generator_late_p50_us", "generator_late_p99_us",
        "generator_late_max_us",
    }
    assert 0.9 * 400 < out["metrics"]["req_per_s"]["value"] <= 400


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    """A CPU trace has the host's spans and no device plane: every
    per-layer metric of the cell but the kernel's device time."""
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == (MINE | JOINED) - {
        "open_entry_us", "entries_per_burst"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["ring_rows_up_per_row"] == pytest.approx(1.0, abs=0.06)
    assert m["uploads_per_burst"] == 1.0
    assert 0 <= m["open_sleep_share"] <= 100
    assert m["open_rows_per_entry"] > 0 and m["open_queue_ms"] > 0


def test_control_a_lane_deadline_sheds_requests(bench):
    full = run.load_json("benchmarks/configs/serve-3tenant-open.json")
    assert set(full["control"]) == {"deadline_s"} and "deadline_s" not in full
    out, lines = tiny(bench, cfg={"deadline_s": 1e-4},
                      mix={"requests_per_stream": 224, "rate_per_s": 2000})
    assert out["correct"] is False and out["failed"] > 0
    assert compared_of(lines)["requests_wrong"][0] > 0


def test_a_slot_that_is_not_wrapped_fails(bench, monkeypatch):
    """One broken timed path: the host publishes row ``i`` into slot ``i``
    of the ring and not ``i % region`` (what the parent did): the stream
    writes past its region and the run does not come out correct."""
    from hclib_tpu.device import tenants

    def unwrapped(self, lane, ring, room, dirty):
        region, self.region_rows = self.region_rows, 1 << 30
        try:
            return publish(self, lane, ring, room, dirty)
        finally:
            self.region_rows = region

    publish = tenants.TenantTable._publish_run_locked
    monkeypatch.setattr(tenants.TenantTable, "_publish_run_locked",
                        unwrapped)
    try:
        out, _ = tiny(bench)
    except Exception:  # a broken path that raises has failed
        return
    assert out["correct"] is False


def test_schedule_rate_shares_and_determinism():
    mix = traffic.load(ROOT, "poisson-steady")
    assert mix["requests_per_stream"] == 32768 and mix["shares"] == [4, 2, 1]
    due, lane, x = open_schedule.schedule(mix, SEED, 5)
    again = open_schedule.schedule(mix, SEED, 5)
    other = open_schedule.schedule(mix, SEED, 6)
    seed2 = open_schedule.schedule(mix, SEED + 1, 5)
    for a, b in zip((due, lane, x), again):
        assert (a == b).all()
    assert not (due == other[0]).all() and not (due == seed2[0]).all()
    n, rate = 32768, mix["rate_per_s"]
    assert len(due) == len(lane) == len(x) == n
    assert (np.diff(due) > 0).all() and due[0] > 0
    assert due[-1] < n / rate
    assert open_schedule.realised_rate(due) == pytest.approx(rate, rel=1e-3)
    gaps = np.diff(due) * rate  # exponential: mean 1, deviation 1
    assert gaps.mean() == pytest.approx(1.0, rel=0.01)
    assert gaps.std() == pytest.approx(1.0, rel=0.03)
    share = np.bincount(lane, minlength=3) / n
    assert share == pytest.approx([4 / 7, 2 / 7, 1 / 7], abs=0.01)
    # bronze wraps its 1,024-row region four times a stream, gold 18
    assert np.bincount(lane).min() // 1024 >= 4
    assert np.bincount(lane).max() // 1024 >= 17
    assert x.min() >= mix["arg_low"] and x.max() < mix["arg_high"]
    big = open_schedule.schedule(mix, 2**31 + 2**20 + 3, 0)
    assert len(big[0]) == n


def test_reducers_the_cell_brings_return_nothing_on_nothing():
    from benchmarks.reducers import record_percentile, span_share

    host = [("bench:run_stream", 0, 1_000), ("bench:stream.sleep", 100, 350)]
    traced = reduce.Run(cfg={}, records=[], window_s=1.0, peaks={},
                        trace={"host": host, "device": {}})
    assert span_share.reduce(
        traced, "bench:stream.sleep", "bench:run_stream") == 25.0
    assert span_share.reduce(
        traced, "bench:stream.nap", "bench:run_stream") == 0.0
    assert span_share.reduce(
        traced, "bench:stream.sleep", "bench:other") is None
    recs = reduce.Run(cfg={}, records=[{"late_s": [3.0, 1.0]},
                                       {"late_s": [2.0, 4.0]}],
                      window_s=1.0, peaks={})
    assert record_percentile.reduce(recs, "late_s", 50, 10.0) == 20.0
    assert record_percentile.reduce(recs, "late_s", 99, 1.0) == 4.0
    assert record_percentile.reduce(recs, "other", 99, 1.0) is None
    for name in sorted(MINE):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        bare = reduce.Run(cfg={}, records=[{"wall_s": 1.0}], window_s=1.0,
                          peaks={}, trace={"host": [], "device": {}})
        assert reduce.reducer(spec["reducer"])(bare, **spec["args"]) is None


def test_the_benchmark_only_gained(bench):
    """A later ``benchmark`` PR that edits a file moves BASE with it."""
    had = _git("ls-tree", "-r", "--name-only", BASE, "benchmarks").decode()
    assert had.split()
    for path in had.split():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == _git("show", f"{BASE}:{path}"), path
    old = json.loads(_git("show", f"{BASE}:BENCHMARK.json"))
    _only_gained(old, bench)
    assert [c["name"] for c in bench["configs"]][len(old["configs"]):] == [
        "serve-3tenant-open"]
    assert [w["name"] for w in bench["workloads"]][len(old["workloads"]):] == [
        CELL]
    new = bench["per_layer"][len(old["per_layer"]):]
    assert {m["name"] for m in new} == MINE
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "front door"
    cell = run.find(bench["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in JOINED | {"req_per_s", "p95_ms"}:
            assert m["workloads"][-1] == CELL
