"""The benchmark's own tests: run by hand on the CPU,

    python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 suite. Drivers run here at a
tiny size through the Pallas interpreter, which only a test may ask for.
"""

import copy
import json
import os
import re
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks import reduce, run, trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

# A tiny size of each cell, for the interpreter: (config, mix) overrides.
TINY = {
    "fib30-scalar": ({"n": 12, "fuel": 1 << 16}, None),
    "cholesky-8192": (
        {"n": 256, "tile": 128,
         # the interpreter's f32 at this size reads 2e-6 (tier-1's own
         # test_device_cholesky_interpret allows 1e-5)
         "guarantees": {"residual_limit": 1e-5}},
        None,
    ),
    "serve-burst-3072": ({"region_rows": 16, "capacity": 64},
                         {"requests_per_tenant": 16}),
}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, cell, traced=False, cfg=None, seed=2**31 + 7):
    c, m = TINY[cell]
    return run.run_cell(bench, cell, seed, 0.2, traced, CPU, interpret=True,
                        cfg_over={**c, **(cfg or {})}, mix_over=m)


# ------------------------------------------------------------ the files


def test_files_exist_and_names_are_allowed(bench):
    assert bench["paths"] == ["benchmarks"]
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        cfg = run.load_json(c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "drivers", cfg["driver"] + ".py"))
    pairs = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        run.find(bench["configs"], w["config"], "configuration")
        run.load_json("benchmarks", "traffic", w["traffic"] + ".json")
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        if m["name"] != "setup_s":
            spec = run.load_json("benchmarks", "metrics", m["name"] + ".json")
            assert spec["name"] == m["name"]
            assert callable(reduce.reducer(spec["reducer"]))
    for root, _, files in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (root, f)


def test_every_cell_reports_what_its_metrics_move(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            assert run.reports(e2e[m["moves"]], cell, bench), (m["name"], cell)
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if run.reports(m, w["name"], bench)]
        assert len(mine) >= 2, w["name"]  # setup_s and one more
        assert any(run.reports(m, w["name"], bench)
                   for m in bench["per_layer"]), w["name"]


def test_refuses_anything_but_a_listed_tpu():
    peaks = run.load_json("benchmarks", "peaks.json")
    with pytest.raises(RuntimeError, match="no TPU"):
        run.device_record(1, peaks)  # JAX is held to the CPU here
    with pytest.raises(RuntimeError, match="no TPU"):
        run.main(["--workload", "fib30-scalar", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])


# ---------------------------------- the drivers, sound and with a fault


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_runs_and_is_correct_at_a_tiny_size(bench, cell):
    out = tiny(bench, cell)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    want = {m["name"] for m in bench["end_to_end"]
            if run.reports(m, cell, bench)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert sorted(out) == ["attempted", "correct", "device", "failed",
                           "metrics"]


def test_traced_run_has_the_traced_keys(bench):
    # No device plane on the CPU: the per-layer readers that need one find
    # nothing to read and are left out; the host-span reader reads.
    out = tiny(bench, "serve-burst-3072", traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"submit_us"}
    assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace",
                              f"serve-burst-3072.{os.getpid()}"))


def test_fib_value_off_by_one_is_not_correct(bench, monkeypatch):
    """The timed path broken where the answer is produced."""
    from hclib_tpu.device.megakernel import Megakernel

    real = Megakernel.run

    def off_by_one(self, *a, **kw):
        iv, data, info = real(self, *a, **kw)
        iv = np.array(iv)
        iv[0] += 1
        return iv, data, info

    monkeypatch.setattr(Megakernel, "run", off_by_one)
    out = tiny(bench, "fib30-scalar")
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_cholesky_tile_perturbed_is_not_correct(bench, monkeypatch):
    from hclib_tpu.device import cholesky

    real = cholesky.device_cholesky

    def perturbed(a, **kw):
        L, info = real(a, **kw)
        L = L.copy()
        L[128:256, 0:128] += 1e-3  # one tile, by 1e-3
        return L, info

    monkeypatch.setattr(cholesky, "device_cholesky", perturbed)
    out = tiny(bench, "cholesky-8192")
    assert out["correct"] is False and out["failed"] >= 1


def test_one_changed_future_is_not_correct(bench, monkeypatch):
    from hclib_tpu.device.egress import Future

    real = Future._finish

    def changed(self, state, value=None, **kw):
        if self.token == 5 and value is not None:
            value += 1
        return real(self, state, value=value, **kw)

    monkeypatch.setattr(Future, "_finish", changed)
    out = tiny(bench, "serve-burst-3072")
    assert out["correct"] is False
    bursts = out["attempted"] // 48
    assert out["failed"] == bursts  # token 5 of every burst


# -------------------------------- the controls (see PERF.md, "correct")


def test_control_fib_cut_fuel(bench):
    """The configuration's control: a task budget below the task count
    breaks 'executed exactly / pending 0'. It stalls or comes out false."""
    try:
        out = tiny(bench, "fib30-scalar", cfg={"fuel": 256})
    except Exception:  # a control that crashes has failed
        return
    assert out["correct"] is False


def test_control_cholesky_one_precision_lower(bench, monkeypatch):
    """The reference's blocked factorisation in the program's place, with
    bfloat16 products: the residual passes the limit. In float32 it holds,
    so the limit separates the two."""
    from benchmarks.reference import cholesky as ref
    from hclib_tpu.device import cholesky

    real = cholesky.device_cholesky
    residual = {}
    for precision in ("float32", "bfloat16"):
        def in_place(a, tile, **kw):
            _, info = real(a, tile=tile, **kw)
            return ref.blocked_cholesky(a, tile, precision), info

        monkeypatch.setattr(cholesky, "device_cholesky", in_place)
        out = tiny(bench, "cholesky-8192")
        residual[precision] = out["correct"]
    assert residual == {"float32": True, "bfloat16": False}


def test_control_serve_sheds_the_tail(bench):
    """A lane deadline sheds what has waited too long: faster to drain,
    and it breaks 'nothing expired, every request RESULT'."""
    out = tiny(bench, "serve-burst-3072", cfg={"deadline_s": 1e-4})
    assert out["correct"] is False and out["failed"] > 0


# --------------------------- adding a cell as new files, no file edited


DRIVER = '''
def setup(cfg, mix, seed, interpret):
    return {"n": cfg["n"], "interpret": interpret}
def operation(st):
    return {"wall_s": 1.0, "attempted": 1,
            "work": st["n"], "interpret": st["interpret"],
            "platform": "cpu"}
def check(st, records):
    return 0, [("nothing", 0, 0)]
'''
REDUCER = '''
def reduce(run, field):
    return float(sum(r[field] for r in run.records))
'''


def test_a_cell_is_added_as_new_files(bench, tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    b = root / "benchmarks"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy_driver", "n": 7, "reduced": []}))
    (b / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"name": "toy-mix", "loop": "closed", "trace_ops": 1}))
    (b / "metrics" / "toy_work.json").write_text(json.dumps(
        {"name": "toy_work", "reducer": "toy_sum",
         "args": {"field": "work"}}))
    (b / "drivers" / "toy_driver.py").write_text(DRIVER)
    (b / "reducers" / "toy_sum.py").write_text(REDUCER)
    new = copy.deepcopy(bench)
    new["configs"].append({"name": "toy", "source": "a test",
                           "file": "benchmarks/configs/toy.json",
                           "reduced": [], "why": "throwaway"})
    new["workloads"].append({"name": "toy-cell", "config": "toy",
                             "traffic": "toy-mix", "chips": 1,
                             "why": "throwaway"})
    new["end_to_end"].append({"name": "toy_work", "unit": "count",
                              "better": "higher", "bound": 0.01,
                              "source": "program_counter",
                              "workloads": ["toy-cell"]})
    import benchmarks.drivers
    import benchmarks.reducers
    monkeypatch.setattr(run, "ROOT", str(root))
    monkeypatch.setattr(benchmarks.drivers, "__path__",
                        benchmarks.drivers.__path__ + [str(b / "drivers")])
    monkeypatch.setattr(benchmarks.reducers, "__path__",
                        benchmarks.reducers.__path__ + [str(b / "reducers")])
    out = run.run_cell(new, "toy-cell", 1, 0.0, False, CPU, interpret=True)
    assert out["correct"] and out["metrics"]["toy_work"]["value"] == 21.0
    assert all(p.read_bytes() == data for p, data in before.items())
    # and the cells that were there report what they did
    assert not run.reports(new["end_to_end"][-1], "fib30-scalar", new)


# ------------- the reduction, on traces recorded on the chip (PR 26)


def recorded(cell):
    return trace.read(os.path.join(HERE, "data", cell + ".xplane.pb"))


def window(tr):
    win = [e for e in tr["host"] if e[0] == "bench:window"]
    return win[0][1], win[-1][2]


def test_trace_of_two_fib_calls_reads_as_worked_out_by_hand():
    """Two calls of fib(30): by hand from the raw events, the window is
    1,040,365,032 ns, some operation ran on the chip for 1,032,376,839 ns
    of it, and the two kernel launches took 1,032,372,847 ns."""
    tr = recorded("fib30-scalar")
    t0, t1 = window(tr)
    dev = tr["device"][0]
    assert t1 - t0 == 1040365032 and len(dev) == 36
    assert trace.busy_ns(dev, t0, t1) == 1032376839
    calls = [e for e in tr["host"] if e[0] == "bench:call"]
    assert trace.inside_spans(dev, calls, "^%tpu_custom_call") == (
        1032372847, 2)
    idle = trace.idle_by_span(dev, tr["host"], t0, t1, 50e3)
    assert set(idle) <= {"bench:call", "bench:build", "bench:window",
                         "(no span)"}
    assert sum(idle.values()) == pytest.approx(7985355, abs=50e3 * 8)
    assert idle["bench:call"] > 0.9 * sum(idle.values())
    run_ = reduce.Run(cfg={}, records=[{"work": 4038805}] * 2,
                      window_s=1.04, peaks={}, trace=tr)
    spec = run.load_json("benchmarks/metrics/dispatch_ns.json")
    assert reduce.reducer(spec["reducer"])(run_, **spec["args"]) == (
        pytest.approx(1032372847 / (2 * 4038805)))  # 127.8 ns a task
    spec = run.load_json("benchmarks/metrics/stage_ms.fib.json")
    assert reduce.reducer(spec["reducer"])(run_, **spec["args"]) == (
        pytest.approx(3.963742))


def test_trace_of_one_burst_reads_as_worked_out_by_hand():
    """One burst of 3,072 requests: 29 kernel launches inside its
    run_stream span, 3,360,989 ns busy of 447,413,457, and 3,072 submit
    spans of 23,990.95 ns in the mean."""
    tr = recorded("serve-burst-3072")
    t0, t1 = window(tr)
    assert t1 - t0 == 447413457
    assert trace.busy_ns(tr["device"][0], t0, t1) == 3360989
    run_ = reduce.Run(cfg={}, records=[], window_s=0.45, peaks={}, trace=tr)
    got = {}
    for name in ("entries_per_burst", "submit_us"):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        got[name] = reduce.reducer(spec["reducer"])(run_, **spec["args"])
    assert got["entries_per_burst"] == 29
    idle = trace.idle_by_span(tr["device"][0], tr["host"], t0, t1, 50e3)
    # every idle instant goes to the innermost span: the 3,072 submit spans
    # hold 3,072 x 23.99 us of it, the loop around them the rest
    assert idle["bench:submit"] == pytest.approx(3072 * 23990.95, rel=1e-3)
    assert idle["bench:run_stream"] > idle["bench:submit_all"] > 0
    assert sum(idle.values()) == pytest.approx(
        447413457 - 3360989, rel=5e-3)
    assert got["submit_us"] == pytest.approx(23.99095052)
    # a reader with nothing to read returns nothing
    spec = run.load_json("benchmarks/metrics/chol_roofline.json")
    assert reduce.reducer(spec["reducer"])(run_, **spec["args"]) is None


def test_roofline_share_is_operations_over_peak_over_kernel_time():
    from benchmarks import ops

    cfg = {"n": 8192}
    peaks = run.load_json("benchmarks/peaks.json")["TPU v5 lite"]
    assert ops.cholesky_flops(cfg) == 8192 ** 3 / 3
    # a kernel event of 8 ms inside one call span: 183 Gflop / 197 TF is
    # 0.93 ms, so 11.6 %
    tr = {"host": [("bench:call", 0.0, 1e9)],
          "device": {0: [("%tpu_custom_call.1 = x", 100.0, 100.0 + 8e6)]}}
    run_ = reduce.Run(cfg=cfg, records=[], window_s=1, peaks=peaks, trace=tr)
    spec = run.load_json("benchmarks/metrics/chol_roofline.json")
    share = reduce.reducer(spec["reducer"])(run_, **spec["args"])
    assert share == pytest.approx(100 * (8192 ** 3 / 3 / 197e12) / 8e-3)
    assert 11 < share < 12
