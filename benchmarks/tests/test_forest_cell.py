"""The cell ``forest-steal-4chip`` (PR 33) at a tiny size through the Pallas
interpreter on a mesh of two virtual CPU devices, run by hand with the
other benchmark tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite. The interpreter mesh costs about
4 s a call, so the sound run is made once for the module.
"""

import contextlib
import io
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):  # before any test of the session reaches JAX
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402
from test_uts_cell import _git, _only_gained  # noqa: E402

from benchmarks import reduce, run  # noqa: E402
from benchmarks.drivers import mesh_run  # noqa: E402
from benchmarks.reference import fib_forest as ref  # noqa: E402

CELL = "forest-steal-4chip"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 33 started from: what the benchmark had.
BASE = "492f83a3c95fd2b015cf0b2d47bc40533afcf133"
# 4 roots of fib(4) on two devices: 52 descriptors, value 12, 5 rounds.
TINY = {"chips": 2, "roots": 4, "n": 4, "capacity": 64, "window": 4,
        "quantum": 8}
# What ``check`` is handed for one sound call of TINY, without a run.
STATE = {"roots": 4, "n": 4, "chips": 2, "twin_wall_s": 1.0, "twin_work": 52}
GOOD = {"value": 12, "executed": 52, "rounds": 5, "pending": 0,
        "overflow": False, "input_devices": 2, "wall_s": 1.0,
        "per_device_executed": [26, 26], "exported": [4, 2],
        "imported": [2, 4]}
MINE = {"mesh_round_us", "stage_ms.mesh", "mesh_scaling", "mesh_balance",
        "steal_rows"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


@pytest.fixture(scope="module", autouse=True)
def fast_interpreter():
    """The interpreter's device threads spin in Python while they wait for
    a remote DMA; at CPython's 5 ms switch interval a mesh call takes six
    times as long (tests/conftest.py has the measurement)."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    yield
    sys.setswitchinterval(old)


def tiny(bench, traced=False, cfg=None):
    return run.run_cell(bench, CELL, 2**31 + 33, 0.1, traced, CPU,
                        interpret=True, cfg_over={**TINY, **(cfg or {})})


@pytest.fixture(scope="module")
def sound(bench):
    """One traced run (per-layer metrics, breakdown) and what it printed."""
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        out = tiny(bench, traced=True)
    return out, [json.loads(x) for x in said.getvalue().splitlines()]


def test_cell_is_correct_and_every_compared_number_is_zero(sound):
    out, lines = sound
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] == 2  # the mix's trace_ops
    compared = {x["compared"]: x for x in lines if "compared" in x}
    assert len(compared) == 11  # nine a call, two of the reference
    assert all(x["value"] == 0 and x["limit"] == 0
               for x in compared.values())
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert (reference["value"], reference["descriptors"]) == (12, 52)
    assert (reference["one_root_closed_form"]
            == reference["one_root_direct_count"])
    (calls,) = [x for x in lines if "calls" in x]
    assert all(min(c["per_device_executed"]) > 0
               and c["input_devices"] == 2 for c in calls["calls"])


def test_traced_run_reads_what_a_cpu_trace_holds(sound):
    # No device plane on the CPU: the two readers of kernel events find
    # nothing and are left out; the records' readers read.
    out, _ = sound
    assert set(out["metrics"]) == {"mesh_scaling", "mesh_balance",
                                   "steal_rows"}
    assert 0 < out["metrics"]["mesh_balance"]["value"] <= 100
    assert 0 < out["metrics"]["steal_rows"]["value"] <= 2 * TINY["roots"]
    # the interpreter mesh is thousands of times slower than its twin
    assert 0 < out["metrics"]["mesh_scaling"]["value"] < 1
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:mesh.partition", "bench:mesh.upload",
                     "bench:mesh.run", "bench:mesh.readback"}


def test_end_to_end_run_reports_tasks_per_s_and_setup_s(bench):
    out = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"tasks_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_a_root_dropped_from_the_builders_is_not_correct(bench, monkeypatch):
    """The timed path broken where the forest is spawned."""
    real = mesh_run.build

    def one_short(st):
        return real({**st, "roots": st["roots"] - 1})

    monkeypatch.setattr(mesh_run, "build", one_short)
    out = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_per_device_sum_off_by_one_fails_check():
    with contextlib.redirect_stdout(io.StringIO()):
        assert mesh_run.check(STATE, [GOOD])[0] == 0
        for broken in ({"per_device_executed": [26, 27]},
                       {"per_device_executed": [52, 0]},
                       {"imported": [2, 3]},
                       {"exported": [0, 0], "imported": [0, 0]},
                       {"input_devices": 1}, {"pending": 1},
                       {"value": 13}, {"executed": 51}):
            assert mesh_run.check(STATE, [GOOD, {**GOOD, **broken}])[0] == 1


def test_a_program_without_the_steal_counters_is_refused_at_once(
        bench, monkeypatch):
    """As the parent of PR 33 under this benchmark's files: set-up raises
    before anything is built, and the command exits non-zero."""
    from hclib_tpu.device import resident

    monkeypatch.delattr(resident, "FS_IMPORTED")
    with pytest.raises(RuntimeError, match="cannot run this deployment"):
        tiny(bench)


def test_a_wrong_reference_fails_as_loudly(bench, monkeypatch):
    monkeypatch.setattr(ref, "direct_count",
                        lambda n: {"value": 3, "descriptors": 14})
    with contextlib.redirect_stdout(io.StringIO()):
        bad, compared = mesh_run.check(STATE, [GOOD, GOOD])
    assert bad == 2
    assert dict((k, v) for k, v, _ in compared)[
        "reference_descriptors_closed_form_minus_direct_count"] == 1


def test_closed_form_against_the_direct_count():
    cfg = run.load_json("benchmarks/configs/fib-forest-mesh.json")
    one = ref.direct_count(cfg["n"])
    assert one == ref.closed_form(1, cfg["n"]) == {
        "value": 2584, "descriptors": 12541}
    whole = ref.closed_form(cfg["roots"], cfg["n"])
    assert whole["value"] == cfg["guarantees"]["value"] == 413440
    assert (whole["descriptors"] == cfg["guarantees"]["executed"]
            == cfg["descriptors"] == 2006560)


def test_control_a_scheduler_that_stops_early(bench):
    """The configuration's control: a round budget below the rounds a call
    takes. The driver passes ``max_rounds`` only when the configuration
    has the key. It raises or comes out false."""
    from hclib_tpu.runtime.resilience import StallError

    control = run.load_json(
        "benchmarks/configs/fib-forest-mesh.json")["control"]
    assert set(control) == {"max_rounds"}
    try:
        out = tiny(bench, cfg={"max_rounds": 2})
    except StallError as e:  # a control that raises has failed
        assert "pending" in str(e)
        return
    assert out["correct"] is False


def test_mesh_scaling_is_the_share_as_computed(bench):
    spec = run.load_json("benchmarks/metrics/mesh_scaling.json")
    fn = reduce.reducer(spec["reducer"])

    def share(walls, twin_wall):
        recs = [{"work": 2006560, "wall_s": w, "twin_work": 2006560,
                 "twin_wall_s": twin_wall} for w in walls]
        return fn(reduce.Run(cfg={"chips": 4}, records=recs, window_s=1,
                             peaks={}), **spec["args"])

    # step 1's readings: a call 0.0873 s, the twin 0.2649 s
    assert share([0.0873, 0.0870, 0.0880], 0.2649) == pytest.approx(
        100 * 0.2649 / (4 * 0.0873))
    assert share([0.25], 1.0) == pytest.approx(100.0)
    # a mistimed twin is there to be seen, and refused, not hidden
    assert share([0.25], 1.06) == pytest.approx(106.0)
    assert share([], 1.0) is None
    # the other two readers
    recs = [{"per_device_executed": [489099, 501640, 514181, 501640],
             "imported": [11, 78, 113, 99]}]
    run_ = reduce.Run(cfg={"chips": 4}, records=recs, window_s=1, peaks={})
    for name, want in (("mesh_balance", 100 * 489099 / 514181),
                       ("steal_rows", 301)):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        got = reduce.reducer(spec["reducer"])(run_, **spec["args"])
        assert got == pytest.approx(want)


# ----------------------- what the benchmark had is as it was (PR 33)


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
        "fib-forest-mesh", "back-to-back", 4)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in MINE}
    assert set(mine) == MINE
    assert all(m["moves"] == "tasks_per_s" and CELL in m["workloads"]
               for m in mine.values())
    assert CELL in run.find(bench["end_to_end"], "tasks_per_s", "metric")[
        "workloads"]
