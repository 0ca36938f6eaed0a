"""The cell ``forasync-2d-hbm`` (PR 40) at 32 x 512 in (8, 128) tiles
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

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from test_uts_cell import _git, _only_gained  # noqa: E402

from benchmarks import reduce, run  # noqa: E402
from benchmarks.reducers import fa_roofline  # noqa: E402
from benchmarks.reference import forasync as ref  # noqa: E402

CELL = "forasync-2d-hbm"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The commit PR 40 started from: what the benchmark had.
BASE = "05336eeabe63864e984632702eecea595d810c7a"
SEED = 2**31 + 40
# 4 x 4 tiles of (8, 128): 15 splits, 4 rounds of 4.
TINY = {"H": 32, "W": 512, "tile": [8, 128], "width": 4}
TINY_COUNTS = {"tiles": 16, "splits": 15, "executed": 31}
MINE = {"fa_kernel_ms", "fa_round_us", "fa_roofline", "fa_occupancy",
        "fa_prefetch_share", "fa_live_rows", "stage_ms.fa"}
MK = {"mk_finalize_ms", "mk_upload_ms", "mk_launch_ms", "mk_tail_ms"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    over = {**TINY, **(cfg or {})}
    full = run.load_json("benchmarks/configs/forasync-stencil.json")
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
    assert len(compared) == 15  # nine a call, two after, four of the reference
    assert all(x["value"] == 0 and x["limit"] == 0 for x in compared)
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert reference["corner"] == 32
    assert {k: reference[k] for k in TINY_COUNTS} == TINY_COUNTS


def test_traced_run_reads_what_a_cpu_trace_holds(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the spans and the counters are read.
    out, _ = tiny(bench, traced=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "fa_occupancy", "fa_prefetch_share", "fa_live_rows",
        "mk_finalize_ms", "mk_upload_ms", "mk_launch_ms"}
    assert out["metrics"]["fa_occupancy"]["value"] == 100.0
    assert out["metrics"]["fa_prefetch_share"]["value"] == 75.0
    assert 0 < out["metrics"]["fa_live_rows"]["value"] < 64
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:fa.seed", "bench:fa.run", "bench:mk.wait"}


def test_control_a_scheduler_that_stops_early_raises(bench):
    from hclib_tpu.runtime.resilience import StallError

    full = run.load_json("benchmarks/configs/forasync-stencil.json")
    assert full["control"] == {"fuel": 4096} and "fuel" not in full
    assert full["control"]["fuel"] < full["guarantees"]["executed"]
    with pytest.raises(StallError, match="pending"):
        tiny(bench, cfg={"fuel": 16})


def test_a_body_that_leaves_out_one_neighbour_is_not_correct(
        bench, monkeypatch):
    from hclib_tpu.device import workloads

    real = workloads.stencil_loop

    def four_point(H, W, th, tw):
        tk, bounds, tile = real(H, W, th, tw)
        whole = tk.compute
        tk.compute = lambda ins: {
            "vout": whole(ins)["vout"] - ins["vin"][1:th + 1, :tw]}
        return tk, bounds, tile

    monkeypatch.setattr(workloads, "stencil_loop", four_point)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["digest_plain_differs"] == c["digest_weighted_differs"] == 1
    assert c["gout_differing"] > 32 * 512 * 0.9
    assert c["executed_abs_err"] == c["batch_tasks_abs_err"] == 0
    assert c["pending"] == c["overflowed"] == c["gin_changed"] == 0


def test_a_tile_counted_and_not_stored_is_not_correct(bench, monkeypatch):
    """What the -1 between calls is for: one tile's window left as the
    call found it, every counter right."""
    import hclib_tpu as hc

    real = hc.forasync

    def one_store_short(*args, **kw):
        out, info = real(*args, **kw)
        return {**out, "gout": out["gout"].at[8:16, 128:256].set(-1)}, info

    monkeypatch.setattr(hc, "forasync", one_store_short)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["digest_plain_differs"] == c["digest_weighted_differs"] == 1
    assert c["gout_differing"] == 8 * 128
    assert not any(v for k, v in c.items() if k not in (
        "digest_plain_differs", "digest_weighted_differs",
        "gout_differing"))


def test_a_program_that_refuses_recursive_on_the_device_is_refused(
        bench, monkeypatch):
    """The parent of PR 40: the driver raises before anything is made."""
    import hclib_tpu as hc
    from benchmarks.drivers import forasync_run

    def parent(fn, bounds, tile=None, mode=hc.FLAT, **kw):
        assert mode == hc.RECURSIVE and kw["place"] == "device"
        raise ValueError("place='device' supports mode=FLAT only")

    monkeypatch.setattr(hc, "forasync", parent)
    monkeypatch.setattr(forasync_run, "_make_gin", None)  # never reached
    with pytest.raises(RuntimeError, match="cannot run this deployment"):
        tiny(bench)


def test_a_wrong_reference_fails_as_loudly(bench, monkeypatch):
    out, lines = tiny(bench, cfg={"guarantees": {"splits": 16}})
    assert out["correct"] is False and out["failed"] == out["attempted"]
    assert compared_of(lines)["reference_splits_abs_err"] == 1
    real = ref.sweep

    def one_off(padded, H, W, band=ref.BAND):
        for row0, block in real(padded, H, W, band):
            block[0, 7] += row0 == 0
            yield row0, block

    monkeypatch.setattr(ref, "sweep", one_off)
    out, lines = tiny(bench)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    c = compared_of(lines)
    assert c["reference_corner_differing"] == 1
    assert c["gout_differing"] == 1 and c["digest_plain_differs"] == 1


def test_a_configuration_of_another_kind_is_refused(bench):
    with pytest.raises(RuntimeError, match="int32 grids resident"):
        tiny(bench, cfg={"resident": False})


def test_reference_against_the_cell_by_cell_loop():
    rng = np.random.default_rng(SEED)
    for H, W, band in ((17, 23, 4), (40, 9, 64), (8, 128, 8)):
        padded = np.zeros((H + 8, W + 128), np.int32)
        padded[1:H + 1, 1:W + 1] = rng.integers(0, 1 << 20, (H, W))
        quick = np.concatenate(
            [b.copy() for _, b in ref.sweep(padded, H, W, band)])
        naive = ref.sweep_naive(padded, H, W)
        assert np.array_equal(quick, naive)
        # the digests, against the weights written out a cell
        i, j = np.indices((H, W)).astype(np.int64)
        w = i * ref.W_ROW + j * ref.W_COL + 1
        want = (ref._wrapped(int(naive.astype(np.int64).sum())),
                ref._wrapped(int((naive.astype(np.int64) * w).sum())))
        assert ref.digests(ref.sweep(padded, H, W, band), W) == want
        # a swap of two cells moves the weighted digest alone
        swapped = naive.copy()
        swapped[0, 0], swapped[H - 1, W - 1] = naive[H - 1, W - 1], naive[0, 0]
        got = ref.digests([(0, swapped)], W)
        assert got[0] == want[0] and got[1] != want[1]
    assert ref.loop_counts(32768, 32768, [256, 1024]) == {
        "tiles": 4096, "splits": 4095, "executed": 8191}


def test_configuration_counts_against_the_programs_plan():
    from hclib_tpu.device.forasync_tier import (
        make_forasync_megakernel, split_plan,
    )
    from hclib_tpu.device.workloads import stencil_loop

    cfg = run.load_json("benchmarks/configs/forasync-stencil.json")
    g = cfg["guarantees"]
    plan = split_plan([cfg["H"], cfg["W"]], cfg["tile"])
    assert (plan["tiles"], plan["splits"]) == (g["tiles"], g["splits"])
    assert g["executed"] == g["tiles"] + g["splits"] == 8191
    assert cfg["bytes_moved"] == fa_roofline.least_bytes(cfg) == 2 ** 33
    assert cfg["reduced"] == [] and set(cfg["assumed"]) >= {
        "recurrence", "H", "W", "tile", "width", "mode", "values"}
    tk, bounds, tile = stencil_loop(cfg["H"], cfg["W"], *cfg["tile"])
    assert tk.data_specs["gin"].shape == (32776, 32896)
    assert tk.data_specs["gout"].shape == (32768, 32768)
    mk = make_forasync_megakernel(tk, width=cfg["width"], interpret=True,
                                  space=(bounds, tile), verify=False)
    assert mk.capacity == 64 < g["tiles"]  # the live set, not a row a tile


def test_each_reducer_on_a_synthetic_run(bench):
    cfg = run.load_json("benchmarks/configs/forasync-stencil.json")
    peaks = run.load_json("benchmarks/peaks.json")["TPU v5 lite"]
    # two calls: 16 ms spans with a 13 ms kernel event in each, and a
    # 6 ms digest pass between the calls
    # the first kernel drawn BEFORE its call span opens, as a profile
    # whose device clock runs a millisecond early draws it
    tr = {"host": [("bench:window", -7e6, 23e6), ("bench:call", 0.0, 14e6),
                   ("bench:window", 24e6, 52e6), ("bench:call", 30e6, 46e6)],
          "device": {0: [("%or.1 = x", -6e6, -1e6),
                         ("%tpu_custom_call.1 = x", -0.5e6, 12.5e6),
                         ("%reduce_fusion = x", 17e6, 23e6),
                         ("%tpu_custom_call.1 = x", 32e6, 45e6)]}}
    recs = [{"batch_rounds": 512, "batch_tasks": 4096,
             "prefetch_hits": 4088, "batch_occupancy": 1.0,
             "live_rows_max": 25}] * 2
    run_ = reduce.Run(cfg=cfg, records=recs, window_s=1, peaks=peaks,
                      trace=tr)

    def read(name):
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        assert set(spec) == {"name", "what", "reducer", "args"}
        return reduce.reducer(spec["reducer"])(run_, **spec["args"])

    assert read("fa_kernel_ms") == pytest.approx(13.0)
    assert read("fa_round_us") == pytest.approx(13e3 / 512)
    assert read("stage_ms.fa") == pytest.approx((1.5 + 3.0) / 2)
    assert read("fa_occupancy") == pytest.approx(100.0)
    assert read("fa_prefetch_share") == pytest.approx(100 * 4088 / 4096)
    assert read("fa_live_rows") == pytest.approx(25.0)
    # 8.59 GB at 819 GB/s is 10.49 ms: 80.7 % of 13 ms
    assert read("fa_roofline") == pytest.approx(
        100 * 2 ** 33 / 819e9 / 13e-3)
    assert 80 < read("fa_roofline") < 81
    # a kernel under another name, or records without the counters (the
    # parent's), are nothing to read
    tr["device"][0] = [("%uts_dfs.1 = x", 2e6, 15e6)]
    assert [read(k) for k in ("fa_kernel_ms", "fa_round_us",
                              "fa_roofline")] == [None] * 3
    run_.records = [{}]
    assert [read(k) for k in ("fa_occupancy", "fa_prefetch_share",
                              "fa_live_rows")] == [None] * 3


# ----------------------- what the benchmark had is as it was (PR 40)


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
        "forasync-stencil", "back-to-back", 1)
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
