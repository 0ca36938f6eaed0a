"""The program's profiler spans (ISSUE 38): the one helper
(``runtime/spans.py``) and its table held to the source, the leaf spans of
``Megakernel.run`` / ``resume`` and of the stream's ``enter()`` in the
order they open (a CPU size through the Pallas interpreter), the counts
held to the program's own counters, and the seven per-layer metric files
that read them, each through its reducer on made-up events."""

import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce, run, trace, traffic  # noqa: E402
from benchmarks.drivers import tenant_burst  # noqa: E402
from hclib_tpu.device import inject  # noqa: E402
from hclib_tpu.device.descriptor import TaskGraphBuilder  # noqa: E402
from hclib_tpu.device.workloads import FIB, make_fib_megakernel  # noqa: E402
from hclib_tpu.runtime import spans  # noqa: E402

PACKAGE = os.path.join(ROOT, "hclib_tpu")
HELPER = os.path.join(PACKAGE, "runtime", "spans.py")
SERVE, CHOL, SW = "serve-burst-3072", "cholesky-8192", "sw-wave-8192"
FA = "forasync-2d-hbm"  # joined the four mk_* metrics' cells in PR 40
MK = ["bench:mk.finalize", "bench:mk.upload", "bench:mk.launch",
      "bench:mk.wait"]
ENTRY = ["bench:stream.pump", "bench:stream.launch", "bench:stream.wait",
         "bench:stream.settle"]


class Recorder:
    """Stands where ``TraceAnnotation`` does in the helper and keeps the
    names in the order they were opened."""

    def __init__(self):
        self.opened = []
        outer = self

        class Span:
            def __init__(self, name):
                outer.opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        self.Span = Span


@pytest.fixture()
def recorded(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(spans, "TraceAnnotation", rec.Span)
    return rec.opened


def sources():
    for top, _, files in os.walk(PACKAGE):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(top, f)
                with open(path) as fh:
                    yield path, fh.read()


# ----------------------------------------------------------- the helper


def test_span_opens_the_prefix_and_the_stage(recorded):
    assert spans.PREFIX == trace.HOST_SPAN_PREFIX == "bench:"
    with spans.span("chol.run"):
        with spans.span("mk.wait"):
            pass
    assert recorded == ["bench:chol.run", "bench:mk.wait"]


def test_spans_land_nested_in_a_profile_the_benchmark_reads(tmp_path):
    """The real ``TraceAnnotation`` under a profiler session on the CPU:
    ``benchmarks/trace.py`` finds both spans, the inner inside the outer.
    Without a session a span opens and closes and leaves nothing."""
    assert isinstance(spans.span("sw.run"), jax.profiler.TraceAnnotation)
    with spans.span("sw.run"), spans.span("mk.upload"):
        pass
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("sw.run"):
            with spans.span("mk.upload"):
                # a jit of a new function compiles, whatever ran before
                jax.block_until_ready(
                    jax.jit(lambda x: x * 3 + 1)(jax.numpy.zeros(8)))
    finally:
        jax.profiler.stop_trace()
    events = trace.read(str(tmp_path))["host"]
    host = {name: (s, e) for name, s, e in events}
    # the build ledger marks the instant each executable was obtained
    # (ISSUE 53), on the same clock, for a jit no runner knows of
    assert set(host) == {"bench:sw.run", "bench:mk.upload",
                         "bench:prog.compiled"}
    (a, b), (s, e) = host["bench:sw.run"], host["bench:mk.upload"]
    assert a <= s < e <= b
    marks = [ev for ev in events if ev[0] == "bench:prog.compiled"]
    assert marks and all(s <= m0 <= m1 <= e for _, m0, m1 in marks)
    assert reduce.reducer("span_count_per_span")(
        a_run(events, []), count="bench:prog.compiled",
        span="bench:mk.upload") == len(marks)


def test_the_table_names_every_stage_once():
    assert len(set(spans.STAGES)) == len(spans.STAGES) == 37
    assert {"prog.first_call", "prog.compiled"} < set(spans.STAGES)
    assert {"slu.seed", "slu.run"} < set(spans.STAGES)
    assert all(
        re.fullmatch(r"[a-z0-9]+(\.[a-z0-9_]+)+", s) for s in spans.STAGES
    )


def test_only_the_helper_imports_the_annotation_and_spells_the_prefix():
    for path, text in sources():
        if path == HELPER:
            assert text.count("import TraceAnnotation") == 1
            assert text.count('"bench:') == 1
        else:
            assert "TraceAnnotation" not in text, path
            assert "bench:" not in text, path


def test_the_spans_the_source_opens_are_the_helpers_table():
    opened = set()
    for path, text in sources():
        calls = re.findall(r"\bspan\(([^)]*)\)", text)
        if path == HELPER or not calls:
            continue
        assert "from ..runtime.spans import span\n" in text, path
        for arg in calls:  # a literal stage and nothing else
            assert re.fullmatch(r'"[a-z0-9._]+"', arg), (path, arg)
            opened.add(arg.strip('"'))
    assert opened == set(spans.STAGES)


# ------------------------------------------------------- Megakernel.run


def test_a_run_opens_its_four_spans_in_order_and_resume_three(recorded):
    mk = make_fib_megakernel(64, interpret=True, checkpoint=True)

    def fib10(**kw):
        b = TaskGraphBuilder()
        b.add(FIB, args=[10], out=0)
        return mk.run(b, **kw)

    iv, _, info = fib10()
    assert int(iv[0]) == 55 and info["pending"] == 0
    # the first call is the build: the ledger's bracket opens around
    # the launch and the wait, a mark falls where the compile ended
    assert [n for n in recorded if n != "bench:prog.compiled"] == (
        MK[:2] + ["bench:prog.first_call"] + MK[2:])
    at = recorded.index("bench:prog.first_call")
    assert "bench:prog.compiled" in recorded[at:recorded.index(MK[3])]
    del recorded[:]
    _, _, cut = fib10(quiesce=40)
    assert cut["quiesced"] and cut["pending"] > 0
    assert recorded == MK
    del recorded[:]
    iv, _, done = mk.resume(cut["state"])
    assert int(iv[0]) == 55 and done["executed"] == info["executed"]
    # the state is final: nothing to finalize; it comes with a live ring,
    # which is another layout and so another program, built here
    assert [n for n in recorded if n != "bench:prog.compiled"] == (
        MK[1:2] + ["bench:prog.first_call"] + MK[2:])
    del recorded[:]
    mk.resume(cut["state"])
    assert recorded == MK[1:]


def test_a_device_forasync_opens_its_two_spans_around_the_run(recorded):
    """``fa.seed`` (the builder and its root range or its tiles), then
    ``fa.run`` with ``Megakernel.run``'s four inside, in either mode."""
    import numpy as np

    import hclib_tpu as hc
    from hclib_tpu.device.workloads import (
        stencil_data, stencil_loop, stencil_reference,
    )

    tk, bounds, tile = stencil_loop(16, 256)
    gin, gout = stencil_data(16, 256)
    for mode in (hc.FLAT, hc.RECURSIVE):
        del recorded[:]
        out, info = hc.forasync(
            tk, bounds, tile=tile, mode=mode, place="device", width=2,
            interpret=True, data={"gin": gin, "gout": gout.copy()})
        assert [n for n in recorded if not n.startswith("bench:prog.")
                ] == ["bench:fa.seed", "bench:fa.run"] + MK
        assert np.array_equal(np.asarray(out["gout"]),
                              stencil_reference(gin))
        assert info["forasync"]["mode"] == mode


# ---------------------------------------------- the stream's entry loop


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


@pytest.fixture(scope="module")
def bursts(bench):
    """Two bursts of 3 x 64 requests through the cell's driver, a mailbox
    of 8: (failed, span names opened, ``info["stream"]`` of each)."""
    cell = run.find(bench["workloads"], SERVE, "workload")
    centry = run.find(bench["configs"], cell["config"], "configuration")
    cfg = {**run.load_json(centry["file"]), "capacity": 64,
           "egress_depth": 8, "region_rows": 64}
    mix = {**traffic.load(ROOT, cell["traffic"]), "requests_per_tenant": 64}
    rec, streams = Recorder(), []
    run_stream = inject.StreamingMegakernel.run_stream

    def counted(self, *a, **kw):
        rec.opened.append("run_stream")
        iv, info = run_stream(self, *a, **kw)
        streams.append(info["stream"])
        return iv, info

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "TraceAnnotation", rec.Span)
        mp.setattr(inject.StreamingMegakernel, "run_stream", counted)
        state = tenant_burst.setup(cfg, mix, 2**31 + 38, True)
        del rec.opened[:]  # the warm burst of set-up, if any
        del streams[:]
        records = [tenant_burst.operation(state) for _ in range(2)]
        failed, _ = tenant_burst.check(state, records)
    return failed, rec.opened, streams


def test_every_entry_opens_pump_launch_wait_settle_in_order(bursts):
    failed, opened, streams = bursts
    assert failed == 0 and len(streams) == 2
    per_burst = " ".join(opened).split("run_stream")[1:]
    assert len(per_burst) == 2
    for names, link in zip(per_burst, streams):
        # the first burst's first entry builds the program: the build
        # ledger's spans are held in tests/test_progcache.py
        names = [n for n in names.split() if not n.startswith("bench:prog.")]
        # the stream's state goes up once, inside the first entry
        assert names[:2] == [ENTRY[0], "bench:stream.upload"]
        names.remove("bench:stream.upload")
        assert "bench:stream.upload" not in names
        assert link["entries"] >= 12
        # the drained exit pulls the values: one more wait, no sleep
        assert names == ENTRY * link["entries"] + ["bench:stream.wait"]


def test_the_span_counts_are_the_programs_own_counters(bursts):
    _, opened, streams = bursts
    total = {k: sum(s[k] for s in streams) for k in streams[0]}
    assert opened.count("bench:stream.launch") == total["entries"]
    assert opened.count("bench:stream.upload") == (
        total["uploads"] - total["entries"]) == 2
    assert opened.count("bench:stream.wait") == total["downloads"]
    assert opened.count("bench:stream.sleep") == total["idle_sleeps"] == 0
    assert opened.count("bench:stream.settle") == total["entries"]
    assert not [n for n in opened if n.startswith("bench:mk.")]


def test_an_open_stream_names_its_idle_sleep(recorded):
    """An open tenant-less stream with nothing to do sleeps under its
    span until ``close()`` lets the last entry drain."""
    import threading

    from hclib_tpu.device.inject import StreamingMegakernel

    mk = make_fib_megakernel(64, interpret=True)
    sm = StreamingMegakernel(mk, ring_capacity=8)
    b = TaskGraphBuilder()
    b.add(FIB, args=[5], out=0)
    closer = threading.Timer(0.3, sm.close)
    closer.start()
    try:
        iv, info = sm.run_stream(b, poll_interval_s=0.01)
    finally:
        closer.cancel()
    assert int(iv[0]) == 5
    sleeps = recorded.count("bench:stream.sleep")
    assert sleeps == info["stream"]["idle_sleeps"] >= 1
    assert recorded.count("bench:stream.launch") == info["stream"]["entries"]


# ------------------------------------------------- the metrics' readers

FRONT = {"source": "program_span", "layer": "front door",
         "moves": "req_per_s",
         # the open-loop cell of PR 44 joined the lists
         "workloads": [SERVE, "serve-open-steady"]}
STAGING = {"layer": "host staging", "moves": "solve_ms",
           # the SparseLU cell of PR 58 joined the lists
           "workloads": [CHOL, SW, FA, "g500-bfs-search",
                         "jacobi-dep-hbm", "sparselu-dep-128"]}
# name: (reducer, args, unit, the rest of the entry, value on HOST below)
METRICS = {
    "launch_us": ("span_mean",
                  {"span": "bench:stream.launch", "scale": 0.001},
                  "us", FRONT, 400.0),
    "wait_us": ("span_mean", {"span": "bench:stream.wait", "scale": 0.001},
                "us", FRONT, 400.0),
    "uploads_per_burst": ("span_count_per_span",
                          {"count": "bench:stream.upload",
                           "span": "bench:run_stream"},
                          "count", FRONT, 2.0),
    "mk_finalize_ms": ("span_mean",
                       {"span": "bench:mk.finalize", "scale": 1e-06},
                       "ms", {"source": "program_span", **STAGING}, 0.4),
    "mk_upload_ms": ("span_mean",
                     {"span": "bench:mk.upload", "scale": 1e-06},
                     "ms", {"source": "program_span", **STAGING}, 0.4),
    "mk_launch_ms": ("span_mean",
                     {"span": "bench:mk.launch", "scale": 1e-06},
                     "ms", {"source": "program_span", **STAGING}, 0.4),
    "mk_tail_ms": ("span_minus_device",
                   {"span": "bench:mk.wait", "scale": 1e-06},
                   "ms", {"source": "device_trace", **STAGING}, 0.3),
}
ACCEPTED = 27  # per-layer entries of the parent's BENCHMARK.json


def a_run(host, device):
    return reduce.Run(cfg={}, records=[], window_s=1.0, peaks={},
                      trace={"host": host, "device": {0: device}})


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_reads_its_span_through_its_reducer(bench, name):
    reducer, args, unit, rest, want = METRICS[name]
    spec = run.load_json("benchmarks", "metrics", name + ".json")
    assert spec["name"] == name and spec["reducer"] == reducer
    assert spec["args"] == args and set(spec) == {
        "name", "what", "reducer", "args"}
    entry = run.find(bench["per_layer"], name, "metric")
    assert entry == {"name": name, "unit": unit, "better": "lower", **rest}
    # appended behind the parent's entries, in the issue's order
    names = [m["name"] for m in bench["per_layer"]]
    assert names[ACCEPTED:ACCEPTED + 7] == list(METRICS)
    # two spans of the metric's name (300 and 500 us) inside one
    # enclosing span, with the chip busy 200 us inside the two together
    mine = args.get("count", args["span"])
    host = [(mine, 1_000, 301_000), ("bench:other", 301_000, 302_000),
            ("bench:run_stream", 0, 1_000_000),
            (mine, 500_000, 1_000_000)]
    device = [("%tpu_custom_call.1", 100_000, 200_000),
              ("%tpu_custom_call.1", 600_000, 700_000)]
    read = reduce.reducer(spec["reducer"])
    assert read(a_run(host, device), **spec["args"]) == pytest.approx(want)
    # The parent's trace has no such span: nothing to read, and no raise
    # (0 uploads inside a burst is a reading, by the reducer's contract).
    bare = read(a_run(host[1:3], device), **spec["args"])
    assert bare == (0.0 if name == "uploads_per_burst" else None)
    assert read(a_run([], []), **spec["args"]) is None


@pytest.mark.parametrize("outer", [1, 2])
@pytest.mark.parametrize("inside", [0, 1, 29])
def test_span_count_per_span_counts_what_starts_inside(inside, outer):
    read = reduce.reducer("span_count_per_span")
    host = []
    for k in range(outer):
        t0 = k * 10_000_000
        host.append(("bench:run_stream", t0, t0 + 5_000_000))
        host += [("bench:stream.upload", t0 + 100 * i, t0 + 100 * i + 50)
                 for i in range(inside)]
        # one that starts between the enclosing spans counts for neither
        host.append(("bench:stream.upload", t0 + 6_000_000, t0 + 6_000_100))
    got = read(a_run(host, []), count="bench:stream.upload",
               span="bench:run_stream")
    assert got == inside and isinstance(got, float)


def test_span_count_per_span_reads_nothing_without_an_enclosing_span():
    read = reduce.reducer("span_count_per_span")
    host = [("bench:stream.upload", 0, 50), ("bench:burst", 0, 100)]
    assert read(a_run(host, []), count="bench:stream.upload",
                span="bench:run_stream") is None


# ------------------------- the program build layer's five (ISSUE 53)

CELLS = ["fib30-scalar", "cholesky-8192", "serve-burst-3072", "uts-t1l",
         "forest-steal-4chip", "sw-wave-8192", "forasync-2d-hbm",
         "serve-open-steady", "g500-bfs-search", "jacobi-dep-hbm"]
# name: (reducer, args, unit, source)
BUILD = {
    "build_trace_s": ("build_ledger", {"field": "trace_s"}, "s",
                      "program_counter"),
    "build_lower_s": ("build_ledger", {"field": "lower_s"}, "s",
                      "program_counter"),
    "build_compile_s": ("build_ledger", {"field": "compile_s"}, "s",
                        "program_counter"),
    "build_traces": ("build_ledger", {"field": "traces"}, "count",
                     "program_counter"),
    "window_builds": ("span_count_per_span",
                      {"count": "bench:prog.compiled",
                       "span": "bench:window"}, "count", "program_span"),
}
BEFORE_BUILD = 66  # per-layer entries of the parent's BENCHMARK.json


@pytest.mark.parametrize("name", sorted(BUILD))
def test_build_metric_file_and_entry_are_the_issues(bench, name):
    reducer, args, unit, source = BUILD[name]
    spec = run.load_json("benchmarks", "metrics", name + ".json")
    assert spec["name"] == name and spec["reducer"] == reducer
    assert spec["args"] == args and set(spec) == {
        "name", "what", "reducer", "args"}
    entry = run.find(bench["per_layer"], name, "metric")
    # every cell reports it: PR 53's ten, and each cell added since
    assert entry.pop("workloads") == [w["name"] for w in bench["workloads"]]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "program build", "moves": "setup_s"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[BEFORE_BUILD:BEFORE_BUILD + 5] == list(BUILD)
    assert CELLS == [w["name"] for w in bench["workloads"]][:10]


@pytest.mark.parametrize("marks", [0, 1, 3])
def test_window_builds_counts_the_marks_inside_a_window(marks):
    spec = run.load_json("benchmarks", "metrics", "window_builds.json")
    read = reduce.reducer(spec["reducer"])
    host = [("bench:window", 1_000, 2_000_000),
            ("bench:window", 3_000_000, 4_000_000),
            # a mark is an instant; one between two windows is set-up's
            ("bench:prog.compiled", 2_500_000, 2_500_000)]
    host += [("bench:prog.compiled", 5_000 + i, 5_000 + i)
             for i in range(marks)]
    assert read(a_run(host, []), **spec["args"]) == marks / 2
    assert read(a_run(host[2:], []), **spec["args"]) is None


def test_build_ledger_reads_the_ledger_a_run_leaves(monkeypatch):
    """The four ``build_*`` through their files on the ledger one tiny
    ``Megakernel.run`` leaves; a program from before the ledger is
    nothing to read, and nothing raises."""
    from hclib_tpu.runtime import progcache

    progcache.reset()
    b = TaskGraphBuilder()
    b.add(FIB, args=[6], out=0)
    make_fib_megakernel(64, interpret=True).run(b)
    rows = progcache.build_ledger()
    (mine,) = [r for r in rows if r["runner"] == "megakernel"]
    read = {}
    for name in sorted(BUILD)[:4]:
        spec = run.load_json("benchmarks", "metrics", name + ".json")
        read[name] = reduce.reducer(spec["reducer"])(
            a_run([], []), **spec["args"])
        assert read[name] == sum(r[spec["args"]["field"]] for r in rows)
    assert read["build_traces"] >= mine["traces"] == 1
    for part in ("trace", "lower", "compile"):
        assert read[f"build_{part}_s"] >= mine[f"{part}_s"] > 0
    monkeypatch.delattr(progcache, "build_ledger")
    assert reduce.reducer("build_ledger")(a_run([], []), "trace_s") is None
    progcache.reset()
