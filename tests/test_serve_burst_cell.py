"""The cell ``serve-burst-3072`` and the entry boundary's two per-layer
metrics (ISSUE 37), at a CPU size through the Pallas interpreter: the
metric files ``settle_us`` / ``pump_us`` read the program's spans
``bench:stream.settle`` / ``bench:stream.pump`` through the reducer the
benchmark has, one span of each an entry; a burst through the cell's own
driver compares 0 on all five numbers, and the configuration's control
(a lane deadline that sheds the burst's tail) still fails."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce, run, traffic  # noqa: E402
from benchmarks.drivers import tenant_burst  # noqa: E402
from hclib_tpu.device import inject  # noqa: E402
from hclib_tpu.runtime import spans  # noqa: E402

CELL = "serve-burst-3072"
METRICS = {"settle_us": "bench:stream.settle",
           "pump_us": "bench:stream.pump"}
# A mailbox of 8 under 3 x 64 requests: a dozen entries and more a burst.
SIZE = {"capacity": 64, "egress_depth": 8}
PER_TENANT = 64


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_reads_its_span_through_span_mean(bench, name):
    spec = run.load_json("benchmarks", "metrics", name + ".json")
    assert spec["name"] == name and spec["reducer"] == "span_mean"
    assert spec["args"] == {"span": METRICS[name], "scale": 0.001}
    entry = run.find(bench["per_layer"], name, "metric")
    assert entry == {
        "name": name, "unit": "us", "better": "lower",
        "source": "program_span", "layer": "front door",
        "moves": "req_per_s",
        # the open-loop cell of PR 44 joined the list
        "workloads": [CELL, "serve-open-steady"],
    }
    # appended to the 25 entries PR 35 left, and nothing moved since
    names = [m["name"] for m in bench["per_layer"]]
    assert names[25:27] == ["settle_us", "pump_us"]
    other = [s for s in METRICS.values() if s != METRICS[name]][0]
    host = [(METRICS[name], 1_000, 301_000), (other, 301_000, 302_000),
            ("bench:run_stream", 0, 900_000),
            (METRICS[name], 500_000, 1_000_000)]
    traced = reduce.Run(cfg={}, records=[], window_s=1.0, peaks={},
                        trace={"host": host, "device": {}})
    value = reduce.reducer(spec["reducer"])(traced, **spec["args"])
    assert value == pytest.approx(400.0)  # ns to us, the mean of two
    # The parent has no such span: the reader returns nothing.
    bare = reduce.Run(cfg={}, records=[], window_s=1.0, peaks={},
                      trace={"host": host[2:3], "device": {}})
    assert reduce.reducer(spec["reducer"])(bare, **spec["args"]) is None


def burst(bench, monkeypatch, per_tenant=PER_TENANT, deadline_s=None):
    """Two bursts of 3 x ``per_tenant`` requests through the cell's
    driver at SIZE; returns (failed, compared, span names opened,
    info["stream"] of each burst)."""
    cell = run.find(bench["workloads"], CELL, "workload")
    centry = run.find(bench["configs"], cell["config"], "configuration")
    cfg = {**run.load_json(centry["file"]), **SIZE,
           "region_rows": per_tenant}
    if deadline_s is not None:
        cfg["deadline_s"] = deadline_s
    mix = {**traffic.load(ROOT, cell["traffic"]),
           "requests_per_tenant": per_tenant}
    opened, streams = [], []

    class Span:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    run_stream = inject.StreamingMegakernel.run_stream

    def counted(self, *a, **kw):
        iv, info = run_stream(self, *a, **kw)
        streams.append(info["stream"])
        return iv, info

    monkeypatch.setattr(spans, "TraceAnnotation", Span)
    monkeypatch.setattr(inject.StreamingMegakernel, "run_stream", counted)
    state = tenant_burst.setup(cfg, mix, 2**31 + 37, True)
    records = [tenant_burst.operation(state) for _ in range(2)]
    failed, compared = tenant_burst.check(state, records)
    return failed, compared, opened, streams


def test_a_burst_opens_one_span_of_each_name_an_entry(bench, monkeypatch):
    failed, compared, opened, streams = burst(bench, monkeypatch)
    assert failed == 0
    assert [(name, value) for name, value, _ in compared] == [
        ("requests_wrong", 0), ("running_sum_abs_err", 0),
        ("tenant_lanes_off_contract", 0), ("ledgers_not_conserved", 0),
        ("streams_not_drained", 0),
    ]
    entries = sum(s["entries"] for s in streams)
    assert entries >= 2 * 12
    for span in METRICS.values():
        assert opened.count(span) == entries
    # an entry's pump comes before it, its settle after (the entry's own
    # spans between them are tests/test_host_spans.py's)
    boundary = [n for n in opened if n in METRICS.values()]
    assert boundary == [METRICS["pump_us"], METRICS["settle_us"]] * entries
    for s in streams:
        assert s["settled"] == 3 * PER_TENANT
        assert 12 <= s["settle_batches"] <= s["entries"]


def test_the_control_still_sheds_the_tail(bench, monkeypatch):
    """The configuration's own control: a lane deadline of 0.05 s, which
    a burst of 768 requests through a mailbox of 8 outlasts under the
    interpreter three times over (0.16 s here), so its tail resolves
    EXPIRED."""
    control = run.load_json("benchmarks", "configs", "serve-3tenant.json")[
        "control"]
    assert control == {"deadline_s": 0.05}
    failed, compared, _, _ = burst(bench, monkeypatch, 256, **control)
    assert failed > 0
    wrong = dict((n, v) for n, v, _ in compared)
    assert wrong["requests_wrong"] > 0
    assert wrong["tenant_lanes_off_contract"] > 0  # lanes count expired
