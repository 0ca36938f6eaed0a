"""Observability: event instrumentation, state timer, stats, watchdog.

The reference's instrumentation recorder is stubbed (src/hclib-instrument.c:
211-252); here it must actually record and round-trip.
"""

import time

import numpy as np
import pytest
from conftest import timeline_mod as _timeline

import hclib_tpu as hc
from hclib_tpu.runtime.instrument import END, START, load_dump, register_event_type
from hclib_tpu.runtime.timer import IDLE, WORK, StateTimer


def test_event_log_records_and_dumps(tmp_path):
    rt = hc.Runtime(nworkers=2, instrument=True)

    def body():
        with hc.finish():
            for _ in range(10):
                hc.async_(lambda: None)

    rt.run(body)
    path = rt.event_log.dump(str(tmp_path))
    names, per_worker = load_dump(path)
    assert "task" in names
    events = np.concatenate(list(per_worker.values()))
    starts = events[events["transition"] == START]
    ends = events[events["transition"] == END]
    # every executed task produced a START/END pair with matching ids
    assert len(starts) >= 11 and len(ends) == len(starts)
    assert set(starts["id"]) == set(ends["id"])
    # timestamps are monotonic per worker
    for w, ev in per_worker.items():
        ts = ev["ts_ns"]
        assert np.all(np.diff(ts) >= 0)


def test_event_log_double_buffer_overflow(tmp_path):
    from hclib_tpu.runtime.instrument import EventLog

    log = EventLog(1, capacity=8)
    t = register_event_type("x")
    for i in range(30):
        log.record(0, t, 2, i)
    path = log.dump(str(tmp_path))
    _, per_worker = load_dump(path)
    assert len(per_worker[0]) == 30
    assert list(per_worker[0]["id"]) == list(range(30))


def test_custom_event_type_ids_stable():
    a = register_event_type("my_phase")
    b = register_event_type("my_phase")
    assert a == b


def test_state_timer_accumulates():
    st = StateTimer(1)
    st.set_state(0, WORK)
    time.sleep(0.02)
    st.set_state(0, IDLE)
    time.sleep(0.01)
    st.finalize()
    totals = st.totals_ns()[0]
    assert totals["WORK"] >= 15_000_000
    assert totals["IDLE"] >= 5_000_000
    assert st.avg_time_ns(WORK) == totals["WORK"]
    assert "WORK".lower() in st.format().lower()


def test_runtime_timer_marks_work_and_search():
    rt = hc.Runtime(nworkers=2, timer=True)

    def body():
        with hc.finish():
            for _ in range(20):
                hc.async_(lambda: time.sleep(0.001))

    rt.run(body)
    totals = rt.state_timer.totals_ns()
    assert sum(t["WORK"] for t in totals) > 0


def test_watchdog_reports_stall(caplog):
    """A task that sleeps while holding the only path to progress triggers
    the stall report (the hazard test/deadlock/README documents), routed
    through logging so tests can assert on it (escalation to StallError
    is covered in test_resilience.py)."""
    import logging

    rt = hc.Runtime(nworkers=1, watchdog_s=0.2, watchdog_escalate=False)

    def body():
        time.sleep(0.7)  # outstanding work, no task transitions

    with caplog.at_level(logging.WARNING, logger="hclib_tpu.resilience"):
        rt.run(body)
    assert rt.stall_reports >= 1
    assert any("watchdog" in r.message for r in caplog.records)


def test_watchdog_quiet_on_healthy_run():
    rt = hc.Runtime(nworkers=2, watchdog_s=5.0)

    def body():
        with hc.finish():
            for _ in range(5):
                hc.async_(lambda: None)

    rt.run(body)
    assert rt.stall_reports == 0


def test_stats_format_contains_steals():
    rt = hc.Runtime(nworkers=2, stats=False)

    def body():
        with hc.finish():
            for _ in range(50):
                hc.async_(lambda: time.sleep(0.0005))

    rt.run(body)
    text = rt.format_stats()
    assert "executed=" in text and "steals=" in text
    executed = sum(st.executed for st in rt.worker_stats)
    assert executed >= 51


def _repo_sources(root):
    """The source files git would commit under ``root``: ``git
    ls-files``, or where there is no git the walk ``tools/lint.py``
    makes, which skips what building and running leave behind."""
    import subprocess

    from tools import lint

    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=root, capture_output=True, text=True,
            check=True, timeout=30,
        ).stdout.split()
    except (OSError, subprocess.SubprocessError):
        out = []
    return out or list(lint._files([str(root)]))


@pytest.mark.parametrize("doc", ["README.md", "tutorial/README.md"])
def test_documents_name_files_that_exist(doc):
    """A document must not outlive the file it sends the reader to:
    every ``*.py`` it names is the path, or the end of the path, of a
    file of the repo (``my_run.py`` is the reader's own script)."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[1]
    files = ["/" + f for f in _repo_sources(root)]
    named = set(re.findall(r"[\w./-]+\.py\b", (root / doc).read_text()))
    assert len(named) > 20, named
    missing = sorted(
        n for n in named - {"my_run.py"}
        if not any(f.endswith("/" + n.lstrip("./")) for f in files)
    )
    assert not missing, missing


def test_event_log_external_lane_counts_non_worker_records(tmp_path):
    """Records from non-worker threads (module init, watchdog, procworld
    engines) used to vanish; they now land in the external lane and are
    counted (the satellite fix)."""
    from hclib_tpu.runtime.instrument import EventLog, load_manifest

    log = EventLog(2, capacity=16)
    t = register_event_type("ext_evt")
    log.record(0, t, 2, 1)      # worker lane
    log.record(-1, t, 2, 2)     # main/module context (no identity)
    log.record(99, t, 2, 3)     # out-of-range id
    assert log.external_records == 2
    path = log.dump(str(tmp_path))
    names, per_worker = load_dump(path)
    man = load_manifest(path)
    assert man["external_lane"] == 2 and man["external_records"] == 2
    assert len(per_worker[2]) == 2
    assert sorted(per_worker[2]["id"]) == [2, 3]


def test_watchdog_stall_event_lands_in_external_lane(tmp_path, caplog):
    import logging

    rt = hc.Runtime(nworkers=1, watchdog_s=0.15, watchdog_escalate=False,
                    instrument=True)

    def body():
        time.sleep(0.5)

    with caplog.at_level(logging.WARNING, logger="hclib_tpu.resilience"):
        rt.run(body)
    assert rt.stall_reports >= 1
    # The watchdog thread's 'stall' records route to the external lane
    # (writing worker 0's lock-free buffer from another thread was a
    # race).
    assert rt.event_log.external_records >= 1


def test_spans_from_events_empty_and_open_paths(tmp_path):
    timeline = _timeline()
    from hclib_tpu.runtime.instrument import _EVENT_DTYPE, EventLog

    # Empty input: no spans, no crash.
    assert timeline.spans_from_events(np.zeros(0, _EVENT_DTYPE)) == []
    # Open span (START without END): kept, flagged, closed at last ts.
    ev = np.zeros(3, _EVENT_DTYPE)
    ev[0] = (100, 0, START, 1)   # never ends
    ev[1] = (200, 0, START, 2)
    ev[2] = (300, 0, END, 2)
    spans = timeline.spans_from_events(ev)
    open_ = [s for s in spans if s.get("open")]
    assert len(spans) == 2 and len(open_) == 1
    assert open_[0]["t0"] == 100 and open_[0]["t1"] == 300
    # Empty-dump render path.
    log = EventLog(1, capacity=4)
    path = log.dump(str(tmp_path))
    text = timeline.render_dump(path)
    assert "(no events recorded)" in text
    # render_stats / render_device_report degrade on empty inputs.
    assert "0 tasks executed" in timeline.render_stats({"workers": []})
    assert "(no per_device_counts in info)" in (
        timeline.render_device_report({"executed": 1})
    )


def test_render_dump_density_vectorization_matches_bruteforce():
    """The np.add.at density must equal the old O(spans*width) loop."""
    timeline = _timeline()
    rng = np.random.default_rng(3)
    width, t_lo, total = 37, 1000, 50000
    bucket = total / width
    spans = []
    for _ in range(200):
        a = int(rng.integers(t_lo, t_lo + total))
        b = int(rng.integers(a, t_lo + total + 1))
        spans.append({"type": 0, "id": 0, "t0": a, "t1": b})
    got = timeline._density(spans, t_lo, bucket, width)
    want = np.zeros(width)
    for s in spans:
        b0 = (s["t0"] - t_lo) / bucket
        b1 = max((s["t1"] - t_lo) / bucket, b0 + 1e-9)
        for bk in range(int(b0), min(int(np.ceil(b1)), width)):
            want[bk] += max(0.0, min(b1, bk + 1) - max(b0, bk))
    assert np.allclose(got, want, atol=1e-6)


def test_render_dump_labels_unknown_types_and_top(tmp_path):
    timeline = _timeline()
    from hclib_tpu.runtime.instrument import EventLog

    log = EventLog(1, capacity=16)
    # A type id past the manifest (simulates a foreign/stale dump).
    log.record(0, 999, START, 1)
    log.record(0, 999, END, 1)
    path = log.dump(str(tmp_path))
    text = timeline.render_dump(path, top=2)
    assert "type<999>" in text
    assert "top 1 spans by duration" in text


def test_metrics_registry_snapshot_delta_and_exports():
    from hclib_tpu.runtime.metrics import MetricsRegistry

    reg = MetricsRegistry()
    live = {"executed": 10, "nested": {"a": 1.5, "flag": True}}
    reg.register("rt", lambda: live)
    reg.record("run", {"tasks": 100, "skip_me": "string", "arr": [1, 2]})
    s1 = reg.snapshot()
    m = s1["metrics"]
    assert m["rt.executed"] == 10.0
    assert m["rt.nested.a"] == 1.5
    assert m["rt.nested.flag"] == 1.0
    assert m["run.tasks"] == 100.0
    assert m["run.arr.0"] == 1.0 and m["run.arr.1"] == 2.0
    assert "run.skip_me" not in m  # strings are not metrics
    live["executed"] = 25
    s2 = reg.snapshot()
    d = MetricsRegistry.delta(s1, s2)
    assert d["metrics"]["rt.executed"] == 15.0
    assert d["metrics"]["run.tasks"] == 0.0
    assert d["t"] >= 0.0
    # JSON export round-trips; Prometheus text is well-formed gauges.
    import json as _json

    assert _json.loads(reg.to_json(s2))["metrics"]["rt.executed"] == 25.0
    prom = reg.to_prometheus(s2)
    assert "# TYPE hclib_tpu_rt_executed gauge" in prom
    assert "hclib_tpu_rt_executed 25.0" in prom
    # A raising live source degrades to an error flag, not a crash.
    reg.register("bad", lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert reg.snapshot()["metrics"]["bad.error"] == 1.0


def test_metrics_registry_add_run_info_summarizes_device_shapes():
    from hclib_tpu.device import tracebuf as tb
    from hclib_tpu.runtime.metrics import MetricsRegistry

    import numpy as _np

    trace = {
        "epoch": {"t0_ns": 0, "t1_ns": 10},
        "rings": [{
            "written": 3, "dropped": 1, "capacity": 2,
            "records": _np.array(
                [[tb.TR_FIRE_SCALAR, 0, 0, 0],
                 [tb.TR_ROUND_END, 1, 1, 0]], dtype=_np.int64),
        }],
    }
    info = {
        "executed": 7,
        "tiers": {"batch_tasks": 5},
        "per_device_counts": _np.zeros((2, 8), _np.int32),
        "extra_outputs": [object()],  # must be dropped, not flattened
        "trace": trace,
    }
    reg = MetricsRegistry()
    reg.add_run_info("dev", info)
    m = reg.snapshot()["metrics"]
    assert m["dev.executed"] == 7.0
    assert m["dev.tiers.batch_tasks"] == 5.0
    assert m["dev.trace.fire_scalar"] == 1.0
    assert m["dev.trace.dropped"] == 1.0
    assert m["dev.per_device_executed.0"] == 0.0
    assert not any(k.startswith("dev.extra_outputs") for k in m)


def test_metrics_lane_occupancy_gauge_per_device():
    """Batch-routed runs export one lane-occupancy gauge per device
    (mesh runs return ``tiers`` as a per-device list; a single-device
    dict normalizes to a one-entry list) - the ROADMAP lane-firing-
    policy detector, readable straight off a Prometheus scrape."""
    from hclib_tpu.runtime.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.add_run_info("mesh", {
        "executed": 12,
        "tiers": [
            {"batch_occupancy": 0.75, "batch_tasks": 6},
            {"batch_occupancy": 0.5, "batch_tasks": 2},
        ],
    })
    reg.add_run_info("solo", {
        "executed": 3,
        "tiers": {"batch_occupancy": 1.0, "batch_tasks": 3},
    })
    reg.add_run_info("scalar", {"executed": 1})  # no tiers: no gauge
    m = reg.snapshot()["metrics"]
    assert m["mesh.lane_occupancy.0"] == 0.75
    assert m["mesh.lane_occupancy.1"] == 0.5
    assert m["solo.lane_occupancy.0"] == 1.0
    assert not any(k.startswith("scalar.lane_occupancy") for k in m)
    prom = reg.to_prometheus()
    assert "hclib_tpu_mesh_lane_occupancy_1 0.5" in prom


def test_runtime_metrics_wiring():
    rt = hc.Runtime(nworkers=2, metrics=True)

    def body():
        with hc.finish():
            for _ in range(10):
                hc.async_(lambda: None)

    rt.run(body)
    m = rt.metrics.snapshot()["metrics"]
    assert sum(
        v for k, v in m.items()
        if k.startswith("runtime.workers.") and k.endswith(".executed")
    ) >= 11


def test_timeline_renders_dump_and_reports(tmp_path):
    """tools/timeline.py turns a dump + info/stats dicts into readable
    reports (the reference's tools/timeline.py + instrument parser
    station)."""
    timeline = _timeline()

    rt = hc.Runtime(nworkers=2, instrument=True)

    def body():
        with hc.finish():
            for _ in range(25):
                hc.async_(lambda: time.sleep(0.0002))

    rt.run(body)
    stats = rt.stats_dict()
    path = rt.event_log.dump(str(tmp_path))

    text = timeline.render_dump(path)
    assert "per-worker timeline" in text
    assert "task" in text  # the registered event type shows up
    assert "w0" in text and "w1" in text
    assert "% busy" in text

    # START/END pairing: spans exist and have nonnegative durations
    names, by_worker = load_dump(path)
    spans = [
        s
        for w, ev in by_worker.items()
        for s in timeline.spans_from_events(ev)
    ]
    assert len(spans) >= 26
    assert all(s["t1"] >= s["t0"] for s in spans)

    # host stats report incl. steal matrix layout
    stext = timeline.render_stats(stats)
    assert "executed=" in stext and "w0" in stext

    # device report from a resident-style info dict
    info = {
        "name": "uts steal",
        "executed": 1000,
        "rounds": 7,
        "seconds": 0.5,
        "per_device_counts": [
            [0, 0, 200, 0, 4, 300, 0, 7],
            [0, 0, 180, 0, 4, 700, 0, 7],
        ],
    }
    dtext = timeline.render_device_report(info)
    assert "dev0" in dtext and "dev1" in dtext
    assert "1,000 tasks" in dtext
    assert "imbalance" in dtext

    # CLI round-trips via files
    import json as _json

    f = tmp_path / "info.json"
    f.write_text(_json.dumps(info))
    rc = timeline.main([str(path), "--device", str(f)])
    assert rc == 0


def test_tenant_metrics_series_live_and_recorded():
    """SATELLITE (multi-tenant ingress): a live TenantTable source and a
    recorded run info both surface the canonical ``tenant.<id>.*``
    series (accepted/rejected/expired/completed/backlog) - the fairness
    numbers a dashboard rates - and Prometheus export carries them."""
    from hclib_tpu.device.tenants import TenantSpec, TenantTable
    from hclib_tpu.runtime.metrics import MetricsRegistry

    table = TenantTable(
        [TenantSpec("alice"), TenantSpec("bob")], 16,
        clock=lambda: 0.0,
    )
    import numpy as _np
    from hclib_tpu.device.tenants import build_row

    for i in range(3):
        table.admit("alice", build_row(0, [i]))
    table.admit("bob", build_row(0, [9]))
    reg = MetricsRegistry()
    reg.register("tenant", table.metrics)
    m = reg.snapshot()["metrics"]
    assert m["tenant.alice.accepted"] == 3.0
    assert m["tenant.bob.accepted"] == 1.0
    assert m["tenant.alice.backlog"] == 3.0
    assert "tenant.alice.quarantine_reason" not in m  # strings dropped
    prom = reg.to_prometheus()
    assert "hclib_tpu_tenant_alice_accepted 3.0" in prom
    # add_run_info mirrors a run's info['tenants'] under the SAME prefix
    # even when the run landed under another name.
    reg2 = MetricsRegistry()
    reg2.add_run_info("stream", {
        "executed": 4,
        "tenants": {"alice": {"accepted": 3, "completed": 2,
                              "expired": 1, "backlog": 0,
                              "quarantine_reason": None}},
    })
    m2 = reg2.snapshot()["metrics"]
    assert m2["stream.executed"] == 4.0
    assert m2["tenant.alice.completed"] == 2.0
    assert m2["tenant.alice.expired"] == 1.0
    # One canonical series: no duplicate under the run-info name.
    assert not any(k.startswith("stream.tenants.") for k in m2)


def test_tr_tenant_perfetto_render(tmp_path):
    """SATELLITE: TR_TENANT records land on a dedicated 'tenant ingress'
    track with lane id, installs, and lazy expired drops decoded."""
    import json

    import numpy as np

    from hclib_tpu.device import tracebuf as tb
    from tools import timeline

    trace = {
        "epoch": {"t0_ns": 1_000_000, "t1_ns": 2_000_000},
        "rings": [{
            "written": 3, "dropped": 0, "capacity": 8,
            "records": np.array(
                [[tb.TR_TENANT, 0, (0 << 16) | 4, 0],
                 [tb.TR_TENANT, 0, (1 << 16) | 2, 0],
                 [tb.TR_TENANT, 1, (2 << 16) | 0, 3]],
                dtype=np.int64),
        }],
    }
    out = tmp_path / "tenants.perfetto.json"
    doc = timeline.export_perfetto(str(out), traces=[trace])
    evs = [e for e in doc["traceEvents"]
           if e.get("cat") == "device" and e["name"].startswith("t")]
    assert len(evs) == 3
    by_lane = {e["args"]["lane"]: e for e in evs}
    assert by_lane[0]["args"]["installed"] == 4
    assert by_lane[1]["name"] == "t1 +2"
    assert by_lane[2]["args"]["expired"] == 3
    assert "expired" in by_lane[2]["name"]
    tracks = [e for e in doc["traceEvents"]
              if e.get("name") == "thread_name"
              and e["args"]["name"] == "tenant ingress"]
    assert tracks, "tenant ingress track must be named"
    json.loads(out.read_text())  # the file is valid Chrome-trace JSON
