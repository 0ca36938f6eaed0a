"""Device flight recorder (device/tracebuf.py): the trace ring written
from inside the scheduler's round loops.

Acceptance (ISSUE 4): a seeded interpret-mode megakernel run with tracing
ON produces records whose batch-tier round spans reconcile EXACTLY with
``info['tiers']`` (rounds, tasks, prefetch hits) and a valid Perfetto
export; the same run with tracing OFF is bit-identical in outputs with no
trace ring added."""

import json

import numpy as np
import pytest
from conftest import timeline_mod as _timeline
from jax.experimental import pallas as pl

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import BatchSpec, Megakernel
from hclib_tpu.device import tracebuf as tb
from hclib_tpu.runtime.resilience import StallError


DOUBLE, NEG = 0, 1


def _scalar_double(ctx):
    ctx.set_out(ctx.arg(0) * 2)


def _scalar_neg(ctx):
    ctx.set_out(-ctx.arg(0))


def _batch_double(ctx):
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            ctx.set_out(s, ctx.arg(s, 0) * 2)


def _drain_noop(ctx):
    return None


def _mk(trace=None, width=2, prefetch=False):
    spec = (
        BatchSpec(_batch_double, width=width, prefetch=True,
                  drain=_drain_noop)
        if prefetch
        else BatchSpec(_batch_double, width=width)
    )
    return Megakernel(
        kernels=[("double", _scalar_double), ("neg", _scalar_neg)],
        route={"double": spec},
        capacity=64,
        num_values=64,
        interpret=True,
        trace=trace,
    )


def _graph(n_first=6, n_negs=3, n_second=5):
    b = TaskGraphBuilder()
    first = [b.add(DOUBLE, args=[i], out=i) for i in range(n_first)]
    negs = [
        b.add(NEG, args=[10 + i], out=n_first + i, deps=[first[i]])
        for i in range(n_negs)
    ]
    for i in range(n_second):
        b.add(DOUBLE, args=[100 + i], out=n_first + n_negs + i, deps=negs)
    return b


def test_traced_run_reconciles_exactly_with_tier_counters():
    """The acceptance reconciliation: batch-fire records vs info['tiers'],
    counted and summed EXACTLY (rounds, tasks, prefetch hits), scalar
    fires vs scalar_tasks, prefetch issue/drain bookkeeping consistent."""
    mk = _mk(trace=512, width=2, prefetch=True)
    iv, _, info = mk.run(_graph())
    assert list(iv[:6]) == [0, 2, 4, 6, 8, 10]
    t = info["tiers"]
    tr = info["trace"]
    ring = tr["rings"][0]
    assert ring["dropped"] == 0
    bat = tb.records_of(tr, tb.TR_FIRE_BATCH)
    sca = tb.records_of(tr, tb.TR_FIRE_SCALAR)
    iss = tb.records_of(tr, tb.TR_PREFETCH_ISSUE)
    assert len(bat) == t["batch_rounds"]
    assert int((bat[:, 2] & 0xFFFF).sum()) == t["batch_tasks"]
    assert int(bat[:, 3].sum()) == t["prefetch_hits"]
    assert t["prefetch_hits"] > 0  # queue depth > width engages it
    assert len(sca) == t["scalar_tasks"]
    # Lane id rides the high half of the fire word.
    assert set(bat[:, 2] >> 16) == {DOUBLE}
    # Announcements can only exceed consumed hits by the final round's
    # (possibly unconsumed-at-full-width) issue; both are recorded.
    assert int(iss[:, 3].sum()) >= t["prefetch_hits"]
    # Round brackets: one begin + one end per sched entry (single run()).
    assert len(tb.records_of(tr, tb.TR_ROUND_BEGIN)) == 1
    ends = tb.records_of(tr, tb.TR_ROUND_END)
    assert len(ends) == 1
    assert int(ends[0, 2]) == info["executed"]
    # Record timebase is monotonic.
    assert np.all(np.diff(ring["records"][:, 1]) >= 0)
    # Host epoch bracketed the launch.
    assert tr["epoch"]["t1_ns"] > tr["epoch"]["t0_ns"]


def test_trace_off_is_bit_identical_with_no_ring_output():
    mk_on = _mk(trace=512, width=2, prefetch=True)
    mk_off = _mk(trace=None, width=2, prefetch=True)
    iv_on, _, info_on = mk_on.run(_graph())
    iv_off, _, info_off = mk_off.run(_graph())
    assert np.array_equal(iv_on, iv_off)
    assert "trace" not in info_off
    # Tracing adds the trace key plus the trace-DERIVED tier gauges
    # (lane_partial_age, ISSUE 9); every device-computed number is
    # identical.
    # (program_cache is a host-side fact of the build - different per
    # build, not device output - so it is excluded from the cross-arm
    # identity; the tiers hold no copy of it since ISSUE 53.)
    on = {k: v for k, v in info_on.items()
          if k not in ("trace", "program_cache")}
    off = {k: v for k, v in info_off.items() if k != "program_cache"}
    host_keys = ("lane_partial_age", "lane_partial_ages")
    assert not {"build_s", "cache_lookup_s"} & set(info_off["tiers"])
    on["tiers"] = {
        k: v for k, v in on["tiers"].items() if k not in host_keys
    }
    off["tiers"] = {
        k: v for k, v in off["tiers"].items() if k not in host_keys
    }
    assert on == off
    assert "lane_partial_age" in info_on["tiers"]
    assert "lane_partial_age" not in info_off["tiers"]
    # No appended ring output on the off build: its pallas out tree is
    # one entry shorter (tasks/ready/counts/ivalues + tstats, no ring).
    assert mk_off.trace is None
    import jax

    b = _graph()
    tasks, succ, ring, counts = b.finalize(
        capacity=mk_off.capacity, succ_capacity=mk_off.succ_capacity
    )
    args = (tasks, succ, ring, counts,
            np.zeros(mk_off.num_values, np.int32))
    n_off = len(jax.eval_shape(mk_off._build_raw(1 << 20), *args))
    n_on = len(jax.eval_shape(mk_on._build_raw(1 << 20), *args))
    assert n_on == n_off + 1


def test_ring_overflow_counted_not_crashed():
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    mk = make_fib_megakernel(256, interpret=True, trace=32)
    b = TaskGraphBuilder()
    b.add(FIB, args=[10], out=0)
    iv, _, info = mk.run(b)
    assert int(iv[0]) == 55  # results unharmed by the wrap
    ring = info["trace"]["rings"][0]
    assert ring["dropped"] > 0
    assert ring["written"] == ring["dropped"] + ring["capacity"]
    assert len(ring["records"]) == ring["capacity"]
    # The ring keeps the LAST records: the run's closing round_end
    # survives the wrap (what a stall post-mortem needs).
    assert int(ring["records"][-1, 0]) == tb.TR_ROUND_END


def test_fuel_spill_traced_in_stall_stats():
    """Fuel exhaustion spills lane entries; the StallError's stats carry
    the trace, and the spill records account for every spilled entry."""
    mk = _mk(trace=256, width=2)
    b = TaskGraphBuilder()
    for i in range(10):
        b.add(DOUBLE, args=[i], out=i)
    with pytest.raises(StallError) as ei:
        mk.run(b, fuel=3)
    tr = ei.value.stats["trace"]
    spills = tb.records_of(tr, tb.TR_SPILL)
    assert int(spills[:, 3].sum()) == ei.value.stats["tiers"]["spilled"] > 0


def test_perfetto_export_round_trips(tmp_path):
    timeline = _timeline()
    mk = _mk(trace=512, width=2, prefetch=True)
    _, _, info = mk.run(_graph())
    out = tmp_path / "trace.perfetto.json"
    doc = timeline.export_perfetto(str(out), traces=[info["trace"]])
    loaded = json.loads(out.read_text())  # valid JSON round-trip
    assert loaded == doc
    evs = loaded["traceEvents"]
    dev = [e for e in evs if e.get("cat") == "device"]
    assert dev, "no device events exported"
    # One process (track group) for the single device, named.
    assert {e["pid"] for e in dev} == {1}
    names = [
        e for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    ]
    assert [n["args"]["name"] for n in names] == ["device 0"]
    # Monotonic ts within every track.
    for tid in {e["tid"] for e in dev}:
        ts = [e["ts"] for e in dev if e["tid"] == tid]
        assert ts == sorted(ts)
    # The batch lane surfaced as its own thread with occupancy labels,
    # and the EXPORTED events reconcile exactly with info['tiers']: one
    # span per batch round, takes summing to batch_tasks, prefetched
    # args summing to prefetch_hits (the acceptance reconciliation, on
    # the Perfetto side).
    t = info["tiers"]
    lane_evs = [e for e in dev if e["name"].startswith("batch x")]
    assert len(lane_evs) == t["batch_rounds"]
    assert sum(e["args"]["take"] for e in lane_evs) == t["batch_tasks"]
    assert (
        sum(e["args"]["prefetched"] for e in lane_evs)
        == t["prefetch_hits"]
    )
    rounds = [
        e for e in dev if e["tid"] == 0 and e["name"].startswith("round")
    ]
    assert len(rounds) == 1  # one sched bracket for the single run()


def test_perfetto_multi_device_one_track_per_device(tmp_path):
    """A two-ring trace (as a 2-device resident run returns) exports one
    process per device - built synthetically so the multi-device shape is
    covered without Mosaic interpret mode."""
    timeline = _timeline()
    recs0 = np.array([
        [tb.TR_ROUND_BEGIN, 0, 3, 5],
        [tb.TR_FIRE_SCALAR, 1, 0, 7],
        [tb.TR_ROUND_END, 2, 1, 4],
        [tb.TR_XFER, 2, 1, 2],
    ], dtype=np.int64)
    recs1 = np.array([
        [tb.TR_ROUND_BEGIN, 0, 1, 1],
        [tb.TR_ABORT, 1, 1, 0],
        [tb.TR_ROUND_END, 1, 0, 1],
    ], dtype=np.int64)
    trace = {
        "epoch": {"t0_ns": 1_000_000, "t1_ns": 2_000_000},
        "rings": [
            {"written": len(r), "dropped": 0, "capacity": 16,
             "records": r}
            for r in (recs0, recs1)
        ],
    }
    out = tmp_path / "mesh.perfetto.json"
    doc = timeline.export_perfetto(str(out), traces=[trace])
    evs = doc["traceEvents"]
    procs = {
        e["args"]["name"]
        for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert procs == {"device 0", "device 1"}
    dev_pids = {e["pid"] for e in evs if e.get("cat") == "device"}
    assert dev_pids == {1, 2}
    # Device-round timestamps interpolate INSIDE the host epoch.
    for e in evs:
        if e.get("cat") == "device":
            assert 1_000_000 / 1e3 <= e["ts"] <= 2_000_000 / 1e3
    # jsonable round-trip matches the direct export.
    j = tb.trace_to_jsonable(trace)
    doc2 = timeline.export_perfetto("", traces=[json.loads(json.dumps(j))])
    assert len(doc2["traceEvents"]) == len(evs)


def test_streaming_megakernel_traces_injection():
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    mk = make_fib_megakernel(256, interpret=True, trace=1024)
    sm = StreamingMegakernel(mk, ring_capacity=16)
    b = TaskGraphBuilder()
    b.add(FIB, args=[8], out=0)
    # The injected row's out slot is the host's: undeclared it lies in
    # row 0's own value block, where the root keeps its children's
    # results since it re-arms in place (ctx.become).
    b.reserve_values(2)
    sm.inject(FIB, [6], out=1)
    sm.close()
    iv, info = sm.run_stream(b, quantum=64, max_rounds=8)
    assert int(iv[0]) == 21 and int(iv[1]) == 8
    inj = tb.records_of(info["trace"], tb.TR_INJECT)
    assert int(inj[:, 2].sum()) == 1  # the injected row was recorded


def test_sharded_runner_refuses_trace(monkeypatch):
    import jax
    from jax.sharding import Mesh
    from hclib_tpu.device.sharded import ShardedMegakernel
    from hclib_tpu.device.workloads import make_fib_megakernel

    devs = np.array(jax.devices()[:1])
    mk = _mk(trace=None, width=2)
    mk.batch_specs = []  # scalar-only for the sharded runner
    mk.trace = tb.TraceRing(64)
    with pytest.raises(ValueError, match="trace"):
        ShardedMegakernel(mk, Mesh(devs, ("d",)))
    # Env-derived tracing degrades (warning + local suppression) WITHOUT
    # mutating the shared kernel: other runners keep their ring.
    monkeypatch.setenv("HCLIB_TPU_TRACE", "64")
    mk2 = make_fib_megakernel(256, interpret=True)
    assert mk2.trace is not None and mk2.trace_from_env
    sm = ShardedMegakernel(mk2, Mesh(devs, ("d",)))
    assert sm._suppress_trace and mk2.trace is not None
    with sm._maybe_untraced():
        assert mk2.trace is None  # suppressed only inside builds
    assert mk2.trace is not None


def test_trace_env_enables_recorder(monkeypatch):
    monkeypatch.setenv("HCLIB_TPU_TRACE", "64")
    assert _mk().trace.capacity == 64
    monkeypatch.setenv("HCLIB_TPU_TRACE", "1")
    assert _mk().trace.capacity == 2048  # 1 = on, default capacity
    monkeypatch.setenv("HCLIB_TPU_TRACE", "0")
    assert _mk().trace is None
    monkeypatch.delenv("HCLIB_TPU_TRACE")
    assert _mk().trace is None
    assert _mk(trace=16).trace.capacity == 16  # explicit arg wins


def test_tracering_normalization_and_decode_validation():
    assert tb.TraceRing.of(None) is None
    assert tb.TraceRing.of(True).capacity == 2048
    assert tb.TraceRing.of(False) is None
    assert tb.TraceRing.of(7).capacity == 7
    r = tb.TraceRing(3)
    assert tb.TraceRing.of(r) is r
    assert r.words == tb.HDR + 3 * tb.TR_WORDS
    with pytest.raises(ValueError):
        tb.TraceRing(0)
    # decode of an all-zero row: no records, nothing dropped.
    d = tb.decode_ring(np.zeros(tb.HDR + 8 * tb.TR_WORDS, np.int32))
    assert d["written"] == 0 and d["dropped"] == 0
    assert d["records"].shape == (0, tb.TR_WORDS)


@pytest.mark.chaos
def test_resident_mesh_trace_rings():
    """2-device resident run with the recorder on: per-device rings with
    round records, reconciled against info."""
    import jax
    from jax.sharding import Mesh
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import (  # noqa: F401
        FIB,
        make_fib_megakernel,
    )

    mk = make_fib_megakernel(256, interpret=True, trace=2048)
    devs = np.array(jax.devices()[:2])
    rk = ResidentKernel(mk, Mesh(devs, ("d",)), steal=True, homed=False)
    builders = []
    for n in (9, 7):
        b = TaskGraphBuilder()
        b.add(FIB, args=[n], out=0)
        builders.append(b)
    iv, _, info = rk.run(builders, quantum=64)
    assert [int(iv[0][0]), int(iv[1][0])] == [34, 13]
    tr = info["trace"]
    assert len(tr["rings"]) == 2
    for d in range(2):
        begins = tb.records_of(tr, tb.TR_ROUND_BEGIN, ring=d)
        ends = tb.records_of(tr, tb.TR_ROUND_END, ring=d)
        # One sched bracket per exchange round on every device.
        assert len(begins) == len(ends) == info["rounds"]
