"""Live telemetry plane (ISSUE 19): on-device latency histograms,
mid-run scrape, and SLO-driven autoscale signals.

Math half: the log2 bucket spec (``bucket_of`` / ``bucket_edges``),
the fold reference (overflow counted, never dropped), the
conservative quantile bound, and the ``EpochBracket`` rounds->ns
conversion. Device half: the real interpret-mode streaming kernel
stamping lifecycles, folding per-tenant histograms that reconcile
bit-exactly with the spans and the egress ledger, scraped MID-RUN by
a ``TelemetryPoller``, and conserved across a quiesce/resume cut.
Mesh half: the 4 -> 2 -> 4 host-model reshard where per-device blocks
merge and per-tenant totals close against resolved futures exactly.
SLO half: streaming quantiles + multi-window burn rates, the typed
``slo_out`` policy rung (fires before the deadline watchdog, during
cooldown), the Perfetto request flow events, the Prometheus
exposition (registry + HTTP endpoint), and the env knobs (typed,
raise on malformed). Off-path: a telemetry-off build lowers to the
EXACT text an env-free build lowers to, even with the env knob set."""

import threading
import urllib.request

import numpy as np
import pytest
from conftest import bump_mk, seed_builder, timeline_mod as _timeline

import hclib_tpu as hc
from hclib_tpu.device.descriptor import (
    RING_ROW,
    TEN_ADMIT_ROUND,
    TEN_ID,
    TEN_TOKEN,
)
from hclib_tpu.device.egress import EGR_WORDS, EgressSpec, HostMailbox
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.telemetry import (
    LAT_BUCKETS,
    LAT_WORDS,
    TG_RETIRES,
    TG_ROUNDS,
    TelemetryBlock,
    TelemetryPoller,
    bucket_edges,
    bucket_of,
    hist_fold_reference,
    quantile_from_hist,
    unpack_spans,
)
from hclib_tpu.device.tenants import (
    MeshTenantTable,
    TenantSpec,
    TenantTable,
    wrr_poll_reference,
)
from hclib_tpu.runtime.clockprobe import EpochBracket
from hclib_tpu.runtime.slo import SloEstimator, parse_windows

BUMP = 0


def _table(specs=None, region=32, depth=64):
    return TenantTable(
        specs or [TenantSpec("a", queue_capacity=64),
                  TenantSpec("b", queue_capacity=64)],
        region, egress=EgressSpec(depth=depth),
    )


def _stream(checkpoint=False, telemetry=True, **kw):
    return StreamingMegakernel(
        bump_mk(checkpoint=checkpoint), ring_capacity=64,
        tenants=_table(**kw), telemetry=telemetry,
    )


# ------------------------------------------------------- bucket math


def test_bucket_of_matches_edges_and_clamps():
    """The branch-free in-kernel formula's host spec lands every delta
    in the bucket whose [lo, hi) brackets it; negatives clamp to 0;
    everything at or past 2^(B-1) lands in the overflow bucket."""
    edges = bucket_edges()
    assert len(edges) == LAT_BUCKETS and edges[0] == (0, 2)
    assert edges[-1][1] is None
    for i, (lo, hi) in enumerate(edges):
        assert bucket_of(lo) == i
        if hi is not None:
            assert bucket_of(hi - 1) == i
            assert bucket_of(hi) == i + 1
    assert bucket_of(-5) == 0
    assert bucket_of(1 << (LAT_BUCKETS - 1)) == LAT_BUCKETS - 1
    assert bucket_of((1 << 30) + 7) == LAT_BUCKETS - 1


def test_hist_fold_reference_counts_overflow_and_validates():
    """Overflow retirements are COUNTED in the last bucket (never
    dropped), TG_RETIRES tracks the histogram mass, and bad shapes or
    tenant indices are refused loudly."""
    tele = np.zeros((3, LAT_BUCKETS), np.int64)
    out = hist_fold_reference(
        tele, [(0, 1), (0, 1 << 20), (1, -3), (1, 3)]
    )
    assert out[1, 0] == 1 and out[1, LAT_BUCKETS - 1] == 1
    assert out[2, 0] == 1 and out[2, 1] == 1  # -3 clamps to bucket 0
    assert out[0, TG_RETIRES] == 4
    assert tele.sum() == 0  # folds a copy
    with pytest.raises(ValueError, match="tenant"):
        hist_fold_reference(tele, [(2, 1)])
    with pytest.raises(ValueError, match="tele block"):
        hist_fold_reference(np.zeros((3, 4), np.int64), [])


def test_quantile_from_hist_is_conservative_upper_edge():
    """The quantile is the UPPER edge of the bucket holding the
    ceil(q*total)-th sample; the unbounded overflow bucket reports its
    LOWER edge; empty histograms report None; q is validated."""
    counts = np.zeros(LAT_BUCKETS, np.int64)
    counts[2] = 6           # six samples in [4, 8)
    counts[5] = 4           # four in [32, 64)
    assert quantile_from_hist(counts, 0.5) == 8.0
    assert quantile_from_hist(counts, 0.99) == 64.0
    counts[LAT_BUCKETS - 1] = 90
    assert quantile_from_hist(counts, 0.99) == float(
        1 << (LAT_BUCKETS - 1)
    )
    assert quantile_from_hist(np.zeros(LAT_BUCKETS), 0.5) is None
    with pytest.raises(ValueError, match="quantile"):
        quantile_from_hist(counts, 1.5)


def test_unpack_spans_roundtrip():
    admit, install, fire, retire = unpack_spans(10, (7 << 16) | 3)
    assert (admit, install, fire) == (10, 13, 20)
    assert retire == fire  # dispatch/completion atomic per round


# -------------------------------------------------- rounds->ns bracket


def test_epoch_bracket_monotone_and_clamped():
    """The wall bracket accumulates (t1-t0, rounds) per entry; the
    factor is total/total; negative wall or round deltas clamp to 0 so
    a clock step never drives the conversion negative; to_ns is
    monotone in rounds."""
    br = EpochBracket()
    assert br.ns_per_round() is None and br.to_ns(5) is None
    br.accumulate(1000, 3000, 4)       # 500 ns/round
    br.accumulate(3000, 7000, 4)       # 1000 ns/round -> avg 750
    assert br.ns_per_round() == pytest.approx(750.0)
    assert br.to_ns(2) == pytest.approx(1500.0)
    assert br.to_ns(4) > br.to_ns(2)
    before = br.ns_per_round()
    br.accumulate(9000, 8000, -3)      # clamped: moves nothing
    assert br.ns_per_round() == before
    assert br.entries == 3


# ---------------------------------------------------- off-path gates


def test_telemetry_requires_egress_stream():
    """Histograms are per-tenant and fold at the egress retire: a
    telemetry build without an egress-enabled tenant stream is a
    loud construction error, not a silent no-op."""
    with pytest.raises(ValueError, match="egress"):
        StreamingMegakernel(bump_mk(), ring_capacity=32,
                            telemetry=True)
    with pytest.raises(ValueError, match="egress"):
        StreamingMegakernel(
            bump_mk(), ring_capacity=32,
            tenants=TenantTable([TenantSpec("a")], 16,
                                clock=lambda: 0.0),
            telemetry=True,
        )


def _lower_text(sm):
    mk = sm.mk
    tasks, succ, ready, counts = seed_builder().finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    args = [
        tasks, succ, ready, counts,
        np.zeros(mk.num_values, np.int32),
        np.zeros((sm.ring_capacity, RING_ROW), np.int32),
        np.zeros(8, np.int32),
        np.zeros((len(sm.tenants), 8), np.int32),
        np.zeros((sm._egress.depth, EGR_WORDS), np.int32),
        np.zeros((sm._egress.depth, EGR_WORDS), np.int32),
        np.zeros(8, np.int32),
        np.zeros(mk.capacity, np.int32),
    ]
    if sm.telemetry:
        args += [
            np.zeros((1 + len(sm.tenants), LAT_BUCKETS), np.int32),
            np.zeros((mk.capacity, LAT_WORDS), np.int32),
        ]
    return sm._build(1 << 10, 64).lower(*args).as_text()


def test_off_path_compiles_zero_telemetry_words(monkeypatch):
    """ACCEPTANCE: telemetry unset lowers to the EXACT text an env-free
    build lowers to, even with HCLIB_TPU_TELEMETRY set - and the
    enabled build differs (the tele/tlat words exist only on-path)."""
    monkeypatch.delenv("HCLIB_TPU_TELEMETRY", raising=False)
    base = _lower_text(_stream(telemetry=None))
    monkeypatch.setenv("HCLIB_TPU_TELEMETRY", "1")
    off = _lower_text(_stream(telemetry=False))
    assert off == base
    on = _lower_text(_stream(telemetry=None))  # env spelling enables
    assert on != base


# ------------------------------------------------- device histograms


def test_device_histograms_reconcile_with_spans_and_ledger():
    """DEVICE: every tracked retirement lands in exactly one per-tenant
    bucket; refolding the per-row (fire - admit) spans through the
    reference reproduces the device block bit-exactly; per-tenant
    totals equal the ledger's resolved counts."""
    sm = _stream()
    futs = {"a": [], "b": []}
    for i in range(12):
        tid = "a" if i % 3 else "b"
        adm = sm.submit(tid, BUMP, args=[1])
        assert adm
        futs[tid].append(adm.future)
    sm.close()
    iv, info = sm.run_stream(seed_builder())
    assert int(iv[0]) == 1000 + 12
    snap = sm.telemetry_snapshot()
    assert snap is not None and snap["entries"] >= 1
    blk = TelemetryBlock(snap["tele"], snap.get("ns_per_round"))
    g = blk.gauges()
    assert g["retires"] == blk.total() == 12
    assert g["rounds"] > 0 and g["installs"] >= 12
    assert blk.total(0) == len(futs["a"]) == sum(
        1 for f in futs["a"] if f.state == "RESULT"
    )
    assert blk.total(1) == len(futs["b"])
    spans = sm.telemetry_spans()
    assert len(spans) == 12
    refold = np.zeros((1 + 2, LAT_BUCKETS), np.int64)
    per_row = []
    for tok, (admit, install, fire) in spans.items():
        assert 0 <= admit <= install <= fire
        ten = 0 if any(f.token == tok for f in futs["a"]) else 1
        per_row.append((ten, fire - admit))
    refold = hist_fold_reference(refold, per_row)
    assert np.array_equal(refold[1:], blk.tele[1:]), (refold, blk.tele)
    assert info["telemetry"]["rounds"] == g["rounds"]


def test_device_quantiles_within_one_bucket_of_exact_stamps():
    """ACCEPTANCE: the histogram-derived p50/p99 equal the upper edge
    of the bucket holding the EXACT order statistic computed from the
    per-request stamps - i.e. they agree within one log2 bucket."""
    sm = _stream()
    for i in range(16):
        assert sm.submit(i % 2, BUMP, args=[1])
    sm.close()
    sm.run_stream(seed_builder(), max_rounds=8)
    blk = TelemetryBlock(sm.telemetry_snapshot()["tele"])
    deltas = sorted(
        fire - admit
        for admit, _, fire in sm.telemetry_spans().values()
    )
    assert len(deltas) == 16
    for q in (0.5, 0.99):
        exact = deltas[max(1, int(np.ceil(q * len(deltas)))) - 1]
        lo, hi = bucket_edges()[bucket_of(exact)]
        assert blk.quantile(q) == float(hi if hi is not None else lo)
        assert blk.quantile(q) >= exact  # conservative bound


def test_live_stream_scraped_midrun_two_monotone_snapshots():
    """ACCEPTANCE: a TelemetryPoller thread snapshots the RUNNING
    stream at least twice, seq and histogram mass monotonically
    advancing, with at least one snapshot strictly before the final
    state (a true mid-run scrape, not an exit artifact)."""
    sm = _stream()
    for i in range(24):
        assert sm.submit(i % 2, BUMP, args=[1])
    sm.close()
    poller = TelemetryPoller(sm.telemetry_snapshot,
                             interval_s=0.001).start()
    sm.run_stream(seed_builder(), max_rounds=4)
    midrun = len(poller.snapshots)
    poller.stop(final_poll=True)
    assert midrun >= 2, "poller never caught the stream mid-run"
    seqs = [s["seq"] for s in poller.snapshots]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    totals = [int(np.asarray(s["tele"])[1:].sum())
              for s in poller.snapshots]
    rounds = [int(np.asarray(s["tele"])[0, TG_ROUNDS])
              for s in poller.snapshots]
    assert totals == sorted(totals) and rounds == sorted(rounds)
    assert totals[-1] == 24
    assert totals[0] < 24, "first scrape already saw the final state"
    assert poller.latest_block().total() == 24
    assert poller.wait_for(2, timeout_s=0.1)


def test_quiesce_resume_conserves_histograms():
    """A checkpoint cut carries the tele/tlat blocks in the bundle: the
    resumed stream keeps folding into the SAME cumulative histogram,
    and the final per-tenant totals equal every tracked retirement
    across both halves of the cut."""
    def fresh():
        return _stream(checkpoint=True)

    sm = fresh()
    t1 = sm.tenants
    futs = [sm.submit("a", BUMP, args=[1]).future for _ in range(8)]
    sm.quiesce(after_executed=3)
    _, info = sm.run_stream(seed_builder())
    assert info["quiesced"]
    state = info["state"]
    assert "tele" in state and "tlat" in state
    cut_rounds = int(np.asarray(state["tele"])[0, TG_ROUNDS])
    cut_mass = int(np.asarray(state["tele"])[1:].sum())
    assert 0 < cut_mass < 8
    tokens = [f.resume_token for f in futs if f.state == "PREEMPTED"]
    assert tokens
    sm2 = fresh()
    sm2.close()
    sm2.run_stream(resume_state=state)
    for tok in tokens:
        f = sm2.tenants.reattach(tok)
        assert f.result(timeout=2.0) is not None
    snap = sm2.telemetry_snapshot()
    blk = TelemetryBlock(snap["tele"])
    assert blk.total() == 8, "histogram mass lost across the cut"
    assert blk.gauges()["rounds"] > cut_rounds  # timebase continued
    # Both halves' ledgers close, and the CUMULATIVE histogram mass
    # equals the resolutions summed across the cut.
    c1 = t1.futures.conservation()
    c2 = sm2.tenants.futures.conservation()
    assert c1["ok"] and c2["ok"], (c1, c2)
    assert c2["reattached"] == len(tokens)
    assert blk.total() == c1["resolved"] + c2["resolved"]


# ------------------------------------------------- mesh reconciliation


def test_mesh_reshard_reconciles_histograms_with_ledger():
    """ACCEPTANCE: across a live 4 -> 2 -> 4 reshard (host model:
    wrr_poll_reference + HostMailbox + hist_fold_reference per device,
    merged per phase), per-tenant histogram totals equal the ledger's
    per-tenant resolved counts EXACTLY, and
    submitted == hist_total + expired + poisoned closes globally."""
    region = 16
    clk = [100.0]
    spec = EgressSpec(depth=64)
    rng = np.random.default_rng(42)
    table = MeshTenantTable(
        [TenantSpec("gold", weight=2, queue_capacity=512),
         TenantSpec("std", queue_capacity=512)],
        4, region, clock=lambda: clk[0], egress=spec,
    )
    futures = table.futures
    merged = TelemetryBlock(np.zeros((3, LAT_BUCKETS), np.int64))
    submitted = 0
    resolved_by = {"gold": 0, "std": 0}

    def drive(table, rings, polls=4, start=0):
        nonlocal merged
        boxes = [HostMailbox(spec, park_cap=8 * region)
                 for _ in range(table.ndev)]
        teles = [np.zeros((3, LAT_BUCKETS), np.int64)
                 for _ in range(table.ndev)]
        table.set_admit_round(start)
        tctl = table.pump(rings)
        for r in range(start, start + polls):
            for d in range(table.ndev):
                rows = wrr_poll_reference(
                    rings[d], tctl[d], table.region_rows, r, 1 << 20
                )
                retires = []
                for row in rows:
                    ten = int(row[TEN_ID])
                    retires.append(
                        (ten, r - int(row[TEN_ADMIT_ROUND]))
                    )
                    resolved_by["gold" if ten == 0 else "std"] += 1
                teles[d] = hist_fold_reference(teles[d], retires)
                boxes[d].publish([
                    (int(row[TEN_TOKEN]), 0, BUMP, 0, 7)
                    for row in rows
                ])
        table.absorb(tctl)
        for d, box in enumerate(boxes):
            box.drain(futures=futures)
            merged = merged.merge(TelemetryBlock(teles[d]))
        clk[0] += 0.05

    def rings_for(ndev):
        return np.zeros((ndev, 2 * region, RING_ROW), np.int32)

    sizes = [4, 2, 4]
    rings = rings_for(4)
    live = []
    for phase, ndev in enumerate(sizes):
        for i in range(10):
            doomed = rng.random() < 0.2
            adm = table.submit(
                i % 2, BUMP, args=[i],
                deadline_s=(0.01 if doomed else 600.0),
            )
            if adm:
                submitted += 1
                live.append(adm.future)
            clk[0] += float(rng.random() * 0.02)
        drive(table, rings, polls=2, start=4 * phase)
        if phase == len(sizes) - 1:
            break
        state = table.export_state(rings)
        tokens = [f.resume_token for f in live
                  if f.state == "PREEMPTED"]
        nxt = table.resized(sizes[phase + 1])
        assert nxt.futures is futures
        nxt.resume_from(state)
        for tok in tokens:
            nxt.reattach(tok)
        table, rings = nxt, rings_for(nxt.ndev)
    for r in range(20, 60):
        drive(table, rings, polls=1, start=r)
        if table.drained():
            break
    assert table.drained()
    cons = futures.conservation()
    assert cons["ok"] and cons["pending"] == 0, cons
    # Per-tenant: histogram mass IS the resolved count.
    assert merged.total(0) == resolved_by["gold"]
    assert merged.total(1) == resolved_by["std"]
    assert merged.total() == cons["resolved"]
    # Global: every submission is accounted for, exactly.
    assert submitted == (
        merged.total() + cons["expired"] + cons["poisoned"]
    ), (submitted, cons)
    assert cons["expired"] > 0, "storm never exercised expiry"


# --------------------------------------------------------- SLO engine


def _degraded_estimator(**kw):
    est = SloEstimator(objective_rounds=64, quantile=0.99,
                       windows_s=(5.0, 30.0), **kw)
    counts, t = np.zeros(LAT_BUCKETS, np.int64), 0.0
    for lo, hi in ((4, 32), (256, 4096)):
        rng = np.random.default_rng(int(lo))
        for _ in range(6):
            for d in rng.integers(lo, hi, size=16):
                counts[bucket_of(int(d))] += 1
            t += 1.0
            est.observe(counts.copy(), t)
    return est, t


def test_slo_estimator_quantiles_and_burn_rates():
    """Streaming quantiles ride the cumulative histogram; burn rates
    are (bad/total)/(1-q) per window over the DELTA from the window's
    baseline snapshot; pressure is the max across windows."""
    est, t = _degraded_estimator()
    qs = est.quantiles((0.5, 0.99))
    assert qs[0.99] >= 256 and qs[0.5] >= 8
    burns = est.burn_rates(t)
    assert set(burns) == {5.0, 30.0}
    # The short window sees only degraded traffic: bad/total ~ 1.0,
    # budget 0.01 -> burn ~100x. The long window dilutes with the
    # healthy prefix but still burns.
    assert burns[5.0] > burns[30.0] > 1.0
    assert est.latency_pressure(t) == max(burns.values())
    st = est.stats()
    assert st["objective_rounds"] == 64 and st["total"] == est.total
    with pytest.raises(ValueError, match="width"):
        est.observe(np.zeros(4, np.int64), t + 1.0)


def test_slo_no_objective_is_inert():
    """No objective -> zero pressure and empty burn map, whatever the
    stream does (the off path a metrics-only deployment rides)."""
    est = SloEstimator(objective_rounds=None, quantile=0.99,
                       windows_s=(5.0,))
    counts = np.zeros(LAT_BUCKETS, np.int64)
    counts[LAT_BUCKETS - 1] = 1000
    for t in (1.0, 2.0, 3.0):
        est.observe(counts * int(t), t)
    assert est.latency_pressure(3.0) == 0.0


def test_parse_windows_and_env_knobs_raise_on_malformed(monkeypatch):
    """Typed env contract: every SLO knob raises NAMING the variable on
    malformed text instead of limping on a default."""
    assert parse_windows("60,300") == (60.0, 300.0)
    assert parse_windows(" 5 ") == (5.0,)
    assert parse_windows("60,,300") == (60.0, 300.0)  # blanks skip
    for bad in ("", "60,nope", "0", "-5"):
        with pytest.raises(ValueError, match="HCLIB_TPU_SLO_WINDOWS_S"):
            parse_windows(bad)
    monkeypatch.setenv("HCLIB_TPU_SLO_QUANTILE", "ninety-nine")
    with pytest.raises(ValueError, match="HCLIB_TPU_SLO_QUANTILE"):
        SloEstimator(objective_rounds=64)
    monkeypatch.delenv("HCLIB_TPU_SLO_QUANTILE", raising=False)
    monkeypatch.setenv("HCLIB_TPU_SLO_OBJECTIVE_ROUNDS", "fast")
    with pytest.raises(ValueError,
                       match="HCLIB_TPU_SLO_OBJECTIVE_ROUNDS"):
        SloEstimator()
    monkeypatch.delenv("HCLIB_TPU_SLO_OBJECTIVE_ROUNDS", raising=False)
    with pytest.raises(ValueError, match="quantile"):
        SloEstimator(objective_rounds=64, quantile=1.5)
    with pytest.raises(ValueError, match="objective"):
        SloEstimator(objective_rounds=-1)
    monkeypatch.setenv("HCLIB_TPU_SLO_BURN", "0")
    with pytest.raises(ValueError, match="slo_burn"):
        hc.AutoscalerPolicy(min_devices=1, max_devices=8,
                            scale_out_backlog=64.0,
                            scale_in_backlog=4.0)


def test_policy_slo_out_fires_before_watchdog_and_rides_trace():
    """The slo_out rung bypasses hysteresis AND cooldown (like
    evacuate/deadline_out), sits BELOW deadline_out in the ladder, and
    the typed event rides TR_SCALE + metrics + Perfetto via SC_NAMES -
    the one-table edit that keeps every renderer in sync."""
    from hclib_tpu.device.tracebuf import (
        SC_NAMES,
        SC_SLO_OUT,
        TR_SCALE,
        records_of,
    )

    assert SC_NAMES[SC_SLO_OUT] == "slo out"

    def policy():
        p = hc.AutoscalerPolicy(
            min_devices=1, max_devices=8, scale_out_backlog=1e9,
            scale_in_backlog=4.0, hysteresis=2, cooldown=3,
            tenant_pressure=0.25, slo_burn=2.0,
        )
        p._cooling = 3  # prove the rung bypasses the gate
        return p

    obs = hc.Observation(2, [4, 4], executed_delta=8, slice_s=1.0,
                         latency_pressure=5.0)
    target, kind, reason = policy().decide(obs)
    assert (target, kind) == (4, "slo_out") and "burn" in reason
    # Zeroing the burn signal: the same observation holds (nothing
    # else would have scaled - the SLO rung acted alone).
    quiet = hc.Observation(2, [4, 4], executed_delta=8, slice_s=1.0,
                           latency_pressure=0.0)
    assert policy().decide(quiet)[1] == "hold"
    # Ladder order: a draining deadline budget outranks the burn
    # (drain is a DELTA, so seed the baseline first).
    p = policy()
    p.decide(hc.Observation(
        2, [4, 4], executed_delta=8, slice_s=1.0,
        tenants={"t": {"expired": 0, "budget": 20}},
    ))
    t2, k2, _ = p.decide(hc.Observation(
        2, [4, 4], executed_delta=8, slice_s=1.0,
        tenants={"t": {"expired": 10, "budget": 20}},
        latency_pressure=5.0,
    ))
    assert k2 == "deadline_out", k2
    # Respects max_devices: already at the ceiling -> not slo_out.
    at_cap = hc.Observation(8, [4] * 8, executed_delta=8, slice_s=1.0,
                            latency_pressure=5.0)
    assert policy().decide(at_cap)[1] != "slo_out"
    # The typed event: ScaleEvent validates the kind via SC_NAMES,
    # Autoscaler mirrors it into metrics + the TR_SCALE host ring.
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(lambda n: None, policy(), metrics=reg)
    asc._event(hc.ScaleEvent("slo_out", 1, 2, 4, reason))
    recs = records_of(asc.trace_info(), TR_SCALE)
    assert len(recs) == 1 and int(recs[0][2]) == (2 << 8) | 4
    snap = reg.snapshot()["metrics"]
    assert snap["autoscale.slo_out.count"] == 1.0
    with pytest.raises(ValueError, match="kind"):
        hc.ScaleEvent("slo_sideways", 0, 2, 4, "no")


# ----------------------------------------------- perfetto flow events


class _FakeFuture:
    def __init__(self, token, t_submit=None, t_done=None):
        self.token = token
        self.t_submit = t_submit
        self.t_done = t_done


def test_request_flow_events_join_host_and_device_stamps():
    """Each request renders as queued + inflight slices and a flow
    chain; a resolved future adds a RESULT marker anchored on the
    round axis through ns_per_round, never before the fire."""
    timeline = _timeline()
    spans = {7: (2, 3, 9), 8: (4, 4, 6)}
    futs = [_FakeFuture(7, t_submit=10.0, t_done=10.0 + 20e-6)]
    ev = timeline.request_flow_events(spans, futs,
                                      ns_per_round=1000.0)
    names = [e.get("name", "") for e in ev]
    assert "req 7 queued" in names and "req 7 inflight" in names
    assert "req 8 queued" in names
    # 20us host wall at 1000 ns/round = 20 rounds past admit=2.
    res = [e for e in ev if e.get("name") == "req 7 result"]
    assert len(res) == 1 and res[0]["ts"] == pytest.approx(22.0)
    chain7 = [e for e in ev
              if e.get("cat") == "request" and e.get("id") == 7]
    assert [e["ph"] for e in chain7] == ["s", "t", "t", "f"]
    assert chain7[-1]["ts"] >= 9  # the finish never precedes the fire
    chain8 = [e for e in ev
              if e.get("cat") == "request" and e.get("id") == 8]
    assert [e["ph"] for e in chain8] == ["s", "t", "f"]
    assert chain8[-1]["ts"] == 6  # no host stamp: flow ends at fire
    assert any(e.get("ph") == "M" for e in ev)  # track names present


def test_export_perfetto_renders_tr_latency():
    """A TR_LATENCY device record decodes tenant/bucket from its packed
    a-word and renders on the events track."""
    timeline = _timeline()
    from hclib_tpu.device.tracebuf import TAG_NAMES, TR_LATENCY

    assert TAG_NAMES[TR_LATENCY] == "latency"
    trace = {
        "epoch": {"t0_ns": 1_000_000, "t1_ns": 2_000_000},
        "rings": [{
            "records": np.array(
                [[int(TR_LATENCY), 5, (2 << 16) | 3, 12]], np.int64
            ),
            "written": 1, "dropped": 0, "capacity": 8,
        }],
    }
    doc = timeline.export_perfetto("", traces=[trace])
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(n.startswith("latency t2 2^3") for n in names), names


# ------------------------------------------------ metrics + exposition


def test_registry_watch_refreshes_and_survives_source_death():
    """watch() polls the source on a daemon thread and records the
    latest mapping; a raising source records an error flag but keeps
    the last good value; unwatch stops the thread; re-watching a name
    replaces the old watch."""
    reg = hc.MetricsRegistry()
    with pytest.raises(ValueError, match="interval"):
        reg.watch("w", lambda: {}, interval_s=0.0)
    hits = threading.Event()
    state = {"n": 0, "die": False}

    def source():
        if state["die"]:
            raise RuntimeError("scrape target gone")
        state["n"] += 1
        hits.set()
        return {"n": state["n"]}

    reg.watch("live", source, interval_s=0.002)
    assert hits.wait(timeout=2.0)
    deadline = 50
    while reg.snapshot()["metrics"].get("live.n", 0) < 1 and deadline:
        deadline -= 1
        threading.Event().wait(0.01)
    assert reg.snapshot()["metrics"]["live.n"] >= 1
    state["die"] = True
    err_seen = 0
    for _ in range(100):
        m = reg.snapshot()["metrics"]
        if m.get("live.error") == 1.0:
            err_seen = 1
            break
        threading.Event().wait(0.01)
    assert err_seen, "raising source never surfaced live.error"
    reg.unwatch("live")


def test_prometheus_latency_exposition_is_cumulative():
    """Native histogram form: per-tenant CUMULATIVE bucket counts, le =
    the bucket's upper edge in rounds, overflow mass ONLY in +Inf,
    plus _count and the rounds->ns gauge."""
    reg = hc.MetricsRegistry()
    tele = np.zeros((2, LAT_BUCKETS), np.int64)
    tele[1, 0], tele[1, 2], tele[1, LAT_BUCKETS - 1] = 3, 2, 4
    reg.record_latency(
        TelemetryBlock(tele, ns_per_round=250.0),
        labels={0: "gold"},
    )
    text = reg.to_prometheus()
    assert '# TYPE hclib_latency histogram' in text
    assert 'hclib_latency_bucket{tenant="gold",le="2"} 3' in text
    assert 'hclib_latency_bucket{tenant="gold",le="8"} 5' in text
    # Overflow: counted in +Inf (total), in NO bounded bucket - the
    # last bounded edge still reads 5, not 9.
    top = 1 << (LAT_BUCKETS - 1)
    assert f'hclib_latency_bucket{{tenant="gold",le="{top}"}} 5' in text
    assert 'hclib_latency_bucket{tenant="gold",le="+Inf"} 9' in text
    assert 'hclib_latency_count{tenant="gold"} 9' in text
    assert "hclib_latency_ns_per_round 250.0" in text


def test_metrics_serve_http_endpoint():
    """tools/metrics_serve.py: a stdlib http.server thread exposes the
    registry at /metrics; other paths 404; the server shuts down
    cleanly."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import metrics_serve

    reg = hc.MetricsRegistry()
    reg.record("svc", {"up": 1})
    tele = np.zeros((2, LAT_BUCKETS), np.int64)
    tele[1, 3] = 5
    reg.record_latency(TelemetryBlock(tele))
    httpd, thread = metrics_serve.serve(reg, port=0)
    try:
        port = httpd.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5.0
        ).read().decode()
        assert "hclib_tpu_svc_up 1.0" in body
        assert 'hclib_latency_bucket{tenant="0",le="16"} 5' in body
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5.0
            )
    finally:
        httpd.shutdown()
        thread.join(timeout=5.0)


# ----------------------------------------------------- env registry


def test_telemetry_env_rows_registered():
    """Every telemetry/SLO knob is a typed registry row (runtime/env.py
    refuses unregistered reads; the registry is the documentation)."""
    from hclib_tpu.runtime.env import registry_table

    names = {row[0] for row in registry_table()}
    for knob in (
        "HCLIB_TPU_TELEMETRY",
        "HCLIB_TPU_TELEMETRY_POLL_S",
        "HCLIB_TPU_SLO_OBJECTIVE_ROUNDS",
        "HCLIB_TPU_SLO_QUANTILE",
        "HCLIB_TPU_SLO_WINDOWS_S",
        "HCLIB_TPU_SLO_BURN",
    ):
        assert knob in names, knob
