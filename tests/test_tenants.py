"""Multi-tenant streaming front door (device/tenants.py + inject.py).

Host-side admission (quotas, token buckets, deadlines, poison ladder,
cancellation) tests run against a deterministic injected clock and the
numpy WRR reference model (``wrr_poll_reference`` - the executable spec
of the in-kernel poll), so every decision is a pure function of the
submission sequence. Device tests drive the real interpret-mode
streaming kernel: exact per-tenant totals, isolation under a poisoned +
greedy mix, and quiesce -> resume -> reshard conservation."""

import gc
import threading
import time

import numpy as np
import pytest
from conftest import bump_mk, seed_builder

from hclib_tpu.device.descriptor import (
    F_A0,
    F_DEP,
    F_FN,
    F_HOME,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    RING_ROW,
    TEN_ADMIT_ROUND,
    TEN_DEADLINE_MS,
    TEN_EXPIRED,
    TEN_ID,
    TEN_TOKEN,
    TaskGraphBuilder,
)
from hclib_tpu.device.egress import EgressSpec
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.tenants import (
    ADMIT_ACCEPTED,
    ADMIT_QUEUED,
    TC_CONSUMED,
    TC_DROPPED,
    TC_EXPIRED,
    TC_INSTALLED,
    TC_PAUSE,
    TC_TAIL,
    TC_WEIGHT,
    MeshTenantTable,
    TenantSpec,
    TenantTable,
    TokenBucket,
    build_row,
    normalize_tenants,
    per_tenant_ring_counts,
    tenants_from_env,
    wrr_poll_reference,
)
from hclib_tpu.runtime.resilience import CancelScope, RetryPolicy

BUMP = 0


class FakeClock:
    """Monotonic test clock: admission decisions become a pure function
    of the submission sequence."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _table(specs, region=16, clock=None):
    return TenantTable(specs, region, clock=clock or FakeClock())


def _row(i=0):
    return build_row(BUMP, [i])


def _drive(table, ring, polls=64, headroom=1 << 20, start_round=0):
    """One host entry + ``polls`` device rounds of the reference poll,
    echo absorbed - the deterministic stand-in for run_stream's inner
    loop."""
    tctl = table.pump(ring)
    installed = []
    for r in range(start_round, start_round + polls):
        installed += wrr_poll_reference(
            ring, tctl, table.region_rows, r, headroom
        )
    table.absorb(tctl)
    return installed


# ---------------------------------------------------------------- host


def test_admission_verdicts_accept_queue_and_every_reject_reason():
    """The typed Admission ladder: ACCEPTED under the in-flight budget,
    QUEUED over it, REJECTED("backlog") past queue_capacity,
    REJECTED("rate") when the bucket is dry, REJECTED("ring") at region
    exhaustion - checked cheapest-first, each reason machine-readable."""
    clock = FakeClock()
    t = _table(
        [TenantSpec("a", max_in_flight=2, queue_capacity=5,
                    rate=1.0, burst=8.0)],
        region=16, clock=clock,
    )
    ring = np.zeros((16, RING_ROW), np.int32)
    verdicts = [t.admit("a", _row(i)) for i in range(5)]
    assert [v.status for v in verdicts] == [
        ADMIT_ACCEPTED, ADMIT_ACCEPTED,            # within in-flight budget
        ADMIT_QUEUED, ADMIT_QUEUED, ADMIT_QUEUED,  # over it, backlog ok
    ]
    assert verdicts[0] and verdicts[2]            # both truthy (admitted)
    assert verdicts[0].accepted and verdicts[2].queued
    over = t.admit("a", _row())
    assert over.rejected and over.reason == "backlog"
    assert not over
    # Rate: burst exhausted (5 accepted + 1 rejected probe took none).
    clock.advance(0.0)
    t2 = _table([TenantSpec("b", rate=1.0, burst=2.0)], clock=clock)
    assert t2.admit("b", _row()) and t2.admit("b", _row())
    dry = t2.admit("b", _row())
    assert dry.rejected and dry.reason == "rate"
    clock.advance(1.0)  # one token refills at rate=1/s
    assert t2.admit("b", _row()).accepted
    # Ring: lifetime region budget (published + queued >= region_rows).
    t3 = _table([TenantSpec("c", queue_capacity=100)], region=8)
    for i in range(8):
        assert t3.admit("c", _row(i))
    full = t3.admit("c", _row())
    assert full.rejected and full.reason == "ring"
    # Unknown tenants raise, they don't silently reject - and negative
    # indices never wrap around to the last lane.
    with pytest.raises(KeyError):
        t.admit("nobody", _row())
    with pytest.raises(KeyError):
        t.admit(-1, _row())
    assert t3.stats()["c"]["rejected"] == 1


def test_token_bucket_deterministic_under_fake_clock():
    """Identical clock scripts produce identical token decisions -
    admission determinism is the token bucket's determinism."""
    def script(bucket, clock):
        out = []
        for dt in (0.0, 0.0, 0.3, 0.0, 0.5, 2.0, 0.0, 0.0):
            clock.advance(dt)
            out.append(bucket.try_take())
        return out

    runs = []
    for _ in range(2):
        clock = FakeClock()
        runs.append(script(TokenBucket(2.0, 2.0, clock), clock))
    assert runs[0] == runs[1]
    assert runs[0] == [True, True, False, False, True, True, True, False]
    b = TokenBucket(2.0, 2.0, FakeClock())
    b.try_take(2)
    assert b.wait_s(1) == pytest.approx(0.5)
    assert TokenBucket(0.0, 1.0, FakeClock()).wait_s(2) == float("inf")
    with pytest.raises(ValueError):
        TokenBucket(-1.0, 1.0)


def test_wrr_fairness_ratios_match_weights():
    """Saturated lanes drain in exact weight proportion: the WRR poll
    installs ``weight`` rows per lane per round, so a 4:2:1 spec yields
    4:2:1 installs over any whole number of rounds."""
    specs = [
        TenantSpec("gold", weight=4, queue_capacity=256),
        TenantSpec("silver", weight=2, queue_capacity=256),
        TenantSpec("bronze", weight=1, queue_capacity=256),
    ]
    t = _table(specs, region=64)
    ring = np.zeros((3 * 64, RING_ROW), np.int32)
    for lane in range(3):
        for i in range(56):  # 8 rounds' worth at the summed rate
            t.admit(lane, _row(i))
    installed = _drive(t, ring, polls=8)
    got = {tid: s["completed"] for tid, s in t.stats().items()}
    assert got == {"gold": 32, "silver": 16, "bronze": 8}
    # Install order interleaves lanes (no head-of-line monopoly) and the
    # rows carry their lane tag.
    lanes_seen = [int(r[TEN_ID]) for r in installed]
    assert set(lanes_seen) == {0, 1, 2}
    assert lanes_seen[:7].count(0) == 4  # first round: 4 gold, 2 silver...


def test_wrr_headroom_backpressure_not_overflow():
    """A tiny scheduler headroom bounds TOTAL installs per poll; the
    un-installed rows stay on the ring as host-visible backpressure
    (consumed cursor lags tail) instead of tripping an overflow."""
    t = _table([TenantSpec("a", weight=8), TenantSpec("b", weight=8)])
    ring = np.zeros((32, RING_ROW), np.int32)
    for lane in ("a", "b"):
        for i in range(8):
            t.admit(lane, _row(i))
    tctl = t.pump(ring)
    got = wrr_poll_reference(ring, tctl, t.region_rows, 0, headroom=3)
    assert len(got) == 3
    t.absorb(tctl)
    s = t.stats()
    assert s["a"]["completed"] + s["b"]["completed"] == 3
    assert s["a"]["in_flight"] + s["b"]["in_flight"] == 13  # still ringed


def test_deadline_admission_reject_drop_and_ring_mark():
    """The three expiry points: expired-at-admission rejects on the
    spot; expired-while-host-queued drops at the next pump (counted
    host-side); expired-while-published is marked on the ring row and
    lazily dropped by the poll (counted device-side) - and the
    conservation identity accepted == completed + expired holds."""
    clock = FakeClock()
    t = _table(
        [TenantSpec("a", weight=4, max_in_flight=4, queue_capacity=64)],
        clock=clock,
    )
    ring = np.zeros((16, RING_ROW), np.int32)
    # 1) expired at admission.
    dead = t.admit("a", _row(), deadline_at=clock() - 1.0)
    assert dead.rejected and dead.reason == "expired"
    # 2) four rows publish now; four more queue behind the budget.
    for i in range(8):
        assert t.admit("a", _row(i), deadline_at=clock() + 5.0)
    tctl = t.pump(ring)          # publishes the first 4
    assert tctl[0, TC_TAIL] == 4
    clock.advance(10.0)          # every deadline passes
    # 3) next pump: published rows get the TEN_EXPIRED mark for the
    # device to drop (the host-queued four stay parked: the in-flight
    # budget is full, so their lazy drop waits for freed budget).
    tctl = t.pump(ring)
    assert all(ring[i, TEN_EXPIRED] == 1 for i in range(4))
    installed = wrr_poll_reference(
        ring, tctl, t.region_rows, 0, headroom=100
    )
    assert installed == []       # all four dropped at the poll
    t.absorb(tctl)               # consumed cursor frees the budget...
    t.pump(ring)                 # ...and this pump drops the queued four
    s = t.stats()["a"]
    assert s["accepted"] == 8 and s["completed"] == 0
    assert s["expired"] == 8     # 4 device-dropped + 4 host-dropped
    assert s["rejected"] == 1    # the at-admission one
    assert s["accepted"] == s["completed"] + s["expired"]


# ---- the boundary in bulk (ISSUE 37): pump publishes a run by one
# store and absorb takes the cursor's advance in one pass, to the word
# what the row-by-row paths that are still there do


def _twins(**lane):
    """Two egress tables on one clock that a script drives alike:
    ``bulk``'s lanes have no validator, so a backlog's head run goes to
    the ring by one store; ``rows``' validator accepts every row, which
    makes ``pump`` take each through its row-by-row loop."""
    clock = FakeClock()
    bulk, rows = (
        TenantTable(
            [TenantSpec("a", weight=2, validator=v, **lane),
             TenantSpec("b", validator=v, **lane)],
            16, clock=clock, egress=EgressSpec(depth=4),
        )
        for v in (None, lambda row: None)
    )
    return clock, bulk, rows


def _lanes_alike(bulk, rows):
    """Every word a later pump, absorb, export or client can see."""
    assert bulk.stats() == rows.stats()
    assert bulk.futures.conservation() == rows.futures.conservation()
    for x, y in zip(bulk._lanes, rows._lanes):
        assert [p.index for p in x.pub_meta] == [p.index for p in y.pub_meta]
        assert [p.index for p in x.pub_meta] == list(
            range(x.consumed, x.published))
        assert [p.token for p in x.queue] == [p.token for p in y.queue]
        assert list(x.latencies) == list(y.latencies)
        assert x.timed == y.timed == sum(
            p.deadline_at is not None for p in x.pub_meta)


def _script_run(clock, t, ring):
    """Backlogs without a deadline, two lanes, an admit round to stamp."""
    for i in range(10):
        assert t.submit("ab"[i % 3 == 0], BUMP, args=[i + 1], out=i)
        clock.advance(0.25)
    t.set_admit_round(7)
    return [t.pump(ring)]


def _script_deadline_on_some(clock, t, ring):
    """A deadline on rows 4 and 6 of lane a: the run ends at row 4;
    once the deadline lapses the published rows are marked on the ring,
    their tokens expire once, each mark is named in ``dirty``, and a
    later pump marks nothing again."""
    futs = []
    for i in range(8):
        adm = t.submit("a", BUMP, args=[i + 1],
                       deadline_s=5.0 if i in (4, 6) else None)
        futs.append(adm.future)
        clock.advance(0.25)
    assert t.submit("b", BUMP, args=[99])
    out = [t.pump(ring)]
    assert t._lanes[0].timed == 2 and t._lanes[1].timed == 0
    clock.advance(10.0)
    marked = []
    out.append(t.pump(ring, marked))
    assert sorted(marked) == [(4, 1), (6, 1)]
    assert [i for i in range(16) if ring[i, TEN_EXPIRED]] == [4, 6]
    assert [f.state for f in futs] == [
        "EXPIRED" if i in (4, 6) else "PENDING" for i in range(8)]
    out.append(t.pump(ring, marked))
    assert len(marked) == 2 and t.stats()["a"]["expired"] == 0
    echo = out[-1].copy()   # the device drops the marked two, takes all
    echo[0, TC_CONSUMED], echo[0, TC_INSTALLED] = 8, 6
    echo[0, TC_EXPIRED] = 2
    t.absorb(echo)
    assert t._lanes[0].timed == 0 and len(t._lanes[0].latencies) == 6
    assert t.stats()["a"]["expired"] == 2
    return out


def _script_budget_below_backlog(clock, t, ring):
    """``max_in_flight`` 3 under a backlog of 8: three go, the cursor's
    echo frees two, two more go."""
    for i in range(8):
        assert t.submit("a", BUMP, args=[i + 1])
    out = [t.pump(ring)]
    assert t.stats()["a"]["published"] == 3
    echo = out[0].copy()
    echo[0, TC_CONSUMED] = echo[0, TC_INSTALLED] = 2
    clock.advance(1.0)
    t.absorb(echo)
    out.append(t.pump(ring))
    assert t.stats()["a"]["published"] == 5
    assert t.stats()["a"]["queued"] == 3
    return out


def _script_stamped_residue(clock, t, ring):
    """Residue of a checkpoint cut re-enters with its admit round
    stamped (3): the pump's stamp (9) lands on the zero words only."""
    for i in range(4):
        row = build_row(BUMP, [i + 1])
        row[TEN_ID] = 0
        row[TEN_ADMIT_ROUND] = 3 if i % 2 else 0
        t.readmit("a", row)
    assert t.submit("a", BUMP, args=[5])
    t.set_admit_round(9)
    out = [t.pump(ring)]
    assert ring[:6, TEN_ADMIT_ROUND].tolist() == [9, 3, 9, 3, 9, 0]
    return out


def _script_no_round(clock, t, ring):
    """Telemetry off (admit round 0): no word is stamped."""
    for i in range(5):
        assert t.submit("b", BUMP, args=[i + 1])
    out = [t.pump(ring)]
    assert not ring[:, TEN_ADMIT_ROUND].any()
    return out


SCRIPTS = {
    "run": (_script_run, {}),
    "deadline_on_some": (_script_deadline_on_some, {}),
    "budget_below_backlog": (_script_budget_below_backlog,
                             {"max_in_flight": 3}),
    "stamped_residue": (_script_stamped_residue, {}),
    "no_round": (_script_no_round, {}),
}


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_pump_and_absorb_in_bulk_equal_the_row_by_row_paths(script):
    fn, lane = SCRIPTS[script]
    clock, bulk, rows = _twins(**lane)
    rings = [np.zeros((32, RING_ROW), np.int32) for _ in range(2)]
    t0 = clock()
    blocks = []
    for t, ring in zip((bulk, rows), rings):
        clock.t = t0
        blocks.append(fn(clock, t, ring))
    assert np.array_equal(rings[0], rings[1])
    assert rings[0].any()
    for x, y in zip(*blocks):
        assert np.array_equal(x, y)
    _lanes_alike(bulk, rows)
    # ... and to the end: the device takes everything, the cursors'
    # echo retires every published row on both.
    for t, ring in zip((bulk, rows), rings):
        echo = t.pump(ring)
        echo[:, TC_CONSUMED] = echo[:, TC_TAIL]
        t.absorb(echo)
        assert not any(lane.pub_meta or lane.timed for lane in t._lanes)
    _lanes_alike(bulk, rows)


def test_pump_takes_a_ring_it_cannot_view_flat_row_by_row():
    """The one-store publish writes through a flat view of the lane's
    region; a ring that is not contiguous gets the same words row by
    row."""
    clock, bulk, rows = _twins()
    wide = np.zeros((32, 2 * RING_ROW), np.int32)
    rings = [wide[:, :RING_ROW], np.zeros((32, RING_ROW), np.int32)]
    assert not rings[0].flags.c_contiguous
    for t, ring in zip((bulk, rows), rings):
        _script_run(clock, t, ring)
    assert np.array_equal(rings[0], rings[1]) and rings[0].any()
    assert not wide[:, RING_ROW:].any()


def test_pump_with_a_validator_publishes_what_it_passes():
    """A lane with a validator never takes the one-store path: the rows
    it refuses poison their futures and leave no gap on the ring."""
    def odd_only(row):
        if row[F_A0] % 2 == 0:
            raise ValueError("even")

    t = TenantTable(
        [TenantSpec("a", validator=odd_only, poison_throttle=99,
                    poison_quarantine=99)],
        16, clock=FakeClock(), egress=EgressSpec(depth=4),
    )
    futs = [t.submit("a", BUMP, args=[i]).future for i in range(1, 9)]
    ring = np.zeros((16, RING_ROW), np.int32)
    wrote = []
    tctl = t.pump(ring, wrote)
    assert tctl[0, TC_TAIL] == 4 and sum(n for _, n in wrote) == 4
    assert ring[:5, F_A0].tolist() == [1, 3, 5, 7, 0]
    assert [f.state for f in futs] == ["PENDING", "POISONED"] * 4
    s = t.stats()["a"]
    assert s["dropped"] == s["poisoned"] == 4 and s["published"] == 4


ABSORBS = ["advance", "no_move", "marked", "swept"]


@pytest.mark.parametrize("case", ABSORBS)
def test_absorb_by_the_cursors_advance(case):
    """``absorb`` retires ``new_consumed - consumed`` published rows in
    one pass and records their latencies in one call; a lane with a
    marked row, or one the device swept, still goes row by row: the
    marked row records no latency, the swept rows' futures poison."""
    clock = FakeClock()
    t = TenantTable([TenantSpec("a"), TenantSpec("b")], 16, clock=clock,
                    egress=EgressSpec(depth=4))
    ring = np.zeros((32, RING_ROW), np.int32)
    futs, sent = [], []
    for i in range(6):
        sent.append(clock())
        futs.append(t.submit(
            "a", BUMP, args=[i + 1],
            deadline_s=4.0 if case == "marked" and i == 1 else None,
        ).future)
        clock.advance(0.5)
    if case == "swept":
        t.pump(ring)
        t.quarantine("a", "test")
    elif case == "marked":
        t.pump(ring)
        clock.advance(2.0)          # row 1's deadline lapses on the ring
    tctl = t.pump(ring)
    lane = t._lanes[0]
    assert tctl[0, TC_TAIL] == 6 and len(lane.pub_meta) == 6
    echo = tctl.copy()
    moved = 0 if case == "no_move" else 4
    echo[0, TC_CONSUMED] = moved
    echo[0, TC_INSTALLED] = {"marked": 3, "swept": 0}.get(case, moved)
    if case == "marked":
        echo[0, TC_EXPIRED] = 1
        assert ring[1, TEN_EXPIRED] == 1 and futs[1].state == "EXPIRED"
        assert lane.timed == 1
    if case == "swept":
        assert tctl[0, TC_PAUSE] == 1
        echo[0, TC_DROPPED] = 4
    clock.advance(0.125)
    now = clock()
    t.absorb(echo)
    assert lane.consumed == moved and len(lane.pub_meta) == 6 - moved
    assert [p.index for p in lane.pub_meta] == list(range(moved, 6))
    assert lane.timed == 0
    want = {
        "advance": [now - s for s in sent[:4]],
        "no_move": [],
        "marked": [now - sent[i] for i in (0, 2, 3)],
        "swept": [],
    }[case]
    assert list(lane.latencies) == want
    states = [f.state for f in futs]
    if case == "swept":
        assert states == ["POISONED"] * 4 + ["PENDING"] * 2
        assert futs[0].reason == "swept (lane paused)"
        assert t.stats()["a"]["dropped"] == 4
    elif case == "marked":
        assert states == ["PENDING", "EXPIRED"] + ["PENDING"] * 4
        assert t.stats()["a"]["expired"] == 1
    else:
        assert states == ["PENDING"] * 6
    assert t.futures.conservation()["ok"]
    t.absorb(echo)                  # the same echo again moves nothing
    assert list(lane.latencies) == want and lane.consumed == moved


def test_cancel_scope_deadline_chain_feeds_admission():
    """resolve_deadline precedence: explicit deadline_s beats the scope
    chain's nearest deadline beats the lane default; CancelScope
    deadlines inherit parent-to-child and the earliest wins."""
    clock = FakeClock()
    t = _table([TenantSpec("a", deadline_s=60.0)], clock=clock)
    parent = CancelScope().set_deadline(at=clock() + 5.0)
    child = CancelScope(parent=parent)
    child.set_deadline(at=clock() + 30.0)
    assert child.effective_deadline() == clock() + 5.0  # parent earlier
    assert t.resolve_deadline("a", None, child) == clock() + 5.0
    assert t.resolve_deadline("a", 1.0, child) == clock() + 1.0
    assert t.resolve_deadline("a", None, None) == clock() + 60.0
    assert not child.deadline_expired(now=clock() + 4.0)
    assert child.deadline_expired(now=clock() + 5.0)
    # Re-arm keeps the earliest; exactly-one-argument contract enforced.
    parent.set_deadline(at=clock() + 99.0)
    assert parent.deadline_t == clock() + 5.0
    with pytest.raises(ValueError):
        CancelScope().set_deadline()
    # A cancelled scope rejects at admission as "cancelled".
    child.cancel("user hit ^C")
    adm = t.admit("a", _row(), cancel_scope=child)
    assert adm.rejected and adm.reason == "cancelled"


def test_deadline_budget_cancels_lane_without_touching_siblings():
    """A tenant drowning in expirations (budget exhausted) gets its
    per-lane CancelScope cancelled at the pump; the sibling lane keeps
    flowing."""
    clock = FakeClock()
    t = _table(
        [TenantSpec("doomed", deadline_budget=3, queue_capacity=64),
         TenantSpec("fine", queue_capacity=64)],
        clock=clock,
    )
    ring = np.zeros((32, RING_ROW), np.int32)
    for i in range(4):
        t.admit("doomed", _row(i), deadline_at=clock() + 1.0)
    t.admit("fine", _row())
    clock.advance(5.0)
    _drive(t, ring, polls=2)  # pump drops the 4 expired, trips the budget
    _drive(t, ring, polls=1)  # budget observed -> lane scope cancels
    s = t.stats()
    assert s["doomed"]["expired"] >= 3
    adm = t.admit("doomed", _row())
    assert adm.rejected and adm.reason == "cancelled"
    assert t._lane("doomed").scope.cancelled()
    assert not t.scope.cancelled()           # parent untouched
    assert t.admit("fine", _row()).accepted  # sibling untouched
    assert "deadline budget" in str(t._lane("doomed").scope.reason)


def test_poison_ladder_throttles_then_quarantines_one_lane():
    """Terminal failures climb throttle (WRR weight clamps to 1) ->
    quarantine (lane paused, backlog dropped, submissions rejected);
    the sibling lane never notices. Cancellation never poisons."""
    t = _table(
        [TenantSpec("bad", weight=4, poison_throttle=2,
                    poison_quarantine=4),
         TenantSpec("good", weight=2)],
    )
    ring = np.zeros((32, RING_ROW), np.int32)
    for i in range(6):
        t.admit("bad", _row(i))
    from hclib_tpu.runtime.resilience import CancelledError
    t.report_failure("bad", CancelledError("control"))  # not poison
    assert t.stats()["bad"]["poisoned"] == 0
    t.report_failure("bad")
    t.report_failure("bad")
    tctl = t.pump(ring)
    assert tctl[0, TC_WEIGHT] == 1   # weight clamped: throttled
    assert t.stats()["bad"]["throttled"] == 1
    t.report_failure("bad")
    t.report_failure("bad")          # 4th terminal failure: quarantine
    s = t.stats()["bad"]
    assert s["quarantined"] == 1 and "poison" in s["quarantine_reason"]
    adm = t.admit("bad", _row())
    assert adm.rejected and adm.reason == "quarantined"
    # The paused lane's published residue is swept, not installed, and
    # the good lane keeps flowing.
    t.admit("good", _row())
    tctl = t.pump(ring)
    assert tctl[0, TC_PAUSE] == 1 and tctl[1, TC_PAUSE] == 0
    installed = wrr_poll_reference(ring, tctl, t.region_rows, 0, 100)
    assert [int(r[TEN_ID]) for r in installed] == [1]
    assert int(tctl[0, TC_DROPPED]) > 0
    assert int(tctl[0, TC_CONSUMED]) == int(tctl[0, TC_TAIL])  # swept
    t.absorb(tctl)
    assert t.stats()["good"]["completed"] == 1
    # Swept rows land in dropped (conservation holds for the paused
    # lane) and never pollute the install-latency reservoir.
    sb = t.stats()["bad"]
    assert sb["dropped"] == 6
    assert sb["accepted"] == (
        sb["completed"] + sb["expired"] + sb["dropped"]
    )
    assert t.latency_stats("bad")["n"] == 0
    assert t.drained()               # a quarantined lane can't wedge exit


def test_validator_retry_policy_and_control_signal_drops():
    """The lane validator retries per its RetryPolicy before poisoning;
    a control-signal failure (CancelledError) drops the row without
    climbing the ladder."""
    calls = {"n": 0}

    def flaky(row):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")

    t = _table(
        [TenantSpec("a", validator=flaky,
                    retry=RetryPolicy(max_attempts=3, backoff_s=0.0))],
    )
    ring = np.zeros((16, RING_ROW), np.int32)
    t.admit("a", _row())
    t.pump(ring)
    assert calls["n"] == 3                      # retried to success
    assert t.stats()["a"]["poisoned"] == 0
    from hclib_tpu.runtime.resilience import CancelledError

    def cancels(row):
        raise CancelledError("scope died")

    t2 = _table([TenantSpec("b", validator=cancels)])
    t2.admit("b", _row())
    t2.pump(ring)
    s = t2.stats()["b"]
    assert s["poisoned"] == 0 and s["dropped"] == 1


def test_per_tenant_cancel_drops_backlog_prospectively():
    """cancel(tenant) cancels that lane's scope, drops its host
    backlog, and pauses its lane at the next pump - completed work
    stays completed, siblings untouched."""
    t = _table(
        [TenantSpec("a", weight=2, max_in_flight=2, queue_capacity=64),
         TenantSpec("b")],
    )
    ring = np.zeros((32, RING_ROW), np.int32)
    for i in range(6):
        t.admit("a", _row(i))
    _drive(t, polls=1, ring=ring)    # 2 in flight install
    t.cancel("a", "tenant offboarded")
    s = t.stats()["a"]
    assert s["completed"] == 2 and s["queued"] == 0 and s["dropped"] == 4
    adm = t.admit("a", _row())
    assert adm.rejected and adm.reason == "cancelled"
    assert t.admit("b", _row()).accepted
    tctl = t.pump(ring)
    assert tctl[0, TC_PAUSE] == 1 and tctl[1, TC_PAUSE] == 0


def test_export_resume_conserves_per_tenant_counts():
    """The survivability core, host half: quiesce-export mid-stream,
    resume into a FRESH table, finish - per-tenant accepted/completed/
    expired counts and residue all conserved exactly."""
    clock = FakeClock()
    specs = lambda: [  # noqa: E731
        TenantSpec("x", weight=2, queue_capacity=64),
        TenantSpec("y", queue_capacity=64),
        TenantSpec("z", queue_capacity=64),
    ]
    t = _table(specs(), clock=clock)
    ring = np.zeros((3 * 16, RING_ROW), np.int32)
    sub = {"x": 10, "y": 7, "z": 4}
    for tid, n in sub.items():
        for i in range(n):
            t.admit(tid, _row(i))
    _drive(t, ring, polls=2)         # partial consumption
    done_before = {
        tid: s["completed"] for tid, s in t.stats().items()
    }
    state = t.export_state(ring)
    # A submit that loses the race with the quiesce cut gets a clean
    # "closed" verdict - never a silently-dropped ACCEPTED row.
    late = t.admit("x", _row(99))
    assert late.rejected and late.reason == "closed"
    # Residue is tenant-tagged and accounts for everything un-consumed.
    res_counts = per_tenant_ring_counts(state["ring_rows"])
    for i, (tid, n) in enumerate(sub.items()):
        assert res_counts.get(i, 0) == n - done_before[tid]
    # Resume into a fresh table + fresh ring: the next pump re-publishes
    # residue per lane from region slot 0.
    t2 = _table(specs(), clock=clock)
    t2.resume_from(state)
    ring2 = np.zeros((3 * 16, RING_ROW), np.int32)
    _drive(t2, ring2, polls=64)      # drain fully
    s2 = t2.stats()
    for tid, n in sub.items():
        assert s2[tid]["accepted"] == n
        assert s2[tid]["completed"] == n
        assert s2[tid]["expired"] == 0
    assert t2.drained()
    # resume_from reopens the front door the export closed.
    assert t2.admit("x", _row(0))
    # Lane-count mismatch is diagnosed, not silently misfiled.
    with pytest.raises(ValueError, match="lanes"):
        _table([TenantSpec("only")]).resume_from(state)
    # So is a same-count REORDERED roster: lane state is keyed by
    # index, so resuming x/y/z residue into y/x/z would silently
    # credit one tenant's work and quotas to another.
    t3 = _table([TenantSpec("y"), TenantSpec("x"), TenantSpec("z")],
                clock=clock)
    with pytest.raises(ValueError, match="roster"):
        t3.resume_from(state)
    # A tenant-LESS snapshot (plain stream: ring_rows only) is refused
    # rather than misfiling every row into lane 0.
    with pytest.raises(ValueError, match="without\\s+tenant lanes"):
        _table(specs(), clock=clock).resume_from(
            {"ring_rows": state["ring_rows"]}
        )
    # Oversized residue is diagnosed at resume, not a forever-wedge.
    t4 = _table([TenantSpec("only")], region=8)
    with pytest.raises(ValueError, match="exceeds"):
        t4.resume_from({
            "ring_rows": np.stack([_row(i) for i in range(10)]),
            "tctl": np.zeros((1, 8), np.int32),
            "tstats": np.zeros((1, 8), np.int32),
        })


def test_submit_wait_true_blocks_through_transient_rejection():
    """submit(wait=True) converts a dry token bucket into a bounded
    blocking wait; terminal rejections (quarantine) return immediately."""
    mk = bump_mk()
    sm = StreamingMegakernel(
        mk, ring_capacity=32,
        tenants=[TenantSpec("a", rate=50.0, burst=1.0)],
    )
    assert sm.submit("a", BUMP, args=[1]).accepted   # burst token
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[2], wait=True, wait_timeout_s=5.0)
    waited = time.monotonic() - t0
    assert adm.accepted
    assert 0.001 < waited < 2.0      # blocked for roughly a refill
    sm.tenants.quarantine("a", "test")
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[3], wait=True, wait_timeout_s=5.0)
    assert adm.rejected and adm.reason == "quarantined"
    assert time.monotonic() - t0 < 1.0  # terminal: no blocking
    # Wait respects the submission's own deadline.
    sm2 = StreamingMegakernel(
        bump_mk(), ring_capacity=32,
        tenants=[TenantSpec("b", rate=0.01, burst=1.0)],
    )
    sm2.submit("b", BUMP, args=[1])
    adm = sm2.submit(
        "b", BUMP, args=[2], wait=True, deadline_s=0.05,
        wait_timeout_s=30.0,
    )
    assert adm.rejected and adm.reason == "expired"


def test_normalize_and_env_spelling(monkeypatch):
    """tenants= plumbing: int, str/dict/TenantSpec sequences, False;
    the HCLIB_TPU_TENANTS* env spelling incl. weight override."""
    assert normalize_tenants(False) is None
    assert [s.id for s in normalize_tenants(3)] == ["t0", "t1", "t2"]
    specs = normalize_tenants(
        ["a", {"id": "b", "weight": 5}, TenantSpec("c")]
    )
    assert [s.id for s in specs] == ["a", "b", "c"]
    assert specs[1].weight == 5
    with pytest.raises(TypeError):
        normalize_tenants([42])
    with pytest.raises(ValueError):
        normalize_tenants(0)
    # bool is an int: True must not silently become one anonymous lane.
    with pytest.raises(ValueError, match="ambiguous"):
        normalize_tenants(True)
    monkeypatch.delenv("HCLIB_TPU_TENANTS", raising=False)
    monkeypatch.delenv("HCLIB_TPU_TENANT_WEIGHTS", raising=False)
    assert tenants_from_env() is None
    assert normalize_tenants(None) is None
    monkeypatch.setenv("HCLIB_TPU_TENANTS", "2")
    got = normalize_tenants(None)
    assert [s.id for s in got] == ["t0", "t1"]
    # Both set and disagreeing is a loud config error, not a silent
    # lane-count change.
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,2,1")
    with pytest.raises(ValueError, match="disagrees"):
        tenants_from_env()
    monkeypatch.delenv("HCLIB_TPU_TENANTS")
    monkeypatch.setenv("HCLIB_TPU_TENANT_RATE", "10")
    monkeypatch.setenv("HCLIB_TPU_TENANT_DEADLINE_S", "1.5")
    got = tenants_from_env()
    assert [s.weight for s in got] == [4, 2, 1]  # weights alone set N
    assert got[0].rate == 10.0 and got[2].deadline_s == 1.5
    # A spec'd table validates its shape contracts.
    with pytest.raises(ValueError, match="duplicate"):
        TenantTable([TenantSpec("a"), TenantSpec("a")], 16)
    with pytest.raises(ValueError, match="multiple of 8"):
        TenantTable([TenantSpec("a")], 12)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("w", weight=0)
    # Malformed env values raise loudly - a typo must not silently run
    # the stream as a single anonymous firehose (or drop a quota).
    monkeypatch.setenv("HCLIB_TPU_TENANTS", "three")
    monkeypatch.delenv("HCLIB_TPU_TENANT_WEIGHTS", raising=False)
    with pytest.raises(ValueError, match="HCLIB_TPU_TENANTS"):
        tenants_from_env()
    monkeypatch.setenv("HCLIB_TPU_TENANTS", "3")
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4;2;1")
    with pytest.raises(ValueError, match="WEIGHTS"):
        tenants_from_env()
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,2,1")
    monkeypatch.setenv("HCLIB_TPU_TENANT_RATE", "fast")
    with pytest.raises(ValueError, match="RATE"):
        tenants_from_env()
    monkeypatch.delenv("HCLIB_TPU_TENANT_RATE")
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,0,1")
    with pytest.raises(ValueError, match="weights must be >= 1"):
        tenants_from_env()
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,,1")
    with pytest.raises(ValueError, match="comma-separated"):
        tenants_from_env()  # empty entry = typo, not a shorter roster
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,2,1")
    monkeypatch.setenv("HCLIB_TPU_TENANT_INFLIGHT", "2.9")
    with pytest.raises(ValueError, match="whole"):
        tenants_from_env()
    monkeypatch.delenv("HCLIB_TPU_TENANT_INFLIGHT")
    monkeypatch.setenv("HCLIB_TPU_TENANT_BURST", "16")
    with pytest.raises(ValueError, match="BURST needs"):
        tenants_from_env()  # burst without rate builds no bucket
    monkeypatch.delenv("HCLIB_TPU_TENANT_BURST")


def test_submit_wait_timeout_is_wall_clock_bounded():
    """wait_timeout_s is a WALL-clock bound: a frozen injected table
    clock (whose token bucket therefore never refills) must yield a
    bounded 'rate' rejection, not an unbounded spin."""
    sm = StreamingMegakernel(
        bump_mk(), ring_capacity=32,
        tenants=TenantTable(
            [TenantSpec("a", rate=10.0, burst=1.0)], 32,
            clock=lambda: 0.0,
        ),
    )
    assert sm.submit("a", BUMP, args=[1]).accepted   # burst token
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[2], wait=True, wait_timeout_s=0.3)
    assert adm.rejected and adm.reason == "rate"
    assert time.monotonic() - t0 < 5.0


# ---- one-pass admission (ISSUE 34): the row, the gates, who owns a row


def _oracle_row(fn, args, out, succ0, succ1, lane_idx, token):
    """The published row as the parent (6b75638) constructed it:
    ``build_row``'s body, then ``admit``'s copy and stamps. Kept here
    word for word as the oracle of the one-pass construction."""
    row = np.zeros(RING_ROW, np.int32)
    row[F_FN] = int(fn)
    row[F_DEP] = 0
    row[F_SUCC0] = int(succ0)
    row[F_SUCC1] = int(succ1)
    for i, a in enumerate(args):
        row[F_A0 + i] = int(a)
    row[F_OUT] = int(out)
    row[F_HOME] = NO_TASK
    r = np.array(row, np.int32).reshape(RING_ROW)
    r[TEN_ID] = lane_idx
    r[TEN_EXPIRED] = 0
    r[TEN_DEADLINE_MS] = 0
    r[TEN_TOKEN] = token
    return r


_ROSTER = [("gold", 4), ("silver", 2), ("bronze", 1)]  # serve-3tenant's


def _front(kind, egress):
    """(submit callable, pump-able table) of each face of the front
    door that builds a request's row itself."""
    specs = [TenantSpec(t, weight=w) for t, w in _ROSTER]
    eg = EgressSpec(depth=8) if egress else False
    if kind == "mesh":
        mesh = MeshTenantTable(specs, 1, 16, egress=eg)
        return mesh.submit, mesh.tables[0]
    table = TenantTable(specs, 16, egress=eg)
    if kind == "table":
        return table.submit, table
    sm = StreamingMegakernel(bump_mk(), ring_capacity=3 * 16,
                             tenants=table)
    return sm.submit, table


@pytest.mark.parametrize("by", ["id", "index"])
@pytest.mark.parametrize("kind", ["stream", "table", "mesh"])
@pytest.mark.parametrize("nargs", range(7))
def test_submit_publishes_the_parents_row_word_for_word(nargs, kind, by):
    """0-6 arguments, an out slot, successor words, a tenant named by id
    and by index, with a token and without: what ``pump`` publishes is
    the parent's ``build_row`` + stamp, every one of the 256 words."""
    for egress in (True, False):
        submit, table = _front(kind, egress)
        want = {i: [] for i in range(len(_ROSTER))}
        token = 0
        for lane_idx, (tid, _) in enumerate(_ROSTER):
            for k, (out, s0, s1) in enumerate(
                [(0, NO_TASK, NO_TASK), (3, 5, NO_TASK), (1, 2, 7)]
            ):
                args = [1000 * lane_idx + 10 * k + j - 3
                        for j in range(nargs)]
                adm = submit(lane_idx if by == "index" else tid, BUMP,
                             args=args, out=out, succ0=s0, succ1=s1)
                assert adm.accepted and adm.tenant == tid
                assert adm.index == k
                token += 1
                if egress:
                    assert adm.future.token == token
                    assert (adm.future.fn, adm.future.slot) == (BUMP, out)
                else:
                    assert adm.future is None
                want[lane_idx].append(_oracle_row(
                    BUMP, args, out, s0, s1, lane_idx,
                    token if egress else 0,
                ))
        ring = np.zeros((3 * 16, RING_ROW), np.int32)
        table.pump(ring)
        for lane_idx, rows in want.items():
            got = ring[lane_idx * 16: lane_idx * 16 + len(rows)]
            np.testing.assert_array_equal(got, np.stack(rows))
            assert not ring[lane_idx * 16 + len(rows)].any()
    with pytest.raises(ValueError, match="at most 6 args"):
        submit("gold", BUMP, args=list(range(7)))
    with pytest.raises(KeyError):
        submit("nobody", BUMP)


_GATES = ["quarantined", "cancelled", "expired", "closed", "ring",
          "backlog", "rate"]


def _gated(first, via):
    """A one-lane front door on which gate ``first`` and EVERY later
    gate of the documented order would refuse, and no earlier one; the
    call that tries it. Returns (call, table, lane)."""
    clock = FakeClock()
    k = _GATES.index(first)
    table = TenantTable(
        [TenantSpec("a", rate=1.0, burst=2.0,
                    queue_capacity=2 if k <= 5 else 8)], 8,
        clock=clock,
    )
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=table)
    for i in range(2):  # the bucket is dry now, and the backlog full
        assert table.submit("a", BUMP, args=[i])
    lane = table._lanes[0]
    if k <= 4:
        lane.published = 6  # 6 published + 2 queued fill the region
    if k <= 3:
        table._closed = True
    scope = CancelScope()
    if k <= 1:
        scope.cancel("test")
    if k <= 0:
        lane.quarantined = "test"
    late = k <= 2
    if via == "admit":
        def call(**kw):
            return table.admit(
                "a", _row(), cancel_scope=scope,
                deadline_at=clock() - 1.0 if late else None, **kw)
    else:
        def call(**kw):
            return (sm if via == "stream" else table).submit(
                "a", BUMP, args=[9], cancel_scope=scope,
                deadline_s=-1.0 if late else None, **kw)
    return call, table


@pytest.mark.parametrize("via", ["stream", "table", "admit"])
@pytest.mark.parametrize("first", _GATES)
def test_gates_refuse_in_the_documented_order(first, via):
    """quarantined, cancelled, expired, closed, ring, backlog, rate:
    with every later gate shut too, the earliest names the rejection,
    it is counted once, and nothing is admitted."""
    call, table = _gated(first, via)
    adm = call()
    assert adm.rejected and adm.reason == first and adm.future is None
    assert adm.tenant == "a" and not adm
    s = table.stats()["a"]
    assert s["rejected"] == 1 and s["accepted"] == 2 and s["queued"] == 2
    if via == "admit":  # a wait loop's probe is not a rejection yet
        assert call(record_reject=False).reason == first
        assert table.stats()["a"]["rejected"] == 1


@pytest.mark.parametrize("via", ["stream", "table", "admit"])
@pytest.mark.parametrize("full", ["ring", "backlog"])
def test_rate_token_is_kept_when_a_cheaper_gate_refused(full, via):
    clock = FakeClock()
    table = TenantTable(
        [TenantSpec("a", rate=1.0, burst=8.0, queue_capacity=2)], 8,
        clock=clock,
    )
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=table)
    for i in range(2):
        assert table.submit("a", BUMP, args=[i])
    lane = table._lanes[0]
    if full == "ring":
        lane.published = 6
    tokens = lane.bucket._tokens
    assert tokens == 6.0
    for _ in range(3):
        if via == "admit":
            adm = table.admit("a", _row())
        else:
            adm = (sm if via == "stream" else table).submit("a", BUMP)
        assert adm.rejected and adm.reason == full
    assert lane.bucket._tokens == tokens
    assert table.stats()["a"]["rejected"] == 3


@pytest.mark.parametrize("via", ["admit", "submit_row"])
def test_a_callers_row_is_copied_at_admission(via):
    """``admit`` / ``submit_row`` take a row the CALLER owns: what the
    caller does to it afterwards does not reach the ring."""
    specs = [TenantSpec("a"), TenantSpec("b")]
    if via == "admit":
        table = TenantTable(specs, 8, egress=EgressSpec(depth=4))
        admit = table.admit
    else:
        mesh = MeshTenantTable(specs, 1, 8, egress=EgressSpec(depth=4))
        table, admit = mesh.tables[0], mesh.submit_row
    row = build_row(BUMP, [11, 22], out=2, succ0=4)
    row[TEN_EXPIRED] = 1        # transport words of an older life
    row[TEN_DEADLINE_MS] = 77
    keep = row.copy()
    adm = admit("b", row)
    assert adm.accepted and adm.future.token == 1
    assert (adm.future.fn, adm.future.slot) == (BUMP, 2)
    np.testing.assert_array_equal(row, keep)  # the caller's is untouched
    row[:] = -5
    ring = np.zeros((16, RING_ROW), np.int32)
    table.pump(ring)
    np.testing.assert_array_equal(
        ring[8], _oracle_row(BUMP, [11, 22], 2, 4, NO_TASK, 1, 1))


@pytest.mark.parametrize("reason", ["rate", "backlog"])
def test_submit_wait_true_turns_a_transient_refusal_into_a_wait(reason):
    """``wait=True``: a dry bucket waits for its refill, a full backlog
    for the pump; the probes that were refused meanwhile are not counted
    as rejections, and the wait is bounded."""
    spec = (TenantSpec("a", rate=20.0, burst=1.0) if reason == "rate"
            else TenantSpec("a", queue_capacity=1))
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=[spec])
    table = sm.tenants
    assert sm.submit("a", BUMP, args=[1]).accepted
    refused = sm.submit("a", BUMP, args=[2])
    assert refused.rejected and refused.reason == reason
    pumper = None
    if reason == "backlog":
        ring = np.zeros((8, RING_ROW), np.int32)
        pumper = threading.Timer(0.05, table.pump, args=(ring,))
        pumper.start()
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[3], wait=True, wait_timeout_s=5.0)
    waited = time.monotonic() - t0
    if pumper is not None:
        pumper.join(5.0)
        assert not pumper.is_alive()
    assert adm.accepted and adm.index == 1
    assert 0.001 < waited < 2.0
    s = table.stats()["a"]
    assert s["accepted"] == 2 and s["rejected"] == 1  # only the plain one


def test_submit_allocates_a_third_of_the_parents_objects():
    """A count, never a time: the collector's young-generation counter
    (tracked allocations less deallocations; it only rises while the
    collector is off) over 1,000 ``submit()``s on serve-3tenant's
    roster, futures and verdicts kept. The parent (6b75638) read 9,007:
    Future, Event, its dict, Condition, its dict, two bound lock
    methods, the waiter deque, _Pending, Admission, and more. A future
    without an Event leaves Future, _Pending, Admission."""
    specs = [TenantSpec(t, weight=w) for t, w in _ROSTER]
    table = TenantTable(specs, 1024, egress=EgressSpec(depth=64))
    sm = StreamingMegakernel(bump_mk(), ring_capacity=3 * 1024,
                             tenants=table)
    kept = [None] * 1000
    was_on = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        before = gc.get_count()[0]
        for i in range(1000):
            kept[i] = sm.submit(_ROSTER[i % 3][0], BUMP, args=[i], out=1)
        rise = gc.get_count()[0] - before
    finally:
        if was_on:
            gc.enable()
    assert all(a.accepted for a in kept)
    assert rise <= 9007 // 2, rise
    assert 2500 <= rise, rise  # the count counts: three objects a request
    assert all(a.future._event is None for a in kept)
    assert not any(isinstance(o, threading.Event)
                   for a in kept for o in gc.get_referents(a.future))
    assert sm.stats_dict()["egress"]["waited"] == 0


# -------------------------------------------------------------- device


def test_stream_wrr_exact_totals_and_stats_fold():
    """DEVICE: a 3-lane weighted stream executes every admitted task
    exactly once (value algebra proves it) and stats_dict names each
    tenant's counters - the StallError-names-the-tenant satellite."""
    sm = StreamingMegakernel(
        bump_mk(), ring_capacity=96,
        tenants=[TenantSpec("gold", weight=4), TenantSpec("silver",
                 weight=2), TenantSpec("bronze")],
    )
    expect = 1000
    for i, tid in enumerate(("gold", "silver", "bronze")):
        for k in range(6 + 4 * i):
            sm.submit(tid, BUMP, args=[k + 1])
            expect += k + 1
    sm.close()
    iv, info = sm.run_stream(seed_builder())
    assert int(iv[0]) == expect
    ten = info["tenants"]
    assert ten["gold"]["completed"] == 6
    assert ten["silver"]["completed"] == 10
    assert ten["bronze"]["completed"] == 14
    assert all(s["backlog"] == 0 for s in ten.values())
    sd = sm.stats_dict()
    assert sd["tenants"]["gold"]["accepted"] == 6
    # The drain exit closed the front door atomically: a submit that
    # raced it gets "closed", never an ACCEPTED row that will not run.
    late = sm.tenants.admit("gold", _row(1))
    assert late.rejected and late.reason == "closed"
    # inject() sugar routes through the default (first) lane.
    sm2 = StreamingMegakernel(
        bump_mk(), ring_capacity=32, tenants=2,
    )
    sm2.inject(BUMP, args=[7])
    sm2.close()
    iv2, info2 = sm2.run_stream(seed_builder())
    assert int(iv2[0]) == 1007
    assert info2["tenants"]["t0"]["completed"] == 1


def test_stream_greedy_and_poisoned_tenants_isolated():
    """DEVICE ISOLATION PROOF (single-chip half): one tenant poisoned
    via its validator, one greedy tenant pushing far past its quota -
    the victim lane still completes its exact totals."""
    def poison(row):
        raise RuntimeError("boom")

    sm = StreamingMegakernel(
        bump_mk(), ring_capacity=96,
        tenants=[
            TenantSpec("bad", validator=poison, poison_throttle=1,
                       poison_quarantine=2),
            TenantSpec("greedy", max_in_flight=2, queue_capacity=4),
            TenantSpec("victim", weight=2),
        ],
    )
    expect = 1000
    for i in range(6):
        sm.submit("bad", BUMP, args=[10_000])  # would wreck the value
    greedy_admitted = 0
    greedy_rejected = 0
    for i in range(40):
        adm = sm.submit("greedy", BUMP, args=[1])
        if adm:
            greedy_admitted += 1
        else:
            greedy_rejected += 1
            assert adm.reason == "backlog"
    assert greedy_rejected > 0       # quota actually pushed back
    expect += greedy_admitted
    for k in range(12):
        assert sm.submit("victim", BUMP, args=[100])
        expect += 100
    sm.close()
    iv, info = sm.run_stream(seed_builder())
    assert int(iv[0]) == expect      # no poison row ever executed
    ten = info["tenants"]
    assert ten["victim"]["completed"] == 12
    assert ten["greedy"]["completed"] == greedy_admitted
    assert ten["bad"]["completed"] == 0
    assert ten["bad"]["quarantined"] == 1
    assert ten["bad"]["poisoned"] >= 2


def test_stream_tenant_quiesce_resume_conserves_counts():
    """DEVICE SURVIVABILITY PROOF (stream half): quiesce mid-stream with
    3 tenants live, residue tenant-tagged, resume re-publishes per lane
    - per-tenant accepted/completed/expired conserved exactly and the
    final value is bit-identical to an uninterrupted run."""
    def fresh(n=64):
        return StreamingMegakernel(
            bump_mk(checkpoint=True), ring_capacity=n,
            tenants=["x", "y", "z"],
        )

    sub = {"x": 9, "y": 6, "z": 3}
    expect = 1000 + sum((tid_i + 1) * n
                        for tid_i, n in enumerate(sub.values()))
    sm = fresh()
    for i, (tid, n) in enumerate(sub.items()):
        for _ in range(n):
            sm.submit(tid, BUMP, args=[i + 1])
    sm.quiesce(after_executed=4)
    iv, info = sm.run_stream(seed_builder())
    assert info["quiesced"] is True
    st = info["state"]
    res = per_tenant_ring_counts(st["ring_rows"])
    ten_q = {i: int(st["tctl"][i, TC_INSTALLED]) for i in range(3)}
    for i, n in enumerate(sub.values()):
        assert ten_q[i] + res.get(i, 0) == n   # conserved at the cut
    # The bundle path refuses a reordered roster (lane state is keyed
    # by index) instead of silently crediting the wrong tenant.
    from hclib_tpu.runtime.checkpoint import (
        CheckpointError, restore_stream, snapshot_stream,
    )
    bundle = snapshot_stream(sm, info)
    assert bundle.meta["tenants"] == ["x", "y", "z"]
    reordered = StreamingMegakernel(
        bump_mk(checkpoint=True), ring_capacity=64,
        tenants=["y", "x", "z"],
    )
    with pytest.raises(CheckpointError, match="roster"):
        restore_stream(bundle, reordered)
    plain = StreamingMegakernel(
        bump_mk(checkpoint=True), ring_capacity=64,
    )
    with pytest.raises(CheckpointError, match="roster"):
        restore_stream(bundle, plain)
    sm2 = fresh()
    sm2.close()
    iv2, info2 = sm2.run_stream(resume_state=st)
    assert int(iv2[0]) == expect
    ten = info2["tenants"]
    for tid, n in sub.items():
        assert ten[tid]["accepted"] == n and ten[tid]["completed"] == n
    # Uninterrupted reference run: bit-identical final value.
    sm3 = fresh()
    for i, (tid, n) in enumerate(sub.items()):
        for _ in range(n):
            sm3.submit(tid, BUMP, args=[i + 1])
    sm3.close()
    iv3, _ = sm3.run_stream(seed_builder())
    assert int(iv3[0]) == int(iv2[0])


def test_reshard_conserves_tenant_tagged_ring_residue():
    """SURVIVABILITY PROOF (mesh half, host-side): a resident bundle's
    per-device inject-ring residue carries TEN_ID on the row, so
    reshard(4 -> 2) and (4 -> 8) re-deal conserves per-tenant counts
    exactly - by construction, checked by the probe the chaos soak
    uses."""
    from hclib_tpu.device.descriptor import DESC_WORDS, F_HOME, NO_TASK
    from hclib_tpu.runtime.checkpoint import CheckpointBundle

    ndev, cap, R = 4, 8, 8
    rr = np.zeros((ndev, R, RING_ROW), np.int32)
    ic = np.zeros((ndev, 8), np.int32)
    lane_of = lambda d, i: (d + i) % 3  # noqa: E731 - mixed ownership
    for d in range(ndev):
        for i in range(4):
            rr[d, i] = build_row(BUMP, [d * 10 + i])
            rr[d, i, TEN_ID] = lane_of(d, i)
        ic[d, 0] = 4
    before = per_tenant_ring_counts(rr, ic)
    assert sum(before.values()) == 16
    # Minimal clean-quiesce resident bundle (live rows ready+link-free).
    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, 2:4] = NO_TASK  # F_SUCC0/F_SUCC1
    tasks[:, :, F_HOME] = NO_TASK
    counts = np.zeros((ndev, 8), np.int32)
    counts[:, 1:4] = 1  # tail / alloc / pending
    counts[:, 4] = 2    # value_alloc
    b = CheckpointBundle("resident", {"ndev": ndev}, {
        "tasks": tasks, "succ": np.full((ndev, 8), -1, np.int32),
        "ready": np.zeros((ndev, cap), np.int32), "counts": counts,
        "ivalues": np.zeros((ndev, 16), np.int32),
        "ring_rows": rr, "ictl": ic,
    })
    for m in (2, 8):
        out = b.reshard(m)
        after = per_tenant_ring_counts(
            out.arrays["ring_rows"], out.arrays["ictl"]
        )
        assert after == before
    with pytest.raises(ValueError, match="ictl"):
        per_tenant_ring_counts(rr)  # 3-D residue needs the cursors


def test_resident_inject_rows_accept_tenant_tags():
    """Mesh-side plumbing: ResidentKernel.run's ring packer takes
    (fn, args[, out[, tenant]]) tuples and prebuilt RING_ROW rows; both
    land on the per-device ring with TEN_ID stamped (the full mesh run
    is the Mosaic-gated chaos soak's job)."""
    from hclib_tpu.device.descriptor import F_A0, F_FN, F_OUT
    from hclib_tpu.device.resident import pack_inject_rows

    tagged = build_row(BUMP, [5])
    tagged[TEN_ID] = 2
    ring, n = pack_inject_rows([(BUMP, (1,), 3, 1), tagged], R=4)
    assert n == 2
    assert ring[0, F_FN] == BUMP and ring[0, F_A0] == 1
    assert ring[0, F_OUT] == 3 and ring[0, TEN_ID] == 1
    assert (ring[1] == tagged).all()
    ic = np.zeros((1, 8), np.int32)
    ic[0, 0] = 2
    assert per_tenant_ring_counts(ring[None], ic) == {1: 1, 2: 1}
    with pytest.raises(ValueError, match="overflow"):
        pack_inject_rows([(BUMP, ())] * 5, R=4)


# ----------------------- deadline survival + mesh-wide tenancy (ISSUE 13)


def test_deadline_budget_survives_export_resume():
    """SATELLITE: deadlines export as REMAINING budget (TEN_DEADLINE_MS
    on the residue row, never a wall-clock instant) and re-arm against
    the resuming clock - a deadline storm straddling a cut reconciles
    exactly: rows with budget left complete, rows whose re-armed budget
    lapses expire, and nothing resumes deadline-free."""
    from hclib_tpu.device.descriptor import TEN_DEADLINE_MS

    clock = FakeClock()
    t = _table([TenantSpec("a", queue_capacity=64)], clock=clock)
    ring = np.zeros((16, RING_ROW), np.int32)
    # Three deadline classes: none, ample (60 s), tight (2 s).
    assert t.admit("a", _row(0))
    assert t.admit("a", _row(1), deadline_at=clock() + 60.0)
    assert t.admit("a", _row(2), deadline_at=clock() + 2.0)
    state = t.export_state(ring)  # nothing pumped: all three queued
    ms = sorted(int(r[TEN_DEADLINE_MS]) for r in state["ring_rows"])
    assert ms == [0, 2000, 60000]
    # Resume on a MUCH later clock: a wall-clock instant would have
    # doomed every row; remaining budget re-arms from now.
    clock.advance(100.0)
    t2 = _table([TenantSpec("a", queue_capacity=64)], clock=clock)
    t2.resume_from(state)
    clock.advance(5.0)  # only the tight row's re-armed 2 s lapses
    ring2 = np.zeros((16, RING_ROW), np.int32)
    _drive(t2, ring2, polls=4)
    s = t2.stats()["a"]
    assert s["accepted"] == 3
    assert s["completed"] == 2 and s["expired"] == 1, s
    assert s["accepted"] == s["completed"] + s["expired"]
    # The republished rows carry a CLEAN deadline word (stamped only at
    # export) - byte-parity with freshly admitted rows.
    assert all(int(r[TEN_DEADLINE_MS]) == 0 for r in ring2[:2])
    # A row already past its deadline AT export folds into the expired
    # count right there (doomed either way), not into the residue.
    t3 = _table([TenantSpec("b", queue_capacity=64)], clock=clock)
    assert t3.admit("b", _row(), deadline_at=clock() + 1.0)
    clock.advance(2.0)
    st3 = t3.export_state(np.zeros((16, RING_ROW), np.int32))
    assert st3["ring_rows"].shape[0] == 0
    assert t3.stats()["b"]["expired"] == 1


def test_mesh_table_routing_quota_and_isolation():
    """Mesh front door (the tentpole's host half): least-backlogged
    routing with explicit device override, the typed Admission ladder
    verbatim per replica, a MESH-WIDE rate bucket, and the poison
    ladder enforced on aggregate counts across devices."""
    from hclib_tpu.device.tenants import MeshTenantTable

    def boom(row):
        raise RuntimeError("poison")

    clock = FakeClock()
    mt = MeshTenantTable(
        [TenantSpec("a", weight=2, queue_capacity=64),
         TenantSpec("rated", rate=1.0, burst=2.0, queue_capacity=64),
         TenantSpec("poi", validator=boom, poison_throttle=1,
                    poison_quarantine=2, queue_capacity=64)],
        ndev=2, region_rows=16, clock=clock,
    )
    rings = np.zeros((2, 3 * 16, RING_ROW), np.int32)
    # Least-backlog routing alternates devices (ties to the lowest id).
    devs = [mt.submit("a", BUMP, args=[i]).device for i in range(4)]
    assert devs == [0, 1, 0, 1]
    # Explicit placement override.
    assert mt.submit("a", BUMP, args=[9], device=1).device == 1
    with pytest.raises(KeyError):
        mt.submit("a", BUMP, device=7)
    with pytest.raises(KeyError):
        mt.submit("nobody", BUMP)
    # The rate quota is MESH-WIDE: burst 2 admits two, the third
    # rejects "rate" no matter which replica it would land on.
    assert mt.submit("rated", BUMP, args=[1])
    assert mt.submit("rated", BUMP, args=[2])
    adm = mt.submit("rated", BUMP, args=[3])
    assert adm.rejected and adm.reason == "rate"
    # Aggregate poison: ONE terminal validator failure per device - no
    # single replica reaches a threshold, the mesh-wide count does.
    assert mt.submit("poi", BUMP, args=[1], device=0)
    assert mt.submit("poi", BUMP, args=[2], device=1)
    mt.pump(rings)   # validator poisons one row on each device
    mt.pump(rings)   # aggregate (2 >= quarantine) applies everywhere
    snap = mt.stats()["poi"]
    assert snap["quarantined"] == 1 and snap["poisoned"] == 2
    for d in range(2):
        adm = mt.submit("poi", BUMP, args=[0], device=d)
        assert adm.rejected and adm.reason == "quarantined"
    # Per-tenant conservation on the aggregate identity.
    for tid, s in mt.stats().items():
        assert s["accepted"] == (
            s["completed"] + s["expired"] + s["dropped"] + s["backlog"]
        ), (tid, s)


def test_mesh_export_reshard_resume_conserves_and_guards():
    """The mesh survivability core, host half: export mid-flight,
    resume on a DIFFERENT device count - per-tenant counts conserved
    exactly, residue re-dealt round-robin, roster mismatches and
    tenant-less states refused (never misfiled)."""
    from hclib_tpu.device.tenants import MeshTenantTable

    clock = FakeClock()
    specs = lambda: [  # noqa: E731
        TenantSpec("x", weight=2, queue_capacity=64),
        TenantSpec("y", queue_capacity=64),
        TenantSpec("z", queue_capacity=64),
    ]
    mt = MeshTenantTable(specs(), 4, 16, clock=clock)
    rings = np.zeros((4, 3 * 16, RING_ROW), np.int32)
    sub = {"x": 11, "y": 7, "z": 5}
    for tid, n in sub.items():
        for i in range(n):
            assert mt.submit(tid, BUMP, args=[i])
    # Partial consumption, then the cut.
    tctl = mt.pump(rings)
    for d in range(4):
        wrr_poll_reference(rings[d], tctl[d], 16, 0, 1 << 20)
    mt.absorb(tctl)
    done_at_cut = {t: mt.stats()[t]["completed"] for t in sub}
    mt2, state = mt.reshard(rings, 2)
    res = per_tenant_ring_counts(state["ring_rows"], state["ictl"])
    for i, (tid, n) in enumerate(sub.items()):
        assert done_at_cut[tid] + res.get(i, 0) == n
    # A submit racing the cut gets a clean "closed" verdict.
    late = mt.submit("x", BUMP, args=[0])
    assert late.rejected and late.reason == "closed"
    # Drain on the 2-device successor: per-tenant totals exact.
    rings2 = np.zeros((2, 3 * 16, RING_ROW), np.int32)
    for r in range(64):
        tctl = mt2.pump(rings2)
        for d in range(2):
            wrr_poll_reference(rings2[d], tctl[d], 16, r, 1 << 20)
        mt2.absorb(tctl)
        if mt2.drained():
            break
    assert mt2.drained()
    for tid, n in sub.items():
        s = mt2.stats()[tid]
        assert s["accepted"] == n and s["completed"] == n, (tid, s)
    # Roster mismatch / tenant-less state / lane-count guards.
    bad = MeshTenantTable(
        [TenantSpec("y"), TenantSpec("x"), TenantSpec("z")], 2, 16,
        clock=clock,
    )
    with pytest.raises(ValueError, match="roster"):
        bad.resume_from(state)
    with pytest.raises(ValueError, match="tctl"):
        MeshTenantTable(specs(), 2, 16, clock=clock).resume_from(
            {"ring_rows": state["ring_rows"], "ictl": state["ictl"]}
        )
    with pytest.raises(ValueError, match="lanes"):
        MeshTenantTable([TenantSpec("only")], 2, 16,
                        clock=clock).resume_from(state)


def test_mesh_tenants_env_and_normalize(monkeypatch):
    """HCLIB_TPU_MESH_TENANTS spelling: lane count, shared per-lane
    knobs, weight-count agreement, and RAISE-on-malformed semantics."""
    from hclib_tpu.device.tenants import (
        mesh_tenants_from_env,
        normalize_mesh_tenants,
    )

    for var in ("HCLIB_TPU_MESH_TENANTS", "HCLIB_TPU_TENANT_WEIGHTS",
                "HCLIB_TPU_TENANT_RATE"):
        monkeypatch.delenv(var, raising=False)
    assert mesh_tenants_from_env() is None
    assert normalize_mesh_tenants(None) is None
    assert normalize_mesh_tenants(False) is None
    monkeypatch.setenv("HCLIB_TPU_MESH_TENANTS", "3")
    specs = normalize_mesh_tenants(None)
    assert [s.id for s in specs] == ["t0", "t1", "t2"]
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,2,1")
    assert [s.weight for s in normalize_mesh_tenants(None)] == [4, 2, 1]
    monkeypatch.setenv("HCLIB_TPU_TENANT_WEIGHTS", "4,2")
    with pytest.raises(ValueError, match="lanes"):
        mesh_tenants_from_env()
    monkeypatch.delenv("HCLIB_TPU_TENANT_WEIGHTS")
    monkeypatch.setenv("HCLIB_TPU_MESH_TENANTS", "nope")
    with pytest.raises(ValueError, match="MESH_TENANTS"):
        mesh_tenants_from_env()


def test_resident_mesh_tenancy_construction_and_off_path():
    """Tenancy-off mesh builds carry ZERO tenant state - no lane count,
    no tctl inputs/outputs, no region partition (the structural half of
    the bit-identity acceptance; the compiled-run half needs Mosaic and
    rides the chaos job) - and the tenant-enabled construction
    validates every shape up front, before any kernel builds."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.tenants import MeshTenantTable
    from hclib_tpu.parallel.mesh import cpu_mesh

    rk_off = ResidentKernel(
        bump_mk(checkpoint=True), cpu_mesh(2, axis_name="q"),
        inject=True,
    )
    assert rk_off.T == 0 and rk_off.tenant_specs is None
    assert rk_off.region_rows == 0
    rk = ResidentKernel(
        bump_mk(checkpoint=True), cpu_mesh(2, axis_name="q"),
        inject=True, tenants=["x", "y", "z"], ring_capacity=96,
    )
    assert rk.T == 3
    assert rk.ring_capacity == rk.T * rk.region_rows
    assert rk.region_rows % 8 == 0
    with pytest.raises(ValueError, match="inject=True"):
        ResidentKernel(bump_mk(), cpu_mesh(2, axis_name="q"),
                       tenants=2)
    builders = [TaskGraphBuilder() for _ in range(2)]
    # Rows enter only through the table on a tenant mesh.
    with pytest.raises(ValueError, match="MeshTenantTable"):
        rk.run(builders, inject_rows=[[(BUMP, (1,))]])
    # Table shape must match the mesh exactly.
    with pytest.raises(ValueError, match="mismatch"):
        rk.run(builders,
               tenant_table=MeshTenantTable([TenantSpec("x")], 2, 16))
    # A tenancy-off mesh refuses a table outright.
    with pytest.raises(ValueError, match="tenant-enabled"):
        rk_off.run(builders,
                   tenant_table=MeshTenantTable(
                       [TenantSpec("x")], 2, 16))



@pytest.mark.chaos
def test_resident_mesh_tenant_wrr_and_quiesce_reshard():
    """DEVICE ACCEPTANCE (mesh half): the in-kernel WRR tenant poll on
    a 4-device mesh installs every routed admission exactly once (value
    algebra proves it), a mid-stream quiesce exports deadline-stamped
    tenant-tagged residue + aggregate counter blocks, and a reshard to
    2 devices resumes with per-tenant totals conserved exactly."""
    import numpy as np

    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.tenants import MeshTenantTable
    from hclib_tpu.parallel.mesh import cpu_mesh
    from hclib_tpu.runtime.checkpoint import (
        restore_resident, snapshot_resident,
    )

    specs = lambda: ["gold", "std", "bg"]  # noqa: E731

    def make(ndev):
        return ResidentKernel(
            bump_mk(checkpoint=True), cpu_mesh(ndev, axis_name="q"),
            migratable_fns=[BUMP], homed=False, window=4, inject=True,
            tenants=specs(), ring_capacity=96,
        )

    def table_for(rk):
        return MeshTenantTable(
            rk.tenant_specs, rk.ndev, rk.region_rows
        )

    def seed(ndev):
        bs = [TaskGraphBuilder() for _ in range(ndev)]
        for b in bs:
            b.add(BUMP, args=[0])
        return bs

    sub = {"gold": 10, "std": 6, "bg": 4}
    # Full run: every admitted row installs + executes exactly once.
    rk = make(4)
    table = table_for(rk)
    expect = 0
    for i, (tid, n) in enumerate(sub.items()):
        for _ in range(n):
            assert table.submit(tid, BUMP, args=[i + 1])
            expect += i + 1
    iv, _, info = rk.run(seed(4), quantum=2, max_rounds=4096,
                         tenant_table=table)
    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 0].sum()) == expect
    ten = info["tenants"]
    for tid, n in sub.items():
        assert ten[tid]["accepted"] == n and ten[tid]["completed"] == n
    # Quiesce mid-stream, reshard 4 -> 2, resume: totals conserved.
    rk2 = make(4)
    t2 = table_for(rk2)
    for i, (tid, n) in enumerate(sub.items()):
        for _ in range(n):
            assert t2.submit(tid, BUMP, args=[i + 1])
    _, _, info_q = rk2.run(seed(4), quantum=1, max_rounds=4096,
                           quiesce=1, tenant_table=t2)
    assert info_q["quiesced"], info_q
    assert "tctl" in info_q["state"]
    bundle = snapshot_resident(rk2, info_q)
    assert bundle.meta["tenants"] == specs()
    rk3 = make(2)
    iv3, _, info3 = restore_resident(
        bundle, rk3, quantum=4, max_rounds=4096,
        tenant_table=table_for(rk3),
    )
    assert info3["pending"] == 0
    total3 = int(np.asarray(iv3)[:, 0].sum())
    assert total3 == expect, (total3, expect)
