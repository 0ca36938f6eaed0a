"""Request/response serving loop (ISSUE 16): completion-mailbox egress,
submit futures, and the wedge-proof degradation ladder.

Host half: the typed ``Future`` face (RESULT | EXPIRED | POISONED |
PREEMPTED - exactly one, exactly once), the ``FutureTable`` ledger's
conservation identity, and the numpy executable specs
(``egress_reference`` / ``flush_parked_reference`` / ``HostMailbox``)
of the in-kernel publish path. Device half: the real interpret-mode
streaming kernel publishing through the completion mailbox, parking on
full (tiny depth forces it), preempting across a quiesce cut, and
poisoning on abort. Protocol half: the ``EgressMailboxModel`` explored
over every schedule - a full mailbox with a DEAD poller provably cannot
wedge the quiesce export or the drained exit - plus the seeded
forgot-the-park-ring bug the explorer must find. Off-path: an
egress-off build lowers to the exact text an env-free build lowers to,
even with the egress env knobs set."""

import os
import sys
import threading
import time

import numpy as np
import pytest
from conftest import Tick, bump_mk, seed_builder

from hclib_tpu.device.egress import (
    EC_CONSUMED,
    EC_PARK_COUNT,
    EC_PARK_HEAD,
    EC_PARKED,
    EC_WRITE,
    EGR_TOKEN,
    EGR_WORDS,
    EgressProtocolError,
    EgressSpec,
    FutureExpired,
    FuturePoisoned,
    FuturePreempted,
    FutureTable,
    FutureTimeout,
    HostMailbox,
    egress_from_env,
    egress_reference,
    flush_parked_reference,
    normalize_egress,
)
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.tenants import MeshTenantTable, TenantSpec, TenantTable

BUMP = 0


def _table(specs=None, region=16, egress=None, clock=None):
    return TenantTable(
        specs or [TenantSpec("a")], region,
        clock=clock or (lambda: 100.0), egress=egress,
    )


# ------------------------------------------------------ future ladder


def test_future_timeout_is_typed_and_carries_stats():
    """result(timeout=) on a PENDING future raises FutureTimeout - a
    TimeoutError subclass - carrying the ledger's stats_dict, so the
    timeout handler can see submitted/resolved/pending without another
    call."""
    ft = FutureTable(backoff_s=0.001)
    f = ft.create("gold", fn=BUMP, slot=0)
    with pytest.raises(FutureTimeout) as ei:
        f.result(timeout=0.02)
    assert isinstance(ei.value, TimeoutError)
    assert ei.value.stats["pending"] == 1
    assert ei.value.stats["submitted"] == 1
    assert f.state == "PENDING"          # a timeout is NOT terminal
    ft.resolve(f.token, 42)              # late result still lands
    assert f.result(timeout=1.0) == 42


def test_double_resolution_is_impossible():
    """Exactly-once: any second terminal transition on a token -
    resolve/resolve, resolve/expire, expire/poison - raises
    EgressProtocolError, as does resolving a token never minted."""
    ft = FutureTable()
    f = ft.create("a", 0, 0)
    ft.resolve(f.token, 7)
    for hit in (lambda: ft.resolve(f.token, 8),
                lambda: ft.expire(f.token, "late"),
                lambda: ft.poison(f.token, "late")):
        with pytest.raises(EgressProtocolError, match="already"):
            hit()
    assert f.result() == 7               # the first resolution stands
    with pytest.raises(EgressProtocolError, match="unknown"):
        ft.resolve(999_999, 0)
    g = ft.create("a", 0, 0)
    ft.expire(g.token, "deadline")
    with pytest.raises(FutureExpired):
        g.result()
    cons = ft.conservation()
    assert cons["ok"] and cons["resolved"] == 1 and cons["expired"] == 1


# ---- the bulk settle (ISSUE 37): resolve_many is a loop of resolve
# under one taking of the lock


def _ledger(kind):
    """A ledger holding 12 pending tokens of the given kind: live
    futures, tokens adopted from a predecessor and not yet reattached,
    or six of each (adopted 1-6, live 7-12). Returns (ledger, live
    futures by token, resume tokens by token)."""
    ft = FutureTable(clock=Tick())
    live, resume = {}, {}
    if kind != "live":
        old = FutureTable()
        gone = [old.create("a", BUMP, i) for i in range(
            12 if kind == "adopted" else 6)]
        resume = {rt[2]: rt for rt in old.preempt_all()}
        assert sorted(resume) == [f.token for f in gone]
        ft.adopt_tokens(old.export_tokens())
    if kind != "adopted":
        for i in range(12 if kind == "live" else 6):
            f = ft.create("b", BUMP, i)
            live[f.token] = f
    assert ft.pending() == 12
    return ft, live, resume


def _told(ft, live, resume):
    """What a client can learn of every token: its future's state,
    value and reason (an adopted one through reattach)."""
    futs = dict(live)
    futs.update((t, ft.reattach(rt)) for t, rt in resume.items())
    return {t: (f.state, f.value, f.reason) for t, f in futs.items()}


@pytest.mark.parametrize("kind", ["live", "adopted", "mix"])
def test_resolve_many_equals_a_loop_of_resolve(kind):
    """The same rows through ``resolve_many`` and through ``resolve``
    one by one leave two ledgers a client cannot tell apart: live
    futures RESULT with their values, adopted-and-unattached tokens
    early-terminal for the ``reattach`` that follows, counters and
    conservation equal."""
    order = [5, 12, 1, 8, 3, 10, 7, 2, 11, 4, 9, 6]
    values = [31 * t + 1 for t in order]
    one, live1, resume1 = _ledger(kind)
    for t, v in zip(order, values):
        one.resolve(t, v)
    many, live2, resume2 = _ledger(kind)
    assert many.resolve_many(order, values) == 12
    assert many.conservation() == one.conservation()
    assert many.conservation()["resolved"] == 12 and not many.pending()
    assert sorted(many._early) == sorted(one._early) == sorted(resume1)
    told = _told(many, live2, resume2)
    assert told == _told(one, live1, resume1)
    assert told == {t: ("RESULT", 31 * t + 1, None) for t in order}
    assert many.resolve_many([], []) == 0


@pytest.mark.parametrize("offender", ["double", "unknown"])
def test_resolve_many_refuses_in_row_order(offender):
    """A token resolved twice, or never issued, raises where a loop of
    ``resolve`` would: the rows before it are RESULT and counted, the
    rows after it still pending, the ledger conserving; the rest then
    settles."""
    ft, live, _ = _ledger("live")
    bad = 3 if offender == "double" else 999_999
    rows = [1, 2, 3, bad, 4, 5]
    with pytest.raises(
        EgressProtocolError,
        match="already RESULT" if offender == "double" else "unknown",
    ):
        ft.resolve_many(rows, [10 * t for t in rows])
    assert [live[t].state for t in (1, 2, 3)] == ["RESULT"] * 3
    assert [live[t].value for t in (1, 2, 3)] == [10, 20, 30]
    assert all(live[t].state == "PENDING" for t in range(4, 13))
    cons = ft.conservation()
    assert cons["ok"] and cons["resolved"] == 3 and cons["pending"] == 9
    assert ft.resolve_many(list(range(4, 13)), [0] * 9) == 9
    cons = ft.conservation()
    assert cons["ok"] and cons["resolved"] == 12 and not cons["pending"]


def test_resolve_many_wakes_who_waits_and_dates_each_future_itself():
    """A client blocked in ``result()`` wakes from the bulk call; every
    future's ``t_done`` is a clock reading of its own, taken inside the
    call: not before the batch began, not after it returned, in row
    order."""
    ft = FutureTable(backoff_s=0.001)
    futs = [ft.create("a", BUMP, i) for i in range(200)]
    got = []
    waiter = threading.Thread(
        target=lambda: got.append(futs[150].result(timeout=60.0)))
    waiter.start()
    while ft.stats_dict()["waited"] < 1:
        time.sleep(0.001)
    t0 = time.monotonic()
    ft.resolve_many([f.token for f in futs], [7 * f.token for f in futs])
    t1 = time.monotonic()
    waiter.join(60.0)
    assert not waiter.is_alive() and got == [7 * futs[150].token]
    done = [f.t_done for f in futs]
    assert t0 <= done[0] and done[-1] <= t1 and done == sorted(done)
    # Under a clock that moves a reading, no two futures share one.
    ft, live, _ = _ledger("live")
    ft.resolve_many(sorted(live), [0] * 12)
    assert len({f.t_done for f in live.values()}) == 12


# ---- the lazy Event (ISSUE 34): a future pays for a waiter only when
# one comes


def test_a_future_builds_an_event_only_for_a_waiter_who_finds_it_pending():
    """``waited`` is the futures waited on while PENDING, each once; a
    future resolved before anyone waits never builds an Event, and a
    timed wait on a pending one returns False in time."""
    ft = FutureTable(backoff_s=0.002)
    futs = [ft.create("a", BUMP, i) for i in range(40)]
    assert all(f._event is None for f in futs)
    assert ft.stats_dict()["waited"] == 0
    for f in futs[:7]:
        t0 = time.monotonic()
        assert f.wait(0.01) is False
        assert time.monotonic() - t0 < 1.0
        assert f.wait(0.0) is False  # a second wait: the same Event
    assert ft.stats_dict()["waited"] == 7
    assert "waited" not in ft.conservation()  # telemetry, not the ledger
    for i, f in enumerate(futs):
        ft.resolve(f.token, 3 * i + 1)
    for i, f in enumerate(futs):
        assert f.wait() and f.wait(0.0) and f.result() == 3 * i + 1
    assert [f._event is not None for f in futs] == [True] * 7 + [False] * 33
    assert all(f._event.is_set() for f in futs[:7])
    d = ft.stats_dict()
    assert d["waited"] == 7 and d["ok"] and d["resolved"] == 40


def test_waiters_before_during_and_after_resolution_all_wake():
    """Some hundreds of futures, eighteen client threads (more than
    cores) that start waiting before the resolver, beside it and after
    it is done, under a short switch interval: no result() hangs, every
    value is right, and every Event that was built was built for a
    waiter (``waited`` counts them all, and no more than the futures
    there are)."""
    ft = FutureTable(backoff_s=0.001)
    n = 360
    futs = [ft.create("a", BUMP, 0) for _ in range(n)]
    got = {}
    errors = []

    def client(k, delay):
        try:
            time.sleep(delay)
            mine = futs[k::18]
            if k % 2:  # odd clients walk theirs backwards
                mine = mine[::-1]
            for f in mine:
                got[f.token] = f.result(timeout=20.0)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    def resolver():
        time.sleep(0.02)
        for i, f in enumerate(futs):
            ft.resolve(f.token, 3 * f.token + 1)
            if i % 60 == 59:
                time.sleep(0.005)

    # clients 0-5 wait first, 6-11 start beside the resolver, 12-17
    # only after every future is terminal
    delays = [0.0] * 6 + [0.02 + 0.004 * i for i in range(6)] + [0.0] * 6
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        early = [threading.Thread(target=client, args=(k, delays[k]))
                 for k in range(12)]
        res = threading.Thread(target=resolver)
        for t in early:
            t.start()
        res.start()
        res.join(30.0)
        assert not res.is_alive()
        waited_then = ft.stats_dict()["waited"]
        late = [threading.Thread(target=client, args=(k, 0.0))
                for k in range(12, 18)]
        for t in late:
            t.start()
        for t in early + late:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert got == {f.token: 3 * f.token + 1 for f in futs}
    d = ft.stats_dict()
    # the late clients found every future terminal: not one Event more
    assert d["waited"] == waited_then
    assert 1 <= d["waited"] <= 12 * (n // 18)
    assert d["waited"] == sum(f._event is not None for f in futs)
    assert all(f._event is None for k in range(12, 18) for f in futs[k::18])
    assert d["ok"] and d["resolved"] == n and d["pending"] == 0


@pytest.mark.parametrize(
    "how", ["resolve", "expire", "poison", "poison_all", "preempt_all",
            "reattach"])
def test_every_terminal_transition_wakes_a_waiter_with_its_rung(how):
    """A client blocked in result() on a PENDING future (so its Event
    exists) is woken by each way a future ends, with that way's typed
    answer; ``reattach`` hands out a future that is waited on, and woken,
    like any other."""
    ft = FutureTable(backoff_s=0.5)  # a lost wake-up would cost 0.5 s
    f = ft.create("a", BUMP, 2)
    if how == "reattach":
        (rt,) = ft.preempt_all()
        with pytest.raises(FuturePreempted):
            f.result(timeout=1.0)
        assert f._event is None  # terminal before anyone waited
        f = ft.reattach(rt)
        assert f.state == "PENDING" and f._event is None
    out = []

    def client():
        try:
            out.append(("value", f.result(timeout=20.0)))
        except Exception as e:  # noqa: BLE001 - the rung under test
            out.append(("raised", e))

    t = threading.Thread(target=client)
    t.start()
    deadline = time.monotonic() + 10.0
    while f._event is None and time.monotonic() < deadline:
        time.sleep(0.001)
    assert f._event is not None and ft.stats_dict()["waited"] == 1
    t0 = time.monotonic()
    if how in ("resolve", "reattach"):
        ft.resolve(f.token, 99)
    elif how == "expire":
        ft.expire(f.token, "deadline")
    elif how == "poison":
        ft.poison(f.token, "quarantined")
    elif how == "poison_all":
        assert ft.poison_all("aborted") == 1
    else:
        assert len(ft.preempt_all()) == 1
    t.join(10.0)
    assert not t.is_alive()
    (kind, what), = out
    want = {"resolve": 99, "reattach": 99, "expire": FutureExpired,
            "poison": FuturePoisoned, "poison_all": FuturePoisoned,
            "preempt_all": FuturePreempted}[how]
    if kind == "value":
        assert what == want
    else:
        assert type(what) is want
    assert f.done() and f.t_done is not None and f.t_done >= f.t_submit
    if how == "reattach":
        # The early rung: a token that ends before its client comes back
        # yields a future that is born terminal and never builds one.
        g = ft.create("a", BUMP, 3)
        (rt,) = ft.preempt_all()
        ft.resolve(g.token, 5)
        h = ft.reattach(rt)
        assert h.result(timeout=1.0) == 5 and h._event is None
    assert ft.stats_dict()["waited"] == 1


def test_cancelled_scope_futures_poison_not_hang():
    """Cancelling a tenant (scope semantics: its lane's CancelScope
    cancels and queued work drains) resolves every queued future
    POISONED - result() raises immediately instead of hanging."""
    t = _table([TenantSpec("a", max_in_flight=1, queue_capacity=8)])
    t.egress = EgressSpec(depth=8)
    t.futures = FutureTable()
    t._owns_futures = True
    futs = [t.submit("a", BUMP, args=[i]).future for i in range(4)]
    assert all(f is not None for f in futs)
    t.cancel("a", "caller gave up")
    # Nothing pumped yet: accepted head AND queued tail all drain
    # through the cancel - every future lands POISONED, none hang.
    assert [f.state for f in futs] == ["POISONED"] * 4
    with pytest.raises(FuturePoisoned, match="cancelled"):
        futs[0].result(timeout=1.0)


def test_expired_future_reconciles_with_expiry_counters():
    """A queued row whose deadline passes resolves EXPIRED, and the
    ledger's expired count reconciles with the lane's expiry stats."""
    clk = [100.0]
    t = TenantTable(
        [TenantSpec("a", queue_capacity=8)], 16,
        clock=lambda: clk[0], egress=EgressSpec(depth=8),
    )
    keep = t.submit("a", BUMP, args=[1])
    doomed = t.submit("a", BUMP, args=[2], deadline_s=0.5)
    clk[0] += 5.0
    ring = np.zeros((16, 256), np.int32)
    t.pump(ring)
    assert doomed.future.state == "EXPIRED"
    with pytest.raises(FutureExpired):
        doomed.future.result()
    assert keep.future.state == "PENDING"
    assert t.futures.stats_dict()["expired"] == t.stats()["a"]["expired"]


# ------------------------------------------- executable spec + mailbox


def test_egress_reference_parks_on_full_and_flushes_fifo():
    """The numpy spec of the kernel publish path: a full mailbox PARKS
    (head-cursor ring, counted, never dropped), token-0 rows are
    skipped, and the entry-start flush drains the park ring FIFO as
    room opens."""
    depth = 2
    egr = np.zeros((depth, EGR_WORDS), np.int32)
    park = np.zeros((3, EGR_WORDS), np.int32)
    ectl = np.zeros(8, np.int32)
    rows = [(t, 0, BUMP, 0, 10 * t) for t in (1, 2, 3, 4)]
    rows.insert(2, (0, 0, BUMP, 0, 999))  # untracked: skipped
    published = egress_reference(rows, egr, park, ectl, depth)
    assert published == 2
    assert int(ectl[EC_PARK_COUNT]) == 2 and int(ectl[EC_PARKED]) == 2
    # Consume one, flush: park head (token 3) moves in, FIFO order.
    ectl[EC_CONSUMED] = 1
    egr[0] = 0
    assert flush_parked_reference(egr, park, ectl, depth) == 1
    assert int(egr[int(ectl[EC_WRITE] - 1) % depth][EGR_TOKEN]) == 3
    assert int(ectl[EC_PARK_HEAD]) == 1 and int(ectl[EC_PARK_COUNT]) == 1
    # Park overflow = a broken install credit gate, loudly.
    ectl[EC_PARK_COUNT] = park.shape[0]
    with pytest.raises(EgressProtocolError, match="credit gate"):
        egress_reference([(9, 0, 0, 0, 0)], egr, park, ectl, depth)


def test_host_mailbox_slow_poller_loses_nothing():
    """Satellite 1's core property at unit scale: a poller consuming
    one row per call against a depth-2 mailbox under 9 publishes -
    backpressure parks rows (park_events > 0) but every token resolves
    exactly once; conservation exact."""
    ft = FutureTable()
    futs = [ft.create("a", BUMP, 0) for _ in range(9)]
    box = HostMailbox(EgressSpec(depth=2), park_cap=16)
    for f in futs:
        box.publish([(f.token, 0, BUMP, 0, f.token * 11)])
    assert box.park_events() > 0
    drained = []
    while True:
        got = box.drain(futures=ft, limit=1)   # the slow poller
        if not got:
            break
        drained += got
    assert len(drained) == 9
    assert box.occupancy() == 0 and box.parked() == 0
    for f in futs:
        assert f.result(timeout=1.0) == f.token * 11
    assert ft.conservation()["ok"]


def test_mailbox_double_consume_is_a_protocol_error():
    box = HostMailbox(EgressSpec(depth=4))
    box.publish([(1, 0, BUMP, 0, 5)])
    box.drain()
    box.ectl[EC_CONSUMED] -= 1               # corrupt the cursor
    with pytest.raises(EgressProtocolError, match="consumed twice"):
        box.drain()


# --------------------------------------------------- protocol model


def test_egress_model_full_mailbox_cannot_wedge():
    """Every schedule of a 1-deep mailbox with a DEAD poller and a
    mid-flight quiesce reaches a clean terminal: both regions drained,
    every row resolved or preempted - the tentpole's wedge-proof
    claim, model-checked."""
    from hclib_tpu.analysis.explore import EgressMailboxModel, explore

    for m in (
        EgressMailboxModel(rows=4, depth=1, poller=False, quiesce=True),
        EgressMailboxModel(rows=3, depth=1, poller=True),
        EgressMailboxModel(rows=3, depth=2, poller=True, quiesce=True),
    ):
        res = explore(m, depth=64, budget_s=30)
        assert res.complete and res.clean, [
            v.message for v in res.violations
        ]
        assert res.terminals > 0


def test_egress_model_finds_the_seeded_park_leak():
    """drain_parked=False plants the bug where the quiesce export
    forgets the park ring; the exploration returns the concrete action
    prefix that loses the parked rows' futures."""
    from hclib_tpu.analysis.explore import EgressMailboxModel, explore

    res = explore(
        EgressMailboxModel(rows=4, depth=1, poller=False, quiesce=True,
                           drain_parked=False),
        depth=64, budget_s=30,
    )
    bad = [v for v in res.violations if "egress-wedge" in v.message]
    assert bad, [v.message for v in res.violations]
    assert any(a[0] == "retire" for a in bad[0].witness)


def test_check_protocols_curated_set_includes_egress_and_is_clean():
    from hclib_tpu.analysis.explore import check_protocols

    rep = check_protocols()
    assert not rep.actionable(), [f.message for f in rep.findings]


def test_layout_table_pins_the_egress_words():
    from hclib_tpu.analysis.layout import LAYOUT, check_layout

    assert not check_layout(force=True).actionable()
    for w in ("EGR_STATUS", "EGR_TOKEN", "EGR_VALUE", "EC_WRITE",
              "EC_PARK_HEAD", "EC_INFLIGHT"):
        assert w in LAYOUT


# ------------------------------------------------------- env knobs


def test_egress_env_knobs_registered_and_raise_on_malformed(monkeypatch):
    from hclib_tpu.runtime.env import REGISTRY

    assert {"HCLIB_TPU_EGRESS_DEPTH",
            "HCLIB_TPU_EGRESS_BACKOFF_S"} <= set(REGISTRY)
    monkeypatch.delenv("HCLIB_TPU_EGRESS_DEPTH", raising=False)
    monkeypatch.delenv("HCLIB_TPU_EGRESS_BACKOFF_S", raising=False)
    assert egress_from_env() is None
    assert normalize_egress(None) is None
    monkeypatch.setenv("HCLIB_TPU_EGRESS_DEPTH", "16")
    monkeypatch.setenv("HCLIB_TPU_EGRESS_BACKOFF_S", "0.01")
    spec = normalize_egress(None)
    assert spec.depth == 16 and spec.backoff_s == 0.01
    assert normalize_egress(False) is None   # explicit off beats env
    monkeypatch.setenv("HCLIB_TPU_EGRESS_DEPTH", "not-an-int")
    with pytest.raises(ValueError, match="HCLIB_TPU_EGRESS_DEPTH"):
        egress_from_env()
    monkeypatch.setenv("HCLIB_TPU_EGRESS_DEPTH", "8")
    monkeypatch.setenv("HCLIB_TPU_EGRESS_BACKOFF_S", "fast")
    with pytest.raises(ValueError, match="HCLIB_TPU_EGRESS_BACKOFF_S"):
        egress_from_env()
    with pytest.raises(ValueError, match="depth"):
        EgressSpec(depth=0)


# ------------------------------------------------- device (interpret)


def test_stream_serve_futures_resolve_with_parking():
    """DEVICE: a depth-4 mailbox under 12 submits forces in-kernel
    parking; every future still resolves RESULT and the ledger's
    conservation identity closes exactly."""
    table = _table(
        [TenantSpec("gold", weight=4), TenantSpec("silver")],
        egress=EgressSpec(depth=4),
    )
    sm = StreamingMegakernel(bump_mk(), ring_capacity=32, tenants=table)
    futs = []
    for i in range(8):
        adm = sm.submit("gold", BUMP, args=[i + 1])
        assert adm.accepted and adm.future.token > 0
        futs.append(adm.future)
    for _ in range(4):
        futs.append(sm.submit("silver", BUMP, args=[100]).future)
    sm.close()
    iv, info = sm.run_stream(seed_builder())
    assert int(iv[0]) == 1000 + sum(range(1, 9)) + 400
    for f in futs:
        assert isinstance(f.result(timeout=2.0), int)
        assert f.state == "RESULT" and f.latency_s() is not None
    cons = table.futures.conservation()
    assert cons["ok"] and cons["resolved"] == 12, cons
    assert sm.stats_dict()["egress"]["resolved"] == 12
    # The client came after the stream: no future ever built an Event.
    assert sm.stats_dict()["egress"]["waited"] == 0
    assert all(f._event is None for f in futs)


def test_stream_quiesce_preempts_then_reattaches_across_resume():
    """DEVICE: a checkpoint cut mid-flight lands every in-flight future
    in RESULT or PREEMPTED (resume token); a fresh equivalent stream
    resumes the snapshot, re-adopts the tokens (etok rides the state),
    and reattached futures resolve - conservation closes on both
    ledgers."""
    t1 = _table([TenantSpec("x"), TenantSpec("y")], region=32,
                egress=EgressSpec(depth=64))
    sm = StreamingMegakernel(bump_mk(checkpoint=True),
                             ring_capacity=64, tenants=t1)
    futs = [sm.submit("x", BUMP, args=[1]).future for _ in range(10)]
    sm.quiesce(after_executed=3)
    _, info = sm.run_stream(seed_builder())
    assert info["quiesced"] and "etok" in info["state"]
    assert {f.state for f in futs} <= {"RESULT", "PREEMPTED"}
    tokens = []
    for f in futs:
        if f.state == "PREEMPTED":
            with pytest.raises(FuturePreempted) as ei:
                f.result()
            assert ei.value.resume_token == f.resume_token
            tokens.append(f.resume_token)
    assert tokens, "expected preempted futures at a cut after 3 tasks"
    c1 = t1.futures.conservation()
    assert c1["ok"] and c1["preempted"] == len(tokens)
    t2 = _table([TenantSpec("x"), TenantSpec("y")], region=32,
                egress=EgressSpec(depth=64))
    sm2 = StreamingMegakernel(bump_mk(checkpoint=True),
                              ring_capacity=64, tenants=t2)
    sm2.close()
    iv2, _ = sm2.run_stream(resume_state=info["state"])
    assert int(iv2[0]) == 1000 + 10
    for tok in tokens:
        f = sm2.tenants.reattach(tok)
        assert f.result(timeout=2.0) is not None and f.state == "RESULT"
    c2 = t2.futures.conservation()
    assert c2["ok"] and c2["reattached"] == len(tokens)


def test_resume_onto_tiny_mailbox_reseeds_inflight_credit():
    """DEVICE regression: a snapshot's ectl block is NOT exported (the
    mailbox drains before the cut), but its adopted etok tokens ARE in
    flight - resume must reseed EC_INFLIGHT from the adopted count or
    each adopted retirement drives it negative, the install credit
    gate inflates, and a depth-4 park ring overwraps its own counted
    rows (found by driving resume under parking pressure)."""
    def table():
        return _table([TenantSpec("x"), TenantSpec("y")], region=32,
                      egress=EgressSpec(depth=4))

    sm = StreamingMegakernel(bump_mk(checkpoint=True),
                             ring_capacity=64, tenants=table())
    futs = [sm.submit("x" if i % 2 else "y", BUMP, args=[i + 1]).future
            for i in range(14)]
    sm.quiesce(after_executed=4)
    _, info = sm.run_stream(seed_builder())
    assert info["quiesced"]
    tokens = [f.resume_token for f in futs if f.state == "PREEMPTED"]
    assert len(tokens) > 4, "need more adopted tokens than the depth"
    t2 = table()
    sm2 = StreamingMegakernel(bump_mk(checkpoint=True),
                              ring_capacity=64, tenants=t2)
    sm2.close()
    iv2, _ = sm2.run_stream(resume_state=info["state"])
    assert int(iv2[0]) == 1000 + sum(range(1, 15))
    for tok in tokens:
        f = sm2.tenants.reattach(tok)
        assert f.result(timeout=2.0) is not None and f.state == "RESULT"
    cons = t2.futures.conservation()
    assert cons["ok"] and cons["pending"] == 0, cons


def test_stream_abort_poisons_outstanding_futures():
    """DEVICE: abort() is the ladder's bottom rung - results already
    in the mailbox resolve, every other outstanding future poisons
    (typed raise, no hang)."""
    t = _table(egress=EgressSpec(depth=64), region=32)
    sm = StreamingMegakernel(bump_mk(), ring_capacity=32, tenants=t)
    futs = [sm.submit("a", BUMP, args=[1]).future for _ in range(5)]
    sm.abort("client disconnect")
    with pytest.raises(Exception, match="abort"):
        sm.run_stream(seed_builder())
    for f in futs:
        assert f.state in ("RESULT", "POISONED")
        if f.state == "POISONED":
            with pytest.raises(FuturePoisoned, match="abort"):
                f.result(timeout=1.0)
    assert t.futures.conservation()["ok"]
    assert t.futures.pending() == 0      # nothing hangs


# ------------------------------------------------ off-path identity


def _lower_text(sm):
    mk = sm.mk
    tasks, succ, ready, counts = seed_builder().finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    args = [
        tasks, succ, ready, counts,
        np.zeros(mk.num_values, np.int32),
        np.zeros((sm.ring_capacity, 256), np.int32),
        np.zeros(8, np.int32),
    ]
    if sm.tenants is not None:
        args.append(np.zeros((len(sm.tenants), 8), np.int32))
    if sm._egress is not None:
        d = sm._egress.depth
        args += [
            np.zeros((d, EGR_WORDS), np.int32),
            np.zeros((d, EGR_WORDS), np.int32),
            np.zeros(8, np.int32),
            np.zeros(mk.capacity, np.int32),
        ]
    return sm._build(1 << 10, 64).lower(*args).as_text()


def test_off_path_builds_compile_zero_egress_words(monkeypatch):
    """egress=False (and plain egress-free tables) lower to the EXACT
    text an env-free tenant build lowers to, even with the egress env
    knobs set - the ISSUE 16 off-path bit-identity gate. An egress-ON
    build lowers cleanly and differs (the words exist only on-path)."""
    monkeypatch.delenv("HCLIB_TPU_EGRESS_DEPTH", raising=False)
    base = _lower_text(
        StreamingMegakernel(bump_mk(), ring_capacity=32, tenants=["a"])
    )
    monkeypatch.setenv("HCLIB_TPU_EGRESS_DEPTH", "64")
    off = _lower_text(
        StreamingMegakernel(
            bump_mk(), ring_capacity=32,
            tenants=TenantTable([TenantSpec("a")], 32,
                                clock=lambda: 0.0, egress=False),
        )
    )
    assert off == base
    on = _lower_text(
        StreamingMegakernel(
            bump_mk(), ring_capacity=32,
            tenants=TenantTable([TenantSpec("a")], 32,
                                clock=lambda: 0.0,
                                egress=EgressSpec(depth=8)),
        )
    )
    assert on != base          # egress words compile only on-path


# ------------------------------------------------- mesh conservation


def test_mesh_serve_conservation_across_4_2_4_reshards():
    """THE SOAK IDENTITY at test scale: a 4-device mesh front door with
    futures, driven on the WRR reference model + per-device host
    mailboxes, resharded live 4 -> 2 -> 4 with futures in flight. At
    every cut: in-flight futures preempt with valid resume tokens and
    reattach on the resized table; at the end
    submitted == resolved + expired + poisoned, exactly."""
    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.tenants import wrr_poll_reference

    region = 16
    clk = [100.0]
    spec = EgressSpec(depth=4)

    def specs():
        return [TenantSpec("gold", weight=2), TenantSpec("std")]

    table = MeshTenantTable(specs(), 4, region, clock=lambda: clk[0],
                            egress=spec)
    futures = table.futures
    assert futures is not None
    submitted = 0

    def drive(table, rings, polls=4, start=0):
        boxes = [HostMailbox(spec) for _ in range(table.ndev)]
        tctl = table.pump(rings)
        for r in range(start, start + polls):
            for d in range(table.ndev):
                rows = wrr_poll_reference(
                    rings[d], tctl[d], table.region_rows, r, 1 << 20
                )
                boxes[d].publish([
                    (int(row[TEN_TOKEN]), 0, BUMP, 0, 7)
                    for row in rows
                ])
        table.absorb(tctl)
        for box in boxes:
            box.drain(futures=futures)

    def rings_for(ndev):
        return np.zeros((ndev, 2 * region, RING_ROW), np.int32)

    sizes = [4, 2, 4]
    rings = rings_for(4)
    live = []
    for phase, ndev in enumerate(sizes):
        for i in range(8):
            adm = table.submit(i % 2, BUMP, args=[i])
            if adm:
                submitted += 1
                live.append(adm.future)
        drive(table, rings, polls=2, start=phase * 4)
        if phase == len(sizes) - 1:
            break
        # live reshard: export (preempts in-flight), resize, re-adopt.
        state = table.export_state(rings)
        tokens = [f.resume_token for f in live
                  if f.state == "PREEMPTED"]
        nxt = table.resized(sizes[phase + 1])
        assert nxt.futures is futures     # ONE ledger across cuts
        nxt.resume_from(state)
        for tok in tokens:
            nxt.reattach(tok)
        table = nxt
        rings = rings_for(table.ndev)
    # final drain: pump/poll until every lane empties.
    for r in range(20, 40):
        drive(table, rings, polls=1, start=r)
        if table.drained():
            break
    cons = futures.conservation()
    assert cons["ok"], cons
    assert cons["pending"] == 0, cons
    assert submitted == (
        cons["resolved"] + cons["expired"] + cons["poisoned"]
    ), (submitted, cons)


def test_mesh_serve_fallback_restore_reattaches_futures(tmp_path):
    """DURABLE STORE x SERVING: a mesh export rides a CheckpointBundle
    into a generational BundleStore; the newest generation is then
    bit-flipped on disk. load_latest self-heals (quarantine + fallback
    to the older valid save of the SAME cut), the table resumes from
    the fallback arrays, preempted futures reattach, and the serving
    ledger's conservation identity still closes exactly."""
    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.tenants import wrr_poll_reference
    from hclib_tpu.runtime.checkpoint import BundleStore, CheckpointBundle

    region = 16
    clk = [100.0]
    spec = EgressSpec(depth=4)
    table = MeshTenantTable(
        [TenantSpec("gold", weight=2), TenantSpec("std")], 2, region,
        clock=lambda: clk[0], egress=spec,
    )
    futures = table.futures
    rings = np.zeros((2, 2 * region, RING_ROW), np.int32)
    submitted = 0
    live = []
    for i in range(8):
        adm = table.submit(i % 2, BUMP, args=[i], deadline_s=600.0)
        if adm:
            submitted += 1
            live.append(adm.future)
    # the cut: export preempts in-flight futures, bundle -> store x2.
    state = table.export_state(rings)
    tokens = [f.resume_token for f in live if f.state == "PREEMPTED"]
    assert tokens, "expected in-flight futures at the cut"
    store = BundleStore(str(tmp_path / "store"), keep=3, fsync=False)
    bundle = CheckpointBundle(
        "resident", {"schema": "mesh-serve-export"}, state
    )
    store.save(bundle)
    store.save(bundle)
    npz = os.path.join(store.path_of(2), "state.npz")
    blob = open(npz, "rb").read()
    with open(npz, "wb") as f:
        f.write(blob[:12] + bytes([blob[12] ^ 0x40]) + blob[13:])
    healer = BundleStore(str(tmp_path / "store"), fsync=False)
    back = healer.load_latest()
    assert back.generation == 1, "fallback to the older valid save"
    assert [f.reason for f in healer.faults] == ["corrupt"]
    # resume from the FALLBACK arrays, reattach, drive to the drain.
    nxt = table.resized(2)
    assert nxt.futures is futures
    nxt.resume_from({k: back.arrays[k] for k in state})
    for tok in tokens:
        f = nxt.reattach(tok)
        assert f.state == "PENDING"
    boxes = [HostMailbox(spec) for _ in range(2)]
    for r in range(40):
        tctl = nxt.pump(rings)
        for d in range(2):
            rows = wrr_poll_reference(
                rings[d], tctl[d], nxt.region_rows, r, 1 << 20
            )
            boxes[d].publish([
                (int(row[TEN_TOKEN]), 0, BUMP, 0, 7) for row in rows
            ])
        nxt.absorb(tctl)
        for box in boxes:
            box.drain(futures=futures)
        if nxt.drained():
            break
    cons = futures.conservation()
    assert cons["ok"], cons
    assert cons["pending"] == 0, cons
    assert cons["reattached"] == len(tokens), cons
    assert submitted == (
        cons["resolved"] + cons["expired"] + cons["poisoned"]
    ), (submitted, cons)
