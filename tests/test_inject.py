"""Host -> resident-kernel task injection (device/inject.py).

Reference counterpart: materializing work on a running runtime from outside
(/root/reference/modules/openshmem-am/src/hclib_openshmem-am.cpp:64-123)."""

import threading
import time
import types

import jax
import numpy as np
import pytest
from conftest import KINDS, Tick, bump_mk, front_door, send

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.egress import (
    EC_CONSUMED,
    EC_PARK_COUNT,
    EC_PARK_HEAD,
    EC_WRITE,
    EGR_STATUS,
    EGR_T_ADMIT,
    EGR_T_SPANS,
    EGR_TOKEN,
    EgressProtocolError,
    EgressSpec,
    FutureTable,
    HostMailbox,
)
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.telemetry import unpack_spans, unpack_spans_rows
from hclib_tpu.device.workloads import FIB, make_fib_megakernel

BUMP = 0


def fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def tree_tasks(n):
    if n < 2:
        return 1
    return 1 + tree_tasks(n - 1) + tree_tasks(n - 2)


def test_ring_rows_discovered_by_in_kernel_poll():
    """Injected rows are NEVER staged with the graph - they can only enter
    through the in-kernel ring poll; exact totals prove that path."""
    sm = StreamingMegakernel(bump_mk(), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1000])
    for i in range(20):
        sm.inject(BUMP, args=[i + 1])
    sm.close()
    iv, info = sm.run_stream(b)
    assert info["executed"] == 21
    assert info["injected"] == 20
    assert int(iv[0]) == 1000 + 20 * 21 // 2


def test_concurrent_feeder_thread():
    """A host thread appends fib seeds while the stream runs; every seed's
    value lands in its out slot and the task totals are exact."""
    mk = make_fib_megakernel(capacity=768, interpret=True)
    sm = StreamingMegakernel(mk, ring_capacity=32)
    b = TaskGraphBuilder()
    b.add(FIB, args=[10], out=0)
    b.reserve_values(10)
    ns = [5, 7, 8, 9, 11, 6, 4, 12]

    def feeder():
        for i, n in enumerate(ns):
            sm.inject(FIB, args=[n], out=1 + i)
            time.sleep(0.02)
        sm.close()

    t = threading.Thread(target=feeder)
    t.start()
    iv, info = sm.run_stream(b, quantum=64)
    t.join()
    assert int(iv[0]) == fib(10)
    for i, n in enumerate(ns):
        assert int(iv[1 + i]) == fib(n), (i, n)
    assert info["injected"] == len(ns)
    # Scalar-tier fib counts FIB nodes plus SUM joins: t + (t-1)//2.
    scalar_tasks = lambda n: tree_tasks(n) + (tree_tasks(n) - 1) // 2
    assert info["executed"] == sum(scalar_tasks(n) for n in [10] + ns)


def test_inject_after_close_raises():
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8)
    sm.close()
    with pytest.raises(RuntimeError):
        sm.inject(BUMP, args=[1])


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_streaming_on_tpu():
    """The ring poll + install path through real Mosaic lowering."""
    sm = StreamingMegakernel(bump_mk(interpret=False), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[7])
    for i in range(10):
        sm.inject(BUMP, args=[i + 1])
    sm.close()
    iv, info = sm.run_stream(b)
    assert info["executed"] == 11
    assert int(iv[0]) == 7 + 55


# ---- the entry boundary (ISSUE 32): what crosses the host link an entry

LINK_KEYS = {"entries", "uploads", "downloads", "ring_uploads", "ring_deltas",
             "ring_rows_up", "idle_sleeps", "settled", "settle_batches"}


def counting_pumps(table):
    """Count the pumps that wrote the host ring."""
    wrote, pump = [], table.pump

    def counted(ring, dirty=None):
        dirty = [] if dirty is None else dirty
        before = len(dirty)
        out = pump(ring, dirty)
        wrote.append(len(dirty) != before)
        return out

    table.pump = counted
    return wrote


@pytest.mark.parametrize("kind", KINDS)
def test_closed_burst_one_upload_one_download_an_entry(kind):
    """A closed burst over several entries: the ring goes up whole once,
    with the first entry, and every later pump that published (a lane
    budget of 4 in flight makes that several) sends just its rows in
    that entry's slab; the loop never sleeps, and the host link is
    crossed at most twice an entry each way - with the totals a burst
    has always given."""
    n = 24
    sm, table = front_door(kind, max_in_flight=4)
    wrote = counting_pumps(table) if table is not None else None
    futs = send(sm, table, n)
    sm.close()
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1000])
    iv, info = sm.run_stream(b, quantum=4, max_rounds=2)
    assert int(iv[0]) == 1000 + n * (n + 1) // 2
    assert info["executed"] == n + 1 and info["pending"] == 0
    assert info["injected"] == n
    link = info["stream"]
    assert set(link) == LINK_KEYS and link == sm.stats_dict()["stream"]
    assert link["entries"] >= 3
    assert link["idle_sleeps"] == 0
    assert link["uploads"] <= 2 * link["entries"] + 1
    assert link["downloads"] <= 2 * link["entries"] + 1
    # The boundaries settle the mailbox in bulk: every request through
    # one ledger call an entry that resolved any (egress builds only).
    assert link["settled"] == (n if kind in ("egress", "telemetry") else 0)
    assert link["settle_batches"] <= link["entries"]
    assert (link["settle_batches"] >= 3) == (link["settled"] > 0)
    assert link["ring_uploads"] == 1
    if table is None:
        assert link["ring_deltas"] == 0
        assert link["ring_rows_up"] == sm.ring_capacity
        return
    assert 1 + link["ring_deltas"] == sum(wrote) >= 3
    # Ring rows refreshed on the chip follow the rows published: all but
    # the first pump's, which went up inside the whole ring.
    assert link["ring_rows_up"] == sm.ring_capacity + n - 2 * 4
    for tid in "ab":
        s = table.stats()[tid]
        assert s["accepted"] == s["completed"] == n // 2, s
        assert not (s["dropped"] or s["expired"] or s["rejected"])
    assert info["tenants"] == table.stats()
    if kind != "tenants":
        assert [f.state for f in futs] == ["RESULT"] * n
        cons = table.futures.conservation()
        assert cons["ok"] and cons["resolved"] == n and not cons["pending"]
    if kind == "telemetry":
        assert info["telemetry"]["tele"][1:].sum() == n


@pytest.mark.parametrize("kind", ["plain", "egress"])
def test_open_stream_sleeps_while_idle_and_picks_late_rows_up(kind):
    """An open stream whose producer submits only after the first
    entries: the loop sleeps while it has nothing to do (the idle
    poll), the late rows dirty the ring so it goes up again, and the
    stream drains exactly."""
    sm, table = front_door(kind)
    late = []

    def producer():
        while sm.stats_dict()["stream"]["idle_sleeps"] < 2:
            time.sleep(0.001)
        late.extend(send(sm, table, 6))
        sm.close()

    t = threading.Thread(target=producer)
    t.start()
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1000])
    iv, info = sm.run_stream(b, deadline_s=120.0)
    t.join()
    assert int(iv[0]) == 1000 + 21 and info["executed"] == 7
    link = info["stream"]
    assert link["idle_sleeps"] >= 2
    # Whole once, with the first entry; the late rows ride the slabs of
    # the entries after them (more than one only if the loop woke while
    # the producer was sending), row for row.
    assert link["ring_uploads"] == 1
    assert 1 <= link["ring_deltas"] <= 6
    assert link["ring_rows_up"] == sm.ring_capacity + 6
    assert [f.state for f in late] == ["RESULT"] * len(late)


# ---- the boundary's bulk settle (ISSUE 37) against the row-at-a-time
# specification of the mailbox (egress.HostMailbox / egress_reference)


def _published(case):
    """A depth-4 mailbox with a 4-row park ring in the state ``case``
    names, its rows published by ``egress_reference`` and its cursors
    moved by ``HostMailbox.drain`` / ``flush``; two twin ledgers that
    both saw what was drained on the way. Returns (box, the ledger the
    specification resolves into, the ledger the driver does, the
    driver's futures by token)."""
    spec, ours = FutureTable(), FutureTable(clock=Tick())
    for i in range(9):
        spec.create("a", BUMP, i)
    futs = {f.token: f for f in (ours.create("a", BUMP, i)
                                 for i in range(9))}
    box = HostMailbox(EgressSpec(depth=4), park_cap=4)
    rows = [(t, t % 2, BUMP, t, 100 + 7 * t) for t in range(1, 10)]

    def drain(limit):
        for t, v in box.drain(futures=spec, limit=limit,
                              include_parked=False):
            ours.resolve(t, v)

    if case == "straight":          # slots 0, 1
        box.publish(rows[:2])
    elif case == "wraps":           # consumed 3: slots 3, 0, 1
        box.publish(rows[:3])
        drain(3)
        box.publish(rows[3:6])
    elif case == "empty":           # consumed == write == 3
        box.publish(rows[:3])
        drain(3)
    elif case == "parked":          # mailbox full, park slots 0, 1, 2
        box.publish(rows[:7])
    elif case == "park_head":       # mailbox 2,3,0,1; park head 2, wraps
        box.publish(rows[:7])
        drain(2)
        assert box.flush() == 2
        box.publish(rows[7:9])
        assert int(box.ectl[EC_PARK_HEAD]) == 2 and box.parked() == 3
    # Telemetry words, as a telemetry build's rows carry them: the
    # packed deltas of every row have the sign bit set.
    for blk in (box.egr, box.park):
        live = blk[:, EGR_TOKEN] != 0
        blk[live, EGR_T_ADMIT] = 1000 + blk[live, EGR_TOKEN]
        blk[live, EGR_T_SPANS] = (
            (0x9000 + blk[live, EGR_TOKEN]) << 16 | 0x21
        ).astype(np.uint32).view(np.int32)
    return box, spec, ours, futs


DRAINS = {"straight": 2, "wraps": 3, "empty": 0, "parked": 7,
          "park_head": 7}


@pytest.mark.parametrize("case", sorted(DRAINS))
def test_drain_egress_equals_the_mailbox_specification(case):
    """``_drain_egress`` settles a boundary in bulk; the row-at-a-time
    ``HostMailbox.drain`` over the same arrays resolves the same tokens
    to the same values in the same order, and both leave the mailbox
    and the park ring empty and re-zeroed."""
    box, spec, ours, futs = _published(case)
    egr, park, ectl = box.egr.copy(), box.park.copy(), box.ectl.copy()
    held = np.concatenate([egr, park])
    held = held[held[:, EGR_TOKEN] != 0]
    early = {t for t, f in futs.items() if f.done()}
    spans = {}
    n = StreamingMegakernel._drain_egress(
        types.SimpleNamespace(futures=ours), egr, park, ectl, spans=spans)
    pairs = box.drain(futures=spec)
    assert n == len(pairs) == len(held) == DRAINS[case]
    settled = sorted((f for t, f in futs.items()
                      if f.done() and t not in early),
                     key=lambda f: f.t_done)
    assert [(f.token, f.value) for f in settled] == pairs
    assert all(f.state == "RESULT" for f in settled)
    assert ours.conservation() == spec.conservation()
    assert not egr.any() and not park.any()
    assert not box.egr.any() and not box.park.any()
    assert int(ectl[EC_CONSUMED]) == int(ectl[EC_WRITE])
    assert int(ectl[EC_PARK_COUNT]) == int(ectl[EC_PARK_HEAD]) == 0
    assert box.occupancy() == box.parked() == 0
    assert spans == {
        int(r[EGR_TOKEN]): unpack_spans(r[EGR_T_ADMIT], r[EGR_T_SPANS])[:3]
        for r in held
    }
    assert all(a < b < c for a, b, c in spans.values())


@pytest.mark.parametrize("where", ["mailbox", "park"])
def test_drain_egress_names_the_first_slot_that_is_not_ok(where):
    """A consumed slot whose status is not ``EGR_OK`` raises, as the
    specification does, naming the slot; the rows before it are
    resolved and re-zeroed, the offender and the cursors are left."""
    box, spec, ours, futs = _published("park_head")
    blk, slot, first = (
        (box.egr, 3, [3]) if where == "mailbox"
        else (box.park, 3, [3, 4, 5, 6, 7])
    )
    blk[slot, EGR_STATUS] = 0
    egr, park, ectl = box.egr.copy(), box.park.copy(), box.ectl.copy()
    with pytest.raises(EgressProtocolError, match=f"{where} slot {slot} "):
        StreamingMegakernel._drain_egress(
            types.SimpleNamespace(futures=ours), egr, park, ectl)
    assert sorted(t for t, f in futs.items() if f.done()) == [1, 2] + first
    assert np.array_equal(ectl, box.ectl)
    assert (egr, park)[where == "park"][slot, EGR_TOKEN] == first[-1] + 1
    assert np.count_nonzero(np.concatenate([egr, park])[:, EGR_TOKEN]) == (
        7 - len(first))
    cons = ours.conservation()
    assert cons["ok"] and cons["resolved"] == 2 + len(first)
    if where == "mailbox":
        with pytest.raises(EgressProtocolError, match="consumed twice"):
            box.drain(futures=spec)
        assert spec.conservation() == cons


def test_drain_egress_lets_the_ledgers_refusal_through():
    """A row whose token the ledger already settled raises out of the
    bulk call with the rows before it resolved; nothing is re-zeroed
    and no cursor moves."""
    box, spec, ours, futs = _published("parked")
    ours.resolve(3, 0)
    egr, park, ectl = box.egr.copy(), box.park.copy(), box.ectl.copy()
    with pytest.raises(EgressProtocolError, match="token 3 already"):
        StreamingMegakernel._drain_egress(
            types.SimpleNamespace(futures=ours), egr, park, ectl)
    assert [futs[t].state for t in (1, 2, 4)] == [
        "RESULT", "RESULT", "PENDING"]
    assert np.array_equal(egr, box.egr) and np.array_equal(ectl, box.ectl)
    assert ours.conservation()["ok"]


def test_vector_span_decode_equals_unpack_spans_word_for_word():
    """``unpack_spans_rows`` over whole columns is ``unpack_spans`` of
    each row: random words, the sign bit of either set, the extremes."""
    rng = np.random.default_rng(37)
    words = rng.integers(-2**31, 2**31, (2, 4096)).astype(np.int32)
    edge = np.array([0, 1, -1, 2**31 - 1, -2**31, 0xFFFF, 0x10000],
                    np.int32)
    admit = np.concatenate([words[0], edge, edge[::-1]])
    spans = np.concatenate([words[1], edge, edge])
    got = [a.tolist() for a in unpack_spans_rows(admit, spans)]
    assert list(zip(*got)) == [
        unpack_spans(a, s)[:3] for a, s in zip(admit, spans)
    ]
    assert (spans < 0).any() and (admit < 0).any()
