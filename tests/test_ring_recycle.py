"""Tenant ring regions that recycle (ISSUE 44): a lane's cursors count
rows for the stream's whole life and a row lives in slot ``index %
region_rows``, so a stream serves many times what its regions hold.

Host half against the numpy poll (``wrr_poll_reference``, which takes the
same wrap); both device polls (``inject.py``'s and ``resident.py``'s,
through the Pallas interpreter) against it on cursors that start near a
region's end; a stream with a live producer thread through the open
cell's own driver, answers against ``benchmarks/reference``; a quiesce
cut and resume across a wrapped region; and the ring rows an entry sends
once the ring is on the chip."""

import os
import sys
import threading
import time

import numpy as np
import pytest
from conftest import BUMP, bump_mk, seed_builder
from test_tenants import FakeClock, _drive as drive  # one pump, the
# reference poll for ``polls`` rounds, the echo absorbed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hclib_tpu.device import inject  # noqa: E402
from hclib_tpu.device.descriptor import (  # noqa: E402
    F_A0, RING_ROW, TEN_EXPIRED, TEN_ID, TaskGraphBuilder,
)
from hclib_tpu.device.egress import EgressSpec  # noqa: E402
from hclib_tpu.device.inject import StreamingMegakernel  # noqa: E402
from hclib_tpu.device.tenants import (  # noqa: E402
    TC_CONSUMED, TC_INSTALLED, TC_TAIL, MeshTenantTable, TenantSpec,
    TenantTable, build_row, per_tenant_ring_counts, wrr_poll_reference,
)


def start_at(table, at):
    """The lanes as a stream leaves them after ``at`` rows each were
    published and consumed: the next row lands in slot ``at % region``."""
    for lane in table._lanes:
        lane.published = lane.consumed = at


# ------------------------------------------------------------ host half


@pytest.mark.parametrize("region,cap,n,polls", [
    (8, None, 100, 1), (16, 3, 120, 2), (24, None, 200, 3), (8, 5, 64, 1),
])
def test_a_region_serves_many_times_what_it_holds(region, cap, n, polls):
    """Requests keep arriving while the poll consumes: every row installs
    once, in its lane's order, long after the region's first fill; the
    occupancy gate is the only thing that ever says "ring", and it clears
    by itself."""
    table = TenantTable(
        [TenantSpec("a", weight=3, max_in_flight=cap, queue_capacity=region),
         TenantSpec("b", weight=1, max_in_flight=cap, queue_capacity=region)],
        region, clock=FakeClock(),
    )
    ring = np.zeros((2 * region, RING_ROW), np.int32)
    got = {0: [], 1: []}
    refused = set()
    sent = {0: 0, 1: 0}
    rnd = 0
    for i in range(n):
        lane = 0 if i % 3 else 1  # a gets two of three
        while True:
            adm = table.submit(lane, BUMP, args=[i])
            if adm:
                break
            refused.add(adm.reason)
            for r in drive(table, ring, polls, start_round=rnd):
                got[int(r[TEN_ID])].append(int(r[F_A0]))
            rnd += polls
        sent[lane] += 1
    while not table.drained():
        for r in drive(table, ring, polls, start_round=rnd):
            got[int(r[TEN_ID])].append(int(r[F_A0]))
        rnd += polls
    assert refused <= {"ring", "backlog"} and "ring" in refused
    want = {0: [i for i in range(n) if i % 3], 1: list(range(0, n, 3))}
    assert got == want
    s = table.stats()
    for tid, lane in (("a", 0), ("b", 1)):
        assert s[tid]["accepted"] == s[tid]["completed"] == sent[lane]
        assert s[tid]["published"] == s[tid]["consumed"] == sent[lane]
        assert s[tid]["wraps"] == sent[lane] // region
        assert s[tid]["latency_n"] == sent[lane]
    assert min(s[t]["wraps"] for t in "ab") >= 1 and s["a"]["wraps"] >= 4


@pytest.mark.parametrize("via", ["admit", "table", "stream"])
def test_ring_gate_is_occupancy_and_clears_as_the_cursor_moves(via):
    """A full region that nothing consumes refuses "ring"; every row the
    device consumes frees a slot, for the stream's whole life."""
    table = TenantTable([TenantSpec("a", queue_capacity=100)], 8,
                        clock=FakeClock())
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=table)
    ring = np.zeros((8, RING_ROW), np.int32)

    def send(i):
        if via == "admit":
            return table.admit("a", build_row(BUMP, [i]))
        return (sm if via == "stream" else table).submit("a", BUMP, args=[i])

    for i in range(8):
        assert send(i)
    assert send(8).reason == "ring"
    table.pump(ring)  # published, not consumed: still full
    assert send(8).reason == "ring"
    served = 0
    for lap in range(5):  # five regions' worth through one region
        served += len(drive(table, ring, polls=3))  # weight 1: 3 rows
        for i in range(3):
            assert send(100 + lap * 3 + i)
        full = send(0)
        assert full.rejected and full.reason == "ring"
    s = table.stats()["a"]
    assert served == 15 and s["accepted"] == 8 + 15
    assert s["published"] - s["consumed"] + s["queued"] == 8
    assert s["rejected"] == 2 + 5


def test_submit_wait_true_waits_for_a_full_region_to_drain():
    """"ring" is transient now: ``wait=True`` blocks until the consume
    cursor frees a slot; the refused probes are not rejections."""
    table = TenantTable([TenantSpec("a", queue_capacity=100)], 8)
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=table)
    ring = np.zeros((8, RING_ROW), np.int32)
    for i in range(8):
        assert sm.submit("a", BUMP, args=[i])
    assert sm.submit("a", BUMP, args=[8]).reason == "ring"
    consumer = threading.Timer(0.05, drive, args=(table, ring, 2))
    consumer.start()
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[9], wait=True, wait_timeout_s=5.0)
    waited = time.monotonic() - t0
    consumer.join(5.0)
    assert adm.accepted and adm.index == 8 and 0.001 < waited < 2.0
    assert table.stats()["a"]["rejected"] == 1  # only the plain one
    # A region nothing consumes stays full: the wait is bounded.
    for i in range(1):
        assert sm.submit("a", BUMP, args=[i])
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[9], wait=True, wait_timeout_s=0.1)
    assert adm.rejected and adm.reason == "ring"
    assert 0.1 <= time.monotonic() - t0 < 2.0


@pytest.mark.parametrize("by", ["pump", "absorb"])
def test_a_blocked_producer_is_woken_by_the_entry_that_makes_room(by):
    """A producer held back by backpressure sleeps on the table, not on
    a guess: ``wait_room`` returns with the next pump (a backlog shrank)
    or absorb (a consume cursor moved), long before its timeout, and a
    ``submit(wait=True)`` that has backed off to its longest sleep is
    admitted as soon as the echo frees a slot."""
    table = TenantTable([TenantSpec("a", queue_capacity=100)], 8)
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8, tenants=table)
    ring = np.zeros((8, RING_ROW), np.int32)
    for i in range(8):
        assert sm.submit("a", BUMP, args=[i])
    tctl = table.pump(ring)
    echo = tctl.copy()
    echo[0, TC_CONSUMED] = echo[0, TC_INSTALLED] = 8
    tell = {"pump": lambda: table.pump(ring),
            "absorb": lambda: table.absorb(echo)}[by]
    waker = threading.Timer(0.05, tell)
    waker.start()
    t0 = time.monotonic()
    table.wait_room(30.0)
    waker.join(5.0)
    assert 0.01 < time.monotonic() - t0 < 5.0
    if by == "pump":
        return
    # every sleep of a blocked submit is this wait: stretched to ten
    # seconds here, so that only the echo can end the first one in time
    slept = []
    room = table.wait_room
    table.wait_room = lambda timeout: (
        slept.append(timeout), room(timeout * 20000))
    for i in range(8):
        assert sm.submit("a", BUMP, args=[i])
    assert sm.submit("a", BUMP, args=[8]).reason == "ring"
    echo[0, TC_TAIL] = echo[0, TC_CONSUMED] = echo[0, TC_INSTALLED] = 16

    def late_echo():
        table.pump(ring)
        table.absorb(echo)

    waker = threading.Timer(0.1, late_echo)
    waker.start()
    t0 = time.monotonic()
    adm = sm.submit("a", BUMP, args=[9], wait=True, wait_timeout_s=30.0)
    waited = time.monotonic() - t0
    waker.join(5.0)
    assert adm.accepted and waited < 5.0
    assert slept and slept[0] == 0.0005 and len(slept) <= 2


@pytest.mark.parametrize("path", ["bulk", "rows"])
@pytest.mark.parametrize("at", [5, 13, 8 * 7 + 6])
def test_a_publish_run_that_crosses_the_regions_end(path, at):
    """Five rows published from slot ``at % 8`` of lane 1's region: up to
    the end, then from slot 0 (two stores in bulk; row by row where the
    rows carry deadlines), the neighbours' regions untouched, the stores
    named to the caller, and the poll reads them back in order."""
    clock = FakeClock()
    table = TenantTable([TenantSpec("x"), TenantSpec("y"), TenantSpec("z")],
                        8, clock=clock)
    start_at(table, at)
    ring = np.full((24, RING_ROW), -7, np.int32)
    for i in range(5):
        assert table.submit("y", BUMP, args=[40 + i],
                            deadline_s=None if path == "bulk" else 60.0)
    dirty = []
    tctl = table.pump(ring, dirty)
    slot = at % 8
    head = min(5, 8 - slot)
    runs = sorted(set(dirty)) if path == "bulk" else dirty
    if path == "bulk":
        assert runs == sorted({(8 + slot, head)} | (
            {(8, 5 - head)} if head < 5 else set()))
    else:
        assert runs == [(8 + (slot + i) % 8, 1) for i in range(5)]
    assert sum(k for _, k in dirty) == 5
    where = [8 + (slot + i) % 8 for i in range(5)]
    assert ring[where, F_A0].tolist() == [40, 41, 42, 43, 44]
    untouched = [r for r in range(24) if r not in where]
    assert (ring[untouched] == -7).all()
    assert tctl[1, TC_TAIL] == at + 5 and tctl[1, TC_CONSUMED] == at
    rows = wrr_poll_reference(ring, tctl, 8, 0, 1 << 20)
    rows += wrr_poll_reference(ring, tctl, 8, 1, 1 << 20)
    rows += [r for k in range(2, 6)
             for r in wrr_poll_reference(ring, tctl, 8, k, 1 << 20)]
    assert [int(r[F_A0]) for r in rows] == [40, 41, 42, 43, 44]
    table.absorb(tctl)
    s = table.stats()["y"]
    assert s["published"] == s["consumed"] == at + 5
    assert s["wraps"] == (at + 5) // 8


@pytest.mark.parametrize("at", [6, 15])
def test_an_expired_mark_lands_on_the_wrapped_slot(at):
    """A published row whose deadline lapses is marked where it lies,
    slot ``index % region``; the poll drops it, counted, and installs its
    neighbours."""
    clock = FakeClock()
    table = TenantTable([TenantSpec("x"), TenantSpec("y")], 8, clock=clock,
                        egress=EgressSpec(depth=4))
    start_at(table, at)
    ring = np.zeros((16, RING_ROW), np.int32)
    futs = [table.submit("y", BUMP, args=[i],
                         deadline_s=1.0 if i == 3 else 60.0).future
            for i in range(5)]
    table.pump(ring)  # published; nothing consumed yet
    clock.advance(2.0)  # request 3's deadline lapses on the ring
    dirty = []
    tctl = table.pump(ring, dirty)
    marked = 8 + (at + 3) % 8
    assert dirty == [(marked, 1)]
    assert np.flatnonzero(ring[:, TEN_EXPIRED]).tolist() == [marked]
    assert futs[3].state == "EXPIRED"
    rows = [r for k in range(6)
            for r in wrr_poll_reference(ring, tctl, 8, k, 1 << 20)]
    assert [int(r[F_A0]) for r in rows] == [0, 1, 2, 4]
    table.absorb(tctl)
    s = table.stats()["y"]
    assert s["expired"] == 1 and s["completed"] == 4
    assert s["published"] == s["consumed"] == at + 5
    # the slot is written over by the row that recycles it
    for i in range(8):
        assert table.submit("y", BUMP, args=[50 + i])
    table.pump(ring)
    assert not ring[:, TEN_EXPIRED].any()


@pytest.mark.parametrize("at", [3, 14, 31])
def test_export_and_resume_across_a_wrapped_region_conserve_counts(at):
    """The residue of a cut is rows ``[consumed, published)`` of each
    lane, read from their slots modulo the region, plus the backlog; the
    successor's cursors restart at 0 and every request is served once."""
    clock = FakeClock()
    specs = lambda: [TenantSpec("x", weight=2, max_in_flight=6),  # noqa: E731
                     TenantSpec("y", queue_capacity=64)]
    table = TenantTable(specs(), 8, clock=clock)
    start_at(table, at)
    ring = np.zeros((16, RING_ROW), np.int32)
    sub = {"x": 8, "y": 7}
    for tid, n in sub.items():
        for i in range(n):
            assert table.submit(tid, BUMP, args=[100 * (tid == "y") + i])
    before = drive(table, ring, polls=2)  # 4 of x, 2 of y installed
    assert len(before) == 6
    table.pump(ring)  # publish more behind the moved cursors: wrapped
    state = table.export_state(ring)
    counts = per_tenant_ring_counts(state["ring_rows"])
    assert counts == {0: 8 - 4, 1: 7 - 2}
    t2 = TenantTable(specs(), 8, clock=clock)
    t2.resume_from(state)
    ring2 = np.zeros((16, RING_ROW), np.int32)
    after = []
    while not t2.drained():
        after += drive(t2, ring2, polls=4)
    args = sorted(int(r[F_A0]) for r in before + after)
    assert args == sorted(list(range(8)) + [100 + i for i in range(7)])
    s = t2.stats()
    for tid, n in sub.items():
        assert s[tid]["accepted"] == n
        assert s[tid]["completed"] == n  # installs are cumulative
        assert not (s[tid]["dropped"] or s[tid]["expired"])


# ---------------------------------------------------------- device polls


@pytest.mark.parametrize("region,at", [(8, 5), (8, 8 * 40 + 7), (24, 20)])
def test_stream_poll_agrees_with_the_reference_on_wrapped_cursors(
        region, at):
    """inject.py's ``tpoll`` through the interpreter, the lanes' cursors
    starting ``at`` rows into the stream's life (a power-of-two region
    masks, another takes the remainder): the echo, the installs and the
    value algebra are the reference's."""
    specs = lambda: [TenantSpec("g", weight=4), TenantSpec("s", weight=2),  # noqa: E731
                     TenantSpec("b")]
    sub = {"g": region - 2, "s": 6, "b": 3}

    def load(table):
        start_at(table, at)
        for i, (tid, n) in enumerate(sub.items()):
            for k in range(n):
                assert table.submit(tid, BUMP, args=[(i + 1) * 100 + k])

    table = TenantTable(specs(), region)
    load(table)
    sm = StreamingMegakernel(bump_mk(), ring_capacity=3 * region,
                             tenants=table)
    sm.close()
    iv, info = sm.run_stream(seed_builder())
    spec = TenantTable(specs(), region)
    load(spec)
    ring = np.zeros((3 * region, RING_ROW), np.int32)
    rows, rnd = [], 0
    while not spec.drained():
        rows += drive(spec, ring, polls=4, start_round=rnd)
        rnd += 4
    assert int(iv[0]) == 1000 + sum(int(r[F_A0]) for r in rows)
    assert info["executed"] == 1 + len(rows) == 1 + sum(sub.values())
    want, got = spec.stats(), info["tenants"]
    for tid, n in sub.items():
        for k in ("accepted", "completed", "published", "consumed",
                  "wraps", "expired", "dropped"):
            assert got[tid][k] == want[tid][k], (tid, k)
        assert got[tid]["published"] == at + n
        assert got[tid]["completed"] == n


@pytest.mark.parametrize("region,at", [(8, 6), (24, 21)])
def test_mesh_poll_agrees_with_the_reference_on_wrapped_cursors(region, at):
    """resident.py's tenant poll (the mesh's replica tables ARE
    ``TenantTable``s) on two devices, cursors starting near the region's
    end: every routed row installs once, the echo is the reference's."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    ids = ["gold", "std", "bg"]
    sub = {"gold": 10, "std": 6, "bg": 4}
    rk = ResidentKernel(
        bump_mk(), cpu_mesh(2, axis_name="q"), migratable_fns=[BUMP],
        homed=False, window=4, inject=True, tenants=list(ids),
        ring_capacity=3 * region,
    )
    assert rk.region_rows == region

    def load():
        mesh = MeshTenantTable(rk.tenant_specs, rk.ndev, rk.region_rows)
        for t in mesh.tables:
            start_at(t, at)
        total = 0
        for i, (tid, n) in enumerate(sub.items()):
            for k in range(n):
                assert mesh.submit(tid, BUMP, args=[(i + 1) * 10 + k])
                total += (i + 1) * 10 + k
        return mesh, total

    mesh, total = load()
    bs = [TaskGraphBuilder() for _ in range(2)]
    for b in bs:
        b.add(BUMP, args=[0])
    iv, _, info = rk.run(bs, quantum=2, max_rounds=4096, tenant_table=mesh)
    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 0].sum()) == total
    spec, _ = load()
    for t in spec.tables:
        ring = np.zeros((3 * region, RING_ROW), np.int32)
        rnd = 0
        while not t.drained():
            drive(t, ring, polls=4, start_round=rnd)
            rnd += 4
    for d in range(2):
        want, got = spec.tables[d].stats(), mesh.tables[d].stats()
        for tid in ids:
            for k in ("accepted", "completed", "published", "consumed",
                      "wraps"):
                assert got[tid][k] == want[tid][k], (d, tid, k)
            assert got[tid]["published"] >= at
    for tid, n in sub.items():
        assert info["tenants"][tid]["completed"] == n


def test_region_slot_masks_a_power_of_two_and_divides_otherwise():
    import jax
    import jax.numpy as jnp

    for region in (8, 24, 1024, 40):
        c = jnp.arange(0, 5 * region, 7, dtype=jnp.int32)
        got = jax.jit(lambda c: inject.region_slot(c, region))(c)
        assert got.tolist() == [int(x) % region for x in c]
        text = jax.jit(
            lambda c: inject.region_slot(c, region)).lower(c).as_text()
        assert ("and" in text) == (region in (8, 1024))
        assert ("rem" in text) == (region in (24, 40))


# ------------------------------------- a stream with a live producer


def open_cell(requests=448, rate=400, **cfg_over):
    """One stream of the open cell through its own driver, tiny, on the
    interpreter: a generator thread submits while run_stream runs."""
    from benchmarks import run, traffic
    from benchmarks.drivers import tenant_open

    bench = run.load_json("BENCHMARK.json")
    cell = run.find(bench["workloads"], "serve-open-steady", "workload")
    centry = run.find(bench["configs"], cell["config"], "configuration")
    cfg = {**run.load_json(centry["file"]), "region_rows": 8,
           "capacity": 64, "egress_depth": 8, "gc_freeze": False,
           **cfg_over}
    mix = {**traffic.load(ROOT, cell["traffic"]),
           "requests_per_stream": requests, "rate_per_s": rate}
    state = tenant_open.setup(cfg, mix, 2**31 + 44, True)
    rec = tenant_open.operation(state)
    return tenant_open, state, rec


def test_a_live_producer_wraps_every_lane_four_times():
    """448 requests from a producer thread through 8-row regions while
    the stream runs: every lane wraps at least four times, every answer
    is the reference's, the books close, and after the first entry the
    ring rows sent are the rows published."""
    from benchmarks.reference import serve as ref

    drv, state, rec = open_cell()
    failed, compared = drv.check(state, [rec])
    assert failed == 0, compared
    assert rec["resolved"].all()
    assert rec["value"].tolist() == [ref.answer(int(x)) for x in rec["x"]]
    assert rec["sum"] == ref.running_sum(rec["x"].tolist())
    wraps = {t: s["wraps"] for t, s in rec["stats"].items()}
    assert min(wraps.values()) >= 4 and wraps["gold"] >= 16, wraps
    link = rec["stream"]
    assert link["ring_uploads"] == 1 and link["ring_deltas"] >= 4
    sent_first = 448 - (link["ring_rows_up"] - 24)
    assert 0 <= sent_first <= 24  # what the first pump found, at most a ring
    assert rec["ring_rows_after_first"] == link["ring_rows_up"] - 24
    assert rec["executed"] == 449 and rec["pending"] == 0
    assert rec["queue_n"] == 448 and rec["queue_sum_s"] > 0
    assert (np.asarray(rec["late_s"]) >= 0).all()
    lat = np.asarray(rec["latency_s"])
    assert np.isfinite(lat).all() and (lat > 0).all()


def test_the_open_cells_control_sheds_and_fails():
    drv, state, rec = open_cell(requests=224, rate=2000,
                                deadline_s=1e-4)
    failed, compared = drv.check(state, [rec])
    assert failed > 0
    assert dict((c[0], c[1]) for c in compared)["requests_wrong"] > 0


def test_a_program_without_recycling_is_refused_not_hung(monkeypatch):
    """The parent's gate (a lifetime budget): the driver raises on the
    first "ring" refusal, as it must on the parent commit."""
    from hclib_tpu.device import tenants

    admit = tenants.TenantTable._admit

    def lifetime(self, lane, *a, **kw):
        if lane.published + len(lane.queue) >= self.region_rows:
            return self._reject(lane, "ring-for-good")
        return admit(self, lane, *a, **kw)

    monkeypatch.setattr(tenants.TenantTable, "_admit", lifetime)
    with pytest.raises(RuntimeError, match="refused.*ring-for-good"):
        open_cell(requests=112, rate=2000)


# ------------------------------------------ quiesce across a wrap, device


def test_stream_quiesce_and_resume_across_a_wrapped_region():
    """A checkpoint stream whose lanes have wrapped is cut mid-stream and
    resumed on a fresh one: per-tenant counts conserved, the value an
    uninterrupted run's."""
    def fresh():
        return StreamingMegakernel(
            bump_mk(checkpoint=True), ring_capacity=24,
            tenants=[TenantSpec("x", weight=2), TenantSpec("y"),
                     TenantSpec("z")],
        )

    sub = {"x": 7, "y": 6, "z": 3}
    expect = 1000 + sum((i + 1) * n for i, n in enumerate(sub.values()))

    def load(sm):
        start_at(sm.tenants, 5)  # slots 5, 6, 7, 0, 1, ... of 8
        for i, (tid, n) in enumerate(sub.items()):
            for _ in range(n):
                assert sm.submit(tid, BUMP, args=[i + 1])

    sm = fresh()
    load(sm)
    sm.quiesce(after_executed=4)
    iv, info = sm.run_stream(seed_builder())
    assert info["quiesced"] is True
    st = info["state"]
    res = per_tenant_ring_counts(st["ring_rows"])
    for i, n in enumerate(sub.values()):
        assert int(st["tctl"][i, TC_INSTALLED]) + res.get(i, 0) == n
    assert sum(res.values()) > 0
    sm2 = fresh()
    sm2.close()
    iv2, info2 = sm2.run_stream(resume_state=st)
    assert int(iv2[0]) == expect
    for tid, n in sub.items():
        t = info2["tenants"][tid]
        assert t["accepted"] == n and t["completed"] == n
        assert not (t["dropped"] or t["expired"] or t["rejected"])


# ------------------------------------------------ what an entry sends up


@pytest.mark.parametrize("kind", ["plain", "tenants", "egress"])
@pytest.mark.parametrize("waves", [(3, 2, 4), (1, 1, 1, 1, 1, 1)])
def test_ring_rows_sent_follow_rows_published(kind, waves):
    """After the ring's first, whole upload an entry's slab carries the
    rows written since the last entry, and ``ring_rows_up`` counts them;
    no second upload of the ring."""
    from conftest import front_door, send

    sm, table = front_door(kind)
    first = send(sm, table, 5)
    sent = list(first)

    def producer():
        sleeps = 0
        for n in waves:
            sleeps += 2
            while sm.stats_dict()["stream"]["idle_sleeps"] < sleeps:
                time.sleep(0.001)
            sent.extend(send(sm, table, n))
            sleeps = sm.stats_dict()["stream"]["idle_sleeps"]
        sm.close()

    t = threading.Thread(target=producer)
    t.start()
    iv, info = sm.run_stream(seed_builder(), deadline_s=120.0)
    t.join()
    total = 5 + sum(waves)
    assert info["executed"] == 1 + total
    link = info["stream"]
    assert link["ring_uploads"] == 1
    assert link["ring_rows_up"] == sm.ring_capacity + sum(waves)
    assert len(waves) <= link["ring_deltas"] <= sum(waves)
    assert link["uploads"] == link["entries"] + 1  # the delta rides the slab


def test_more_rows_than_the_delta_block_holds_go_whole(monkeypatch):
    """The program decides from the count: rows written since the last
    entry ride its slab up to RING_DELTA_ROWS of them, and more send the
    ring whole again."""
    monkeypatch.setattr(inject, "RING_DELTA_ROWS", 4)
    # a table size no other test builds: the program cache keys on it
    sm = StreamingMegakernel(bump_mk(capacity=72), ring_capacity=64,
                             tenants=False)
    for k in (1, 2):
        sm.inject(BUMP, args=[k])

    def producer():
        seen = 0
        for wave in ((3, 4, 5), (6, 7, 8, 9, 10, 11)):
            while sm.stats_dict()["stream"]["idle_sleeps"] < seen + 2:
                time.sleep(0.001)
            with sm._lock:  # one pump finds the whole wave
                sm._pending_rows.extend(build_row(BUMP, [k]) for k in wave)
            while sm.stats_dict()["stream"]["ring_rows_up"] < 64 + 3:
                time.sleep(0.001)
            seen = sm.stats_dict()["stream"]["idle_sleeps"]
        sm.close()

    t = threading.Thread(target=producer)
    t.start()
    iv, info = sm.run_stream(seed_builder(), deadline_s=120.0)
    t.join()
    assert int(iv[0]) == 1000 + sum(range(12)) and info["executed"] == 12
    link = info["stream"]
    assert link["ring_uploads"] == 2 and link["ring_deltas"] == 1
    assert link["ring_rows_up"] == 64 + 3 + 64


# -------------------------------------------------------- the idle poll


def test_the_idle_poll_never_sleeps_on_a_queued_request(monkeypatch):
    """A request that arrives while an entry runs is pumped by the next
    iteration, not after a sleep: the loop sleeps only with nothing
    queued. Arrivals are made from the stream's own thread, inside
    ``absorb``, so what the sleep sees is exact."""
    table = TenantTable([TenantSpec("a"), TenantSpec("b")], 8,
                        egress=EgressSpec(depth=4))
    sm = StreamingMegakernel(bump_mk(), ring_capacity=16, tenants=table)
    absorb, calls, slept_on = table.absorb, [0], []

    def arriving(tctl):
        absorb(tctl)
        calls[0] += 1
        if calls[0] in (2, 3, 6, 7, 8):  # also behind entries that ran nothing
            assert sm.submit("ab"[calls[0] % 2], BUMP, args=[calls[0]])
        if calls[0] == 12:
            sm.close()

    monkeypatch.setattr(table, "absorb", arriving)
    monkeypatch.setattr(
        inject.time, "sleep",
        lambda s: slept_on.append(table.queued()),
    )
    iv, info = sm.run_stream(seed_builder(), deadline_s=120.0)
    assert int(iv[0]) == 1000 + 2 + 3 + 6 + 7 + 8
    assert info["stream"]["idle_sleeps"] == len(slept_on) >= 3
    assert set(slept_on) == {0}
