"""Driver of the tenant front door kept up as a service: one operation is
one stream as a long-lived service sees it - a fresh ``TenantTable``,
``Megakernel`` and ``StreamingMegakernel`` (the program cache serves the
build), ``run_stream`` on the calling thread, and ONE generator thread
that submits each request when the clock passes its due time
(``open_schedule``: Poisson arrivals at the mix's fixed rate), closes the
stream after the last, and lets it run to drained; then the client reads
its futures. The stream serves many times what its tenants' ring regions
hold, so the regions recycle all through it. A request's latency is
``Future.t_done`` minus its DUE time: what the generator ran late is
inside it (and printed beside it).

Backpressure blocks the generator inside ``submit(wait=True)`` (a full
region or backlog clears as the device consumes) and shows as lateness.
A REFUSED request is the deployment's broken guarantee, not load to
shed: the generator stops the stream at the first one and the operation
raises (a program whose regions do not recycle refuses the 1,025th
request of a lane for good). ``run_stream`` is given a deadline of several stream
lengths, so a stream that never drains raises too instead of hanging.

Interface: see drivers/megakernel_run.py.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import serve as ref
from . import open_schedule
from .tenant_burst import respond  # serve-3tenant's kernel: 3x+1, a sum


# How long one submit() may block on backpressure (a full region or
# backlog is transient: the producer waits, and what it waited is inside
# the request's latency). Only the warm stream's compiles, tens of
# seconds with a cold cache, come near it.
WAIT_S = 180.0


class State:
    def __init__(self, cfg, mix, seed, interpret):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.interpret = interpret
        self.streams = 0
        # Only the control sets a lane deadline: a request that has
        # waited longer than it for its slot when a pump looks is shed.
        self.deadline_s = cfg.get("deadline_s")


def setup(cfg, mix, seed, interpret):
    return State(cfg, mix, seed, interpret)


def _generate(sm, ids, due, lane, x, t0, futs, late, failure):
    """The generator thread: each request at its due time, never early.
    It sleeps to the next due time (a sleep gives the interpreter up; a
    spin would take it from the stream's driver) and submits everything
    that came due meanwhile. The first refusal, or any exception, ends
    the stream."""
    tids = [ids[i] for i in lane.tolist()]
    xs = x.tolist()
    due_at = (t0 + due).tolist()
    try:
        for i, at in enumerate(due_at):
            now = time.monotonic()
            if now < at:
                time.sleep(at - now)
                now = time.monotonic()
            late[i] = now - at
            with TraceAnnotation("bench:submit"):  # as tenant_burst's
                adm = sm.submit(tids[i], 0, args=[xs[i]], out=1,
                                wait=True, wait_timeout_s=WAIT_S)
            if not adm and adm.reason != "expired":
                raise RuntimeError(
                    f"request {i} of the stream refused: tenant "
                    f"{adm.tenant!r}, reason {adm.reason!r} (the "
                    "deployment admits every request)"
                )
            # "expired": only the control's lane deadline can lapse while
            # a submit waits; the request was shed, and counts as wrong.
            futs.append(adm.future)
    except BaseException as e:  # noqa: BLE001 - handed to the caller
        failure.append(e)
    finally:
        sm.close()


def operation(st: State):
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    cfg, mix = st.cfg, st.mix
    roster = cfg["tenants"]
    due, lane, x = open_schedule.schedule(mix, st.seed, st.streams)
    st.streams += 1
    n = len(due)
    futs, failure = [], []
    late = np.zeros(n, np.float64)
    t_begin = time.monotonic()
    with TraceAnnotation("bench:stream"):
        with TraceAnnotation("bench:build"):
            table = TenantTable(
                [TenantSpec(t, weight=w, deadline_s=st.deadline_s)
                 for t, w in roster],
                cfg["region_rows"],
                egress=EgressSpec(depth=cfg["egress_depth"]),
            )
            mk = Megakernel(
                kernels=[("respond", respond)], capacity=cfg["capacity"],
                num_values=cfg["num_values"],
                succ_capacity=cfg["succ_capacity"],
                interpret=st.interpret,
            )
            sm = StreamingMegakernel(
                mk, ring_capacity=len(roster) * cfg["region_rows"],
                tenants=table, telemetry=cfg["telemetry"],
            )
            b = TaskGraphBuilder()
            b.add(0, args=[0], out=1)  # the resident graph the stream joins
        t0 = time.monotonic()  # the stream's start: due times count from it
        gen = threading.Thread(
            target=_generate, name="open-loop-generator",
            args=(sm, [t for t, _ in roster], due, lane, x, t0, futs, late,
                  failure),
        )
        gen.start()
        try:
            with TraceAnnotation("bench:run_stream"):
                iv, info = sm.run_stream(
                    b, poll_interval_s=cfg["poll_interval_s"],
                    # several stream lengths: never drained raises
                    deadline_s=4 * float(due[-1]) + WAIT_S,
                )
        finally:
            sm.close()  # whatever ended the stream ends the generator
            gen.join()
        if failure:
            raise failure[0]
        total = int(iv[0])
    # The client reads its answers (between streams, inside the window)
    # and lets the stream's objects go: check() compares these arrays.
    resolved = np.fromiter(
        (f is not None and f.state == "RESULT" for f in futs), bool, n)
    values = np.fromiter(
        (f.value if ok else -1 for f, ok in zip(futs, resolved)),
        np.int64, n)
    t_done = np.fromiter(
        (f.t_done if ok else np.inf for f, ok in zip(futs, resolved)),
        np.float64, n)
    stats = table.stats()
    link = info["stream"]
    rec = {
        "wall_s": time.monotonic() - t_begin, "attempted": n,
        "x": x, "lane": lane, "due_at": t0 + due, "late_s": late.tolist(),
        "realised_rate": open_schedule.realised_rate(due),
        "resolved": resolved, "value": values, "t_done": t_done,
        "sum": total, "stats": stats, "stream": link,
        "ledger": table.futures.conservation(),
        "executed": info["executed"], "pending": info["pending"],
        "interpret": info["interpret"], "platform": info["platform"],
        # the program's counters, flat, for the per-layer reducers
        "entries": link["entries"], "settled": link["settled"],
        "idle_sleeps": link["idle_sleeps"],
        "published": sum(s["published"] for s in stats.values()),
        # filled by check(): what the end-to-end reducers read
        "work": 0, "latency_s": [],
    }
    if "ring_rows_up" in link:
        # Ring rows sent after the ring's first, whole upload (the
        # stream's state going up once): what publishing costs the link.
        rec["ring_rows_after_first"] = (
            link["ring_rows_up"] - sm.ring_capacity)
    if all("latency_n" in s for s in stats.values()):
        rec["queue_n"] = sum(s["latency_n"] for s in stats.values())
        rec["queue_sum_s"] = sum(s["latency_sum_s"] for s in stats.values())
    if cfg["gc_freeze"] and st.streams == 1:
        # Once, after the warm stream (set-up): what the process holds
        # from here on is not the collector's to walk again.
        gc.collect()
        gc.freeze()
    return rec


def _compare(rec: dict) -> None:
    """``work`` is the requests resolved to RESULT with the reference's
    value; ``latency_s`` has one entry per request, from its DUE time,
    ``inf`` for one that failed."""
    good = rec["resolved"] & (rec["value"] == ref.answer(rec["x"]))
    rec["work"] = int(good.sum())
    rec["latency_s"] = np.where(
        good, rec["t_done"] - rec["due_at"], np.inf).tolist()


def check(st: State, records):
    cfg, mix = st.cfg, st.mix
    region = cfg["region_rows"]
    for r in records:
        _compare(r)
    wrong = sum(r["attempted"] - r["work"] for r in records)
    sum_err = max(
        abs(r["sum"] - ref.running_sum(r["x"].tolist())) for r in records
    )
    lanes_off = ledger_off = unfinished = unwrapped = 0
    extra = 0
    for r in records:
        # per-tenant counts from the schedule alone
        sent = np.bincount(r["lane"], minlength=len(cfg["tenants"]))
        for (tid, _), want in zip(cfg["tenants"], sent.tolist()):
            s = r["stats"][tid]
            if not (s["accepted"] == s["completed"] == want) or (
                s["dropped"] or s["expired"] or s["rejected"]
                or s["poisoned"]
            ):
                lanes_off += 1
            if s["published"] // region < cfg["min_wraps"]:
                unwrapped += 1
        c = r["ledger"]
        if not c["ok"] or c["resolved"] != r["attempted"] or (
            c["pending"] or c["expired"] or c["poisoned"]
        ):
            ledger_off += 1
        if r["pending"]:
            unfinished += 1
        extra = max(extra, abs(r["executed"] - r["attempted"] - 1))
    rate_off = max(
        abs(r["realised_rate"] / mix["rate_per_s"] - 1.0) for r in records
    )
    late = np.sort(np.concatenate([r["late_s"] for r in records]))
    compared = [
        ("requests_wrong", wrong, 0),
        ("running_sum_abs_err", sum_err, 0),
        ("tenant_lanes_off_contract", lanes_off, 0),
        ("ledgers_not_conserved", ledger_off, 0),
        ("streams_not_drained", unfinished, 0),
        ("executed_minus_requests_minus_1", extra, 0),
        ("lanes_wrapped_under_%d_times" % cfg["min_wraps"], unwrapped, 0),
        # Printed, not limited. The schedule is scaled to the file's rate
        # (open_schedule), so the first says how far one gap moved it; the
        # generator's lateness is inside every latency already.
        ("schedule_rate_rel_err", rate_off, None),
        ("generator_late_p50_us", float(late[len(late) // 2]) * 1e6, None),
        ("generator_late_p99_us",
         float(late[int(0.99 * (len(late) - 1))]) * 1e6, None),
        ("generator_late_max_us", float(late[-1]) * 1e6, None),
    ]
    # A stream with any broken guarantee fails whole: every request of
    # it counts, beside the single wrong requests of the other streams.
    failed = wrong
    if (sum_err or lanes_off or ledger_off or unfinished or extra
            or unwrapped):
        failed = max(failed, 1)
    return failed, compared
