"""Driver of the tenant front door: one operation is one whole burst as a
batch client sends it - a fresh ``TenantTable``, ``Megakernel`` and
``StreamingMegakernel`` (the program cache serves the build), every
request submitted, the stream closed and run to drained. The call sequence
is chip_smoke.py's ``phase_serve``, proven on the chip.

Interface: see drivers/megakernel_run.py.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from .. import traffic
from ..reference import serve as ref


def respond(ctx):  # the request: answer 3x+1, keep a running sum
    ctx.set_value(0, ctx.value(0) + ctx.arg(0))
    ctx.set_out(ctx.arg(0) * 3 + 1)


class State:
    def __init__(self, cfg, mix, seed, interpret):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.interpret = interpret
        self.bursts = 0
        # Only the control sets a deadline: it sheds the burst's tail.
        self.deadline_s = cfg.get("deadline_s")


def setup(cfg, mix, seed, interpret):
    return State(cfg, mix, seed, interpret)


def operation(st: State):
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    cfg = st.cfg
    roster = cfg["tenants"]
    args = traffic.burst_args(st.mix, st.seed, st.bursts, len(roster))
    st.bursts += 1
    asked = []  # (x, future or None)
    t0 = time.monotonic()
    with TraceAnnotation("bench:burst"):
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
        due = time.monotonic()
        with TraceAnnotation("bench:submit_all"):
            for (tid, _), xs in zip(roster, args.tolist()):
                for x in xs:
                    with TraceAnnotation("bench:submit"):
                        adm = sm.submit(tid, 0, args=[x], out=1)
                    asked.append((x, adm.future if adm else None))
            sm.close()
        with TraceAnnotation("bench:run_stream"):
            b = TaskGraphBuilder()
            b.add(0, args=[0], out=1)  # the resident graph the stream joins
            iv, info = sm.run_stream(b)
            total = int(iv[0])
    t1 = time.monotonic()
    # The client reads its answers (between bursts, inside the window) and
    # lets the burst's objects go: what check() compares is these arrays.
    n = len(asked)
    xs = np.fromiter((x for x, _ in asked), np.int64, n)
    futs = [f for _, f in asked]
    resolved = np.fromiter(
        (f is not None and f.state == "RESULT" for f in futs), bool, n)
    values = np.fromiter(
        (f.value if ok else -1 for f, ok in zip(futs, resolved)),
        np.int64, n)
    t_done = np.fromiter(
        (f.t_done if ok else np.inf for f, ok in zip(futs, resolved)),
        np.float64, n)
    return {
        "wall_s": t1 - t0, "due": due, "attempted": n,
        "x": xs, "resolved": resolved, "value": values, "t_done": t_done,
        "sum": total, "stats": table.stats(),
        "ledger": table.futures.conservation(),
        "executed": info["executed"], "pending": info["pending"],
        "interpret": info["interpret"], "platform": info["platform"],
        # filled by check(): what the end-to-end reducers read
        "work": 0, "latency_s": [],
    }


def _compare(rec: dict) -> None:
    """``work`` is the requests resolved to RESULT with the reference's
    value; ``latency_s`` has one entry per request sent, ``inf`` for one
    that failed."""
    good = rec["resolved"] & (rec["value"] == ref.answer(rec["x"]))
    rec["work"] = int(good.sum())
    rec["latency_s"] = np.where(
        good, rec["t_done"] - rec["due"], np.inf).tolist()


def check(st: State, records):
    per = st.mix["requests_per_tenant"]
    for r in records:
        _compare(r)
    wrong = sum(r["attempted"] - r["work"] for r in records)
    sum_err = max(
        abs(r["sum"] - ref.running_sum(r["x"].tolist()))
        for r in records
    )
    lanes_off = ledger_off = unfinished = 0
    for r in records:
        for tid, _ in st.cfg["tenants"]:
            s = r["stats"][tid]
            if not (s["accepted"] == s["completed"] == per) or (
                s["dropped"] or s["expired"] or s["rejected"]
                or s["poisoned"]
            ):
                lanes_off += 1
        c = r["ledger"]
        if not c["ok"] or c["resolved"] != r["attempted"] or (
            c["pending"] or c["expired"] or c["poisoned"]
        ):
            ledger_off += 1
        if r["executed"] != r["attempted"] + 1 or r["pending"]:
            unfinished += 1
    compared = [
        ("requests_wrong", wrong, 0),
        ("running_sum_abs_err", sum_err, 0),
        ("tenant_lanes_off_contract", lanes_off, 0),
        ("ledgers_not_conserved", ledger_off, 0),
        ("streams_not_drained", unfinished, 0),
    ]
    # A burst with any broken guarantee fails whole: every request of it
    # counts, beside the single wrong requests of the other bursts.
    failed = wrong
    if sum_err or lanes_off or ledger_off or unfinished:
        failed = max(failed, 1)
    return failed, compared
