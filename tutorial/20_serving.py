"""Lesson 20: the request/response serving loop.

Lesson 13 built the ingress half of a service: typed admission into
weighted tenant lanes. This lesson closes the loop with the EGRESS half
(device/egress.py): how a caller who submitted a request gets its
result back - at sustained load, across preemption, without ever
wedging.

- **Submit returns a Future**: on an egress-enabled table every
  ``submit()``'s ``Admission`` carries a typed ``Future``;
  ``future.result(timeout=)`` blocks until exactly ONE terminal rung of
  the degradation ladder: RESULT (the payload), EXPIRED (deadline),
  POISONED (aborted/cancelled/validator), or PREEMPTED carrying a
  ``resume_token`` that reattaches after the stream resumes.
- **The completion mailbox**: each device owns a small ring of EGR
  result rows (result slot, tenant, fn, status, cursors). The kernel
  publishes at task retirement inside the round loop; the host drains
  it at every entry boundary. A FULL mailbox is explicit backpressure:
  the retiring row parks (counted, TR_EGRESS-traced) and an install
  credit gate throttles new installs - results are NEVER dropped, and
  there is no overflow abort by construction.
- **Wedge-proof by model checking**: the same bounded-interleaving
  explorer that certifies the inject/credit protocols (lesson 18)
  explores ``EgressMailboxModel`` - a full mailbox with a dead poller
  still quiesces and drains (tools/hclint.py runs it in CI).
- **Conservation**: the ledger's identity
  ``submitted == resolved + expired + poisoned (+ pending)`` closes
  exactly - across checkpoint cuts, resumes, and mesh reshards
  (tools/chaos_soak.py --serve soaks it; the cells serve-burst-3072
  and serve-open-steady check it every run).

Ordering rule worth memorizing: after a preemption cut, ``reattach``
a resume token only AFTER the resumed stream has re-adopted the
snapshot (i.e. after ``run_stream(resume_state=...)``) - the fresh
ledger learns the outstanding tokens from the snapshot's ``etok``
block. Off path (``egress=`` unset / ``egress=False``) the kernel
lowers bit-identically to the pre-egress build: you pay nothing.

Env spelling for wrapper scripts: ``HCLIB_TPU_EGRESS_DEPTH=N`` (0=off)
and ``HCLIB_TPU_EGRESS_BACKOFF_S`` (the ``result()`` poll backoff).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from hclib_tpu.device.descriptor import (  # noqa: E402
    RING_ROW,
    TEN_TOKEN,
    TaskGraphBuilder,
)
from hclib_tpu.device.egress import (  # noqa: E402
    EgressSpec,
    FutureExpired,
    FuturePoisoned,
    FuturePreempted,
    HostMailbox,
)
from hclib_tpu.device.inject import StreamingMegakernel  # noqa: E402
from hclib_tpu.device.megakernel import Megakernel  # noqa: E402
from hclib_tpu.device.tenants import (  # noqa: E402
    TenantSpec,
    TenantTable,
    wrr_poll_reference,
)

BUMP = 0


def _mk(checkpoint=False):
    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    return Megakernel(
        kernels=[("bump", bump)], capacity=256, num_values=8,
        succ_capacity=8, interpret=True, checkpoint=checkpoint,
    )


def _table(egress=EgressSpec(depth=16), region=32, clock=None):
    return TenantTable(
        [TenantSpec("gold", weight=2), TenantSpec("std")],
        region, egress=egress,
        **({"clock": clock} if clock else {}),
    )


def part_one_submit_futures():
    """The happy path: submit -> Future -> RESULT, conservation exact."""
    table = _table()
    sm = StreamingMegakernel(_mk(), ring_capacity=64, tenants=table)
    futs = []
    for i in range(6):
        adm = sm.submit("gold" if i % 2 else "std", BUMP, args=[i + 1])
        assert adm.accepted and adm.future.token > 0
        futs.append(adm.future)
    sm.close()
    b = TaskGraphBuilder()
    b.add(BUMP, args=[100])
    iv, info = sm.run_stream(b)
    assert int(iv[0]) == 100 + sum(range(1, 7))
    for f in futs:
        assert isinstance(f.result(timeout=2.0), int)
        assert f.state == "RESULT" and f.latency_s() is not None
    cons = table.futures.conservation()
    assert cons["ok"] and cons["resolved"] == 6, cons
    print(f"  6 futures resolved RESULT through the mailbox; "
          f"ledger closes: {cons['resolved']} resolved / "
          f"{cons['submitted']} submitted")


def part_two_backpressure():
    """A depth-2 mailbox under a poller consuming ONE row per step:
    sustained backpressure parks (counted), loses nothing."""
    spec = EgressSpec(depth=2)
    table = _table(egress=spec, region=32, clock=lambda: 100.0)
    box = HostMailbox(spec, park_cap=24)
    ring = np.zeros((2 * 32, RING_ROW), np.int32)
    futs = {}
    for i in range(24):
        adm = table.submit(i % 2, BUMP, args=[i])
        futs[adm.future.token] = (adm.future, 3 * i)
    drained, rnd = 0, 0
    while drained < len(futs):
        tctl = table.pump(ring)
        rows = wrr_poll_reference(ring, tctl, 32, rnd, 1 << 20)
        table.absorb(tctl)
        box.publish([(int(r[TEN_TOKEN]), 0, BUMP,
                      0, futs[int(r[TEN_TOKEN])][1]) for r in rows])
        drained += len(box.drain(futures=table.futures, limit=1))
        rnd += 1
    assert box.park_events() > 0, "the tiny mailbox never parked"
    for f, payload in futs.values():
        assert f.result(timeout=1.0) == payload and f.state == "RESULT"
    print(f"  24 results through a depth-2 mailbox, slow poller: "
          f"{box.park_events()} park events, zero loss, {rnd} steps")


def part_three_degradation_ladder():
    """Every failure is a TYPED terminal state, never a hang: deadline
    -> EXPIRED, abort -> POISONED."""
    clk = [100.0]
    table = _table(region=32, clock=lambda: clk[0])
    ring = np.zeros((2 * 32, RING_ROW), np.int32)
    doomed = table.submit("gold", BUMP, args=[1],
                          deadline_s=0.01).future
    clk[0] += 1.0  # the deadline lapses before the pump pops the row
    table.absorb(table.pump(ring))
    try:
        doomed.result(timeout=1.0)
        raise AssertionError("expected FutureExpired")
    except FutureExpired:
        assert doomed.state == "EXPIRED"
    sm = StreamingMegakernel(_mk(), ring_capacity=64, tenants=_table())
    poisoned = [sm.submit("std", BUMP, args=[1]).future
                for _ in range(3)]
    sm.abort("client disconnect")
    try:
        sm.run_stream(TaskGraphBuilder())
    except Exception as e:
        assert "abort" in str(e)
    for f in poisoned:
        try:
            f.result(timeout=1.0)
            raise AssertionError("expected FuturePoisoned")
        except FuturePoisoned:
            assert f.state == "POISONED"
    print("  deadline -> FutureExpired; abort -> FuturePoisoned "
          "(typed raises, nothing hangs)")


def part_four_preempt_reattach():
    """A checkpoint cut with futures in flight: PREEMPTED + resume
    token; reattach AFTER the resumed stream re-adopts the snapshot."""
    def fresh():
        return StreamingMegakernel(
            _mk(checkpoint=True), ring_capacity=64,
            tenants=_table(egress=EgressSpec(depth=64)),
        )

    sm = fresh()
    futs = [sm.submit("gold", BUMP, args=[1]).future for _ in range(8)]
    sm.quiesce(after_executed=3)
    _, info = sm.run_stream(TaskGraphBuilder())
    assert info["quiesced"] and "etok" in info["state"]
    tokens = []
    for f in futs:
        if f.state == "PREEMPTED":
            try:
                f.result()
            except FuturePreempted as e:
                assert e.resume_token == f.resume_token
            tokens.append(f.resume_token)
        else:
            assert f.state == "RESULT"
    sm2 = fresh()
    sm2.close()
    sm2.run_stream(resume_state=info["state"])  # re-adopts etok
    done = [sm2.tenants.reattach(tok) for tok in tokens]  # THEN attach
    for f in done:
        assert f.result(timeout=2.0) is not None and f.state == "RESULT"
    cons = sm2.tenants.futures.conservation()
    assert cons["ok"] and cons["reattached"] == len(tokens)
    print(f"  cut at 3 tasks: {len(tokens)} futures PREEMPTED with "
          f"resume tokens, all reattached and resolved after resume")


if __name__ == "__main__":
    part_one_submit_futures()
    part_two_backpressure()
    part_three_degradation_ladder()
    part_four_preempt_reattach()
    print("lesson 20 OK")
