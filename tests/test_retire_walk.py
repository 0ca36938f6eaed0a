"""``retire()``'s successor walk (PR 46): F_SUCC0 is released inline; the
second inline successor and the CSR list sit behind one branch, taken per
ROW from what the row holds, and ``info["walked"]`` counts the
retirements that took it. The order of pushes to the ready ring is
F_SUCC0, F_SUCC1, the list in order, whichever side a row takes.

Every case builds its rows' links by hand (the stock builder fills
F_SUCC0 first, and a caller need not: ``spawn(succ1=x)``,
``tenants.py:build_row``), runs them on the scalar tier and on the batch
tier under the Pallas interpreter on the CPU, and compares WHICH rows ran
and IN WHICH ORDER with a host reference of the scheduler's policy: the
ready ring popped newest-first; a routed kind parked in its lane until the
ring drains, then the newest ``width`` fired together, their bodies first
and their completions after, slot by slot.
"""

import functools

import pytest
from conftest import fib_exec_count

from hclib_tpu.device.descriptor import (
    F_CSR_N,
    F_CSR_OFF,
    F_DEP,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    TaskGraphBuilder,
)
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.device.workloads import batch_of, make_fib_megakernel
from hclib_tpu.models.fib import fib_seq
from hclib_tpu.parallel.mesh import cpu_mesh

STAMP, SPAWN1, FORK = 0, 1, 2
CAP, SEQ, NVAL, WIDTH = 32, 40, 48, 4  # slot SEQ counts the stamps so far


def _stamp(ctx):
    """Write this dispatch's place in the order into the row's out slot."""
    seq = ctx.value(SEQ)
    ctx.set_value(SEQ, seq + 1)
    ctx.set_out(seq)


def _spawn1(ctx):
    """A child whose ONLY link is its second inline slot."""
    ctx.spawn(STAMP, succ0=NO_TASK, succ1=ctx.arg(0), out=ctx.arg(1), nargs=0)


def _fork(ctx):
    """Re-arm this row as a STAMP that waits for one child: the links the
    host gave the row pass to the continuation where they lie."""
    ctx.become(STAMP, 1)
    ctx.spawn(STAMP, succ0=ctx.idx, out=ctx.arg(0), nargs=0)


@functools.lru_cache(maxsize=None)
def _mk(tier):
    return Megakernel(
        kernels=[("stamp", _stamp), ("spawn1", _spawn1), ("fork", _fork)],
        capacity=CAP, num_values=NVAL, succ_capacity=16, interpret=True,
        route={"stamp": batch_of(_stamp, width=WIDTH)}
        if tier == "batch" else None,
    )


class Row:
    """One host-built row: its kind, its links as the TEST states them,
    and how many releases it waits for. Its out slot is its index."""

    def __init__(self, fn=STAMP, s0=NO_TASK, s1=NO_TASK, csr=(), dep=0,
                 args=()):
        self.fn, self.s0, self.s1, self.csr = fn, s0, s1, list(csr)
        self.dep, self.args = dep, list(args)

    @property
    def walks(self):
        return self.s1 != NO_TASK or bool(self.csr)


class _Linked(TaskGraphBuilder):
    """The stock builder's arrays with the rows' links, dependency counts
    and the ready ring rewritten from ``Row``s."""

    def __init__(self, rows):
        super().__init__()
        self.rows = rows
        for i, r in enumerate(rows):
            self.add(r.fn, args=r.args, out=i)
        self.reserve_values(NVAL)

    def finalize(self, capacity=None, succ_capacity=None):
        tasks, succ, ring, counts = super().finalize(capacity, succ_capacity)
        csr = []
        for i, r in enumerate(self.rows):
            tasks[i, F_DEP] = r.dep
            tasks[i, F_SUCC0], tasks[i, F_SUCC1] = r.s0, r.s1
            tasks[i, F_CSR_OFF], tasks[i, F_CSR_N] = len(csr), len(r.csr)
            csr += r.csr
        succ[: len(csr)] = csr
        ready = [i for i, r in enumerate(self.rows) if r.dep == 0]
        ring[:] = NO_TASK
        ring[: len(ready)] = ready
        counts[1] = len(ready)
        return tasks, succ, ring, counts


def reference(rows, tier):
    """What the scheduler does with ``rows``, in plain Python: the stamp
    each out slot gets, and the executed / became / walked counts."""
    live = {i: Row(r.fn, r.s0, r.s1, r.csr, r.dep, r.args)
            for i, r in enumerate(rows)}
    out = {i: i for i in live}
    ring = [i for i, r in live.items() if r.dep == 0]
    lane, stamps = [], {}
    n = {"executed": 0, "became": 0, "walked": 0}

    def body(t):
        """Run row ``t``'s handler; True when it re-armed the row."""
        r = live[t]
        if r.fn == STAMP:
            stamps[out[t]] = len(stamps)
            return False
        child = max(live) + 1  # nobody names a spawned row: any id does
        ring.append(child)
        if r.fn == SPAWN1:
            live[child], out[child] = Row(s1=r.args[0]), r.args[1]
            return False
        live[child], out[child] = Row(s0=t), r.args[0]
        r.fn, r.dep = STAMP, 1
        return True

    def complete(t, stayed):
        n["executed"] += 1
        if stayed:
            n["became"] += 1
            return
        r = live[t]
        n["walked"] += r.walks
        for s in [r.s0, r.s1] + r.csr:
            if s != NO_TASK:
                live[s].dep -= 1
                if live[s].dep == 0:
                    ring.append(s)

    while ring or lane:
        if not ring:
            block, lane[:] = lane[-WIDTH:], lane[:-WIDTH]
            for t, stayed in [(t, body(t)) for t in block]:
                complete(t, stayed)
            continue
        t = ring.pop()
        if tier == "batch" and live[t].fn == STAMP:
            lane.append(t)
        else:
            complete(t, body(t))
    return stamps, n


def _fan(head, targets, dep=None):
    """``head`` (row 0), its targets (rows 1..), each with one F_SUCC0 to
    a sink (the last row) that waits for them all, and a second ready row
    beside the head (the last but one) so that the order has a choice."""
    sink = 1 + targets + 1
    dep = dep or [1] * targets
    rows = [head] + [Row(s0=sink, dep=d) for d in dep]
    return rows + [Row(), Row(dep=targets)]


CASES = {
    "no_successor": [Row(), Row()],
    "succ0_only": _fan(Row(s0=1), 1),
    "succ1_only": _fan(Row(s1=1), 1),
    "succ1_only_spawned": _fan(Row(SPAWN1, args=[1, 39]), 1),
    "both_inline": _fan(Row(s0=1, s1=2), 2),
    "succ0_and_a_list": _fan(Row(s0=1, csr=[2]), 2),
    "both_and_a_list_of_1": _fan(Row(s0=1, s1=2, csr=[3]), 3),
    "both_and_a_list_of_5": _fan(Row(s0=1, s1=2, csr=[3, 4, 5, 6, 7]), 7),
    "one_row_in_both_slots": _fan(Row(s0=1, s1=1), 1, dep=[2]),
    "become_then_walk": _fan(
        Row(FORK, s0=1, s1=2, csr=[3], args=[39]), 3
    ),
    "two_heads_two_lists": [
        Row(s0=2, s1=3, csr=[4]), Row(s0=4, s1=3, csr=[2, 5]),
        Row(dep=2), Row(dep=2), Row(dep=2), Row(dep=1),
    ],
}


@pytest.mark.parametrize("tier", ["scalar", "batch"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_releases_the_same_rows_in_the_same_order(case, tier):
    rows = CASES[case]
    stamps, n = reference(rows, tier)
    iv, _, info = _mk(tier).run(_Linked(rows))
    assert info["pending"] == 0 and not info["overflow"]
    assert {s: int(iv[s]) for s in stamps} == stamps
    assert int(iv[SEQ]) == len(stamps)
    assert info["executed"] == n["executed"]
    assert info["became"] == n["became"]
    # The rows that held a second or a listed successor, counted by hand.
    hand = sum(r.walks for r in rows) + (case == "succ1_only_spawned")
    assert info["walked"] == n["walked"] == hand
    if tier == "batch":
        assert info["tiers"]["walked"] == hand
        assert info["tiers"]["batch_tasks"] == len(stamps)


@pytest.mark.parametrize("batch_width", [None, 4])
def test_a_fork_join_tree_never_leaves_the_fast_path(batch_width):
    """fib's rows hold one successor, in F_SUCC0: no retirement of the
    697 dispatches of fib(12) takes the slow region, on either tier, and
    ``became`` is what it was."""
    mk = make_fib_megakernel(128, interpret=True, batch_width=batch_width)
    b = TaskGraphBuilder()
    b.add(0, args=[12], out=0)
    iv, _, info = mk.run(b)
    assert int(iv[0]) == fib_seq(12) == 144
    assert info["executed"] == fib_exec_count(12) == 697
    assert info["became"] == 232 and info["walked"] == 0


WORK, JOIN = 0, 1


def _work(ctx):
    ctx.set_out(ctx.arg(0) * 2)


def _join(ctx):
    total = ctx.arg(0)
    for s in range(1, 7):
        total = total + ctx.value(s)
    ctx.set_out(total)


def test_a_remote_completion_releases_its_proxy_through_the_slow_region():
    """Two devices, ``homed=True``: six WORK rows on device 0, each
    awaited by three JOINs (F_SUCC0, F_SUCC1 and a list of one). A stolen
    WORK leaves a proxy that keeps the links; its copy carries none and
    retires on the thief by the fast path; the remote completion
    (``core.complete(hrow)``) walks the proxy's three successors at home.
    Every WORK is walked exactly once, all on device 0, however many ran
    away."""
    ndev, nwork, cap = 2, 6, 32
    mk = Megakernel(
        kernels=[("work", _work), ("join", _join)], capacity=cap,
        num_values=16 + cap, succ_capacity=16, interpret=True,
    )
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[WORK],
        homed=True, window=8, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    works = [
        builders[0].add(WORK, args=[i + 1], out=1 + i) for i in range(nwork)
    ]
    for j in range(3):
        builders[0].add(JOIN, args=[j], deps=works, out=8 + j)
    for b in builders:
        b.reserve_values(16)
    iv, _, info = rk.run(builders, quantum=2)
    assert info["pending"] == 0
    total = sum(2 * (i + 1) for i in range(nwork))
    assert [int(v) for v in iv[0, 8:11]] == [total, total + 1, total + 2]
    assert info["executed"] == nwork + 3
    ran_away = int(info["per_device_counts"][1, 5])
    assert ran_away > 0  # some WORK completed remotely
    assert [f["walked"] for f in info["fault_stats"]] == [nwork, 0]
    assert info["walked"] == nwork and info["became"] == 0
