"""``KernelContext.become``: a running task re-arms its own row as its
continuation (F_FN and F_DEP rewritten; successors, out slot, args, value
block and home-link where they lie), and the ``complete()`` of that
dispatch counts it executed and leaves the row pending. What
``_help_finish_ctx`` does in the reference (src/hclib-runtime.c:1032-1065):
the blocked task itself becomes the continuation.

Every case runs the Pallas interpreter on the CPU (``interpret=True``).
"""

import pytest
from conftest import fib_exec_count

from hclib_tpu.device.descriptor import F_DEP, F_FN, TaskGraphBuilder
from hclib_tpu.device.megakernel import VBLOCK, Megakernel
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.device.workloads import FIB, SUM, make_fib_megakernel
from hclib_tpu.models.fib import fib_seq
from hclib_tpu.parallel.mesh import cpu_mesh


def _fib_run(mk, n, **kw):
    b = TaskGraphBuilder()
    b.add(FIB, args=[n], out=0)
    iv, _, info = mk.run(b, **kw)
    return int(iv[0]), info


def test_scalar_tier_counts_the_fork_and_its_continuation_on_one_row():
    """fib(12): 3 F(13) - 2 dispatches, F(13) - 1 of them ended re-armed
    (one a SUM), and the table's high-water mark is no higher than it was
    when every continuation took a second row (14 then, PR 40)."""
    value, info = _fib_run(make_fib_megakernel(128, interpret=True), 12)
    assert value == fib_seq(12) == 144
    assert info["executed"] == 3 * fib_seq(13) - 2 == 697
    assert info["became"] == fib_seq(13) - 1 == 232
    assert info["pending"] == 0 and not info["overflow"]
    assert info["allocated"] <= 13
    assert "tiers" not in info  # no batch route: the one word rides alone


@pytest.mark.parametrize("width,rows_before", [(4, 72), (8, 78)])
def test_batch_of_rearms_slot_by_slot(width, rows_before):
    """Through ``batch_of`` the mark is the SLOT's: a round's
    ``complete()`` calls run after all its bodies, and a round of fib
    holds leaves that finish beside forks that re-arm."""
    mk = make_fib_megakernel(128, interpret=True, batch_width=width)
    value, info = _fib_run(mk, 12)
    assert value == 144
    assert info["executed"] == 697 and info["pending"] == 0
    assert info["became"] == info["tiers"]["became"] == 232
    assert info["tiers"]["batch_tasks"] == 697 - 232  # the FIBs; SUMs scalar
    assert info["allocated"] <= rows_before


def test_one_batch_round_of_two_leaves_and_two_forks():
    """Four roots in one round of width 4: fib(0) and fib(1) finish,
    fib(2) and fib(3) re-arm; each slot's completion reads its own mark."""
    mk = make_fib_megakernel(64, interpret=True, batch_width=4)
    b = TaskGraphBuilder()
    for n in range(4):
        b.add(FIB, args=[n], out=n)
    iv, _, info = mk.run(b)
    assert [int(v) for v in iv[:4]] == [0, 1, 1, 2]
    assert info["executed"] == sum(fib_exec_count(n) for n in range(4)) == 13
    assert info["became"] == 1 + 2  # the internal nodes of fib(2), fib(3)
    assert info["tiers"]["full_rounds"] >= 1 and info["pending"] == 0


def test_traveling_copy_rearms_on_the_thief_and_forwards_home_once():
    """Two devices, ``homed=True``: a stolen FIB re-arms ON THE THIEF
    with its home-link still on the row, and the continuation that ends
    the chain forwards the result home: exact value, every dispatch
    counted once."""
    ndev, n, cap = 2, 8, 96
    mk = make_fib_megakernel(
        capacity=cap, interpret=True,
        num_values=VBLOCK * cap + 16 + cap,  # + one result slot a row
    )
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)}, homed=True,
        window=16, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=4)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)
    per_dev = [f["became"] for f in info["fault_stats"]]
    assert info["became"] == sum(per_dev) == fib_seq(n + 1) - 1
    assert per_dev[1] > 0, per_dev  # a copy re-armed away from home


def test_checkpoint_cut_with_rearmed_rows_pending_resumes_exact():
    """A re-armed row is an ordinary pending row (F_FN SUM, F_DEP > 0):
    a quiesce cut exports it, ``resume`` finishes the tree, and the two
    entries' ``became`` (a per-entry counter, like every tier word) add
    up to the tree's."""
    mk = make_fib_megakernel(128, interpret=True, checkpoint=True)
    b = TaskGraphBuilder()
    b.add(FIB, args=[12], out=0)
    _, _, cut = mk.run(b, quiesce=300)
    assert cut["quiesced"] and cut["pending"] > 0
    rows = cut["state"]["tasks"]
    waiting = (rows[:, F_FN] == SUM) & (rows[:, F_DEP] > 0)
    assert waiting.sum() > 0  # forks cut between their re-arm and their join
    iv, _, done = mk.resume(cut["state"])
    assert int(iv[0]) == 144
    assert done["executed"] == 697 and done["pending"] == 0
    assert cut["became"] + done["became"] == 232


def test_become_refuses_a_static_dep_count_of_zero_at_trace_time():
    """A continuation that is ready at once is a plain ``spawn``."""

    def ready_at_once(ctx):
        ctx.become(0, 0)

    mk = Megakernel(
        kernels=[("k", ready_at_once)], capacity=8, num_values=16,
        succ_capacity=8, interpret=True, verify=False,
    )
    b = TaskGraphBuilder()
    b.add(0, args=[0], out=0)
    with pytest.raises(ValueError, match="dep_count > 0"):
        mk.run(b)


FORK, LEAF, JOIN = 0, 1, 2


def _fork(ctx):
    k = ctx.arg(0)
    ctx.become(JOIN, 1)
    base = ctx.row_values(ctx.idx)
    ctx.set_arg(ctx.idx, 0, base)
    ctx.spawn(LEAF, [k], succ0=ctx.idx, out=base, nargs=1)


def _leaf(ctx):
    ctx.set_out(ctx.arg(0) * 2)


def _join(ctx):
    ctx.set_out(ctx.value(ctx.arg(0)) + 1)


def test_rearming_and_plain_handlers_in_one_table_leave_no_stale_mark():
    """A table whose FORK re-arms and whose LEAF and JOIN never do: every
    plain dispatch after a re-armed one must still retire its row (a mark
    left set would count it ``became`` and strand it pending), and rows
    recycle, so the sixteen children find theirs among the 32 the host
    built."""
    m, cap = 16, 40
    mk = Megakernel(
        kernels=[("fork", _fork), ("leaf", _leaf), ("join", _join)],
        capacity=cap, num_values=2 * m + VBLOCK * cap, succ_capacity=8,
        interpret=True, uses_row_values=True,
    )
    b = TaskGraphBuilder()
    for i in range(m):  # interleaved: fork, plain leaf, fork, ...
        b.add(FORK, args=[i], out=i)
        b.add(LEAF, args=[i], out=m + i)
    iv, _, info = mk.run(b)
    assert [int(v) for v in iv[:m]] == [2 * i + 1 for i in range(m)]
    assert [int(v) for v in iv[m:2 * m]] == [2 * i for i in range(m)]
    assert info["became"] == m
    assert info["executed"] == 3 * m + m  # fork, leaf, join; the plain leaf
    assert info["pending"] == 0 and info["allocated"] <= 2 * m + 1
