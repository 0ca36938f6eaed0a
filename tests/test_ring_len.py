"""The ready ring and the lane rings are a power of two long (ISSUE 45):
``ring_len(capacity)`` words round a table of ``capacity`` rows, every
index a mask (``ring_slot``), so the scheduler's loop holds no integer
divide. The counter this mechanism has is "zero divides"; what it must
not change is where a window may lie in a ring and when a table is full.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import hclib_tpu.device.megakernel as megakernel
from hclib_tpu.device.descriptor import (
    DESC_WORDS,
    NO_TASK,
    TaskGraphBuilder,
    relay_ring,
    ring_len,
    ring_slot,
    ring_window,
)
from hclib_tpu.device.megakernel import (
    C_HEAD,
    C_TAIL,
    BatchSpec,
    Megakernel,
)

CAPS = [5, 64, 320, 640, 768]
STEP, LEAF = 0, 1


def _step(ctx):
    """A chained producer: the next STEP first, then this step's LEAF, so
    the LEAF pops next and the ring never holds more than the two."""
    n = ctx.arg(0)

    @pl.when(n > 1)
    def _():
        ctx.spawn(STEP, [n - 1], nargs=1)

    ctx.spawn(LEAF, [n], nargs=1)


def _leaf(ctx):
    ctx.set_value(0, ctx.value(0) + ctx.arg(0))


def _leaf_batch(ctx):
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            ctx.k.set_value(0, ctx.k.value(0) + ctx.arg(s, 0))


def _mk(capacity, fifo, **kw):
    """STEP on the scalar tier, LEAF through a width-2 lane: a FIFO lane
    that fires at two over a hot ring (``fifo``: its cursors count every
    LEAF of the run, so they pass the ring's length), or the plain LIFO
    lane that fires when the ring has drained (and so holds a whole
    graph when a quiesce spills it)."""
    spec = (
        BatchSpec(_leaf_batch, width=2, prefetch=True,
                  drain=lambda ctx: None, fire_at=2)
        if fifo else BatchSpec(_leaf_batch, width=2)
    )
    return Megakernel(
        kernels=[("step", _step), ("leaf", _leaf)], route={"leaf": spec},
        capacity=capacity, num_values=8, succ_capacity=8, interpret=True,
        # every LEAF adds into value slot 0, on purpose
        verify=False, **kw,
    )


# ---------------------------------------------- the counter: zero divides


def _kernel_jaxprs(fn, *args):
    """Every jaxpr of the ``pallas_call`` kernels inside ``fn(*args)``,
    nested ones (cond arms, loop bodies) included."""
    out = []

    def walk(jaxpr, inside):
        if inside:
            out.append(jaxpr)
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return out


def _integer_divides(mk):
    raw = mk._build_raw(1 << 10)
    cap = mk.capacity
    args = [
        jnp.zeros((cap, DESC_WORDS), jnp.int32),
        jnp.zeros((mk.succ_capacity,), jnp.int32),
        jnp.zeros((mk.ring_len,), jnp.int32),
        jnp.zeros((8,), jnp.int32),
        jnp.zeros((mk.num_values,), jnp.int32),
    ]
    jaxprs = _kernel_jaxprs(raw, *args)
    assert jaxprs, "no pallas_call kernel found"
    return [
        eqn for j in jaxprs for eqn in j.eqns
        if eqn.primitive.name in ("rem", "div")
        and jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.integer)
    ]


def _fib_768():
    from hclib_tpu.device.workloads import make_fib_megakernel

    return make_fib_megakernel(768, interpret=True)


@pytest.mark.parametrize("build", [
    pytest.param(_fib_768, id="fib-scalar-768"),
    pytest.param(lambda: _mk(640, fifo=True), id="batch-routed-640"),
    pytest.param(lambda: _mk(320, fifo=False), id="batch-routed-320"),
])
def test_the_scheduler_holds_no_integer_divide(build, monkeypatch):
    """The traced kernel (pop, push, complete, lane push / fire / spill,
    ``stage()``) has no integer ``rem`` or ``div`` at a capacity that is
    not a power of two. The walk does see one: the same build with the
    rings indexed by ``%`` holds a ``rem`` at every site."""
    assert _integer_divides(build()) == []
    monkeypatch.setattr(megakernel, "ring_slot", lambda x, ring: x % ring)
    assert len(_integer_divides(build())) >= 3


# --------------------------------------------- the lengths, and the helpers


def test_ring_len_and_the_host_helpers():
    assert [ring_len(c) for c in [1, 2] + CAPS + [1024, 4096]] == [
        1, 2, 8, 64, 512, 1024, 1024, 1024, 4096,
    ]
    for bad in (0, 768, -8):
        with pytest.raises(ValueError, match="power of two"):
            ring_slot(3, bad)
    # the floor modulus, for a head below zero too
    assert [ring_slot(x, 8) for x in (-9, -1, 0, 7, 8, 17)] == [
        7, 7, 0, 7, 0, 1,
    ]
    old = np.arange(10, 15)  # a ring of capacity 5, the old layout
    assert ring_window(old, -2, 2).tolist() == [13, 14, 10, 11]
    new = relay_ring(old, [-2, 2, 0, 0, 0, 0, 0, 0], 8)
    assert new.tolist() == [10, 11, -1, -1, -1, -1, 13, 14]
    assert ring_window(new, -2, 2).tolist() == [13, 14, 10, 11]
    assert relay_ring(new, [-2, 2], 8) is new
    stacked = relay_ring(
        np.stack([old, old]), np.array([[4, 7, 0], [0, 0, 0]]), 8
    )
    assert stacked.shape == (2, 8)
    assert ring_window(stacked[0], 4, 7).tolist() == [14, 10, 11]
    assert (stacked[1] == NO_TASK).all()


# ------------------------------------- a window anywhere in the ring runs


def _shifted(mk, builder, start):
    """``builder``'s fresh state with its ready window moved to begin at
    the all-time position ``start``: what a steal side that advanced the
    head, or a spill that walked it below zero, leaves behind."""
    tasks, succ, ring, counts = builder.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    n = int(counts[C_TAIL])
    moved = np.full(mk.ring_len, NO_TASK, np.int32)
    moved[ring_slot(start + np.arange(n), mk.ring_len)] = ring[:n]
    counts = counts.copy()
    counts[C_HEAD], counts[C_TAIL] = start, start + n
    return {
        "tasks": tasks, "succ": succ, "ready": moved, "counts": counts,
        "ivalues": np.zeros(mk.num_values, np.int32), "data": {},
    }


@pytest.mark.parametrize("capacity", CAPS)
def test_a_ring_wraps_right_wherever_its_window_lies(capacity):
    """One build a capacity, four things: (a) a lane's FIFO cursors run
    past ``capacity``, past ``ring_len`` and round again while the table
    recycles; (b) a ready window that straddles the ring's end, that lies
    past ``capacity`` (words the old layout did not have), that starts
    below zero, and that lies several laps on, each with successors
    pushed behind it; (c) a table of ``capacity`` live rows runs, and the
    one spawn more raises the overflow it always raised; (d) more rows
    than ``capacity`` are refused by the builder, as before."""
    mk = _mk(capacity, fifo=True)
    ring = mk.ring_len
    assert ring == ring_len(capacity) >= capacity

    # (a) 2 ring_len + 3 LEAFs through the lane, a live set of four rows
    n = 2 * ring + 3
    b = TaskGraphBuilder()
    b.add(STEP, args=[n])
    iv, _, info = mk.run(b)
    assert int(iv[0]) == n * (n + 1) // 2
    assert info["executed"] == 2 * n and info["pending"] == 0
    assert info["tiers"]["batch_tasks"] == n
    assert info["tiers"]["scalar_tasks"] == n

    # (b) chains of two: completing a LEAF pushes its successor behind
    # the window, wherever the window lies
    pairs = min(capacity // 2, 24)

    def chains():
        g = TaskGraphBuilder()
        for i in range(pairs):
            head = g.add(LEAF, args=[1 + i])
            g.add(LEAF, args=[100 * (1 + i)], deps=[head])
        return g

    want = 101 * pairs * (pairs + 1) // 2
    for start in (ring - 1 - pairs // 2, capacity - 1, -(pairs // 2) - 1,
                  5 * ring - 1):
        iv, _, info = mk.resume(_shifted(mk, chains(), start))
        assert int(iv[0]) == want, start
        assert info["executed"] == 2 * pairs and info["pending"] == 0

    # (c) a full table: ``capacity`` live rows, all of them on the ring
    full = TaskGraphBuilder()
    for i in range(capacity):
        full.add(LEAF, args=[1])
    iv, _, info = mk.run(full)
    assert int(iv[0]) == capacity and info["executed"] == capacity
    over = TaskGraphBuilder()
    for i in range(capacity - 1):
        over.add(LEAF, args=[1])
    over.add(STEP, args=[1])  # pops first, spawns into a full table
    with pytest.raises(RuntimeError, match="megakernel overflow.*rows"):
        mk.run(over)

    # (d)
    full.add(LEAF, args=[1])
    with pytest.raises(ValueError, match="exceed capacity"):
        mk.run(full)


@pytest.mark.parametrize("capacity", CAPS)
def test_a_spilled_lane_walks_the_head_below_zero_and_resumes(capacity):
    """A quiesce that finds the whole graph in a lane spills it to the
    ring's cold end: C_HEAD goes negative, the window lies at the top of
    a ring that is ``ring_len`` long, and the resumed run finishes it."""
    mk = _mk(capacity, fifo=False, checkpoint=True)
    b = TaskGraphBuilder()
    for i in range(capacity):
        b.add(LEAF, args=[1 + i])
    _, _, q = mk.run(b, quiesce=capacity // 2)
    assert q["quiesced"] and q["pending"] > 0
    st = q["state"]
    head, tail = int(st["counts"][C_HEAD]), int(st["counts"][C_TAIL])
    assert head < 0 and tail - head == q["pending"]
    assert st["ready"].shape == (mk.ring_len,)
    rows = ring_window(st["ready"], head, tail)
    assert len(set(rows.tolist())) == q["pending"]
    assert q["tiers"]["spilled"] == q["pending"]
    # where they lie: the top of the ring_len ring, not of the table
    assert (np.flatnonzero(st["ready"] != NO_TASK).max() == mk.ring_len - 1)
    iv, _, done = mk.resume(st)
    assert done["pending"] == 0 and done["executed"] == capacity
    assert int(iv[0]) == capacity * (capacity + 1) // 2
