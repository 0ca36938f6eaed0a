"""Batch-dispatch (vector) tier: exactness, stealing, megakernel bridge.

The reference has no vector tier (its fib is one heap task per call,
test/fib/fib.c); these tests pin the rebuild-specific contract instead:
exact counts/results for the whole family, overflow reporting, and the
scalar<->vector bridge (a vector task firing scalar successors)."""

import jax
import jax.numpy as jnp
import pytest

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.vector_engine import fib_spec, make_subtree_runner
from hclib_tpu.device.workloads import device_vfib


def fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def tree_tasks(n):
    # Naive recursion-tree node count: N(n) = 1 + N(n-1) + N(n-2).
    if n < 2:
        return 1
    return 1 + tree_tasks(n - 1) + tree_tasks(n - 2)


@pytest.fixture(scope="module")
def runner():
    spec = fib_spec(max_n=14, lanes=(1, 8))
    run = make_subtree_runner(spec, max_steps=100000)
    jitted = jax.jit(lambda n: run((n,), jnp.where(n >= 2, 2, 0)))
    cpu = jax.devices("cpu")[0]

    # Pin via committed inputs, NOT a default_device context held across
    # the yield - that context would leak into every other test in the
    # module (a TPU-gated test then lowers its kernel for CPU and fails).
    def call(n):
        return jitted(jax.device_put(n, cpu))

    return call


@pytest.mark.parametrize("n", [2, 3, 5, 10, 14])
def test_runner_exact(runner, n):
    nodes, accs, over = runner(jnp.int32(n))
    assert int(accs["value"]) == fib(n)
    assert int(nodes) + 1 == tree_tasks(n)  # +1: the seed task itself
    assert not bool(over)


def test_runner_leaf_seed(runner):
    # Seeds with count 0 do no vector work (the megakernel bridge adds
    # root_contrib for them).
    for n in (0, 1):
        nodes, accs, over = runner(jnp.int32(n))
        assert int(nodes) == 0 and int(accs["value"]) == 0


def test_runner_stack_overflow_flag():
    spec = fib_spec(max_n=3, lanes=(1, 8))  # depth 5: too shallow for 12
    run = make_subtree_runner(spec, max_steps=100000)
    with jax.default_device(jax.devices("cpu")[0]):
        _, _, over = jax.jit(lambda: run((12,), jnp.int32(2)))()
    assert bool(over)


def test_device_vfib_interpret():
    v, info = device_vfib(10, lanes=(1, 8), interpret=True)
    assert v == fib(10)
    assert info["executed"] == tree_tasks(10)


def test_vector_task_fires_scalar_successors():
    # A vfib task's completion must run downstream scalar-tier tasks with
    # its reduced output visible in the out slot.
    spec = fib_spec(max_n=12, lanes=(1, 8))

    def double(ctx):
        ctx.set_value(1, ctx.value(0) * 2)

    mk = Megakernel(
        kernels=[("vfib", spec), ("double", double)],
        capacity=16,
        num_values=8,
        succ_capacity=8,
        interpret=True,
    )
    b = TaskGraphBuilder()
    t0 = b.add(0, args=[9], out=0)
    b.add(1, deps=[t0], out=1)
    b.reserve_values(2)
    ivalues, _, info = mk.run(b)
    assert ivalues[0] == fib(9)
    assert ivalues[1] == 2 * fib(9)
    assert info["executed"] == tree_tasks(9) + 1  # +1: the double task
    assert info["pending"] == 0


KNOWN_NQ = {1: 1, 4: 2, 5: 10, 6: 4, 8: 92}


@pytest.mark.parametrize("n", [1, 4, 5, 6])
def test_nqueens_runner_exact(n):
    """The vector tier is a generic engine, not a fib special case: the
    n-queens family (3-word bitboard frames, data-dependent child counts)
    counts exactly (reference workload test/misc/nqueens)."""
    from hclib_tpu.device.vector_engine import nqueens_spec

    spec = nqueens_spec(n, lanes=(1, 8))
    run = make_subtree_runner(spec, max_steps=200000)
    with jax.default_device(jax.devices("cpu")[0]):
        _, accs, over = jax.jit(
            lambda: run(spec.seed((jnp.int32(0),))[0], n)
        )()
    assert int(accs["solutions"]) == KNOWN_NQ[n]
    assert not bool(over)


def test_device_nqueens_interpret():
    from hclib_tpu.device.workloads import device_nqueens

    v, info = device_nqueens(6, lanes=(1, 8), interpret=True)
    assert v == KNOWN_NQ[6]
    # The host model agrees (it runs under the host runtime).
    from hclib_tpu.models import nqueens as nq

    assert nq.run(6)["value"] == v


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_device_nqueens_tpu():
    from hclib_tpu.device.workloads import device_nqueens

    v, info = device_nqueens(10)
    assert v == 724


def test_route_irregular_dag_gets_fast_path():
    """``route=``: a scalar fib kernel's family is routed to the
    batch-dispatch tier by NAME (VERDICT r4 #3) - an irregular DAG mixing
    scalar tasks and a routed recursive family runs the family's whole
    subtree on the VPU lanes (executed counts the expanded tree, not one
    descriptor) while dependencies and out slots behave exactly as on the
    scalar tier."""
    from hclib_tpu.device.workloads import _fib_kernel, _sum_kernel

    def seedv(ctx):
        ctx.set_value(0, 7)

    def consume(ctx):
        ctx.set_value(2, ctx.value(1) + ctx.value(0))

    mk = Megakernel(
        kernels=[
            ("seed", seedv),
            ("fib", _fib_kernel),   # scalar definition of the family
            ("sum", _sum_kernel),
            ("consume", consume),
        ],
        route={"fib": fib_spec(max_n=14, lanes=(1, 8))},
        capacity=32,
        num_values=16,
        succ_capacity=16,
        interpret=True,
    )
    b = TaskGraphBuilder()
    t0 = b.add(0)                        # scalar: writes value 0
    t1 = b.add(1, args=[12], deps=[t0], out=1)  # routed family subtree
    b.add(3, deps=[t1])                  # scalar: reads family's out
    b.reserve_values(3)
    ivalues, _, info = mk.run(b)
    assert ivalues[1] == fib(12)
    assert ivalues[2] == fib(12) + 7
    # Proof the fast path ran: executed counts the whole expanded
    # recursion tree (465 nodes for fib(12)), not 3 descriptors - and no
    # SUM continuation descriptors were ever spawned.
    assert info["executed"] == tree_tasks(12) + 2
    assert info["allocated"] == 3
    assert info["pending"] == 0


def test_route_unknown_name_rejected():
    with pytest.raises(ValueError, match="route names unknown"):
        Megakernel(
            kernels=[("a", lambda ctx: None)],
            route={"b": fib_spec(max_n=4, lanes=(1, 8))},
            interpret=True,
        )
