"""Test configuration.

Force the CPU backend with 8 virtual devices so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs the multichip
path); must be set before jax initializes.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Persistent XLA compilation cache (works for the CPU backend too): the
# suite's one-time engine compiles (~40-120 s each for the UTS engines and
# the big interpret kernels) are disk-cached, so repeated suite runs on
# one machine skip them (measured 41 s -> 17 s for a single UTS test).
# Tutorial subprocesses inherit the env. Cold runs are unaffected. The
# directory is the one every entry point uses (runtime/env.py).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

from hclib_tpu.runtime.env import use_compile_cache  # noqa: E402

use_compile_cache()
# NOTE: do not be tempted to speed the suite up with non-default
# InterpretParams (eager DMA / unchecked OOB reads): both variants
# sporadically deadlock the Mosaic interpreter's io_callback machinery
# on 1-vCPU hosts (see megakernel.interpret_mode).

import faulthandler  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# A device thread of the Mosaic interpreter that waits for a remote DMA
# spins in pure Python (jax's Semaphore.wait with has_tasks=True), so it
# gives the GIL up only when the switch interval forces it to, and every
# kernel load, store and semaphore operation of the other device threads
# (one io_callback each) first waits that interval out. At CPython's
# default of 5 ms a 2-device fib(6) mesh run of 7.5 k callbacks took
# 50.0 s; at 0.1 ms it takes 8.2 s with the same rounds and per-device
# counts (ISSUE 27, measured on this host).
sys.setswitchinterval(1e-4)

# Stack dumps must BYPASS pytest's stderr capture (captured output dies
# with the os._exit the watchdog fires), so they go to an on-disk log
# next to this file; the handle stays open for the whole session.
# Appended to: the worker xdist starts in a dead one's place would
# otherwise empty the log of the stacks it was started over.
_WEDGE_LOG = open(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 ".wedge_traceback.log"),
    "a",
)


# The one time limit a test has (seconds), and what a test gives a child
# process it waits for (tutorial lessons, apps): less, so that the child
# is reaped by its test and not orphaned by the watchdog's exit.
WEDGE_SECONDS = 240
CHILD_SECONDS = WEDGE_SECONDS - 30


@pytest.fixture(autouse=True)
def _wedge_watchdog():
    """Hard per-test ceiling: WEDGE_SECONDS, four times what the slowest
    test should take.

    Measured by ISSUE 27 with the driver's command (six workers on 8
    cores): under the 900 s limit this replaces the slowest tests took
    328 s to over 900 s, and a worker killed by the limit under `--dist
    loadfile` hangs the session until the outer clock cuts it; after the
    cuts the slowest tests take about a minute (table in CHANGES.md).

    The Mosaic interpreter's io_callback machinery can SPORADICALLY wedge
    on 1-vCPU hosts even with the strict default InterpretParams (device
    threads park in buffer allocation; observed roughly once per hundreds
    of multi-device kernel runs). pytest-timeout isn't available in this
    image, and a thread-based timeout can't interrupt parked threads -
    faulthandler's timer CAN: it dumps every thread's stack (to
    tests/.wedge_traceback.log, see above) and exits, so a wedged run
    fails loudly with evidence instead of hanging forever."""
    faulthandler.dump_traceback_later(
        WEDGE_SECONDS, exit=True, file=_WEDGE_LOG
    )
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _clean_modules():
    """Each test sees the module registries as it found them."""
    from hclib_tpu.runtime import module

    saved_modules = list(module._modules)
    saved_mem = {k: dict(v) for k, v in module._mem_fns.items()}
    saved_factories = list(module._per_worker_factories)
    yield
    module._modules[:] = saved_modules
    module._mem_fns.clear()
    module._mem_fns.update(saved_mem)
    module._per_worker_factories[:] = saved_factories


def timeline_mod():
    """Import tools/timeline.py (shared by the observability tests so the
    sys.path dance lives in ONE place)."""
    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    )
    sys.path.insert(0, tools)
    try:
        import timeline
    finally:
        sys.path.remove(tools)
    return timeline


def fib_exec_count(n):
    """Descriptors the scalar tier executes for fib(n): every FIB node
    plus one SUM continuation per internal node (``task_count`` counts
    FIB calls only). Shared by the resident-mesh test files."""
    from hclib_tpu.models.fib import task_count

    t = task_count(n)
    return t + (t - 1) // 2


class Tick:
    """A ledger clock that advances a step a reading: two futures
    finished from one reading would share a ``t_done``, and ``t_done``
    orders futures as they were resolved."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


BUMP = 0  # bump_kernel's id in bump_mk's one-entry kernel table


def bump_kernel(ctx):
    """The kernel most runner tests share: add the argument to value slot
    0, so a run's total is the sum of what was submitted (per device; the
    host sums across devices)."""
    ctx.set_value(0, ctx.value(0) + ctx.arg(0))


def bump_mk(capacity=128, num_values=4, **kw):
    """A Megakernel whose one kernel is ``bump_kernel``."""
    from hclib_tpu.device.megakernel import Megakernel

    kw.setdefault("interpret", True)
    return Megakernel(
        kernels=[("bump", bump_kernel)], capacity=capacity,
        num_values=num_values, succ_capacity=8, **kw,
    )


# The builds StreamingMegakernel's entry boundary serves (ISSUE 32).
KINDS = ["plain", "tenants", "egress", "telemetry"]


def front_door(kind, checkpoint=False, **lane):
    """A tiny stream of each build the entry boundary serves: plain
    firehose, tenant lanes, + egress mailbox (depth 4: it parks), +
    telemetry. Returns (stream, table or None)."""
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    mk = bump_mk(checkpoint=checkpoint)
    if kind == "plain":
        return StreamingMegakernel(mk, ring_capacity=64, tenants=False), None
    table = TenantTable(
        [TenantSpec("a", weight=2, **lane), TenantSpec("b", **lane)], 32,
        egress=None if kind == "tenants" else EgressSpec(depth=4),
    )
    sm = StreamingMegakernel(
        mk, ring_capacity=64, tenants=table, telemetry=kind == "telemetry",
    )
    return sm, table


def send(sm, table, n, first=1):
    """n requests bump(first) .. bump(first + n - 1), alternating lanes
    on a tenant stream; returns their futures (egress builds)."""
    futs = []
    for i in range(n):
        if table is None:
            sm.inject(BUMP, args=[first + i])
        else:
            adm = sm.submit("ab"[i % 2], BUMP, args=[first + i])
            assert adm
            futs.append(adm.future)
    return futs


def skewed_builders(ndev, ntasks, dev=0):
    """bump(1) .. bump(ntasks) all on device ``dev``'s queue; the other
    devices start empty."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for i in range(ntasks):
        builders[dev].add(BUMP, args=[i + 1])
    return builders


def seed_builder():
    """One bump(1000): the graph a stream test starts its kernel on."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder

    b = TaskGraphBuilder()
    b.add(BUMP, args=[1000])
    return b


def uts_mesh_rk(ndev, max_depth, fault_plan=None, **mk_kw):
    """A checkpoint-enabled UTS megakernel on an ``ndev``-device resident
    mesh. homed=False: UTS rows are link-free (count-accumulate only), so
    whole-row migration suffices - and it keeps the quiesced state
    proxy-free, which is what makes N -> M re-homing legal (reshard
    refuses linked rows)."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk_kw.setdefault("checkpoint", True)
    mk = make_uts_megakernel(max_depth=max_depth, interpret=True, **mk_kw)
    return ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[UTS_NODE],
        window=4, homed=False, fault_plan=fault_plan,
    )


def uts_mesh_builders(ndev, per_dev=1, depth=0):
    """UTS roots 1 .. ndev * per_dev at ``depth``, ``per_dev`` to each of
    ``ndev`` builders in order (a root at the kernel's max_depth is a
    leaf)."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import UTS_NODE

    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        for r in range(per_dev):
            builders[d].add(UTS_NODE, args=[d * per_dev + r + 1, depth])
    return builders

