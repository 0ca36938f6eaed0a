"""The unified resident kernel (device/resident.py): general migration of
dependency-bearing tasks, steal + PGAS + AM + injection in ONE kernel,
device-side remote atomics and locks.

Reference parity targets: the thief taking ANY task - dependency edges
included - from a victim's deque (/root/reference/src/hclib-deque.c:75-106),
one scheduler serving every module's locales
(/root/reference/inc/hclib-module.h:79-97), and the SHMEM AMO + lock layer
(/root/reference/modules/openshmem/src/hclib_openshmem.cpp:572-600,124-134).
"""

import math

import jax
import pytest
from conftest import bump_mk, fib_exec_count

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import VBLOCK
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.device.workloads import FIB, SUM, make_fib_megakernel
from hclib_tpu.models.fib import fib_seq
from hclib_tpu.parallel.mesh import cpu_mesh, make_mesh

BUMP = 0


def _fib_mk(capacity=512):
    # Migration reserves one result slot per row at the top of the value
    # buffer: size num_values = row blocks + host slots + result slots.
    return make_fib_megakernel(
        capacity=capacity,
        interpret=True,
        num_values=VBLOCK * capacity + 16 + capacity,
    )


# ---------------------------------------------------------------- migration


@pytest.mark.parametrize("ndev", [pytest.param(8, marks=pytest.mark.slow), 4])
def test_skewed_fib_rebalances_across_devices(ndev):
    """THE round-3 gap: a skewed dynamic fib graph - every task carrying
    successor links - rebalances over the in-kernel steal. Device 0 holds
    fib(9) (109 FIB tasks); at least half the devices must execute work;
    the value and net executed count must be exact. (fib(13)/753 tasks
    passes identically - interpret-mode wall time scales with task count,
    so the suite runs the smallest tree that still spreads over half the
    mesh: fib(9), 109 FIB tasks. The 8-device case, whose point is its
    size, is `slow`: 121 s beside five busy workers, ISSUE 27; the
    4-device case is its twin.)"""
    n = 9
    mk = _fib_mk(capacity=160)
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=8, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=16)
    assert info["pending"] == 0
    # exactly one device's slot 0 holds the result (root may migrate whole)
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= ndev // 2, per_dev


def test_homed_chain_two_devices_exact():
    """2-device fib: stolen FIB tasks leave proxies whose successors fire
    only when the remote-completion AM lands; totals and the value must be
    exact even with migration forced aggressively (window > backlog)."""
    ndev, n = 2, 8
    mk = _fib_mk(capacity=96)
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=16, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=4)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)
    assert info["per_device_counts"][1, 5] > 0  # work actually migrated


def test_migration_race_free_under_detector():
    """Mosaic interpret race detection over the full home-link protocol
    (steal + remote completion + value-arg rehydration)."""
    from jax.experimental.pallas import tpu as pltpu

    ndev, n = 2, 6
    mk = _fib_mk(capacity=64)
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=8, am_window=8,
    )
    orig = rk._build

    def build_with_detector(*build_args):
        import unittest.mock as m

        real = pltpu.InterpretParams
        with m.patch.object(
            pltpu, "InterpretParams",
            # Ignore incoming kwargs: the suite's fast-interpret mode
            # (eager DMA, unchecked OOB) must not leak into race
            # detection, which needs the async on_wait DMA model.
            lambda **kw: real(detect_races=True),
        ):
            return orig(*build_args)

    rk._build = build_with_detector
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=8)
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)


def test_proxy_cap_throttles_migration_but_stays_exact():
    """The outstanding-proxy budget (migrate-once hardening): with
    proxy_cap=1 at most one dep-bearing subtree may be outstanding per
    device at a time, so exports throttle hard - totals and values must
    still be exact (throttling must never deadlock or drop work; local
    execution continues while the budget is spent)."""
    ndev, n = 2, 8
    mk = _fib_mk(capacity=96)
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=16, am_window=8, proxy_cap=1,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=4)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)


@pytest.mark.parametrize(
    "dims", [pytest.param((2, 2, 2), marks=pytest.mark.slow), (2, 2)]
)
def test_homed_fib_migrates_on_3d_mesh(dims):
    """Dependency-bearing migration across a 3D torus: the home-link
    protocol's completion AMs route over all three axes of a 2x2x2 mesh
    (the earlier 3D test moves only link-free rows). Eight interpreter
    threads make even fib(5)'s five rounds 75-90 s beside five busy
    workers (ISSUE 27), so the 2x2x2 case is `slow` and the same body on
    a 2x2 torus is its twin."""
    n, ndev = 5, math.prod(dims)
    mk = _fib_mk(capacity=64)
    rk = ResidentKernel(
        mk, make_mesh(dims, ("x", "y", "z")[:len(dims)],
                      jax.devices("cpu")[:ndev]),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=8, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=8)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == fib_exec_count(n)
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 2, per_dev


def test_successor_free_rows_still_migrate_whole():
    """Link-free tasks keep the cheap whole-row path (no proxy, no AM):
    the classic skewed-bump workload is exact and spreads."""
    ndev, ntasks = 4, 28
    rk = ResidentKernel(
        bump_mk(128, 512), cpu_mesh(ndev, axis_name="q"),
        migratable_fns=[BUMP], window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for i in range(ntasks):
        builders[0].add(BUMP, args=[i + 1])
    iv, _, info = rk.run(builders, quantum=8)
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


@pytest.mark.parametrize("dims", [(3,), (3, 2)])
def test_non_power_of_two_mesh_is_refused(dims):
    """The hypercube hop schedule needs every axis a power of two; the
    refusal says so, and says what to do instead."""
    names = ("r", "c")[: len(dims)]
    n = math.prod(dims)
    mesh = make_mesh(dims, names, jax.devices("cpu")[:n])
    with pytest.raises(ValueError, match=r"power-of-two.*next power of two"):
        ResidentKernel(bump_mk(32), mesh, migratable_fns=[0])


def test_scheduler_core_has_exactly_three_embedders():
    """``Megakernel._make_core`` is embedded by hand (scratch, ref order,
    hooks), so every caller repeats every change to the core. Three
    kernels do: the single-chip one, the stream's, the mesh's. A fourth
    caller fails here and has to say why it is not one of those."""
    import pathlib
    import re

    import hclib_tpu

    root = pathlib.Path(hclib_tpu.__file__).parent
    callers = sorted(
        str(p.relative_to(root))
        for p in root.rglob("*.py")
        if re.search(r"\._make_core\(", p.read_text())
    )
    assert callers == [
        "device/inject.py", "device/megakernel.py", "device/resident.py",
    ]
