"""The unified resident kernel (device/resident.py): general migration of
dependency-bearing tasks, steal + PGAS + AM + injection in ONE kernel,
device-side remote atomics and locks.

Reference parity targets: the thief taking ANY task - dependency edges
included - from a victim's deque (/root/reference/src/hclib-deque.c:75-106),
one scheduler serving every module's locales
(/root/reference/inc/hclib-module.h:79-97), and the SHMEM AMO + lock layer
(/root/reference/modules/openshmem/src/hclib_openshmem.cpp:572-600,124-134).
"""

import jax
import numpy as np
import pytest

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel, VBLOCK
from hclib_tpu.device.resident import ResidentKernel, lock_block_slots
from hclib_tpu.device.workloads import FIB, SUM, make_fib_megakernel
from hclib_tpu.models.fib import fib_seq, task_count
from hclib_tpu.parallel.mesh import cpu_mesh, make_mesh

BUMP = 0


def _exec_count(n):
    """Descriptors the kernel executes for fib(n): every FIB node plus one
    SUM continuation per internal node (task_count counts FIB calls only)."""
    t = task_count(n)
    return t + (t - 1) // 2


def _bump_kernel(ctx):
    ctx.set_value(0, ctx.value(0) + ctx.arg(0))


def _bump_mk(capacity=256, num_values=512):
    return Megakernel(
        kernels=[("bump", _bump_kernel)],
        capacity=capacity,
        num_values=num_values,
        succ_capacity=8,
        interpret=True,
    )


def _fib_mk(capacity=512):
    # Migration reserves one result slot per row at the top of the value
    # buffer: size num_values = row blocks + host slots + result slots.
    return make_fib_megakernel(
        capacity=capacity,
        interpret=True,
        num_values=VBLOCK * capacity + 16 + capacity,
    )


# ---------------------------------------------------------------- migration


def test_skewed_fib_rebalances_across_devices():
    """THE round-3 gap: a skewed dynamic fib graph - every task carrying
    successor links - rebalances over the in-kernel steal. Device 0 holds
    fib(9) (109 FIB tasks); >= 4 of 8 devices must execute work; the
    value and net executed count must be exact. (fib(13)/753 tasks passes
    identically - interpret-mode wall time scales with task count, so the
    suite runs the smallest tree that still spreads over half the mesh:
    fib(9), 109 FIB tasks.)"""
    ndev, n = 8, 9
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
    assert info["executed"] == _exec_count(n)
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 4, per_dev


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
    assert info["executed"] == _exec_count(n)
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
    assert info["executed"] == _exec_count(n)


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
    assert info["executed"] == _exec_count(n)


def test_homed_fib_migrates_on_3d_mesh():
    """Dependency-bearing migration across a 3D torus: the home-link
    protocol's completion AMs route over all three axes of a 2x2x2 mesh
    (the earlier 3D test moves only link-free rows)."""
    n = 6
    mk = _fib_mk(capacity=64)
    rk = ResidentKernel(
        mk, make_mesh((2, 2, 2), ("x", "y", "z"), jax.devices("cpu")[:8]),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=8, am_window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(8)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=4)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    assert info["executed"] == _exec_count(n)
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 2, per_dev


def test_successor_free_rows_still_migrate_whole():
    """Link-free tasks keep the cheap whole-row path (no proxy, no AM):
    the classic skewed-bump workload is exact and spreads."""
    ndev, ntasks = 4, 28
    rk = ResidentKernel(
        _bump_mk(capacity=128), cpu_mesh(ndev, axis_name="q"),
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


# ------------------------------------------------------------- composition


ROWS, COLS = 8, 128
PUT = 1
CONSUME = 2


def _compose_mk(ndev, capacity=256):
    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    def put(ctx):
        ctx.pgas.put(ctx.arg(0), 0, ctx.arg(1), ctx.arg(2))

    def consume(ctx):
        ctx.set_value(ctx.arg(0), ctx.pgas.count(0))

    return Megakernel(
        kernels=[("bump", bump), ("put", put), ("consume", consume)],
        data_specs={"heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)},
        capacity=capacity,
        num_values=512,
        succ_capacity=8,
        interpret=True,
    )


def _heap(ndev):
    h = np.zeros((ndev, ROWS, COLS), np.int32)
    for d in range(ndev):
        for r in range(ROWS):
            h[d, r, :] = 1000 * d + r
    return h


def test_steal_pgas_and_injection_coexist():
    """ONE kernel per device does all three at once (round-3 directive #2):
    a skewed bump load rebalances by stealing, device 0 puts a row into
    device 1 whose parked consumer wakes on arrival, and injected stream
    rows land mid-run on several devices."""
    ndev, ntasks = 4, 24
    mk = _compose_mk(ndev, capacity=128)
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns=[BUMP],
        channels={"c0": ("heap", 1)},
        inject=True,
        window=4,
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for i in range(ntasks):
        builders[0].add(BUMP, args=[i + 1])
    builders[0].add(PUT, args=[1, 3, 2])  # my row 2 -> dev1 row 3
    t = builders[1].add(CONSUME, args=[1])
    waits = [[], [(0, 1, t)], [], []]
    inject_rows = [[(BUMP, [1000])], [], [(BUMP, [2000])], [(BUMP, [3000])]]
    iv, data, info = rk.run(
        builders, data={"heap": _heap(ndev)}, waits=waits,
        inject_rows=inject_rows, quantum=4,
    )
    assert info["pending"] == 0
    base = ntasks * (ntasks + 1) // 2
    assert int(iv[:, 0].sum()) == base + 1000 + 2000 + 3000
    assert (np.asarray(data["heap"])[1, 3] == 2).all()  # the put landed
    assert iv[1, 1] == 1  # parked consumer saw the arrival
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


def test_pgas_on_2d_mesh():
    """Channels work on a 2D mesh (round-3 missing #4): puts cross both
    axes of a 2x2 torus; consumers wake on arrival."""
    cpus = jax.devices("cpu")
    mesh = make_mesh((2, 2), ("r", "c"), cpus[:4])
    mk = _compose_mk(4)
    rk = ResidentKernel(
        mk, mesh, channels={"c0": ("heap", 1)}, steal=False,
    )
    builders = [TaskGraphBuilder() for _ in range(4)]
    waits = [[] for _ in range(4)]
    # device 0 puts to 1 (same row), 2 (other row), 3 (diagonal)
    for d in (1, 2, 3):
        builders[0].add(PUT, args=[d, d, d])
        t = builders[d].add(CONSUME, args=[1])
        waits[d].append((0, 1, t))
    iv, data, info = rk.run(
        builders, data={"heap": _heap(4)}, waits=waits, quantum=8,
    )
    heap = np.asarray(data["heap"])
    for d in (1, 2, 3):
        assert (heap[d, d] == d).all(), heap[d, d][:4]
        assert iv[d, 1] == 1
    assert info["pending"] == 0


def test_steal_and_pgas_on_3d_mesh():
    """3D torus (v4/v5p slice shape): the hypercube hops decompose over
    all three axes of a 2x2x2 mesh - a skewed bump load spreads by
    stealing while puts cross each axis (neighbor along z, y, x and the
    full diagonal) and wake parked consumers."""
    cpus = jax.devices("cpu")
    mesh = make_mesh((2, 2, 2), ("x", "y", "z"), cpus[:8])
    mk = _compose_mk(8, capacity=128)
    rk = ResidentKernel(
        mk, mesh, migratable_fns=[BUMP], channels={"c0": ("heap", 1)},
        window=4,
    )
    ntasks = 12
    builders = [TaskGraphBuilder() for _ in range(8)]
    for i in range(ntasks):
        builders[0].add(BUMP, args=[i + 1])
    waits = [[] for _ in range(8)]
    # puts from device 0 along each axis and across all three at once
    for d in (1, 2, 4, 7):
        builders[0].add(PUT, args=[d, d % ROWS, d % ROWS])
        t = builders[d].add(CONSUME, args=[1])
        waits[d].append((0, 1, t))
    iv, data, info = rk.run(
        builders, data={"heap": _heap(8)}, waits=waits, quantum=4,
    )
    assert info["pending"] == 0
    heap = np.asarray(data["heap"])
    for d in (1, 2, 4, 7):
        assert (heap[d, d % ROWS] == d % ROWS).all(), heap[d, d % ROWS][:4]
        assert iv[d, 1] == 1  # parked consumer saw the arrival
    base = ntasks * (ntasks + 1) // 2
    assert int(iv[:, 0].sum()) == base
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


# --------------------------------------------------------- atomics + locks


FADD_ALL = 0
CSECT = 1
LOCKER = 2


def test_remote_atomics_and_lock():
    """One kernel, one compile, four protocols at once (interpret-mode
    compiles dominate suite time, so the AMO family shares a table):

    - fire-and-forget fadd: every device adds its rank+1 into device 0's
      slot 5, twice - owner-computes atomicity must sum exactly;
    - fadd_get: device 1 parks a continuation until the owner's reply
      deposits the OLD value of slot 6 (exact fetch-add semantics);
    - compare-swap: device 2 cswaps device 0's slot 12 (55 -> 77) and its
      parked continuation must observe old == 55 (the reply path routes
      device/row/slot words exactly - a dropped src word here once
      shifted the whole reply);
    - distributed lock: every device bumps a counter pair on device 0
      under the lock FIFO; the queue must drain and the lock must end
      released."""
    ndev, per = 4, 2
    qcap = ndev
    LBASE = 16
    X, Y = 8, 9
    ASKER, CONSUME_R, LOCKER_FN, CSECT_FN, SWAPPER = 1, 2, 3, 4, 5

    def fadd_all(ctx):
        for _ in range(per):
            ctx.pgas.fadd(0, 5, 1 + ctx.pgas.me)

    def asker(ctx):
        row = ctx.spawn(CONSUME_R, args=[3], dep_count=1)
        ctx.pgas.fadd_get(0, 6, 10, row, 3)

    def consume_r(ctx):
        ctx.set_value(4, ctx.value(ctx.arg(0)))

    def swapper(ctx):
        row = ctx.spawn(CONSUME_R, args=[3], dep_count=1)
        ctx.pgas.cswap(0, 12, 55, 77, row, 3)

    def locker(ctx):
        row = ctx.spawn(CSECT_FN, dep_count=1)
        ctx.pgas.lock(0, LBASE, row, qcap)

    def csect(ctx):
        ctx.pgas.fadd(0, X, 1)
        ctx.pgas.fadd(0, Y, 1)
        ctx.pgas.unlock(0, LBASE, qcap)

    mk = Megakernel(
        kernels=[("fadd_all", fadd_all), ("asker", asker),
                 ("consume_r", consume_r), ("locker", locker),
                 ("csect", csect), ("swapper", swapper)],
        capacity=64, num_values=256, succ_capacity=8, interpret=True,
    )
    rk = ResidentKernel(mk, cpu_mesh(ndev, axis_name="q"), steal=False)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].add(FADD_ALL)
        builders[d].add(LOCKER_FN)
        builders[d].reserve_values(LBASE + lock_block_slots(qcap))
    builders[1].add(ASKER)
    builders[2].add(SWAPPER)
    iv0 = np.zeros((ndev, 256), np.int32)
    iv0[0, 6] = 100
    iv0[0, 12] = 55
    iv, _, info = rk.run(builders, ivalues=iv0, quantum=8)
    assert iv[0, 5] == per * sum(1 + d for d in range(ndev))
    assert iv[0, 6] == 110  # owner applied the fetch-add
    assert iv[1, 4] == 100  # asker observed the OLD value
    assert iv[0, 12] == 77  # cswap matched and swapped
    assert iv[2, 4] == 55  # swapper observed the OLD value
    assert iv[0, X] == ndev and iv[0, Y] == ndev, iv[0, :12]
    assert iv[0, LBASE] == 0  # lock released
    assert iv[0, LBASE + 1] == 0  # queue drained
    assert info["pending"] == 0


# ------------------------------------------------------------ real hardware


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_resident_compiles_and_runs_on_tpu():
    """The FULL five-way composition on the real chip (1-device
    self-loop): work stealing enabled, one-sided put + wait machinery,
    AMs (fetch-add + lock acquire/release), and an injected task stream,
    all in one kernel compiled through Mosaic. (The interpret-mode dry
    run exercises the same class in four-way compositions; stacking every
    feature's SMEM scratch in one interpreted kernel wedges the Mosaic
    interpreter on 1-vCPU hosts, so hardware carries the five-way proof.)
    """
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("q",))
    qcap = 2
    LBASE = 16
    BUMPF = 2

    def driver(ctx):
        ctx.pgas.fadd(0, 5, 7)
        row = ctx.spawn(1, dep_count=1)
        ctx.pgas.lock(0, LBASE, row, qcap)
        ctx.pgas.put(0, 0, 3, 2)  # self-put row 2 -> row 3

    def csect(ctx):
        ctx.pgas.fadd(0, 5, 30)
        ctx.pgas.unlock(0, LBASE, qcap)

    def bump(ctx):
        ctx.set_value(6, ctx.value(6) + ctx.arg(0))

    mk = Megakernel(
        kernels=[("driver", driver), ("csect", csect), ("bump", bump)],
        data_specs={"heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)},
        capacity=64, num_values=256, succ_capacity=8, interpret=False,
    )
    rk = ResidentKernel(
        mk, mesh, channels={"c0": ("heap", 1)}, steal=True,
        migratable_fns=[0], inject=True,
    )
    b = TaskGraphBuilder()
    b.add(0)
    b.reserve_values(LBASE + lock_block_slots(qcap))
    iv, data, info = rk.run(
        [b], data={"heap": _heap(1)}, quantum=8,
        inject_rows=[[(BUMPF, [41]), (BUMPF, [1])]],
    )
    assert iv[0, 5] == 37
    assert iv[0, 6] == 42  # injected stream rows ran
    assert (np.asarray(data["heap"])[0, 3] == 2).all()
    assert info["pending"] == 0


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_resident_volume_stress_on_tpu():
    """Protocol VOLUME on the real chip (round-3 weak item: the resident
    protocols had only been exercised on tiny graphs). One kernel run,
    compiled through Mosaic, simultaneously:

    - runs a 1,828-descriptor dynamic fib(14) graph through the scalar
      scheduler (rows + value blocks recycling far past capacity);
    - pushes 64 fire-and-forget fetch-adds through the outbox pacer
      (16 senders x 4 AMs each; the self-loop inbox window drains only
      a handful per round, so the outbox carry-over path runs for many
      consecutive rounds - emitting faster than the credit-paced drain
      exhausts the outbox, which the overflow bitmask names exactly);
    - contends one lock FIFO from 8 waiters whose critical sections
      compare-swap an occupancy flag 0->1 on entry and reset it on exit:
      every observed old value must be 0, so overlapping grants are
      DETECTED, not just summed away (cswap replies are atomic either
      way - the observation, not the counter, is the tripwire);
    - drains a 64-row injected task stream;
    - parks a consumer on a channel until 4 self-puts land.

    Every effect is asserted exactly."""
    from jax.sharding import Mesh

    from hclib_tpu.device import workloads as _wl

    mesh = Mesh(np.array(jax.devices()[:1]), ("q",))
    qcap = 8
    LBASE = 32
    FADD_SLOT, X, Y, OCC, TEAR = 2, 4, 5, 10, 11
    RS0 = 20  # per-locker cswap reply slots [RS0, RS0 + nlockers)
    (FIBF, SUMF, BUMPF, FADDER, LOCKER_F, CSECT_F, PUTF, CONSUMEF,
     OBS_F) = range(9)
    nfadders, per_fadder = 16, 4
    nlockers = 8
    ninject = 64
    nputs = 4

    def fadder(ctx):
        for _ in range(per_fadder):
            ctx.pgas.fadd(0, FADD_SLOT, ctx.arg(0))

    def locker(ctx):
        row = ctx.spawn(CSECT_F, args=[ctx.arg(0)], dep_count=1)
        ctx.pgas.lock(0, LBASE, row, qcap)

    def csect(ctx):
        # Occupancy tripwire: cswap(OCC: 0 -> 1). The observer parks
        # until the reply deposits the OLD value into this locker's own
        # reply slot; under mutual exclusion every old is 0. The AMs are
        # FIFO per target, so OCC is back to 0 before unlock grants the
        # next waiter.
        s = ctx.arg(0)
        obs = ctx.spawn(OBS_F, args=[s], dep_count=1)
        ctx.pgas.cswap(0, OCC, 0, 1, obs, s)
        ctx.pgas.fadd(0, X, 1)
        ctx.pgas.fadd(0, Y, 1)
        ctx.pgas.fadd(0, OCC, -1)
        ctx.pgas.unlock(0, LBASE, qcap)

    def observe(ctx):
        # Accumulate the observed old occupancy; any overlap makes TEAR
        # nonzero.
        ctx.pgas.fadd(0, TEAR, ctx.value(ctx.arg(0)))

    def putk(ctx):
        ctx.pgas.put(0, 0, ctx.arg(0), 0)  # my row 0 -> row arg0

    def consume(ctx):
        ctx.set_value(6, ctx.pgas.count(0))

    def bump(ctx):
        ctx.set_value(7, ctx.value(7) + ctx.arg(0))

    # SMEM pads scalar words to ~32 B, so the table budget is tight:
    # capacity 512 x 16 words x 32 B = 256 KB per window (in + out =
    # 512 KB of the chip's ~1 MB); rows and value blocks recycle, so
    # the 1.8k-task graph runs through the 512-row table regardless.
    cap = 512
    mk = Megakernel(
        kernels=[("fib", _wl._fib_kernel), ("sum", _wl._sum_kernel),
                 ("bump", bump), ("fadder", fadder), ("locker", locker),
                 ("csect", csect), ("put", putk), ("consume", consume),
                 ("observe", observe)],
        data_specs={"heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)},
        capacity=cap,
        num_values=VBLOCK * cap + 64 + cap,
        succ_capacity=64,
        interpret=False,
        uses_row_values=True,
    )
    rk = ResidentKernel(
        mk, mesh,
        migratable_fns={FIBF: (), SUMF: (0, 1)},
        channels={"c0": ("heap", 1)},
        inject=True,
        window=8, am_window=8, outbox=128,
    )
    b = TaskGraphBuilder()
    b.add(FIBF, args=[14], out=3)
    for i in range(nfadders):
        b.add(FADDER, args=[i + 1])
    for i in range(nlockers):
        b.add(LOCKER_F, args=[RS0 + i])
    for r in range(nputs):
        b.add(PUTF, args=[2 + r])
    t = b.add(CONSUMEF)
    b.reserve_values(LBASE + lock_block_slots(qcap))
    inject_rows = [[(BUMPF, [j + 1]) for j in range(ninject)]]
    iv, data, info = rk.run(
        [b], data={"heap": _heap(1)}, waits=[[(0, nputs, t)]],
        inject_rows=inject_rows, quantum=4,
    )
    assert info["pending"] == 0
    assert int(iv[0, 3]) == fib_seq(14)
    assert int(iv[0, FADD_SLOT]) == per_fadder * sum(
        i + 1 for i in range(nfadders)
    )
    assert int(iv[0, X]) == nlockers and int(iv[0, Y]) == nlockers
    assert int(iv[0, TEAR]) == 0  # no critical section saw another inside
    assert int(iv[0, OCC]) == 0  # occupancy balanced
    assert int(iv[0, LBASE]) == 0 and int(iv[0, LBASE + 1]) == 0
    assert int(iv[0, 7]) == ninject * (ninject + 1) // 2
    assert int(iv[0, 6]) == nputs  # consumer saw all four arrivals
    heap = np.asarray(data["heap"])
    for r in range(nputs):
        assert (heap[0, 2 + r] == 0).all()  # row 0 (value 0) landed
    assert info["executed"] == (
        _exec_count(14) + nfadders + 3 * nlockers + nputs + 1 + ninject
    )


# ------------------------------------- batched dispatch on the mesh (ISSUE 7)


def _batched_fib_rk(ndev, batch_width=0, capacity=160, trace=None,
                    window=8):
    mk = make_fib_megakernel(
        capacity=capacity,
        interpret=True,
        num_values=VBLOCK * capacity + 16 + capacity,
        batch_width=batch_width or None,
        trace=trace,
    )
    rk = ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"),
        migratable_fns={FIB: (), SUM: (0, 1)},
        window=window, am_window=8,
    )
    return rk, mk


def test_mesh_batch_fib_matches_scalar_resident():
    """ISSUE 7 acceptance (resident arm): the batch-routed skewed fib
    mesh - homed migration, remote completions, the full round loop -
    computes the exact scalar-mesh result, every executed total matches,
    and info['tiers'] reports per-device occupancy with nonzero batch
    rounds where work ran."""
    ndev, n = 4, 9
    rk_s, _ = _batched_fib_rk(ndev)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv_s, _, info_s = rk_s.run(builders, quantum=16)
    assert "tiers" not in info_s

    rk_b, _ = _batched_fib_rk(ndev, batch_width=4)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv_b, _, info_b = rk_b.run(builders, quantum=16)
    assert info_b["pending"] == 0
    assert int(iv_b[:, 0].sum()) == int(iv_s[:, 0].sum()) == fib_seq(n)
    assert info_b["executed"] == info_s["executed"] == _exec_count(n)
    tiers = info_b["tiers"]
    assert len(tiers) == ndev
    batched = sum(t["batch_tasks"] for t in tiers)
    scalar = sum(t["scalar_tasks"] for t in tiers)
    assert batched + scalar == info_b["executed"]
    assert tiers[0]["batch_rounds"] > 0  # the seed device fired batches
    per_dev = info_b["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 2, per_dev


def test_mesh_batch_trace_reconciles_with_tstats():
    """Mesh TR_FIRE_BATCH records (the ROADMAP lane-firing-policy
    detector, now live on the mesh): per device, the flight-recorder
    batch records reconcile EXACTLY with that device's tstats counters -
    rounds, dispatched tasks, and occupancy all read the same from
    either source."""
    from hclib_tpu.device.tracebuf import TR_FIRE_BATCH, records_of

    ndev, n = 2, 8
    rk, mk = _batched_fib_rk(ndev, batch_width=4, trace=512)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(FIB, args=[n], out=0)
    iv, _, info = rk.run(builders, quantum=8)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == fib_seq(n)
    tiers = info["tiers"]
    for d in range(ndev):
        ring = info["trace"]["rings"][d]
        assert ring["dropped"] == 0  # capacity covers the whole run
        recs = records_of(info["trace"], TR_FIRE_BATCH, ring=d)
        assert recs.shape[0] == tiers[d]["batch_rounds"]
        takes = (recs[:, 2] & 0xFFFF).sum() if recs.size else 0
        assert int(takes) == tiers[d]["batch_tasks"]


@pytest.mark.chaos
def test_mesh_batch_checkpoint_reshard_4_to_2():
    """Checkpoint/reshard with lanes ACTIVE: a batch-routed UTS mesh
    quiesces mid-traversal (sched()'s exit spilled every lane entry to
    the ring and drained prefetches before the lockstep cut, so the
    bundle sees only ring rows), reshards 4 -> 2, and the resumed
    smaller batched mesh drains the remainder with totals conserved
    exactly."""
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.runtime.checkpoint import snapshot_resident

    def make_rk(ndev):
        mk = make_uts_megakernel(
            max_depth=6, interpret=True, capacity=256,
            checkpoint=True, batch_width=4,
        )
        # homed=False: UTS rows are link-free, which is what makes the
        # N -> M re-homing legal (reshard refuses linked rows).
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"),
            migratable_fns=[UTS_NODE], window=4, homed=False,
        )

    def builders_of(ndev):
        builders = [TaskGraphBuilder() for _ in range(ndev)]
        for d in range(ndev):
            builders[d].add(UTS_NODE, args=[d + 1, 0])
        return builders

    ndev = 4
    iv_f, _, info_f = make_rk(ndev).run(
        builders_of(ndev), quantum=8, max_rounds=4096
    )
    total = int(np.asarray(iv_f)[:, 0].sum())
    assert info_f["pending"] == 0 and total == info_f["executed"]
    assert sum(t["batch_tasks"] for t in info_f["tiers"]) > 0

    rk = make_rk(ndev)
    iv_q, _, info_q = rk.run(
        builders_of(ndev), quantum=8, max_rounds=4096, quiesce=2,
    )
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    bundle = snapshot_resident(rk, info_q)
    small = bundle.reshard(2)  # refuses any lane-shaped residue
    rk2 = make_rk(2)
    iv_r, _, info_r = rk2.run(
        resume_state=small.state(), quantum=8, max_rounds=1 << 14,
    )
    assert info_r["pending"] == 0
    assert int(np.asarray(iv_r)[:, 0].sum()) == total
    # reshard folds the executed counters, so the resumed total equals
    # the uninterrupted run's.
    assert info_r["executed"] == info_f["executed"]
