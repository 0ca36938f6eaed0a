"""The unified resident kernel composed (device/resident.py): steal +
PGAS + injection in ONE kernel on 1D/2D/3D meshes, device-side remote
atomics and locks, and the TPU-gated compile and volume runs. (Migration
itself is in test_resident.py.)
"""

import jax
import numpy as np
import pytest
from conftest import fib_exec_count

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel, VBLOCK
from hclib_tpu.device.resident import ResidentKernel, lock_block_slots
from hclib_tpu.models.fib import fib_seq
from hclib_tpu.parallel.mesh import cpu_mesh, make_mesh

BUMP = 0


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
        fib_exec_count(14) + nfadders + 3 * nlockers + nputs + 1 + ninject
    )
