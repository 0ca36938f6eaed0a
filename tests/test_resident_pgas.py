"""One-sided device PGAS: ResidentKernel in its PGAS-only configuration
(``steal=False`` plus channels): put / AM / wait-until on data between
resident schedulers, on simulated multi-device meshes (Mosaic
TPU interpret mode emulates the remote DMAs + semaphores) plus a TPU-gated
1-device compile.

Reference parity targets: one-sided put + wait-until on user data
(/root/reference/modules/openshmem/src/hclib_openshmem.cpp:136-920) and
active messages at a chosen PE
(/root/reference/modules/openshmem-am/src/hclib_openshmem-am.cpp:64-123).
"""

import jax
import numpy as np
import pytest

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.parallel.mesh import cpu_mesh

ROWS = 16
COLS = 128

# kernel ids
PUT = 0
CONSUME = 1
BUMP = 2
SERVE = 3
NOP = 4


def _mk(interpret=True, ndev=8, capacity=256, batch_width=0):
    """Kernel table used by every test in this file.

    PUT: put my heap row arg2 to device arg0's row arg1 on channel arg3.
    CONSUME: record the channel-0 arrival count into value slot arg0.
    BUMP: ivalues[arg0] += arg1 (the classic AM side effect).
    SERVE: the 'get' responder - put my row arg1 back to requester arg0's
           row arg2 on channel arg3 (reply channel).
    """

    def put(ctx):
        def b(c):
            def go():
                ctx.pgas.put(ctx.arg(0), c, ctx.arg(1), ctx.arg(2))

            return go

        # channel id must be static: branch on the arg
        from jax.experimental import pallas as pl

        for c in range(ctx.pgas.nchan):
            @pl.when(ctx.arg(3) == c)
            def _(go=b(c)):
                go()

    def consume(ctx):
        ctx.set_value(ctx.arg(0), ctx.pgas.count(0))

    def bump(ctx):
        ctx.set_value(ctx.arg(0), ctx.value(ctx.arg(0)) + ctx.arg(1))

    def serve(ctx):
        ctx.pgas.put(ctx.arg(0), 1, ctx.arg(2), ctx.arg(1))

    def nop(ctx):
        pass

    # batch_width > 0 routes BUMP (the AM payload kind) through the
    # batched same-kind tier - slot_ctx re-applies the pgas ctx_hook, so
    # a batched AM task sees the same facilities scalar dispatch gives it.
    from hclib_tpu.device.workloads import batch_of

    return Megakernel(
        kernels=[("put", put), ("consume", consume), ("bump", bump),
                 ("serve", serve), ("nop", nop)],
        data_specs={"heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)},
        capacity=capacity,
        num_values=64,
        succ_capacity=64,
        interpret=interpret,
        route={"bump": batch_of(bump, width=batch_width)}
        if batch_width else None,
    )


def _heap(ndev):
    """Device d's row r prefilled with 1000*d + r."""
    h = np.zeros((ndev, ROWS, COLS), np.int32)
    for d in range(ndev):
        for r in range(ROWS):
            h[d, r, :] = 1000 * d + r
    return h


def test_put_wakes_parked_consumer_across_devices():
    """Device 0 puts two rows into every other device; each target's
    consumer task is parked on wait_until(chan 0, need 2) and runs only
    after both arrive - the signal-driven wakeup the reference implements
    as SHMEM wait-sets."""
    ndev = 4
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev, capacity=128)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
    )
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    waits = [[] for _ in range(ndev)]
    for d in range(1, ndev):
        # device 0: two puts at target d (rows d and d+8 <- rows 1 and 2)
        builders[0].add(PUT, args=[d, d % ROWS, 1, 0])
        builders[0].add(PUT, args=[d, (d + 8) % ROWS, 2, 0])
        # device d: parked consumer, one wait-dep
        t = builders[d].add(CONSUME, args=[0], out=0)
        waits[d].append((0, 2, t))
    iv, data, info = pg.run(builders, data={"heap": _heap(ndev)}, waits=waits)
    heap = np.asarray(data["heap"])
    for d in range(1, ndev):
        assert (heap[d, d % ROWS] == 1).all(), heap[d, d % ROWS][:4]
        assert (heap[d, (d + 8) % ROWS] == 2).all()
        # the consumer observed both arrivals when it ran
        assert iv[d, 0] == 2, (d, iv[d, :2])
    assert info["pending"] == 0 and not info["overflow"]


def test_am_targets_specific_device_mid_run():
    """Every device AMs a BUMP at every other device (all-to-all, more
    messages than one round's window cap so the outbox pacing runs):
    device d ends with the sum of all senders' payloads - tasks pushed at
    a *chosen* device, not a steal partner."""
    ndev = 4
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev, capacity=128)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
        # am_window 2 < the 4 messages each sender queues, so the
        # outbox's capped-head carry-over path actually runs.
        am_window=2,
    )

    SEND = 5

    def send_all(ctx):
        # AM a bump at every device (including self: loopback rides the
        # same inbox path).
        me = ctx.pgas.me

        for d in range(ndev):
            ctx.pgas.am(d, BUMP, args=[0, 1 + me])

    mk.kernel_names.append("send_all")
    mk.kernel_fns.append(send_all)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].add(SEND)
    iv, _, info = pg.run(builders, data={"heap": _heap(ndev)})
    expect = sum(1 + s for s in range(ndev))
    for d in range(ndev):
        assert iv[d, 0] == expect, (d, iv[d, 0])
    assert info["executed"] == ndev + ndev * ndev
    assert info["pending"] == 0


def test_get_composes_am_and_reply_put():
    """The SHMEM 'get': device 0 AMs a SERVE task at each owner d, which
    puts its heap row back on the reply channel; device 0's consumer is
    parked until all replies land (request/response over one-sided
    primitives, the reference's AM-over-SHMEM composition)."""
    ndev = 4
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
    )
    GET_ROW = 3  # fetch row 3 of each owner
    REQUEST = 5  # appended below after the 5 base kernels
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    waits = [[] for _ in range(ndev)]
    for d in range(1, ndev):
        # am(SERVE) at owner d: serve(requester=0, src_row=GET_ROW,
        # dst_row=d) -> reply channel. Issued from a task on device 0.
        builders[0].add(REQUEST, args=[d])
    # consumer on device 0 parked until ndev-1 replies
    t = builders[0].add(CONSUME, args=[1])
    waits[0].append((1, ndev - 1, t))

    def request(ctx):
        d = ctx.arg(0)
        ctx.pgas.am(d, SERVE, args=[0, GET_ROW, d, 0])

    # SERVE args: (requester, src_row, dst_row, unused) -> uses channel 1
    mk.kernel_names.append("request")
    mk.kernel_fns.append(request)
    iv, data, info = pg.run(builders, data={"heap": _heap(ndev)}, waits=waits)
    heap = np.asarray(data["heap"])
    for d in range(1, ndev):
        # owner d's row GET_ROW (value 1000*d+3) landed in requester row d
        assert (heap[0, d] == 1000 * d + GET_ROW).all(), heap[0, d][:4]
    assert info["pending"] == 0


def test_wait_until_device_side_spawn():
    """A task spawns a parked child and registers the wait itself
    (device-side wait_until, not host-declared): child runs after the
    producer's put lands."""
    ndev = 2
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
    )

    SPAWNER = 5

    def spawner(ctx):
        row = ctx.spawn(CONSUME, args=[2], dep_count=1)
        ctx.pgas.wait_until(0, 1, row)

    mk.kernel_names.append("spawner")
    mk.kernel_fns.append(spawner)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    builders[0].add(PUT, args=[1, 0, 5, 0])  # put my row 5 -> dev1 row 0
    builders[1].add(SPAWNER)
    iv, data, info = pg.run(builders, data={"heap": _heap(ndev)})
    assert iv[1, 2] == 1  # consumer ran, saw one arrival
    assert (np.asarray(data["heap"])[1, 0] == 5).all()
    assert info["pending"] == 0


def test_pgas_race_free_under_detector():
    """Mosaic interpret race detection over the one-sided protocol: the
    counting discipline (wait total arrivals before any inbox read) must
    induce a happens-before order with no data race - this detector is
    what caught the shared-semaphore per-source-wait race during
    development."""
    from jax.experimental.pallas import tpu as pltpu

    ndev = 2
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
        am_window=4,
    )

    SEND = 5

    def send_all(ctx):
        for d in range(ndev):
            ctx.pgas.am(d, BUMP, args=[0, 1 + ctx.pgas.me])
        ctx.pgas.put((ctx.pgas.me + 1) % ndev, 0, 0, 1)

    mk.kernel_names.append("send_all")
    mk.kernel_fns.append(send_all)
    orig = pg._build

    def build_with_detector(*build_args):
        import unittest.mock as m

        real = pltpu.InterpretParams
        with m.patch.object(
            pltpu, "InterpretParams",
            # Ignore kwargs: if interpret_mode() ever grows non-default
            # InterpretParams variants, they must not silently alter
            # race-detection semantics (same in test_resident and
            # test_resident_steal).
            lambda **kw: real(detect_races=True),
        ):
            return orig(*build_args)

    pg._build = build_with_detector
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].add(SEND)
    iv, data, info = pg.run(builders, data={"heap": _heap(ndev)})
    expect = sum(1 + s for s in range(ndev))
    for d in range(ndev):
        assert iv[d, 0] == expect
        assert (np.asarray(data["heap"])[d, 0] == 1000 * ((d + 1) % ndev) + 1).all()


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_pgas_compiles_and_runs_on_tpu():
    """1-device self-loop: the identical kernel compiles for real hardware
    and the full put + AM + wait-until protocol runs (remote DMA to self)."""
    mesh_devs = jax.devices()[:1]
    from jax.sharding import Mesh

    mesh = Mesh(np.array(mesh_devs), ("queues",))
    mk = _mk(interpret=False, ndev=1)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
    )

    SPAWNER = 5

    def spawner(ctx):
        row = ctx.spawn(CONSUME, args=[2], dep_count=1)
        ctx.pgas.wait_until(0, 1, row)
        ctx.pgas.am(0, BUMP, args=[3, 7])

    mk.kernel_names.append("spawner")
    mk.kernel_fns.append(spawner)
    builders = [TaskGraphBuilder()]
    builders[0].add(PUT, args=[0, 0, 5, 0])  # self-put row 5 -> row 0
    builders[0].add(SPAWNER)
    iv, data, info = pg.run(builders, data={"heap": _heap(1)})
    assert iv[0, 2] == 1
    assert iv[0, 3] == 7
    assert (np.asarray(data["heap"])[0, 0] == 5).all()
    assert info["pending"] == 0


# --------------------------------- batched dispatch under PGAS/AM (ISSUE 7)


def test_pgas_batch_routed_am_bumps_exact():
    """ISSUE 7 acceptance (PGAS arm): AM-delivered BUMP tasks fire through
    the batched same-kind tier - the lane scratch binds positionally
    inside the resident kernel's scratch tail, so this is the coverage
    that a _build edit misplacing lanes/lstate/tstats fails loudly.
    Every device AMs a BUMP at every other device; batched delivery must
    land the exact all-senders sum on each device (slot_ctx carries the
    pgas ctx_hook, so a batched AM task behaves exactly like scalar
    dispatch), and tier counters reconcile with the executed count."""
    ndev = 4
    mesh = cpu_mesh(ndev, axis_name="queues")
    mk = _mk(ndev=ndev, capacity=128, batch_width=4)
    pg = ResidentKernel(
        mk, mesh, steal=False,
        channels={"c0": ("heap", 1), "reply": ("heap", 1)},
        am_window=2,
    )

    SEND = 5

    def send_all(ctx):
        me = ctx.pgas.me
        for d in range(ndev):
            ctx.pgas.am(d, BUMP, args=[0, 1 + me])

    mk.kernel_names.append("send_all")
    mk.kernel_fns.append(send_all)
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for d in range(ndev):
        builders[d].add(SEND)
    iv, _, info = pg.run(builders, data={"heap": _heap(ndev)})
    expect = sum(1 + s for s in range(ndev))
    for d in range(ndev):
        assert iv[d, 0] == expect, (d, iv[d, 0])
    assert info["executed"] == ndev + ndev * ndev
    assert info["pending"] == 0
    tiers = info["tiers"]
    assert len(tiers) == ndev
    batched = sum(t["batch_tasks"] for t in tiers)
    scalar = sum(t["scalar_tasks"] for t in tiers)
    assert batched + scalar == info["executed"], (batched, scalar)
    assert batched > 0, tiers
