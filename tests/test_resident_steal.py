"""In-kernel ICI work stealing: ResidentKernel in its steal-only,
whole-row-migration configuration (``steal=True, homed=False``), exercised
under Mosaic's TPU interpret mode (which simulates remote DMA + semaphores
on CPU; the same kernel compiles and runs on real TPU hardware - see the
tpu-gated test).

Reference counterpart: thief-side deque CAS across cores
(/root/reference/src/hclib-locality-graph.c:843-888, src/hclib-deque.c:75-106).
"""

import jax
import numpy as np
import pytest
from conftest import bump_kernel, bump_mk, skewed_builders

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.parallel.mesh import cpu_mesh

BUMP = 0


def steal_only(mk, mesh, migratable_fns=(), **kw):
    """The steal-only resident kernel: successor-free rows of the
    whitelisted kinds migrate whole, nothing is homed."""
    return ResidentKernel(
        mk, mesh, steal=True, migratable_fns=migratable_fns, homed=False,
        **kw,
    )


def test_resident_steal_rebalances_skewed_load():
    # (8-device spread coverage lives in the hypercube test below and the
    # resident skewed-fib test; 4 devices keep this one's semantics at a
    # quarter of the interpret cost.)
    ndev, ntasks = 4, 28
    smk = steal_only(
        bump_mk(64), cpu_mesh(ndev, axis_name="queues"),
        migratable_fns=[BUMP], window=8,
    )
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), quantum=8)
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


def test_resident_steal_two_devices_exact():
    ndev, ntasks = 2, 16
    smk = steal_only(
        bump_mk(64), cpu_mesh(ndev, axis_name="queues"),
        migratable_fns=[BUMP], window=8,
    )
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), quantum=8)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    assert info["per_device_counts"][1, 5] > 0  # work actually migrated
    steal = info["steal"]  # and the exchange counted what it moved
    assert sum(steal["exported"]) == sum(steal["imported"]) > 0
    assert steal["imported"][1] > 0


def test_resident_steal_dependency_graphs_stay_home():
    """Non-whitelisted dynamic graphs (fib spawns with successors) run
    where placed; the steal rounds must not corrupt them."""
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    ndev = 2
    mk = make_fib_megakernel(capacity=128, interpret=True)
    smk = steal_only(
        mk, cpu_mesh(ndev, axis_name="queues")
    )  # empty whitelist
    builders = []
    for d, n in enumerate((7, 9)):
        b = TaskGraphBuilder()
        b.add(FIB, args=[n], out=0)
        builders.append(b)
    iv, _, info = smk.run(builders, quantum=64)
    assert info["pending"] == 0
    assert int(iv[0, 0]) == 13 and int(iv[1, 0]) == 34


def test_resident_steal_race_free_under_detector():
    """Mosaic interpret race detection over the full steal protocol - the
    remote DMAs + credit semaphores must induce a happens-before order with
    no data race (an aux capability the reference lacks entirely: its deque
    relies on hand-audited fences, SURVEY.md section 5)."""
    from jax.experimental.pallas import tpu as pltpu

    ndev, ntasks = 2, 12
    smk = steal_only(
        bump_mk(256), cpu_mesh(ndev, axis_name="queues"),
        migratable_fns=[BUMP], window=4,
    )
    # Rebuild with the race detector on.
    orig = smk._build

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

    smk._build = build_with_detector
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), quantum=4)
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_resident_steal_compiles_and_runs_on_tpu():
    """The steal kernel on a REAL TPU chip: 1-device mesh, self-loop ring -
    remote DMA + semaphores exercise the actual Mosaic lowering."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("queues",))
    mk = Megakernel(
        kernels=[("bump", bump_kernel)],
        capacity=256, num_values=4, succ_capacity=8, interpret=False,
    )
    smk = steal_only(mesh=mesh, mk=mk, migratable_fns=[BUMP])
    ntasks = 100
    iv, _, info = smk.run(skewed_builders(1, ntasks), quantum=16)
    assert info["pending"] == 0
    assert int(iv[0, 0]) == ntasks * (ntasks + 1) // 2


def test_resident_steal_hypercube_spreads_max_skew_fast():
    """VERDICT round-2 efficiency target: a 48-task skew on 8 devices
    spreads across the whole mesh in a handful of exchange rounds (the
    paired dimension-exchange moves (mine-theirs)/2 per hop, all hops per
    round, vs. one fixed window to a single partner per round)."""
    ndev, ntasks = 8, 48
    smk = steal_only(
        bump_mk(128), cpu_mesh(ndev, axis_name="queues"),
        migratable_fns=[BUMP], window=16,
    )
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), quantum=8)
    assert info["pending"] == 0
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) == ndev, per_dev  # EVERY device worked
    # Round 1's three hops spread 48 -> 6 per device; quantum=8 then
    # drains everyone in about one execution round.
    assert info["rounds"] <= 4, info["rounds"]


def test_resident_steal_2d_mesh_exact():
    """2x2 mesh (VERDICT item 6): the XOR dimension-exchange decomposes
    into per-axis torus hops; totals must be exact and work must reach
    both rows and columns."""
    from hclib_tpu.parallel.mesh import make_mesh

    cpus = jax.devices("cpu")
    mesh = make_mesh((2, 2), ("r", "c"), cpus[:4])
    ntasks = 20
    smk = steal_only(
        bump_mk(64), mesh, migratable_fns=[BUMP], window=8,
    )
    builders = [TaskGraphBuilder() for _ in range(4)]
    for i in range(ntasks):
        builders[0].add(BUMP, args=[i + 1])
    iv, _, info = smk.run(builders, quantum=8)
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 3, per_dev


# ------------------------------------- batched dispatch in the ring (ISSUE 7)


def test_resident_steal_batch_routed_bump_exact():
    """ISSUE 7 acceptance (ICI arm): a batch-routed mk through the
    steal-only resident kernel - the lane scratch binds behind its
    scratch tail and info['tiers'] surfaces per device. Totals stay
    exact, work still spreads (lane residue spills to the ring's cold
    end before every steal round), and tier counters reconcile with the
    executed count."""
    from hclib_tpu.device.workloads import batch_of

    ndev, ntasks = 4, 28
    mk = bump_mk(64, route={"bump": batch_of(bump_kernel, width=4)})
    smk = steal_only(
        mk, cpu_mesh(ndev, axis_name="queues"),
        migratable_fns=[BUMP], window=8,
    )
    iv, _, info = smk.run(skewed_builders(ndev, ntasks), quantum=8)
    assert info["pending"] == 0
    assert info["executed"] == ntasks
    assert int(iv[:, 0].sum()) == ntasks * (ntasks + 1) // 2
    tiers = info["tiers"]
    assert len(tiers) == ndev
    batched = sum(t["batch_tasks"] for t in tiers)
    scalar = sum(t["scalar_tasks"] for t in tiers)
    assert batched + scalar == ntasks, (batched, scalar)
    assert batched > 0, tiers
    per_dev = info["per_device_counts"][:, 5]
    assert int((per_dev > 0).sum()) >= 2, per_dev
