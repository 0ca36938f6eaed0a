"""Batched same-kind dispatch on the resident mesh (ISSUE 7): the batch
tier inside device/resident.py's round loop - exact against the scalar
mesh, flight-recorder records reconciling with tstats, and a
checkpoint/reshard cut with lanes active. (The scalar resident kernel's
own tests are in test_resident.py.)
"""

import numpy as np
import pytest
from conftest import fib_exec_count, uts_mesh_builders, uts_mesh_rk

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import VBLOCK
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.device.workloads import FIB, SUM, make_fib_megakernel
from hclib_tpu.models.fib import fib_seq
from hclib_tpu.parallel.mesh import cpu_mesh


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
    ndev, n = 2, 9
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
    assert info_b["executed"] == info_s["executed"] == fib_exec_count(n)
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
    from hclib_tpu.runtime.checkpoint import snapshot_resident

    def make_rk(ndev):
        return uts_mesh_rk(ndev, 3, capacity=256, batch_width=4)

    ndev = 4
    # The uninterrupted run: the same four roots, two a device, on the
    # 2-device mesh and program the resumed half runs (a quarter of the
    # 4-device interpreter time; the totals do not depend on the mesh).
    iv_f, _, info_f = make_rk(2).run(
        uts_mesh_builders(2, 2), quantum=8, max_rounds=1 << 14
    )
    total = int(np.asarray(iv_f)[:, 0].sum())
    assert info_f["pending"] == 0 and total == info_f["executed"]
    assert sum(t["batch_tasks"] for t in info_f["tiers"]) > 0

    rk = make_rk(ndev)
    iv_q, _, info_q = rk.run(
        uts_mesh_builders(ndev), quantum=8, max_rounds=4096, quiesce=2,
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
