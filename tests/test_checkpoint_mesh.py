"""Checkpoint/restore of the resident mesh (ISSUE 5): quiesce a mesh
mid-run, resume on the same mesh size or re-home N -> M, with pending
waits and an inject cursor riding the bundle - totals exact against the
uninterrupted run. Needs the Mosaic interpret mode; the mesh runs ride
the chaos marker like the other mesh tests.
"""

import functools

import numpy as np
import pytest
from conftest import uts_mesh_builders, uts_mesh_rk

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.runtime.checkpoint import (
    CheckpointBundle,
    restore_resident,
    snapshot_resident,
)


# ------------------------------------------------------- resident mesh


def _mesh_uts_rk(ndev, checkpoint=True):
    return uts_mesh_rk(ndev, 4, capacity=256, checkpoint=checkpoint)


@pytest.fixture(scope="module")
def mesh_uts_ref():
    """``ref(ndev) -> (summed ivalues, executed)`` of the uninterrupted
    traversal of ``uts_mesh_builders(ndev)``'s roots - the totals the
    mesh round trips compare against, each forest run once. It runs on
    ONE device: the totals do not depend on the mesh, and an n-device
    interpreter run costs n times n as much."""
    @functools.lru_cache(maxsize=None)
    def ref(ndev):
        iv, _, info = _mesh_uts_rk(1).run(
            uts_mesh_builders(1, ndev), quantum=8, max_rounds=4096
        )
        assert info["pending"] == 0
        return int(np.asarray(iv)[:, 0].sum()), info["executed"]

    return ref


def test_resident_quiesce_validation_needs_no_mesh():
    """Host-side guards (no Mosaic needed): quiesce on a non-checkpoint
    build, malformed waits, and resume_state conflicts all refuse before
    any kernel builds. (Quiesce WITH pending waits is no longer refused -
    the wait table exports with the snapshot; see
    test_resident_quiesce_with_pending_waits_roundtrip.)"""
    rk = _mesh_uts_rk(2, checkpoint=False)
    with pytest.raises(ValueError, match="checkpoint=True"):
        rk.run(uts_mesh_builders(2), quiesce=1)
    rk2 = _mesh_uts_rk(2, checkpoint=True)
    # Wait validation still applies (this kernel declares no channels).
    with pytest.raises(ValueError, match="bad channel id"):
        rk2.run(uts_mesh_builders(2), quiesce=1, waits=[[(0, 1, 0)]])
    with pytest.raises(ValueError, match="exactly one"):
        rk2.run(uts_mesh_builders(2), resume_state={})
    with pytest.raises(ValueError, match="exactly one"):
        rk2.run()
    # resume_state with mismatched wait-table / ring shapes refuses with
    # a diagnostic naming the device counts.
    with pytest.raises(ValueError, match="wait table covers"):
        rk2.run(resume_state={
            "tasks": np.zeros((2, 4, 16), np.int32),
            "succ": np.zeros((2, 8), np.int32),
            "ready": np.zeros((2, 4), np.int32),
            "counts": np.zeros((2, 8), np.int32),
            "ivalues": np.zeros((2, 16), np.int32),
            "waits": np.zeros((4, 65, 3), np.int32),
        })


@pytest.mark.chaos
def test_resident_quiesce_with_pending_waits_roundtrip():
    """ACCEPTANCE (lifted limit #1): a resident mesh with PENDING
    host-declared waits quiesces - the live wait table exports through
    the aliased output (needs rebased) - and the resumed run re-arms the
    parked rows exactly: the late put still wakes its consumer, results
    match the uninterrupted run."""
    import jax

    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    ROWS, COLS = 8, 128
    BUMP, PUT, CONSUME = 0, 1, 2

    def make_rk():
        def bump(ctx):
            ctx.set_value(0, ctx.value(0) + ctx.arg(0))

        def put(ctx):
            ctx.pgas.put(ctx.arg(0), 0, ctx.arg(1), ctx.arg(2))

        def consume(ctx):
            ctx.set_value(ctx.arg(0), ctx.pgas.count(0))

        mk = Megakernel(
            kernels=[("bump", bump), ("put", put), ("consume", consume)],
            data_specs={
                "heap": jax.ShapeDtypeStruct((ROWS, COLS), np.int32)
            },
            capacity=128, num_values=64, succ_capacity=64,
            interpret=True, checkpoint=True,
        )
        return ResidentKernel(
            mk, cpu_mesh(2, axis_name="q"),
            channels={"c0": ("heap", 1)}, window=4,
        )

    def heap():
        h = np.zeros((2, ROWS, COLS), np.int32)
        for d in range(2):
            for r in range(ROWS):
                h[d, r, :] = 1000 * d + r
        return h

    def build():
        builders = [TaskGraphBuilder(), TaskGraphBuilder()]
        # The put hides behind a serial bump chain, so an early quiesce
        # cuts BEFORE it runs and the wait is still parked.
        prev = builders[0].add(BUMP, args=[1])
        for i in range(20):
            prev = builders[0].add(BUMP, args=[i + 2], deps=[prev])
        builders[0].add(PUT, args=[1, 3, 2], deps=[prev])
        t = builders[1].add(CONSUME, args=[1])
        return builders, [[], [(0, 1, t)]]

    builders, waits = build()
    iv_f, data_f, info_f = make_rk().run(
        builders, data={"heap": heap()}, waits=waits, quantum=2,
        max_rounds=4096,
    )
    assert int(np.asarray(iv_f)[1, 1]) == 1  # consumer saw the arrival

    builders, waits = build()
    rk = make_rk()
    iv_q, _, info_q = rk.run(
        builders, data={"heap": heap()}, waits=waits, quantum=2,
        max_rounds=4096, quiesce=2,
    )
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    w = np.asarray(info_q["state"]["waits"])
    assert int(w[1, 0, 0]) == 1, w[1]  # the wait is STILL parked
    assert int(w[1, 1, 1]) >= 1  # rebased need is still positive
    iv_r, data_r, info_r = rk.run(
        resume_state=info_q["state"], quantum=2, max_rounds=4096,
    )
    assert info_r["pending"] == 0
    assert info_r["executed"] == info_f["executed"]
    assert int(np.asarray(iv_r)[1, 1]) == 1  # re-armed wait fired
    assert np.array_equal(
        np.asarray(data_r["heap"]), np.asarray(data_f["heap"])
    )


@pytest.mark.chaos
def test_resident_inject_cursor_survives_reshard():
    """ACCEPTANCE (lifted limit #2): a mid-stream quiesce keeps
    published-but-unconsumed inject rows as ring residue with the
    consumed cursor; the bundle reshards 2 -> 1 (residue re-homed,
    conserved) and the resumed smaller mesh drains everything exactly.
    (4 -> 2 cost four times the interpreter time and asserted the same;
    the dealing arithmetic across several survivors is held by
    test_checkpoint_store.py's ring-residue test on hand-built bundles.)"""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    BUMP = 0

    def make_rk(ndev):
        def bump(ctx):
            ctx.set_value(0, ctx.value(0) + ctx.arg(0))

        # 200 rows, not a power of two: the reshard lays the new rings
        # ``ring_len`` (256) long round tables of 200 (ISSUE 45).
        mk = Megakernel(
            kernels=[("bump", bump)], capacity=200, num_values=1024,
            succ_capacity=8, interpret=True, checkpoint=True,
        )
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[BUMP],
            window=4, homed=False, inject=True,
        )

    ndev = 2
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    v = 0
    for d in range(ndev):
        for _ in range(2):
            v += 1
            builders[d].add(BUMP, args=[v])
    inject_rows = []
    for d in range(ndev):
        rows = []
        for _ in range(6):
            v += 1
            rows.append((BUMP, [v]))
        inject_rows.append(rows)
    want = v * (v + 1) // 2

    rk = make_rk(ndev)
    # quiesce=True: threshold round 0 - the poll never consumes, so ALL
    # inject rows are residue and the cut is maximally mid-stream.
    _, _, info_q = rk.run(
        builders, inject_rows=inject_rows, quantum=4, max_rounds=4096,
        quiesce=True,
    )
    assert info_q["quiesced"] is True
    st = info_q["state"]
    assert int(np.asarray(st["ictl"])[:, 0].sum()) == ndev * 6  # residue
    bundle = snapshot_resident(rk, info_q)
    small = bundle.reshard(1)
    assert int(np.asarray(small.arrays["ictl"])[:, 0].sum()) == ndev * 6
    assert bundle.arrays["ready"].shape == (2, 256)
    assert small.arrays["ready"].shape == (1, 256)
    assert small.arrays["tasks"].shape[:2] == (1, 200)
    rk2 = make_rk(1)
    iv, _, info = rk2.run(
        resume_state=small.state(), quantum=8, max_rounds=1 << 14,
    )
    assert info["pending"] == 0
    assert int(np.asarray(iv)[:, 0].sum()) == want
    assert info["executed"] == v
    # Partial consumption: a later cut consumes some rounds' rows first;
    # the cursor still reconciles (consumed + residue == published).
    rk3 = make_rk(ndev)
    _, _, info_q3 = rk3.run(
        builders, inject_rows=inject_rows, quantum=4, max_rounds=4096,
        quiesce=2,
    )
    if info_q3["quiesced"]:
        ic = np.asarray(info_q3["inject_ctl"])
        residue = int(np.asarray(info_q3["state"]["ictl"])[:, 0].sum())
        assert int(ic[:, 2].sum()) + residue == int(ic[:, 0].sum())
        iv3, _, info3 = rk3.run(  # the program rk3 already built
            resume_state=info_q3["state"], quantum=4, max_rounds=4096,
        )
        assert int(np.asarray(iv3)[:, 0].sum()) == want


@pytest.mark.chaos
def test_resident_mesh_checkpoint_roundtrip_same_mesh(mesh_uts_ref):
    """ACCEPTANCE: quiesce a 4-device resident mesh mid-traversal (the
    fold observes the word, sched stops popping, the wire drains, the
    mesh exits in lockstep), resume on the same mesh size, and the totals
    equal the uninterrupted run exactly."""
    ndev = 4
    total, executed = mesh_uts_ref(ndev)
    assert total == executed

    rk = _mesh_uts_rk(ndev)
    iv_q, _, info_q = rk.run(
        uts_mesh_builders(ndev), quantum=8, max_rounds=4096, quiesce=2,
    )
    assert info_q["quiesced"] is True
    assert info_q["pending"] > 0
    fs = info_q["fault_stats"]
    assert all(f["quiesce_round"] >= 2 for f in fs)  # threshold honored
    iv_r, _, info_r = rk.run(
        resume_state=info_q["state"], quantum=8, max_rounds=4096
    )
    assert info_r["pending"] == 0
    assert info_r["executed"] == executed
    assert int(np.asarray(iv_r)[:, 0].sum()) == total


@pytest.mark.chaos
def test_resident_mesh_restore_onto_smaller_and_larger_mesh(
    tmp_path, mesh_uts_ref,
):
    """ACCEPTANCE (elastic resume): a 4-chip checkpoint restores onto 2
    chips (and a 2-chip one onto 4) - per-chip queues re-homed host-side
    with the dead-chip conservation semantics, the full workload drains,
    totals conserved exactly."""
    ndev = 4
    total, executed = mesh_uts_ref(ndev)

    rk = _mesh_uts_rk(ndev)
    _, _, info_q = rk.run(
        uts_mesh_builders(ndev), quantum=8, max_rounds=4096, quiesce=2,
    )
    bundle = snapshot_resident(rk, info_q)
    path = str(tmp_path / "mesh-ckpt")
    bundle.save(path)

    # 4 -> 2: restore_resident reshards automatically off the manifest.
    rk_small = _mesh_uts_rk(2)
    iv_s, _, info_s = restore_resident(
        CheckpointBundle.load(path), rk_small, quantum=8,
        max_rounds=4096,
    )
    assert info_s["pending"] == 0
    assert info_s["executed"] == executed
    assert int(np.asarray(iv_s)[:, 0].sum()) == total

    # 2 -> 4: checkpoint the 2-chip run, grow back to 4.
    rk2 = _mesh_uts_rk(2)
    _, _, info_q2 = rk2.run(
        uts_mesh_builders(2), quantum=8, max_rounds=4096, quiesce=2,
    )
    if info_q2["pending"] > 0:
        rk_big = _mesh_uts_rk(4)
        iv_b, _, info_b = restore_resident(
            snapshot_resident(rk2, info_q2), rk_big, quantum=8,
            max_rounds=4096,
        )
        assert info_b["pending"] == 0
        # 2-chip seeds 1,2 are a subset of the 4-chip run's totals: check
        # against the 2-chip uninterrupted run instead.
        total2, executed2 = mesh_uts_ref(2)
        assert info_b["executed"] == executed2
        assert int(np.asarray(iv_b)[:, 0].sum()) == total2
