"""Checkpoint bundles on the host (ISSUEs 5, 17, 20): what needs no
kernel. N -> M reshard arithmetic on hand-built resident bundles (the
refusals, the M edge cases, ring residue, wait tables, dyngraph
adjacency), bundle diffs, and the durable generational BundleStore
(publish ordering, retention, self-healing, crash sites). The round
trips that run a kernel are in test_checkpoint.py.
"""

import os

import numpy as np
import pytest

import hclib_tpu as hc
from hclib_tpu.runtime.checkpoint import (
    BundleFault,
    BundleStore,
    CheckpointBundle,
    CheckpointError,
    default_store,
)


def test_reshard_refuses_unsafe_rows():
    """N -> M re-homing moves only ready link-free rows (the PR 2
    dead-chip semantics): dependent rows, successor links, home-links,
    and dynamic out slots are refused with a diagnostic."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_DEP, F_HOME, F_OUT, F_SUCC0, NO_TASK,
    )

    def fake_bundle(mutate):
        ndev, cap, V = 2, 8, 16
        tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
        tasks[:, :, F_SUCC0] = NO_TASK
        tasks[:, :, 2:4] = NO_TASK
        tasks[:, :, F_HOME] = NO_TASK
        counts = np.zeros((ndev, 8), np.int32)
        counts[:, 1] = 1  # tail
        counts[:, 2] = 1  # alloc
        counts[:, 3] = 1  # pending
        counts[:, 4] = 2  # value_alloc
        ready = np.zeros((ndev, cap), np.int32)
        mutate(tasks)
        return CheckpointBundle(
            "resident", {"ndev": ndev},
            {
                "tasks": tasks, "succ": np.full((ndev, 8), -1, np.int32),
                "ready": ready, "counts": counts,
                "ivalues": np.zeros((ndev, V), np.int32),
            },
        )

    ok = fake_bundle(lambda t: None).reshard(1)
    assert int(ok.arrays["counts"][0][3]) == 2  # both rows re-homed

    def dep(t):
        t[0, 0, F_DEP] = 1

    with pytest.raises(CheckpointError, match="dependency counter"):
        fake_bundle(dep).reshard(1)

    def linked(t):
        t[0, 0, F_SUCC0] = 1

    with pytest.raises(CheckpointError, match="successor links"):
        fake_bundle(linked).reshard(1)

    def homed(t):
        t[0, 0, F_HOME] = 1

    with pytest.raises(CheckpointError, match="home-link"):
        fake_bundle(homed).reshard(1)

    def dyn_out(t):
        t[0, 0, F_OUT] = 5  # >= value_alloc 2

    with pytest.raises(CheckpointError, match="dynamic out slot"):
        fake_bundle(dyn_out).reshard(1)
    with pytest.raises(CheckpointError, match="power-of-two"):
        fake_bundle(lambda t: None).reshard(3)


def _fake_resident_bundle(ndev=2, cap=8, live_per_dev=1, extra=None):
    """Minimal clean-quiesce resident bundle for host-side reshard tests
    (live rows are ready + link-free)."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_HOME, NO_TASK,
    )

    V = 16
    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, 2:4] = NO_TASK  # F_SUCC0/F_SUCC1
    tasks[:, :, F_HOME] = NO_TASK
    counts = np.zeros((ndev, 8), np.int32)
    counts[:, 1] = live_per_dev  # tail
    counts[:, 2] = live_per_dev  # alloc
    counts[:, 3] = live_per_dev  # pending
    counts[:, 4] = 2  # value_alloc
    ready = np.zeros((ndev, cap), np.int32)
    arrays = {
        "tasks": tasks, "succ": np.full((ndev, 8), -1, np.int32),
        "ready": ready, "counts": counts,
        "ivalues": np.zeros((ndev, V), np.int32),
    }
    arrays.update(extra or {})
    return CheckpointBundle("resident", {"ndev": ndev}, arrays)


@pytest.mark.parametrize("cap", [6, 8, 12, 200])
def test_reshard_lays_rings_ring_len_long(cap):
    """The re-homed ready rings are ``ring_len(capacity)`` words round
    tables of ``capacity`` rows, whatever the source's layout (these
    hand-built bundles carry ``ready`` of length ``capacity``, what every
    snapshot written before PR 45 has); a table may be filled to its last
    row, and the live window reads back through ``ring_window``."""
    from hclib_tpu.device.descriptor import NO_TASK, ring_len, ring_window

    src = _fake_resident_bundle(ndev=2, cap=cap, live_per_dev=3)
    assert src.arrays["ready"].shape == (2, cap)
    for m, per_dev in ((1, 6), (2, 3), (4, None)):
        out = src.reshard(m)
        assert out.arrays["tasks"].shape[:2] == (m, cap)
        assert out.arrays["ready"].shape == (m, ring_len(cap))
        counts = out.arrays["counts"]
        assert int(counts[:, 3].sum()) == 6
        for d in range(m):
            n = int(counts[d][1] - counts[d][0])
            assert per_dev is None or n == per_dev
            assert ring_window(
                out.arrays["ready"][d], counts[d][0], counts[d][1]
            ).tolist() == list(range(n))
            assert (out.arrays["ready"][d][n:] == NO_TASK).all()
        # and again from the new layout
        again = out.reshard(1)
        assert again.arrays["ready"].shape == (1, ring_len(cap))
        assert int(again.arrays["counts"][0][3]) == 6


def test_reshard_m_edge_cases_diagnosed():
    """SATELLITE: M=1 and M>N re-home cleanly (totals conserved, empty
    new devices legal); illegal/overfull targets get diagnostics naming
    the fix, never shape errors."""
    b = _fake_resident_bundle(ndev=2, live_per_dev=2)
    one = b.reshard(1)  # M=1: everything folds onto the survivor
    assert int(one.arrays["counts"][0][3]) == 4
    big = _fake_resident_bundle(ndev=2, live_per_dev=2).reshard(8)
    assert big.arrays["tasks"].shape[0] == 8  # M > N: empty devices ok
    assert int(big.arrays["counts"][:, 3].sum()) == 4
    assert big.meta["resharded_from"] == 2
    with pytest.raises(CheckpointError, match="power-of-two"):
        _fake_resident_bundle().reshard(3)
    with pytest.raises(CheckpointError, match="power-of-two"):
        _fake_resident_bundle().reshard(0)
    with pytest.raises(CheckpointError, match="integer"):
        _fake_resident_bundle().reshard("two")
    # Overfull scale-in: the diagnostic names the minimum mesh size.
    with pytest.raises(CheckpointError, match="scale in less"):
        _fake_resident_bundle(ndev=2, cap=4, live_per_dev=3).reshard(1)


def test_reshard_rehomes_ring_residue_and_empty_waits():
    """SATELLITE (lifted limits, host half): inject-ring residue
    re-deals across mesh sizes with its count conserved, and an empty
    wait table rides along resized to the new roster (pending waits
    re-home too - the conservation matrix below)."""
    from hclib_tpu.device.inject import RING_ROW

    R = 8
    rr = np.zeros((2, R, RING_ROW), np.int32)
    ic = np.zeros((2, 8), np.int32)
    for d in range(2):
        for i in range(3):
            rr[d, i, 0] = 10 * d + i  # distinguishable payload
        ic[d, 0] = 3
        ic[d, 1] = 1
    wz = np.zeros((2, 5, 3), np.int32)
    b = _fake_resident_bundle(
        ndev=2, live_per_dev=1,
        extra={"ring_rows": rr, "ictl": ic, "waits": wz},
    )
    for m in (1, 4):
        out = b.reshard(m)
        assert int(out.arrays["ictl"][:, 0].sum()) == 6  # residue conserved
        assert out.arrays["ring_rows"].shape[:2] == (m, R)
        assert out.arrays["waits"].shape == (m, 5, 3)
        assert (out.arrays["ictl"][:, 1] == 1).all()  # close flag survives
        # Every payload survives exactly once.
        vals = sorted(
            int(out.arrays["ring_rows"][d, i, 0])
            for d in range(m)
            for i in range(int(out.arrays["ictl"][d, 0]))
        )
        assert vals == [0, 1, 2, 10, 11, 12], vals
    # Ring overflow on aggressive scale-in diagnoses, not IndexErrors.
    ic_full = ic.copy()
    ic_full[:, 0] = R
    bf = _fake_resident_bundle(
        ndev=2, live_per_dev=1,
        extra={"ring_rows": rr, "ictl": ic_full, "waits": wz},
    )
    with pytest.raises(CheckpointError, match="ring"):
        bf.reshard(1)


def test_bundle_diff():
    """SATELLITE: the structural diff the bit-identity storms use -
    equal bundles report equal; value, shape, and key differences are
    named with counts."""
    a = _fake_resident_bundle(ndev=2, live_per_dev=2)
    b = _fake_resident_bundle(ndev=2, live_per_dev=2)
    assert a.diff(b)["equal"] is True
    b.arrays["ivalues"] = b.arrays["ivalues"].copy()
    b.arrays["ivalues"][0, 0] = 7
    d = a.diff(b)
    assert d["equal"] is False
    assert d["mismatched"]["ivalues"]["n"] == 1
    assert d["mismatched"]["ivalues"]["max_abs"] == 7.0
    c = _fake_resident_bundle(ndev=4, live_per_dev=2)
    d2 = a.diff(c)
    assert not d2["equal"] and "shape" in d2["mismatched"]["tasks"]
    e = _fake_resident_bundle(
        ndev=2, live_per_dev=2,
        extra={"waits": np.zeros((2, 5, 3), np.int32)},
    )
    d3 = a.diff(e)
    assert d3["only_other"] == ["waits"] and not d3["equal"]


# ------------------------------------------------- durable store (ISSUE 17)


def _waits_bundle(ndev=4, cap=8, live=1, parked=(), channels=("left",
                  "right"), host_residue=None, max_waits=4, seed=0):
    """Clean-quiesce resident bundle with wait-parked rows: each
    ``parked`` triple (device, channel, need) parks one row carrying
    exactly one dep bump, with its wait entry in the exported table."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_DEP, F_FN, F_HOME, NO_TASK,
    )

    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, 2:4] = NO_TASK
    tasks[:, :, F_HOME] = NO_TASK
    ready = np.full((ndev, cap), NO_TASK, np.int32)
    counts = np.zeros((ndev, 8), np.int32)
    waits = np.zeros((ndev, max_waits + 1, 3), np.int32)
    for d in range(ndev):
        for i in range(live):
            tasks[d, i, F_FN] = 1
            ready[d, i] = i
        npk = 0
        for (pd, ch, need) in parked:
            if pd != d:
                continue
            slot = live + npk
            tasks[d, slot, F_FN] = 2
            tasks[d, slot, F_DEP] = 1
            w = int(waits[d, 0, 0])
            waits[d, 1 + w] = (ch, need, slot)
            waits[d, 0, 0] = w + 1
            npk += 1
        counts[d, 1] = live
        counts[d, 2] = live + npk  # alloc
        counts[d, 3] = live + npk  # pending
        counts[d, 4] = 2  # value_alloc
    rng = np.random.default_rng(seed)
    meta = {"ndev": ndev, "channels": list(channels)}
    if host_residue:
        meta["host_residue"] = dict(host_residue)
    return CheckpointBundle("resident", meta, {
        "tasks": tasks,
        "succ": np.full((ndev, 8), -1, np.int32),
        "ready": ready, "counts": counts,
        "ivalues": rng.integers(0, 1 << 20, (ndev, 16)).astype(np.int32),
        "waits": waits,
    })


def _need_sums(waits):
    acc = {}
    w = np.asarray(waits)
    for d in range(w.shape[0]):
        for i in range(int(w[d, 0, 0])):
            ch, need, _row = (int(x) for x in w[d, 1 + i])
            acc[ch] = acc.get(ch, 0) + need
    return acc


def test_reshard_waits_conservation_matrix():
    """TENTPOLE: exported wait tables RE-HOME across mesh sizes - the
    4 -> 2 and 2 -> 4 matrix conserves wait counts, per-channel need
    sums, and the pending total; parked rows land allocated but NOT in
    the ready ring, keeping exactly one dep bump per parked wait."""
    from hclib_tpu.device.descriptor import F_DEP

    parked = [(0, 0, 3), (1, 1, 2), (2, 0, 1), (3, 1, 4)]
    b = _waits_bundle(ndev=4, parked=parked)
    want_needs = _need_sums(b.arrays["waits"])
    want_pend = int(b.arrays["counts"][:, 3].sum())
    for m in (2, 4, 1, 8):
        out = b.reshard(m) if m != 4 else b.reshard(2).reshard(4)
        w = np.asarray(out.arrays["waits"])
        assert w.shape[0] == m
        assert int(w[:, 0, 0].sum()) == len(parked)
        assert _need_sums(w) == want_needs
        assert int(out.arrays["counts"][:, 3].sum()) == want_pend
        for d in range(m):
            tail = int(out.arrays["counts"][d, 1])
            alloc = int(out.arrays["counts"][d, 2])
            for i in range(int(w[d, 0, 0])):
                _ch, _need, row = (int(x) for x in w[d, 1 + i])
                # The wait entry targets a real parked row on ITS device:
                # allocated past the ready ring, dep bump preserved.
                assert tail <= row < alloc, (d, row, tail, alloc)
                assert int(out.arrays["tasks"][d, row, F_DEP]) == 1


def test_reshard_refuses_satisfier_in_residue():
    """TENTPOLE: the narrowed refusal - waits whose satisfier sits in
    unexported host residue (meta['host_residue']) refuse with ONE
    whole-program diagnostic naming every stranded channel; residue on
    channels nobody waits on does not refuse."""
    b = _waits_bundle(
        ndev=4, parked=[(0, 0, 3), (1, 0, 1), (2, 1, 2)],
        host_residue={"left": 2, "right": 1},
    )
    with pytest.raises(CheckpointError) as ei:
        b.reshard(2)
    msg = str(ei.value)
    assert "host residue" in msg
    assert "'left'" in msg and "'right'" in msg  # every stranded channel
    assert "3 pending wait(s) on 2 channel(s)" in msg
    # Residue on an un-waited channel is harmless: the waits re-home.
    ok = _waits_bundle(
        ndev=4, parked=[(0, 0, 3)], host_residue={"right": 5},
    ).reshard(2)
    assert int(np.asarray(ok.arrays["waits"])[:, 0, 0].sum()) == 1


def test_reshard_diagnoses_wait_dep_mismatch():
    """A declared wait whose parked row does NOT carry the matching dep
    bump is a violation named per-row (the export contract), not a
    silent re-home."""
    from hclib_tpu.device.descriptor import F_DEP

    b = _waits_bundle(ndev=2, parked=[(0, 0, 2)])
    b.arrays["tasks"][0, 1, F_DEP] = 0  # strip the bump
    with pytest.raises(CheckpointError,
                       match="dependency counter 0 != its 1"):
        b.reshard(1)


def test_bundle_store_publish_retention_and_reload(tmp_path):
    """Generational publish: gen-N dirs + CURRENT pointer, bounded
    retention (keep=K prunes oldest), load_latest bit-identical to the
    newest save, provenance stamped on the loaded bundle."""
    root = str(tmp_path / "store")
    store = BundleStore(root, keep=2, fsync=False)
    bundles = [_waits_bundle(seed=i) for i in range(4)]
    gens = [store.save(b) for b in bundles]
    assert gens == [1, 2, 3, 4]
    assert store.generations() == [3, 4]  # keep=2 pruned 1, 2
    assert open(os.path.join(root, "CURRENT")).read().strip() == "4"
    got = BundleStore(root, fsync=False).load_latest()
    assert got.diff(bundles[-1])["equal"]
    assert got.generation == 4
    assert got.source_path == store.path_of(4)
    with pytest.raises(CheckpointError, match="keep"):
        BundleStore(root, keep=0)


def test_bundle_store_self_heals_and_quarantines(tmp_path):
    """Self-healing restore: a corrupted newest generation is moved to
    quarantine/ with a typed BundleFault, load_latest falls back to the
    newest VALID generation bit-identically, and the fallback/quarantine
    counters + TR_CKPT records fire."""
    from hclib_tpu.device import tracebuf as tb

    root = str(tmp_path / "store")
    reg = hc.MetricsRegistry()
    store = BundleStore(root, keep=3, fsync=False, metrics=reg)
    good = _waits_bundle(seed=1)
    store.save(good)
    store.save(_waits_bundle(seed=2))
    npz = os.path.join(store.path_of(2), "state.npz")
    blob = open(npz, "rb").read()
    with open(npz, "wb") as f:
        f.write(blob[:-4] + b"\xff" * 4)
    healer = BundleStore(root, keep=3, fsync=False, metrics=reg)
    back = healer.load_latest()
    assert back.generation == 1 and back.diff(good)["equal"]
    assert [isinstance(f, BundleFault) for f in healer.faults] == [True]
    f = healer.faults[0]
    assert (f.generation, f.reason) == (2, "corrupt")
    assert "quarantine" in f.path and os.path.isdir(f.path)
    assert healer.generations() == [1]  # the damaged one moved aside
    m = reg.snapshot()["metrics"]
    assert m["checkpoint.quarantined.count"] == 1
    assert m["checkpoint.fallback.count"] == 1
    assert m["checkpoint.load.count"] == 1
    assert m["checkpoint.save.count"] == 2
    # Every host record decodes through the CK_* name table.
    codes = [-int(r[2]) - 1 for r in healer.events]
    assert codes == [tb.CK_QUARANTINE, tb.CK_FALLBACK, tb.CK_LOAD]
    assert all(c in tb.CK_NAMES for c in codes)
    info = healer.trace_info()
    assert info["rings"][0]["written"] == 3


def test_bundle_store_unrecoverable_raises_with_every_fault(tmp_path):
    """No valid generation -> CheckpointError naming EVERY fault and
    the poison handoff (the degradation-ladder contract), never a hang
    or a partial restore."""
    root = str(tmp_path / "store")
    store = BundleStore(root, keep=3, fsync=False)
    store.save(_waits_bundle(seed=1))
    store.save(_waits_bundle(seed=2))
    for g in store.generations():
        os.remove(os.path.join(store.path_of(g), "manifest.json"))
    healer = BundleStore(root, fsync=False)
    with pytest.raises(CheckpointError) as ei:
        healer.load_latest()
    msg = str(ei.value)
    assert "unrecoverable" in msg and "poison" in msg
    assert "gen 1" in msg and "gen 2" in msg
    assert all(f.reason == "torn" for f in healer.faults)
    # An empty store raises too (cold start is explicit, not a wedge).
    with pytest.raises(CheckpointError, match="no generations"):
        BundleStore(str(tmp_path / "empty"), fsync=False).load_latest()


def test_bundle_store_crash_sites_leave_staging_invisible(tmp_path):
    """FaultPlan preempt-mid-save dies BEFORE the rename: the store is
    unchanged and the staged dir invisible; preempt-mid-restore retries
    idempotently (quarantine moves are re-entrant)."""
    from hclib_tpu.runtime.resilience import FaultPlan, InjectedFault

    root = str(tmp_path / "store")
    good = _waits_bundle(seed=3)
    BundleStore(root, fsync=False).save(good)
    plan = FaultPlan(seed=0, preempt_save_at=0)
    writer = BundleStore(root, fsync=False, fault_plan=plan)
    with pytest.raises(InjectedFault, match="mid-save"):
        writer.save(_waits_bundle(seed=4))
    after = BundleStore(root, fsync=False)
    assert after.generations() == [1]
    assert after.load_latest().diff(good)["equal"]
    # A later clean save reuses the staging slot and publishes.
    assert BundleStore(root, fsync=False).save(_waits_bundle(seed=5)) == 2
    plan = FaultPlan(seed=0, preempt_restore_at=0)
    reader = BundleStore(root, fsync=False, fault_plan=plan)
    with pytest.raises(InjectedFault, match="mid-restore"):
        reader.load_latest()
    assert reader.load_latest().generation == 2  # the retry succeeds


def test_bundle_store_env_knobs(tmp_path, monkeypatch):
    """SATELLITE: HCLIB_TPU_CKPT_DIR roots default_store();
    HCLIB_TPU_CKPT_KEEP sets retention (malformed text raises, naming
    the variable); HCLIB_TPU_CKPT_FSYNC=0 selects the fast mode."""
    monkeypatch.delenv("HCLIB_TPU_CKPT_DIR", raising=False)
    assert default_store() is None
    root = str(tmp_path / "envstore")
    monkeypatch.setenv("HCLIB_TPU_CKPT_DIR", root)
    monkeypatch.setenv("HCLIB_TPU_CKPT_KEEP", "2")
    monkeypatch.setenv("HCLIB_TPU_CKPT_FSYNC", "0")
    store = default_store()
    assert store is not None and store.root == root
    assert store.keep == 2 and store.fsync is False
    for i in range(3):
        store.save(_waits_bundle(seed=i))
    assert store.generations() == [2, 3]
    monkeypatch.setenv("HCLIB_TPU_CKPT_KEEP", "junk")
    with pytest.raises(ValueError, match="HCLIB_TPU_CKPT_KEEP"):
        default_store()


def test_bundle_load_errors_name_path_and_generation(tmp_path):
    """SATELLITE: version/corruption errors name the offending FILE and
    store generation; a kernel-table mismatch carries the positional
    diff AND the bundle's provenance."""
    import json
    import types

    root = str(tmp_path / "store")
    store = BundleStore(root, fsync=False)
    b = _waits_bundle(seed=1)
    b.meta.update({"kernel_names": ["seed", "waiter"], "capacity": 8,
                   "num_values": 16, "succ_capacity": 8,
                   "data_specs": {}})
    store.save(b)
    man_path = os.path.join(store.path_of(1), "manifest.json")
    man = json.load(open(man_path))
    man["version"] = 9
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointError) as ei:
        CheckpointBundle.load(store.path_of(1), generation=1)
    assert man_path in str(ei.value) and "(generation 1)" in str(ei.value)
    man["version"] = 1
    json.dump(man, open(man_path, "w"))
    loaded = CheckpointBundle.load(store.path_of(1), generation=1)
    mk = types.SimpleNamespace(
        kernel_names=["waiter", "seed"], capacity=8, num_values=16,
        succ_capacity=8, data_specs={},
    )
    from hclib_tpu.runtime.checkpoint import _check_kernel_meta, _where

    with pytest.raises(CheckpointError) as ei:
        _check_kernel_meta(mk, loaded.meta, where=_where(loaded))
    msg = str(ei.value)
    assert "[0] 'waiter' != 'seed' in the bundle" in msg.replace(
        "'waiter' here", "'waiter'"
    )
    assert "generation 1" in msg and store.path_of(1) in msg


def test_bundle_store_model_certifies_publish_ordering():
    """SATELLITE: the BundleStoreModel explores save x crash x
    concurrent-load clean under the shipped rename-LAST ordering, and
    catches the planted publish-before-manifest bug with a concrete
    witness."""
    from hclib_tpu.analysis.explore import BundleStoreModel, explore

    ok = explore(BundleStoreModel(saves=2, crash=True, max_reads=2),
                 depth=64, budget_s=20)
    assert ok.complete and ok.clean, ok.violations
    bad = explore(
        BundleStoreModel(saves=2, crash=True, max_reads=2,
                         publish_before_manifest=True),
        depth=64, budget_s=20,
    )
    assert not bad.clean
    assert any("partial generation" in v.message for v in bad.violations)
    assert all(v.witness for v in bad.violations)


def test_autoscaler_resume_from_store_root(tmp_path):
    """SATELLITE: Autoscaler.run(resume_bundle=<store root>) walks the
    generational store with the self-healing load_latest - and an
    unrecoverable root raises the poison diagnostic instead of
    wedging."""
    from hclib_tpu.runtime.autoscaler import Autoscaler

    root = str(tmp_path / "store")
    BundleStore(root, fsync=False).save(_waits_bundle(seed=7))
    scaler = Autoscaler(lambda ndev: None, checkpoint_dir=root)
    # The store root resolves through load_latest; the resolved bundle
    # then fails the resident-kind gate only if damaged - here it
    # reaches kernel construction (our stub factory returns None).
    with pytest.raises(AttributeError):
        scaler.run(resume_bundle=root)
    for g in BundleStore(root, fsync=False).generations():
        os.remove(os.path.join(root, f"gen-{g:06d}", "manifest.json"))
    with pytest.raises(CheckpointError, match="unrecoverable"):
        scaler.run(resume_bundle=root)


# --------------------------------------- dyngraph bundles (ISSUE 20)


def _dyngraph_fixture(applied, *, serve_query=True, residue=True):
    """A synthetic ``ndev=4`` dyngraph bundle: each device has applied
    the uids in ``applied[d]`` (in that order - the host mirror of the
    device splice arithmetic), labels show divergent partial progress,
    and the scheduler holds residue rows (each device's UNapplied
    updates, a dynamic EXPAND, one pending QUERY). Returns
    ``(bundle, graph, ups, iv, counts)``."""
    from hclib_tpu.device.descriptor import (
        DESC_WORDS, F_A0, F_FN, F_OUT, NO_TASK,
    )
    from hclib_tpu.device.dyngraph import (
        DG_QUERY, DG_UPDATE, DynGraph, V_FREE, V_QUERIES, V_UPDATES,
        _bind_updates, make_dyngraph_megakernel,
    )
    from hclib_tpu.device.frontier import (
        EBLOCK, INF, V_EDGES, V_RELAX, VT_BASE,
    )
    from hclib_tpu.device.megakernel import (
        C_ALLOC, C_EXECUTED, C_PENDING, C_VALLOC,
    )

    rng = np.random.default_rng(0)
    n, m = 12, 40
    g = DynGraph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                 rng.integers(1, 8, m), spare_blocks=2, upd_cap=8)
    ups = [(1, 5, 3), (2, 7, 1), (1, 9, 2), (4, 3, 6)]
    for u, v, w in ups:
        g.add_update(u, v, w)
    mk = make_dyngraph_megakernel("sssp", g, width=0, interpret=True)
    _bind_updates(mk, g)

    ndev, cap, V = 4, 32, mk.num_values
    sb, spare, bcs = g.spare_base, g.spare, g.blk_count.astype(np.int64)
    flag_base, st = g.flag_base, g.st_base
    iv = np.zeros((ndev, V), np.int64)
    ind = np.zeros((ndev,) + g.indices.shape, np.int32)
    wgt = np.zeros((ndev,) + g.weights.shape, np.int32)
    for d in range(ndev):
        iv[d] = g.preset_values(V, INF)
        ind[d] = g.indices
        wgt[d] = g.weights

    def apply_on(d, uid):
        u, v, w = ups[uid]
        vt = iv[d, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
        deg, bc = int(vt[u, 2]), int(vt[u, 1])
        if deg == bc * EBLOCK:
            r = sb + u * spare + (bc - int(bcs[u]))
            ind[d, r, :] = -1
            wgt[d, r, :] = 0
            ind[d, r, 0] = v
            wgt[d, r, 0] = w
            vt[u, 1] = bc + 1
            iv[d, V_FREE] += 1
        else:
            blk = deg // EBLOCK
            r = (int(vt[u, 0]) + blk if blk < int(bcs[u])
                 else sb + u * spare + (blk - int(bcs[u])))
            ind[d, r, deg % EBLOCK] = v
            wgt[d, r, deg % EBLOCK] = w
        vt[u, 2] = deg + 1
        iv[d, flag_base + uid] = 1
        iv[d, V_UPDATES] += 1

    for d, uids in applied.items():
        for uid in uids:
            apply_on(d, uid)
    for d in range(ndev):
        iv[d, st] = 0
        for vtx in range(1, n):
            iv[d, st + vtx] = INF if (vtx + d) % 3 else 10 + vtx + d
        iv[d, V_EDGES] = 5 + d
        iv[d, V_RELAX] = 2 + d
    if serve_query:  # one served query on device 1, out slot st + n
        iv[1, V_QUERIES] = 1
        iv[1, st + n] = 13

    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    counts = np.zeros((ndev, 8), np.int32)
    ready = np.full((ndev, cap), NO_TASK, np.int32)
    succ = np.full((ndev, 16), NO_TASK, np.int32)
    for d in range(ndev):
        rows = []
        for uid in range(len(ups)):
            if uid not in applied[d]:
                u, v, w = ups[uid]
                r = np.zeros(DESC_WORDS, np.int32)
                r[F_FN] = DG_UPDATE
                r[F_A0:F_A0 + 4] = (u, v, w, uid)
                r[2] = r[3] = r[13] = NO_TASK
                rows.append(r)
        if residue:
            r = np.zeros(DESC_WORDS, np.int32)  # a dynamic EXPAND
            r[F_FN] = 0
            r[F_A0:F_A0 + 2] = (d % n, 4)
            r[2] = r[3] = r[13] = NO_TASK
            rows.append(r)
            if d == 2:  # one pending QUERY
                r = np.zeros(DESC_WORDS, np.int32)
                r[F_FN] = DG_QUERY
                r[F_A0] = 7
                r[F_OUT] = st + n + 1
                r[2] = r[3] = r[13] = NO_TASK
                rows.append(r)
        for i, r in enumerate(rows):
            tasks[d, i] = r
            ready[d, i] = i
        counts[d, 1] = counts[d, C_ALLOC] = len(rows)
        counts[d, C_PENDING] = len(rows)
        counts[d, C_VALLOC] = g.num_value_slots
        counts[d, C_EXECUTED] = 3 + d
    arrays = {
        "tasks": tasks, "succ": succ, "ready": ready, "counts": counts,
        "ivalues": iv.astype(np.int32),
        "data/indices": ind, "data/weights": wgt,
    }
    meta = {"ndev": ndev, "dyngraph": dict(mk._dyngraph),
            "kernel_names": list(mk.kernel_names)}
    return CheckpointBundle("resident", meta, arrays), g, ups, iv, counts


def test_dyngraph_reshard_shrink_grow_conserves():
    """4 -> 2 -> 4: the canonical rebuilt adjacency broadcasts
    identically, edge count conserves (static + union-applied), labels
    min-fold, accumulators sum-fold, the served query value survives,
    and residue deals without loss."""
    from hclib_tpu.device.frontier import V_EDGES, VT_BASE
    from hclib_tpu.device.dyngraph import V_QUERIES
    from hclib_tpu.device.megakernel import C_EXECUTED, C_PENDING

    applied = {d: [u for u in range(4) if (u + d) % 2 == 0]
               for d in range(4)}
    applied[1] = applied[1][::-1]  # order-divergent application
    applied[3] = applied[3][::-1]
    bundle, g, ups, iv, counts = _dyngraph_fixture(applied)
    n, st = g.n, g.st_base

    b2 = bundle.reshard(2)
    assert b2.meta["ndev"] == 2
    assert b2.meta["dyngraph_reshard"]["union_applied"] == 4
    assert b2.meta["dyngraph_reshard"]["pending_updates"] == 0
    i2 = b2.arrays["data/indices"]
    assert np.array_equal(i2[0], i2[1])  # canonical broadcast
    iv2 = b2.arrays["ivalues"].astype(np.int64)
    vt2 = iv2[0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt2[:, 2].sum()) == int(g.deg.sum()) + 4
    c2 = b2.arrays["counts"]
    assert int(c2[:, C_PENDING].sum()) == 5  # 4 EXPANDs + 1 QUERY dealt
    assert int(c2[:, C_EXECUTED].sum()) == int(counts[:, C_EXECUTED].sum())
    want = iv[:, st:st + n].min(axis=0)
    assert np.array_equal(iv2[0, st:st + n], want)
    assert np.array_equal(iv2[1, st:st + n], want)
    assert int(iv2[:, V_EDGES].sum()) == int(iv[:, V_EDGES].sum())
    assert int(iv2[:, V_QUERIES].sum()) == 1
    assert int(iv2[0, st + n]) == 13  # served query value max-folds

    b3 = b2.reshard(4)  # grow back
    assert b3.meta["ndev"] == 4 and b3.meta["resharded_from"] == 2
    for d in range(4):
        assert np.array_equal(b3.arrays["data/indices"][d], i2[0])
    iv3 = b3.arrays["ivalues"].astype(np.int64)
    vt3 = iv3[0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt3[:, 2].sum()) == int(g.deg.sum()) + 4
    assert int(iv3[:, V_EDGES].sum()) == int(iv[:, V_EDGES].sum())


def test_dyngraph_reshard_broadcasts_unapplied_update():
    """A pending update NO replica has applied dedupes by uid and
    broadcasts to every new device - the mesh invariant 'every replica
    sees every update' survives the resize."""
    from hclib_tpu.device.descriptor import F_A0, F_FN
    from hclib_tpu.device.dyngraph import DG_UPDATE
    from hclib_tpu.device.frontier import VT_BASE
    from hclib_tpu.device.megakernel import C_ALLOC

    applied = {0: [0], 1: [1, 0], 2: [], 3: [2]}  # uid 3 nowhere
    bundle, g, ups, _, _ = _dyngraph_fixture(
        applied, serve_query=False, residue=False,
    )
    b2 = bundle.reshard(2)
    rs = b2.meta["dyngraph_reshard"]
    assert rs["union_applied"] == 3 and rs["pending_updates"] == 1
    t, c = b2.arrays["tasks"], b2.arrays["counts"]
    for j in range(2):
        uids = [int(t[j, i, F_A0 + 3]) for i in range(int(c[j, C_ALLOC]))
                if int(t[j, i, F_FN]) == DG_UPDATE]
        assert uids == [3], uids
    n = g.n
    vt = b2.arrays["ivalues"][0, VT_BASE:VT_BASE + 3 * n].reshape(n, 3)
    assert int(vt[:, 2].sum()) == int(g.deg.sum()) + 3


def test_dyngraph_reshard_refusals():
    """Structured refusals: pagerank mid-run (no device-count-free
    fold), dropped splices (adjacency no longer the stream's), and
    foreign data buffers."""
    from hclib_tpu.device.dyngraph import V_DROPPED

    applied = {0: [0, 1, 2, 3], 1: [], 2: [], 3: []}
    bundle, g, ups, _, _ = _dyngraph_fixture(applied)

    pr = CheckpointBundle(
        bundle.kind,
        {**bundle.meta,
         "dyngraph": {**bundle.meta["dyngraph"], "kind": "pagerank"}},
        bundle.arrays,
    )
    with pytest.raises(CheckpointError, match="pagerank"):
        pr.reshard(2)

    dropped = {k: np.array(v) for k, v in bundle.arrays.items()}
    dropped["ivalues"] = dropped["ivalues"].copy()
    dropped["ivalues"][2, V_DROPPED] = 1
    with pytest.raises(CheckpointError, match="spare"):
        CheckpointBundle(bundle.kind, bundle.meta, dropped).reshard(2)

    extra = dict(bundle.arrays)
    extra["data/other"] = np.zeros((4, 8), np.int32)
    with pytest.raises(CheckpointError, match="extra data buffers"):
        CheckpointBundle(bundle.kind, bundle.meta, extra).reshard(2)
