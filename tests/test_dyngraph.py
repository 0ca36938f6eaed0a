"""Dynamic graph service (ISSUE 20): mutable blocked-CSR adjacency
with per-vertex spare blocks, UPDATE splices + QUERY reads riding the
scheduler as descriptor kinds, and incremental recompute.

The acceptance spine: the mutated fixpoint is bit-identical to the
from-scratch host reference on the mutated graph across the scalar,
batched, bucketed, and 4-device mesh arms (pagerank: mass conserved
exactly); spare exhaustion DROPS the splice and raises overflow rather
than corrupting static rows; the splice protocol is machine-checked
(hclint ``check_splice``) and the schedule-independence claim
certifies bound streams; static frontier builds compile zero new
device words with the dyngraph module loaded.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import CHILD_SECONDS

from hclib_tpu.analysis.model import certify_claim, certify_dyngraph_schedule
from hclib_tpu.analysis.races import check_splice
from hclib_tpu.device.dyngraph import (
    DynGraph,
    host_dyngraph,
    host_incremental,
    host_incremental_pagerank,
    make_dyngraph_megakernel,
    run_dyngraph,
    serve_dyngraph,
)
from hclib_tpu.device.frontier import EBLOCK, INF
from hclib_tpu.device.tracebuf import TR_SPLICE, records_of
from hclib_tpu.device.workloads import rmat_edges
from hclib_tpu.runtime.locality import MeshPlacement

# One small seeded R-MAT shared by every arm (each distinct build is an
# XLA compile; the program cache dedupes content-identical rebuilds).
N, SRC, DST, W = rmat_edges(5, efactor=4, seed=9)
UPS = [(1, 5, 3), (2, 7, 1), (0, 9, 2), (4, 3, 6)]
M0, REPS = 1 << 12, 64


def _graph(**kw):
    kw.setdefault("spare_blocks", 2)
    kw.setdefault("upd_cap", 16)
    return DynGraph(N, SRC, DST, W, **kw)


# ------------------------------------------------- container + stream


def test_dyngraph_container_layout_and_update_stream():
    g = _graph()
    # Spare rows appended behind the static blocked-CSR rows, pristine.
    assert g.nblocks == g.spare_base + g.n * g.spare
    assert g.indices.shape[0] == g.nblocks
    assert (g.indices[g.spare_base:] == -1).all()
    assert (g.weights[g.spare_base:] == 0).all()
    # Value-slot layout: counters | vt | static counts | flags | state.
    iv = g.preset_values(g.num_value_slots, INF)
    assert np.array_equal(
        iv[g.bcs_base : g.bcs_base + g.n], g.blk_count
    )
    assert (iv[g.flag_base : g.flag_base + g.upd_cap] == 0).all()
    # The stream: uids are dense, endpoints validated.
    assert g.add_update(1, 5, 3) == 0
    assert g.add_update(2, 7) == 1
    with pytest.raises(ValueError, match="out of range"):
        g.add_update(0, g.n)
    with pytest.raises(ValueError, match="weight"):
        g.add_update(0, 1, -2)
    tight = DynGraph(N, SRC, DST, W, spare_blocks=1, upd_cap=1)
    tight.add_update(0, 1)
    with pytest.raises(ValueError, match="upd_cap"):
        tight.add_update(1, 2)
    with pytest.raises(ValueError, match="spare_blocks"):
        DynGraph(N, SRC, DST, W, spare_blocks=-1)
    # The host twin: static edges + the registered stream.
    tw = g.mutated()
    assert int(tw.deg.sum()) == int(g.deg.sum()) + 2
    assert g.spare_needed() <= 2


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", "3")
    assert DynGraph(N, SRC, DST, W).spare == 3
    monkeypatch.setenv("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", "0")
    with pytest.raises(ValueError, match="SPARE_BLOCKS"):
        DynGraph(N, SRC, DST, W)
    monkeypatch.setenv("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", "two")
    with pytest.raises(ValueError):
        DynGraph(N, SRC, DST, W)
    monkeypatch.delenv("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", raising=False)
    # UPDATE_PRIORITY stamps the bucketed build (clamped to the range).
    monkeypatch.setenv("HCLIB_TPU_DYNGRAPH_UPDATE_PRIORITY", "1")
    mk = make_dyngraph_megakernel(
        "bfs", _graph(), width=4, interpret=True, priority_buckets=2,
    )
    assert mk._dyngraph["update_priority"] == 1
    monkeypatch.setenv("HCLIB_TPU_DYNGRAPH_UPDATE_PRIORITY", "9")
    mk2 = make_dyngraph_megakernel(
        "bfs", _graph(), width=4, interpret=True, priority_buckets=2,
    )
    assert mk2._dyngraph["update_priority"] == 1  # clamped to B-1
    # Bucket rings layer over batch lanes: the scalar arm refuses them.
    with pytest.raises(ValueError, match="batched arm"):
        make_dyngraph_megakernel(
            "bfs", _graph(), width=0, interpret=True, priority_buckets=2,
        )


# ------------------------------------------------ bit-identity arms


def test_scalar_update_storm_bit_identical_and_counters():
    g = _graph()
    res, info = run_dyngraph(
        "sssp", g, 0, updates=UPS, queries=[0, 5, 9], width=0,
        interpret=True,
    )
    ref = host_dyngraph("sssp", g, 0)  # after registration: mutated
    assert np.array_equal(res, ref)
    assert info["updates_applied"] == len(UPS)
    assert info["dropped"] == 0
    assert info["spare_in_use"] == g.spare_needed()
    assert info["queries"] == 3 and len(info["query_values"]) == 3
    # The incremental host twin lands on the same fixpoint.
    assert np.array_equal(host_incremental("sssp", g, src=0), ref)


def test_batched_and_bucketed_arms_bit_identical():
    g = _graph()
    res, info = run_dyngraph(
        "bfs", g, 0, updates=UPS, width=4, interpret=True,
    )
    assert np.array_equal(res, host_dyngraph("bfs", g, 0))
    assert info["updates_applied"] == len(UPS)
    g2 = _graph()
    res2, _ = run_dyngraph(
        "bfs", g2, 0, updates=UPS, width=4, interpret=True,
        priority_buckets=2, update_priority=0,
    )
    assert np.array_equal(res2, host_dyngraph("bfs", g2, 0))


def test_mesh_update_broadcast_bit_identical():
    """4-device mesh: the update stream broadcasts to every replica
    (idempotent splices), EXPANDs migrate, labels min-combine - the
    fixpoint is exactly the mutated single-device result."""
    g = _graph()
    res, info = run_dyngraph(
        "sssp", g, 0, updates=UPS, queries=[3], width=4, capacity=256,
        interpret=True, placement=MeshPlacement(4, policy="block"),
        quantum=4, window=8,
    )
    assert np.array_equal(res, host_dyngraph("sssp", g, 0))
    assert info["updates_applied"] == len(UPS)
    assert info["dropped"] == 0


def test_pagerank_mass_conserved_under_updates():
    g = _graph()
    res, info = run_dyngraph(
        "pagerank", g, updates=UPS, width=0, m0=M0, reps=REPS,
        interpret=True, capacity=768,
    )
    twin, _ = host_incremental_pagerank(g, m0=M0, reps=REPS)
    assert int(res.sum()) == int(twin.sum())
    assert info["updates_applied"] == len(UPS)


def test_spare_exhaustion_drops_and_raises_overflow():
    """A full tail with no spare ordinal left DROPS the splice (flagged
    as engine overflow - the run raises instead of corrupting static
    rows), and the host mirror excludes the drop identically."""
    n = 8
    src = np.concatenate([np.zeros(EBLOCK, np.int64), [1]])
    dst = np.concatenate(
        [1 + np.arange(EBLOCK) % (n - 1), [2]]
    ).astype(np.int64)
    g = DynGraph(n, src, dst, np.ones(len(src), np.int64),
                 spare_blocks=0, upd_cap=4)
    with pytest.raises(RuntimeError, match="overflow"):
        run_dyngraph(
            "bfs", g, 0, updates=[(0, 7, 1), (1, 3, 1)], width=0,
            interpret=True,
        )
    # Host mirror of the drop rule: vertex 0's tail is full (deg ==
    # EBLOCK, spare 0) so its insert is excluded; vertex 1 has slack.
    assert g.spare_needed() == 0
    tw = g.mutated()
    assert int(tw.deg.sum()) == int(g.deg.sum()) + 1
    assert np.array_equal(
        host_incremental("bfs", g, src=0), host_dyngraph("bfs", g, 0)
    )


# ---------------------------------------------- serving front door


def test_serve_two_tenants_update_query_futures():
    rng = np.random.default_rng(3)
    n, m = 24, 80
    g = DynGraph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                 rng.integers(1, 8, m), spare_blocks=2, upd_cap=16)
    res, info = serve_dyngraph(
        "sssp", g, src=0, updates=[(1, 5, 3), (2, 7, 1), (0, 9, 2)],
        queries=[0, 5, 9], interpret=True, ring_capacity=64,
        egress_depth=32, max_rounds=512,
    )
    assert np.array_equal(res, host_dyngraph("sssp", g, src=0))
    assert info["updates_applied"] == 3 and info["queries"] == 3
    assert all(f.state == "RESULT" for f in info["update_futures"])
    assert all(f.state == "RESULT" for f in info["query_futures"])
    # Drained stream: the published labels are exact, and the future
    # resolved to the same out-slot value the run reported.
    assert info["query_results"] == info["query_values"]
    assert info["query_results"][0] == 0  # the source's own label
    eg = info["serve_stats"]["egress"]
    assert eg["resolved"] == eg["submitted"] == 6
    r = records_of(info["splice_trace"], TR_SPLICE)
    assert r.shape[0] == 1 and int(r[0, 2]) >> 16 == 3
    # The stream front door is the scalar arm only.
    with pytest.raises(ValueError, match="scalar arm"):
        serve_dyngraph("sssp", _graph(), width=4, interpret=True)


# ------------------------------------- certification + splice lint


def test_certify_claim_unbound_then_bound():
    g = _graph()
    mk = make_dyngraph_megakernel("sssp", g, width=0, interpret=True)
    cert0 = certify_claim(mk)
    assert cert0["claim"] == "dyngraph"
    assert cert0["status"].startswith("unbound")
    res, _ = run_dyngraph(
        "sssp", g, 0, updates=UPS[:2], width=0, interpret=True, mk=mk,
    )
    assert np.array_equal(res, host_dyngraph("sssp", g, 0))
    cert = certify_claim(mk)
    assert cert["status"] == "certified"
    assert cert["updates"] == 2 and cert["orders"] >= 4


def test_certify_dyngraph_pagerank_conserves_mass():
    cert = certify_dyngraph_schedule(
        "pagerank", updates=UPS[:2], perms=2,
    )
    assert cert["status"] == "certified" and cert["mass"] > 0


def test_check_splice_protocol_and_negatives():
    g = _graph()
    mk = make_dyngraph_megakernel("bfs", g, width=4, interpret=True)
    assert not check_splice(mk).errors()

    # (2) spare-region bounds wiring must be exact.
    mk._dyngraph["total_blocks"] += 1
    rep = check_splice(mk)
    assert any("bounds disagree" in f.message for f in rep.errors())
    mk._dyngraph["total_blocks"] -= 1

    # (1) no lane of a dyngraph build may run the cross-round prefetch.
    upd_spec = next(
        s for fid, s in mk.batch_specs
        if mk.kernel_names[fid] == "dg_update"
    )
    upd_spec.prefetch = True
    rep = check_splice(mk)
    assert any("prefetch" in f.message for f in rep.errors())
    upd_spec.prefetch = False

    # (3) the blind-overwrite exemption is scoped to the spare region:
    # pushing spare_base past the buffer makes the splice's blind
    # spare-row store look like a static-row write, which is refused.
    real = mk._dyngraph["spare_base"]
    mk._dyngraph["spare_base"] = 1 << 40
    rep = check_splice(mk)
    assert any("blind DMA store" in f.message for f in rep.errors())
    mk._dyngraph["spare_base"] = real
    assert not check_splice(mk).errors()


# --------------------------------------------------- off-path purity


_OFFPATH_SCRIPT = """
import hashlib
import numpy as np, jax
{extra}
from hclib_tpu.device.workloads import rmat_edges
from hclib_tpu.device.frontier import _KINDS, Graph, make_frontier_megakernel
from hclib_tpu.device.descriptor import TaskGraphBuilder
n, s, d, w = rmat_edges(4, efactor=3, seed=5)
g = Graph(n, s, d, w)
mk = make_frontier_megakernel(_KINDS["bfs"](), g, width=0, interpret=True)
tasks, succ, ring, counts = TaskGraphBuilder().finalize(
    capacity=mk.capacity, succ_capacity=mk.succ_capacity)
args = [tasks, succ, ring, counts, np.zeros(mk.num_values, np.int32)]
for sp in mk.data_specs.values():
    args.append(np.zeros(sp.shape, sp.dtype))
structs = [jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)
           for x in args]
text = mk._build_raw(1 << 12).lower(*structs).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def _offpath_hash(extra: str) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    ))
    out = subprocess.run(
        [sys.executable, "-c", _OFFPATH_SCRIPT.format(extra=extra)],
        capture_output=True, text=True, env=env, timeout=CHILD_SECONDS,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_static_frontier_lowered_text_unchanged_by_dyngraph():
    """Zero new device words off-path: a STATIC frontier build lowers
    to byte-identical text whether or not the dyngraph module was ever
    imported (the spawn hook defaults compile out entirely)."""
    plain = _offpath_hash("")
    with_dg = _offpath_hash("import hclib_tpu.device.dyngraph")
    assert plain == with_dg
