"""SparseLU with task dependences on the device (ISSUE 58): the program
against BOTS' sequential ``sparselu_seq_call`` in numpy float32
(``benchmarks/reference/sparselu.py``), element by element, on the CPU
interpreter; the release's counters against the symbolic factorisation and
against the schedule replayed on the host; the tile LU against numpy."""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import sparselu as ref
from hclib_tpu.device import block_release as br
from hclib_tpu.device.sparselu import (
    PANEL_WIDTH, UPDATE_WIDTH, device_sparselu, make_sparselu_megakernel,
)
from hclib_tpu.models import sparselu as model
from hclib_tpu.ops.tiles import lu_and_inv

M = 128
# the cell's limit on the componentwise backward error (PERF.md section 2)
LIMIT = json.load(open(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "sparselu-taskdep.json"
)))["guarantees"]["residual_limit"]
# |program - BOTS' sequential float32| an element, over the diagonal's
# scale: both round at float32's 6e-8 a step, the program's 3-pass bf16
# products at 2^-16 of a product (PERF.md section 2).
TOL = 4e-6


def _arrow(n: int) -> np.ndarray:
    """Full first row and column, the diagonal: the first step fills
    every block."""
    p = np.eye(n, dtype=bool)
    p[0, :] = p[:, 0] = True
    return p


def _band(n: int) -> np.ndarray:
    """Block tridiagonal: no step fills anything."""
    i, j = np.indices((n, n))
    return abs(i - j) <= 1


PATTERNS = {
    "genmat4": lambda: ref.genmat_pattern(4),
    "genmat6": lambda: ref.genmat_pattern(6),
    "genmat8": lambda: ref.genmat_pattern(8),
    "band5_no_fill": lambda: _band(5),
    "arrow5_fills_completely": lambda: _arrow(5),
}


@functools.lru_cache(maxsize=None)
def _build(name: str):
    p = PATTERNS[name]()
    return make_sparselu_megakernel(len(p), M, pattern=p, interpret=True)


def _blocks(p, seed):
    return np.asarray(ref.make_blocks(seed, p, M))


def _sequential(a, sym):
    A = ref.sparselu_seq(a, sym.present)
    return np.stack([A[i, j] for i, j in zip(sym.rows, sym.cols)])


def _close(got, want, shift):
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOL * shift


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_program_against_the_sequential_reference(name):
    mk = _build(name)
    sym, plan = mk.slu_sym, mk.slu_plan
    want_sym = ref.symbolic(sym.present)
    assert sym.counts == want_sym["counts"]
    assert (sym.final == want_sym["final"]).all()
    a = _blocks(sym.present, 58)
    factor, info = device_sparselu(a, mk=mk)
    slu = info["sparselu"]
    # the four counts and the fill count against the symbolic ones
    assert {k: slu[k] for k in sym.counts} == sym.counts
    assert slu["fill_blocks"] == sym.fill_blocks == want_sym["fill_blocks"]
    if name == "band5_no_fill":
        assert sym.fill_blocks == 0
    if name == "arrow5_fills_completely":
        assert sym.final.all() and sym.fill_blocks == 25 - 13
    # every task but the root is made on the device, straight on its lane
    # (the diagonal tasks and the ranges through the ring)
    assert slu["released"] == sym.tasks - 1
    assert info["executed"] == sym.tasks + slu["scans"]
    assert info["tiers"]["batch_tasks"] == sym.tasks - sym.n
    assert info["tiers"]["direct"] == sym.tasks - sym.n
    assert info["tiers"]["routed"] == 0
    assert info["pending"] == 0 and not info["overflow"]
    # the table's high-water, and every other counter, against the replay
    replay = plan.simulate(PANEL_WIDTH, UPDATE_WIDTH)
    assert {k: slu[k] for k in replay} == replay
    assert slu["live_rows_max"] < mk.capacity
    # the factor, element by element; the slot map is the final pattern
    rows, cols = ref.slots(sym.present, want_sym["final"])
    assert (slu["rows"] == rows).all() and (slu["cols"] == cols).all()
    _close(np.asarray(factor), _sequential(a, sym),
           ref.diag_shift(sym.present, M))
    read = ref.readings(factor, a, sym.present, sym.final)
    assert read["finite"] and read["residual"] < LIMIT


def test_two_calls_on_one_build_leave_no_residue():
    """The input is not consumed; the second call factors another matrix
    into the first call's factor (and a third into a buffer of NaN) and
    nothing of what the buffer held shows."""
    mk = _build("genmat6")
    sym = mk.slu_sym
    shift = ref.diag_shift(sym.present, M)
    a1 = jnp.asarray(_blocks(sym.present, 1))
    a2 = jnp.asarray(_blocks(sym.present, 2**31 + 2))
    f1, _ = device_sparselu(a1, mk=mk)
    want1 = _sequential(np.asarray(a1), sym)
    _close(np.asarray(f1), want1, shift)
    f2, info = device_sparselu(a2, mk=mk, out=f1)
    assert info["sparselu"]["fill_blocks"] == sym.fill_blocks
    want2 = _sequential(np.asarray(a2), sym)
    _close(np.asarray(f2), want2, shift)
    poison = jnp.full((sym.slots, M, M), jnp.nan, jnp.float32)
    f3, _ = device_sparselu(a1, mk=mk, out=poison)
    assert np.array_equal(np.asarray(f3), np.asarray(
        device_sparselu(a1, mk=mk)[0]))
    _close(np.asarray(f3), want1, shift)
    assert not a1.is_deleted() and not a2.is_deleted()


@pytest.mark.parametrize("n", [50, 96, 100, 128])
def test_replay_of_the_sources_classes(n):
    """The schedule replayed on the host at the source's own sizes: all
    but the root released, the counts the symbolic ones, and a table of
    the live front only."""
    sym = model.symbolic(model.genmat_pattern(n))
    plan = br.BlockPlan(sym.present, sym.final, sym.slot_of)
    r = plan.simulate(PANEL_WIDTH, UPDATE_WIDTH)
    assert {k: r[k] for k in sym.counts} == sym.counts
    assert r["released"] == sym.tasks - 1
    assert r["fill_blocks"] == sym.fill_blocks
    assert r["live_rows_max"] < 160 < sym.widest_step
    assert r["bmod_rounds"] * UPDATE_WIDTH < 1.01 * sym.counts["bmod"] + 64
    if n == 128:
        assert sym.counts == dict(lu0=128, fwd=4096, bdiv=4096, bmod=174784)
        assert (sym.n_present, sym.fill_blocks, sym.slots) == (1768, 6552,
                                                               8320)
        assert sym.widest_step == 4096 and r["live_rows_max"] == 115


class _Values:
    def __init__(self, vals):
        self.vals = vals

    def value(self, i):
        return self.vals[i]


@pytest.mark.parametrize("seed", range(4))
def test_next_bit_against_python(seed):
    sym = model.symbolic(model.genmat_pattern(70))
    plan = br.BlockPlan(sym.present, sym.final, sym.slot_of)
    vals = jnp.asarray(plan.presets())
    rng = np.random.default_rng(seed)
    f = jax.jit(lambda a, b, after: plan.next_bit(
        _Values(vals), plan.row_base + a * plan.nw,
        plan.col_base + b * plan.nw, after))
    for _ in range(60):
        ii, jj = (int(x) for x in rng.integers(0, 70, 2))
        after = int(rng.integers(-1, 70))
        x = (plan.rowmask[ii] & plan.colmask[jj]) >> (after + 1)
        want = after + 1 + ((x & -x).bit_length() - 1) if x else 70
        assert int(f(ii, jj, after)) == want, (ii, jj, after)


@pytest.mark.parametrize("ts", [8, 64, 128])
def test_tile_lu_against_numpy_on_a_dominant_tile(ts):
    rng = np.random.default_rng(ts)
    a = rng.uniform(-2, 2, (ts, ts)).astype(np.float32)
    a += np.float32(2 * ts) * np.eye(ts, dtype=np.float32)
    lu, il, iu = (np.asarray(x, np.float64)
                  for x in jax.jit(lambda t: lu_and_inv(t, ts))(a))
    want = a.astype(np.float64)
    for k in range(ts):  # LU without pivoting, in float64
        want[k + 1:, k] /= want[k, k]
        want[k + 1:, k + 1:] -= np.outer(want[k + 1:, k], want[k, k + 1:])
    assert np.abs(lu - want).max() <= 4e-6 * 2 * ts
    low = np.tril(want, -1) + np.eye(ts)
    assert np.abs(il @ low - np.eye(ts)).max() < 1e-5
    assert np.abs(np.triu(want) @ iu - np.eye(ts)).max() < 1e-4


def test_host_model_factors_and_fills():
    r = model.run(8, 16)
    assert r["ok"] and r["tasks"] == 84 and r["fill_blocks"] == 12


@pytest.mark.parametrize("n", [5, 8, 50, 128])
def test_host_model_and_reference_agree_on_the_structure(n):
    """The pattern rule, the symbolic factorisation and the slot order are
    written twice (the reference imports nothing of the program): neither
    copy may drift."""
    p = ref.genmat_pattern(n)
    assert (model.genmat_pattern(n) == p).all()
    sym, want = model.symbolic(p), ref.symbolic(p)
    assert sym.counts == want["counts"] and sym.tasks == want["descriptors"]
    assert (sym.final == want["final"]).all()
    assert sym.fill_blocks == want["fill_blocks"]
    rows, cols = ref.slots(p, want["final"])
    assert (sym.rows == rows).all() and (sym.cols == cols).all()
    assert sym.flops(M) == ref.flops(want["counts"], M)
    assert len(ref.bmods(p)) == sym.counts["bmod"]


def test_host_model_against_the_sequential_reference():
    """The futures DAG on the host runtime against BOTS' sequential loop,
    on the reference's own matrix."""
    p = ref.genmat_pattern(6)
    sym = model.symbolic(p)
    a = np.asarray(ref.make_blocks(58, p, 16))
    got = model.sparselu_tasks(a, sym, nworkers=2)
    want = ref.sparselu_seq(a, p)
    assert np.isfinite(got).all()
    for s, (i, j) in enumerate(zip(sym.rows, sym.cols)):
        assert np.abs(got[s] - want[i, j]).max() < 1e-4, (i, j)
    dense = model.to_dense(a, sym.rows[:sym.n_present],
                           sym.cols[:sym.n_present], 6)
    assert (dense == np.asarray(ref.dense(
        a, sym.rows[:sym.n_present], sym.cols[:sym.n_present], 6))).all()


def test_lanes_stand_outside_the_prefetch_protocol_and_nothing_else():
    """The two lanes declare ``prefetch`` for its FIFO pop and spawn-time
    routing and load on demand: the one verifier rule they are excused
    from is the prefetch protocol's, and what the scheduler announces is
    out of the bodies' sight (a body that read it would not trace)."""
    mk = _build("genmat4")
    specs = [spec for _, spec in mk.batch_specs]
    assert len(specs) == 2 and mk.verify_suppress == ()
    for spec in specs:
        assert spec.prefetch and spec.verify_suppress == (
            "prefetch-protocol",)
    seen = {}

    class Ctx:
        width = 0

        def __init__(self):
            self.prefetched = self.buf = self.prefetch_count = 1

        def value(self, i):
            return 0

        def set_value(self, i, v):
            seen["rounds"] = i

    ctx = Ctx()
    from hclib_tpu.device.sparselu import _batch_round
    _batch_round(ctx, None, lambda: None, None, None, br.V_UPD_ROUNDS)
    assert seen == {"rounds": br.V_UPD_ROUNDS}
    assert not any(hasattr(ctx, k)
                   for k in ("prefetched", "buf", "prefetch_count"))
