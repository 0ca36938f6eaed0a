"""SparseLU with task dependences on the device (ISSUE 58): the program
against BOTS' sequential ``sparselu_seq_call`` in numpy float32
(``benchmarks/reference/sparselu.py``), element by element, on the CPU
interpreter; the release's counters against the symbolic factorisation and
against the schedule replayed on the host; the tile LU against numpy. The
lanes' cross-round prefetch (ISSUE 59): its counts against the replay's,
the verifier's rule, a run cut short with a prefetch in flight. The
interpreter performs a copy when it is WAITED, so a prefetched tile is
read a round after its start: what the protocol counts on not to change
in between is held to the reference here."""

import functools
import json
import os
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import sparselu as ref
from hclib_tpu.device import block_release as br
from hclib_tpu.device import tracebuf as tb
from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.sparselu import (
    PANEL_WIDTH, UPDATE_WIDTH, device_sparselu, make_sparselu_megakernel,
)
from hclib_tpu.models import sparselu as model
from hclib_tpu.ops.tiles import lu_and_inv
from hclib_tpu.runtime.resilience import StallError

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
    # 49 updates ready at once: the lanes fire with batches queued behind
    # them, and most rounds find their tiles prefetched
    "arrow8_prefetches": lambda: _arrow(8),
}


# This pattern's build carries the flight recorder (a ring of 256 records,
# the last ones kept): the test that cuts a run short reads the exit's
# records, and shares the build, and its whole program, with the others.
TRACED = "arrow8_prefetches"


@functools.lru_cache(maxsize=None)
def _build(name: str):
    p = PATTERNS[name]()
    env = {"HCLIB_TPU_TRACE": "256"} if name == TRACED else {}
    with mock.patch.dict(os.environ, env):
        return make_sparselu_megakernel(len(p), M, pattern=p,
                                        interpret=True)


@pytest.fixture(scope="module", params=sorted(PATTERNS))
def name(request):
    """A pattern of ``PATTERNS``. Of module scope, so that the tests of
    one pattern run one after the other and share its build and its run
    (a build takes the interpreter most of a minute to trace)."""
    return request.param


def _blocks(p, seed):
    return np.asarray(ref.make_blocks(seed, p, M))


@functools.lru_cache(maxsize=None)
def _run(name: str):
    """One call on pattern ``name``'s build: the matrix, the factor and
    ``info``, shared by the tests that read one run."""
    mk = _build(name)
    a = _blocks(mk.slu_sym.present, 58)
    factor, info = device_sparselu(a, mk=mk)
    return a, np.asarray(factor), info


def _schedule(plan):
    """The replay's counters and its schedule: ``(lane, tasks, prefetched,
    announced)`` a batch round, the entry a pop of the ring."""
    log = []
    return plan.simulate(PANEL_WIDTH, UPDATE_WIDTH, log), log


def _sequential(a, sym):
    A = ref.sparselu_seq(a, sym.present)
    return np.stack([A[i, j] for i, j in zip(sym.rows, sym.cols)])


def _close(got, want, shift):
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOL * shift


def test_program_against_the_sequential_reference(name):
    mk = _build(name)
    sym, plan = mk.slu_sym, mk.slu_plan
    want_sym = ref.symbolic(sym.present)
    assert sym.counts == want_sym["counts"]
    assert (sym.final == want_sym["final"]).all()
    a, factor, info = _run(name)
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
    nothing of what the buffer held shows. The ``bmod`` lane runs an odd
    number of rounds, so a call ends in the other half of the lanes'
    tiles than it began in, and the next begins as the first did."""
    mk = _build(TRACED)
    sym = mk.slu_sym
    replay = mk.slu_replay
    assert replay["bmod_rounds"] % 2 == 1 and replay["bmod_prefetched"] > 0
    shift = ref.diag_shift(sym.present, M)
    a1 = jnp.asarray(_blocks(sym.present, 1))
    a2 = jnp.asarray(_blocks(sym.present, 2**31 + 2))
    f1, _ = device_sparselu(a1, mk=mk)
    want1 = _sequential(np.asarray(a1), sym)
    _close(np.asarray(f1), want1, shift)
    f2, info = device_sparselu(a2, mk=mk, out=f1)
    assert info["sparselu"]["fill_blocks"] == sym.fill_blocks
    assert info["sparselu"]["bmod_prefetched"] == replay["bmod_prefetched"]
    want2 = _sequential(np.asarray(a2), sym)
    _close(np.asarray(f2), want2, shift)
    poison = jnp.full((sym.slots, M, M), jnp.nan, jnp.float32)
    f3, _ = device_sparselu(a1, mk=mk, out=poison)
    assert np.array_equal(np.asarray(f3), np.asarray(
        device_sparselu(a1, mk=mk)[0]))
    _close(np.asarray(f3), want1, shift)
    assert not a1.is_deleted() and not a2.is_deleted()


def test_prefetched_tasks_are_the_replays(name):
    """Tasks whose tiles were in flight before their round began, by lane
    and as the scheduler counts them: the replay's handshake to the unit
    (a round announces what was queued behind its batch before its own
    releases pushed, a batch at most, and the lane's next round finds as
    many of its tasks prefetched)."""
    mk = _build(name)
    _, _, info = _run(name)
    slu, tiers = info["sparselu"], info["tiers"]
    replay, log = _schedule(mk.slu_plan)
    rounds = [e for e in log if e[0] in ("p", "u")]
    for lane, key, width in (("p", "panel", PANEL_WIDTH),
                             ("u", "bmod", UPDATE_WIDTH)):
        mine = [e for e in rounds if e[0] == lane]
        assert len(mine) == slu[key + "_rounds"]
        assert all(pre <= len(take) <= width for _, take, pre, _ in mine)
        assert mine[0][2] == 0  # nothing is in flight before the first
        assert mine[-1][3] == 0  # and nothing after the last
        assert slu[key + "_prefetched"] == replay[key + "_prefetched"] == sum(
            pre for _, _, pre, _ in mine)
    assert tiers["prefetch_hits"] == (
        slu["bmod_prefetched"] + slu["panel_prefetched"])
    assert tiers["batch_rounds"] == len(rounds)
    if name == "arrow8_prefetches":
        assert (slu["bmod_prefetched"], slu["panel_prefetched"]) == (75, 7)
        assert slu["bmod_rounds"] == 11


def test_a_fill_blocks_first_update_in_a_prefetched_slot_starts_from_zero():
    """A fill block's first update loads nothing and zeroes its tile where
    it computes. In a prefetched slot that is the half the round before
    last stored from: the block must come out as the reference's, not
    that tile's leftovers less the product."""
    mk = _build(TRACED)
    sym, plan = mk.slu_sym, mk.slu_plan
    a, factor, _ = _run(TRACED)
    _, log = _schedule(plan)
    updates = [e for e in log if e[0] == "u"]
    firsts = [
        (ii, jj)
        for r, (_, take, pre, _) in enumerate(updates) if r >= 2
        for s, (ii, jj, kk) in enumerate(take)
        if s < pre and not sym.present[ii, jj]
        and kk == plan.first_step(ii, jj)
        # the slot's tile in this half is one an earlier round left there
        and s < len(updates[r - 2][1])
    ]
    assert firsts
    want = ref.sparselu_seq(a, sym.present)
    shift = ref.diag_shift(sym.present, M)
    for ii, jj in firsts:
        _close(factor[sym.slot_of[ii, jj]], want[ii, jj], shift)


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
        # all but 134 of the updates find their tiles prefetched
        assert (r["bmod_rounds"], r["bmod_prefetched"]) == (10931, 174650)
        assert (r["panel_rounds"], r["panel_prefetched"]) == (1050, 7826)


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


def _cut_with_a_prefetch_in_flight(log):
    """A ``fuel`` that stops the scheduler after a pop of the ring that
    follows a ``bmod`` round with a batch queued behind it, past the
    lane's third round: the fuel, what each lane has in flight there, and
    the tasks that had been found prefetched by then."""
    executed = hits = 0
    flying = {"p": 0, "u": 0}
    rounds = {"p": 0, "u": 0}
    for e, nxt in zip(log, log[1:]):
        if e[0] in flying:
            lane, take, pre, announced = e
            executed += len(take)
            hits += pre
            flying[lane] = announced
            rounds[lane] += 1
            if (lane == "u" and rounds["u"] >= 3 and announced
                    and nxt[0] not in flying):
                return executed + 1, flying, hits
        else:
            executed += 1
    raise AssertionError("no such point in this schedule")


def test_a_run_cut_short_drains_its_prefetch_and_the_next_is_whole():
    """``fuel`` runs out one task after a ``bmod`` round that started the
    next batch's loads: the scheduler's exit retires them through the
    lane's ``drain`` (the flight recorder's ``prefetch_drain`` record
    names the lane and the count), and the same build then factors the
    matrix whole."""
    mk = _build(TRACED)
    sym, plan = mk.slu_sym, mk.slu_plan
    _, log = _schedule(plan)
    fuel, flying, hits = _cut_with_a_prefetch_in_flight(log)
    assert flying["u"] > 0
    a = _blocks(sym.present, 59)
    b = TaskGraphBuilder()
    b.add(br.K_DIAG, args=[0, plan.root_word()])
    with pytest.raises(StallError) as cut:
        mk.run(b, fuel=fuel, ivalues=plan.presets(), data={
            "a": jnp.asarray(a),
            "blocks": jnp.zeros((sym.slots, M, M), jnp.float32),
            "linv": jnp.zeros((sym.n, 2, 2, M, M), jnp.bfloat16)})
    stats = cut.value.stats
    assert stats["executed"] == fuel and stats["pending"] > 0
    assert stats["tiers"]["prefetch_hits"] == hits
    drains = tb.records_of(stats["trace"], tb.TR_PREFETCH_DRAIN)
    assert {int(fid): int(n) for _, _, fid, n in drains} == {
        kind: flying[lane]
        for lane, kind in (("p", br.K_PANEL), ("u", br.K_UPDATE))
        if flying[lane]}
    factor, info = device_sparselu(a, mk=mk)
    assert info["sparselu"]["bmod_prefetched"] == mk.slu_replay[
        "bmod_prefetched"]
    _close(np.asarray(factor), _sequential(a, sym),
           ref.diag_shift(sym.present, M))


def test_lanes_run_the_prefetch_protocol_under_the_verifier():
    """Both lanes declare ``prefetch`` and keep it: no rule of the build's
    verifier is suppressed, the build passes with the prefetch protocol's
    rule on and its passes run (no body the shim could not read), and the
    rule bites: the same lane with a drain that waits nothing is refused,
    by the copies the body's prefetch left in flight."""
    from hclib_tpu.analysis import verify_megakernel
    from hclib_tpu.analysis.races import check_batch_spec
    from hclib_tpu.device.megakernel import BatchSpec

    mk = _build("genmat4")
    specs = dict(mk.batch_specs)
    assert sorted(specs) == [br.K_PANEL, br.K_UPDATE]
    assert mk.verify and mk.verify_suppress == ()
    for spec in specs.values():
        assert spec.prefetch and spec.verify_suppress == ()
        assert spec.fire_at == 2 * spec.width
    assert [str(f) for f in verify_megakernel(mk).findings] == []
    for fid, spec in specs.items():
        idle = BatchSpec(spec.body, width=spec.width, prefetch=True,
                         drain=lambda ctx: None, fire_at=spec.fire_at)
        found = check_batch_spec(
            mk.kernel_names[fid], fid, idle, mk.data_specs,
            mk.scratch_specs).findings
        assert [f.rule for f in found] == ["prefetch-protocol"]
        assert "never drained" in found[0].message
