"""UTS's binomial trees (``-t 0``): the model's rule against a hashlib
traversal written out here, the integer threshold against the float64
compare, both vector engines against the model on trees that force a ring
to overflow and lanes to starve, the balance round's conservation where a
slab has to leave for the pool and come back, frames split as they change
hands (PR 57: a child a starved lane, the books in children, the steps
against the parent's), and the geometric trees' counters as the parent of
PR 56 gave them (CPU; interpreter for Pallas).
"""

import functools
import hashlib
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import uts_bin as ref
from hclib_tpu.device import uts_pallas as up
from hclib_tpu.device import uts_vec as uv
from hclib_tpu.device.uts_pallas import uts_pallas
from hclib_tpu.models.uts import (
    BIN, FIXED, T3, T3L, T_TINY, UTSParams, bin_threshold, count_parallel,
    count_seq, num_children, root_state,
)

LANES = (8, 128)

# name -> (tree, nodes, leaves, depth, non-leaf root children). Each has
# about 16 roots for 1,024 lanes, so nearly every lane starts starved.
TREES = {
    # subcritical (m q = 0.9) and bushy: most frames die in a few nodes
    "bushy": (dict(b0=55, q=0.3, m=3, root_seed=1), 662, 459, 27, 15),
    "m8": (dict(b0=130, q=0.12, m=8, root_seed=3), 9651, 8460, 68, 23),
    # deeper than the 127 columns of the geometric in-row table
    "deep": (dict(b0=80, q=0.2, m=5, root_seed=4), 27051, 21656, 167, 17),
    # a BIN root is not capped at MAXNUMCHILDREN
    "wide_root": (dict(b0=300, q=0.08, m=12, root_seed=4), 3625, 3347, 18,
                  20),
}


def _params(name):
    return UTSParams(tree=BIN, **{**TREES[name][0],
                                  "b0": float(TREES[name][0]["b0"])})


def _cpu():
    return jax.devices("cpu")[0]


def _hashlib_count(tree):
    """uts.c's rule, one node at a time, nothing shared with the model."""
    root = hashlib.sha1(b"\0" * 16 + struct.pack(">i", tree["root_seed"]))
    stack, nodes, leaves, deepest = [(root.digest(), 0)], 0, 0, 0
    while stack:
        state, depth = stack.pop()
        nodes += 1
        deepest = max(deepest, depth)
        rand = struct.unpack(">I", state[16:])[0] & 0x7FFFFFFF
        if depth == 0:
            kids = int(tree["b0"])
        else:
            kids = tree["m"] if rand / 2.0**31 < tree["q"] else 0
        leaves += kids == 0
        for i in range(kids):
            stack.append((hashlib.sha1(state + struct.pack(">i", i)).digest(),
                          depth + 1))
    return nodes, leaves, deepest


@pytest.mark.parametrize("name", sorted(TREES))
def test_model_counts_as_a_hashlib_traversal_does(name):
    tree, nodes, leaves, depth, roots = TREES[name]
    assert _hashlib_count(tree) == (nodes, leaves, depth)
    p = _params(name)
    assert count_seq(p) == (nodes, leaves, depth)
    top = root_state(p.root_seed)
    assert num_children(p, top, 0) == tree["b0"]  # uncapped
    kids = [hashlib.sha1(top + struct.pack(">i", i)).digest()
            for i in range(tree["b0"])]
    counts = [num_children(p, k, 1) for k in kids]
    assert set(counts) <= {0, tree["m"]}
    assert sum(c > 0 for c in counts) == roots


def test_model_counts_in_parallel_too():
    assert count_parallel(_params("bushy"), nworkers=4) == TREES["bushy"][1:4]


@pytest.mark.parametrize("name", sorted(TREES))
def test_plain_reference_counts_as_a_hashlib_traversal_does(name):
    tree, nodes, leaves, depth, _ = TREES[name]
    got = ref.count_tree(tree, np)
    assert (got["nodes"], got["leaves"], got["depth"]) == (
        nodes, leaves, depth)
    assert got["hashed_nodes"] == nodes - 1
    assert tree["b0"] <= got["widest_level"] < nodes


def test_plain_reference_device_loop_equals_its_numpy_form(monkeypatch):
    monkeypatch.setattr(ref, "CHUNK", 16)  # several chunks a level
    tree = TREES["m8"][0]
    assert ref.count_tree(tree, jnp) == ref.count_tree(tree, np)
    monkeypatch.setattr(ref, "PARENTS", 32)  # m8's widest level holds more
    with pytest.raises(OverflowError):
        ref.count_tree(tree, jnp)


@pytest.mark.parametrize("params", [T3, T3L], ids=["T3", "T3L"])
@pytest.mark.parametrize("off", [-1, 0, 1])
def test_integer_threshold_is_the_float64_compare(params, off):
    t = bin_threshold(params.q)
    assert ref.nonleaf_below(params.q) == t  # two derivations
    r = t + off
    assert (r / 2147483648.0 < params.q) == (r < t)
    state = b"\0" * 16 + struct.pack(">I", r)
    assert num_children(params, state, 3) == (params.m if r < t else 0)


COUNTERS = ("steps", "refills", "donated", "claimed", "balance_rounds",
            "pool_max", "spills", "stack_max", "split_gifts")


def _engine(engine, p, **kw):
    if engine == "vec":
        return uv.uts_vec(p, lanes=LANES, device=_cpu(), **kw)
    return uts_pallas(p, lanes=LANES, device=_cpu(), interpret=True, **kw)


@pytest.mark.parametrize("stack_size", [None, 8], ids=["default", "ring8"])
@pytest.mark.parametrize("name", sorted(TREES))
@pytest.mark.parametrize("engine", ["vec", "pallas"])
def test_engines_count_exactly_and_move_every_frame_once(
        engine, name, stack_size):
    _, nodes, leaves, depth, roots = TREES[name]
    r = _engine(engine, _params(name), stack_size=stack_size)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == (nodes, leaves, depth)
    assert r["roots"] == roots
    assert r["host_seed_nodes"] == 1 + TREES[name][0]["b0"]
    assert r["host_seed_nodes"] + r["device_nodes"] == nodes
    assert r["donated"] + r["roots"] == r["claimed"]
    assert roots <= r["pool_max"] <= r["pool_capacity"]
    assert 0 < r["balance_rounds"] <= r["refills"]
    assert r["stack_max"] <= r["stack_size"] == (stack_size or uv.BIN_STACK)
    assert r["device_nodes"] <= r["steps"] * LANES[0] * LANES[1]
    # 1,000 lanes start starved beside some 16 roots: frames must move,
    # and the default ring, two frames, overflows on any path three deep
    assert r["donated"] > 0
    # and they are split as they do: a gift that splits a frame leaves its
    # holder a child and gives at least one
    assert 0 < r["split_gifts"] <= r["donated"] - roots * (
        TREES[name][0]["m"] - 1)
    if r["stack_size"] == 2:
        assert r["stack_max"] == 2


def test_both_engines_take_the_same_steps():
    a = _engine("vec", _params("deep"), stack_size=2)
    b = _engine("pallas", _params("deep"), stack_size=2)
    for k in COUNTERS:
        assert a[k] == b[k], k


# The steps the parent of PR 57 (commit 4718ed3) took on these calls, a
# frame moving whole: 3.5 steps a level on the deepest path, where a frame
# split as it changes hands costs two.
PARENT_STEPS = {"bushy": 60, "deep": 614, "m8": 304, "wide_root": 116}


@pytest.mark.parametrize("name", sorted(PARENT_STEPS))
def test_split_frames_take_at_most_seven_tenths_of_the_parents_steps(name):
    r = _engine("vec", _params(name))
    assert (r["nodes"], r["leaves"], r["max_depth"]) == TREES[name][1:4]
    assert r["steps"] <= 0.7 * PARENT_STEPS[name], r["steps"]
    # the deepest path at a round every two steps: two steps a level and
    # no more (the parent: 3.7 on "deep")
    assert r["steps"] <= 2 * r["max_depth"]


def test_the_unrolled_driver_the_chip_runs_counts_the_same(monkeypatch):
    """The compiled kernel writes its BIN_EVERY steps out; the interpreter
    keeps them in a loop (XLA's CPU backend is slow to compile two fused
    hashes) in every test but this one."""
    looped = _engine("pallas", _params("m8"))
    monkeypatch.setattr(up, "_uts_bin_pallas", functools.partial(
        up._uts_bin_pallas, unroll=True))
    unrolled = _engine("pallas", _params("m8"))
    assert (unrolled["nodes"], unrolled["leaves"], unrolled["max_depth"]) == (
        TREES["m8"][1:4])
    for k in COUNTERS:
        assert unrolled[k] == looped[k], k


# m q = 2: every level twice the last, for ever. No pool holds it.
FLOOD = UTSParams(tree=BIN, b0=64.0, q=0.4, m=5, root_seed=2)


@pytest.mark.parametrize("engine", ["vec", "pallas"])
def test_a_pool_too_small_raises(engine, monkeypatch):
    monkeypatch.setattr(uv, "BIN_POOL_SLABS", 1)
    with pytest.raises(RuntimeError, match="pool is full"):
        _engine(engine, FLOOD, max_steps=4000)


@pytest.mark.parametrize("engine", ["vec", "pallas"])
def test_too_few_steps_raise(engine):
    with pytest.raises(RuntimeError, match="ran out of steps"):
        _engine(engine, _params("deep"), stack_size=2, max_steps=100)


def test_keywords_of_the_other_tree_type_raise():
    with pytest.raises(ValueError, match="target_roots has no meaning"):
        uv.uts_vec(_params("bushy"), target_roots=64, device=_cpu())
    with pytest.raises(ValueError, match="depth_bound has no meaning"):
        uts_pallas(_params("bushy"), depth_bound=9, device=_cpu(),
                   interpret=True)
    with pytest.raises(ValueError, match="stack_size is a binomial"):
        uv.uts_vec(T_TINY, stack_size=4, device=_cpu())
    with pytest.raises(ValueError, match="power of two"):
        uv.uts_vec(_params("bushy"), stack_size=3, device=_cpu())


def test_a_node_wider_than_one_mxu_pass_sums_raises():
    """A frame's width is summed along a row as one product on the MXU,
    exact up to 8 bits an operand: ``m`` beyond that raises, before
    anything is seeded."""
    wide = UTSParams(tree=BIN, b0=4.0, q=0.001, m=uv.BIN_MAX_M + 1,
                     root_seed=1)
    for run in (uv.uts_vec, functools.partial(uts_pallas, interpret=True)):
        with pytest.raises(ValueError, match="m is at most 255"):
            run(wide, lanes=LANES, device=_cpu())


def test_a_tree_without_a_non_leaf_root_child_ends_in_the_seeding():
    p = UTSParams(tree=BIN, b0=3.0, q=1e-9, m=5, root_seed=1)
    r = uv.uts_vec(p, lanes=LANES, device=_cpu())
    assert (r["nodes"], r["leaves"], r["max_depth"], r["steps"]) == (
        4, 3, 1, 0) == count_seq(p) + (0,)


def _word(lo, hi):
    """A frame's children word: [lo, hi) still to hash."""
    return lo | (hi << 16)


def _balance_state(S, e_fill, sp_all):
    """Every lane holds ``sp_all + 1`` frames whose words name their lane
    and level, each with one child left (its level's number); every row
    of the exchange ``e_fill`` frames that name their slot."""
    rows, cols = LANES
    lane = np.arange(rows * cols, dtype=np.int32).reshape(LANES)
    col = np.broadcast_to(np.arange(cols, dtype=np.int32), LANES)
    st = tuple(
        tuple(jnp.asarray((lane * 64 + L * 8 + i).astype(np.uint32))
              for i in range(5))
        for L in range(S)
    )
    ch = tuple(jnp.asarray(lane * 0 + _word(L, L + 1)) for L in range(S))
    dp = tuple(jnp.asarray(lane * 0 + 1 + L) for L in range(S))
    sp = jnp.full(LANES, sp_all, jnp.int32)
    top = jnp.full(LANES, sp_all, jnp.int32)
    live = col < e_fill
    E = tuple(jnp.asarray(np.where(live, -(lane * 8 + w) - 1, 0))
              for w in range(5)) + (
        jnp.asarray(np.where(live, _word(3, 4), 0)),
        jnp.asarray(np.where(live, 99, 0)))
    return (sp, top, st, ch, dp), E, jnp.full(LANES, e_fill, jnp.int32)


def _slab_moves():
    def spill(pool, do, k, planes):
        return jax.lax.cond(
            do, lambda p: p.at[k].set(jnp.stack(planes)), lambda p: p, pool)

    def fetch(pool, do, k):
        return tuple(pool[jnp.maximum(k, 0)][w] for w in range(7))

    return spill, fetch


def _pool(E, e, slabs=0):
    zero = jnp.int32(0)
    return (E, e, jnp.int32(slabs), jnp.zeros((2, 7) + LANES, jnp.int32),
            zero, zero, zero, zero, zero, zero)


def test_balance_round_spills_a_slab_and_takes_it_back():
    """An exchange over half full leaves for the pool whole before it is
    given more, and an empty exchange takes the newest slab back: the two
    whole-slab moves no small tree reaches. Frames are told apart by
    their words, so a lost or doubled one shows."""
    S = 4
    rows, cols = LANES
    balance = jax.jit(uv.make_balance(
        S, LANES, 2, *_slab_moves(), lambda x: jnp.roll(x, 1, 0)))
    lane, E, e = _balance_state(S, e_fill=100, sp_all=3)
    # every lane holds four frames and gives its bottom one; every row
    # holds 100 of its 128, over half of the exchange in all
    lane, pool = balance(lane, _pool(E, e))
    (E, e, slabs, pstate, donated, claimed, moved, err, spills,
     splits) = pool
    assert (int(slabs), int(donated), int(claimed), int(moved), int(err),
            int(spills), int(splits)) == (1, rows * cols, 0, 1, 0, 1, 0)
    assert np.asarray(e).tolist() == np.full(LANES, cols).tolist()
    assert (np.asarray(lane[0]) == 2).all()  # each gave one frame
    # the slab is the old exchange: 100 live slots a row, named as made
    slab = np.asarray(pstate[0])
    assert (slab[6] == np.where(np.arange(cols) < 100, 99, 0)).all()
    assert (slab[0][:, :100] < 0).all()
    # the new exchange holds every lane's BOTTOM frame (level 0) once,
    # turned by one row
    got = np.sort(np.asarray(E[0]).view(np.uint32).ravel())
    want = np.sort((np.arange(rows * cols) * 64).astype(np.uint32))
    assert (got == want).all()
    assert (np.asarray(E[6]) == 1).all()
    assert (np.asarray(E[5]) == _word(0, 1)).all()

    # now every lane is starved: two rounds hand out the exchange, then
    # the slab, each frame once
    starved = (jnp.full(LANES, -1, jnp.int32),) + lane[1:]
    lane2, pool = balance(starved, pool)
    assert int(pool[5]) == rows * cols and int(pool[2]) == 1
    assert (np.asarray(lane2[0]) == 0).all()
    took = np.sort(np.asarray(lane2[2][0][0]).ravel())
    assert (took == want).all()
    lane3, pool = balance(starved, pool)
    assert int(pool[2]) == 0 and int(pool[5]) == rows * cols + rows * 100
    took = np.asarray(lane3[2][0][0]).view(np.int32)
    claimed_now = np.asarray(lane3[0]) == 0
    assert claimed_now.sum() == rows * 100
    names = np.sort(took[claimed_now])
    slab_names = np.sort(slab[0][:, :100].ravel())
    assert (names == slab_names).all()
    assert (np.asarray(lane3[3][0])[claimed_now] == _word(3, 4)).all()

    # a third slab has nowhere to go: the pool's two are taken
    lane, E, e = _balance_state(S, e_fill=100, sp_all=3)
    _, pool = balance(lane, _pool(E, e, slabs=2))
    assert int(pool[7]) == 1


# Lanes of a ring of two for the rounds below: (row, col) -> its frames,
# bottom first, each (name, lo, hi, depth); every other lane is starved,
# or, in the rows of ``busy``, holds one frame with one child left and so
# neither gives nor takes.
def _lanes_with(frames, busy=()):
    sp = np.full(LANES, -1, np.int32)
    top = np.zeros(LANES, np.int32)
    st = np.zeros((2, 5) + LANES, np.uint32)
    ch = np.zeros((2,) + LANES, np.int32)
    dp = np.zeros((2,) + LANES, np.int32)
    for r in busy:
        sp[r], ch[0][r], dp[0][r], st[0][:, r] = 0, _word(0, 1), 1, 7
    for (r, c), held in frames.items():
        sp[r, c], top[r, c] = len(held) - 1, len(held) - 1
        for L, (name, lo, hi, depth) in enumerate(held):
            st[L][:, r, c] = name * 8 + np.arange(5)
            ch[L][r, c], dp[L][r, c] = _word(lo, hi), depth
    return (jnp.asarray(sp), jnp.asarray(top),
            tuple(tuple(jnp.asarray(st[L][i]) for i in range(5))
                  for L in range(2)),
            tuple(jnp.asarray(ch[L]) for L in range(2)),
            tuple(jnp.asarray(dp[L]) for L in range(2)))


@functools.lru_cache(maxsize=None)
def _round_of_two():
    return jax.jit(uv.make_balance(
        2, LANES, 2, *_slab_moves(), lambda x: jnp.roll(x, 1, 0)))


def _empty_pool():
    zeros = jnp.zeros(LANES, jnp.int32)
    return _pool(tuple(zeros for _ in range(7)), zeros)


def _held(lane, r, c, level=None):
    """(name, lo, hi, depth) of lane (r, c)'s top frame, or of ``level``."""
    sp, top, st, ch, dp = lane
    L = int(top[r, c]) if level is None else level
    word = int(ch[L][r, c])
    assert [int(st[L][i][r, c]) % 8 for i in range(5)] == list(range(5))
    return (int(st[L][0][r, c]) // 8, word & 0xFFFF, word >> 16,
            int(dp[L][r, c]))


def test_a_lanes_only_frame_is_split_and_dealt_a_child_a_starved_lane():
    """The mechanism of PR 57: a lane whose only frame has five children
    left keeps the next and gives four, and four starved lanes of its row
    hash them in the same step; a frame dealt to four lanes is four gifts
    and four claims."""
    lane, pool = _round_of_two()(
        _lanes_with({(2, 5): [(1, 0, 5, 7)]}), _empty_pool())
    assert _held(lane, 2, 5) == (1, 0, 1, 7) and int(lane[0][2, 5]) == 0
    took = np.argwhere(np.asarray(lane[0]) == 0).tolist()
    assert took == [[2, c] for c in (0, 1, 2, 3, 5)]
    got = [_held(lane, 2, c) for c in range(4)]
    assert sorted(got) == [(1, c, c + 1, 7) for c in (1, 2, 3, 4)]
    E, e, _, _, donated, claimed, moved, err, spills, splits = pool
    assert (int(donated), int(claimed), int(moved), int(err), int(spills),
            int(splits)) == (4, 4, 1, 0, 0, 1)
    assert not np.asarray(e).any()


def test_a_frame_the_row_cannot_use_up_waits_cut_for_the_next_row():
    """Two starved lanes for four children: the last two are dealt, the
    frame stays in the exchange with its ``hi`` cut, turns to the next
    row, and is dealt out there; the books close to the unit."""
    row2 = {(2, c): [(9, 0, 1, 1)] for c in range(2, 128) if c != 5}
    lane, pool = _round_of_two()(
        _lanes_with({(2, 5): [(1, 0, 5, 7)], **row2}, busy=range(3, 8)),
        _empty_pool())
    assert sorted(_held(lane, 2, c) for c in (0, 1)) == [
        (1, 3, 4, 7), (1, 4, 5, 7)]
    E, e, _, _, donated, claimed = pool[:6]
    assert (int(donated), int(claimed)) == (4, 2)
    assert np.asarray(e)[:, 0].tolist() == [0, 0, 0, 1, 0, 0, 0, 0]
    assert int(E[5][3, 0]) == _word(1, 3) and int(E[6][3, 0]) == 7
    # row 3 now has three starved lanes for the two children left
    sp = np.asarray(lane[0]).copy()
    sp[3, 10:13] = -1
    lane, pool = _round_of_two()((jnp.asarray(sp),) + lane[1:], pool)
    assert sorted(_held(lane, 3, c) for c in (10, 11)) == [
        (1, 1, 2, 7), (1, 2, 3, 7)]
    assert int(lane[0][3, 12]) == -1
    assert (int(pool[4]), int(pool[5])) == (4, 4)
    assert not np.asarray(pool[1]).any()


def test_a_lane_gives_its_bottom_frame_and_its_top_frames_tail_at_once():
    """A node pushed in the step before the round is split in that round:
    the lane's bottom frame leaves whole, its top frame's children but
    the next leave too, and eight starved lanes hash the eight."""
    lane, pool = _round_of_two()(
        _lanes_with({(1, 7): [(2, 1, 5, 3), (3, 0, 5, 4)]}), _empty_pool())
    assert int(lane[0][1, 7]) == 0
    assert _held(lane, 1, 7) == (3, 0, 1, 4)
    got = sorted(_held(lane, 1, c) for c in (0, 1, 2, 3, 4, 5, 6, 8))
    assert got == ([(2, c, c + 1, 3) for c in (1, 2, 3, 4)]
                   + [(3, c, c + 1, 4) for c in (1, 2, 3, 4)])
    assert (int(pool[4]), int(pool[5]), int(pool[9])) == (8, 8, 1)


def test_where_no_lane_is_starved_frames_move_whole_as_before():
    """A bushy tree: no lane of the row could take a child now, so no
    frame is split; a bottom frame still leaves, whole, for the next row
    of the exchange."""
    lane, pool = _round_of_two()(
        _lanes_with({(4, 0): [(1, 0, 5, 7)],
                     (4, 9): [(2, 1, 5, 3), (3, 0, 5, 4)]},
                    busy=range(8)),
        _empty_pool())
    assert _held(lane, 4, 0) == (1, 0, 5, 7)
    assert int(lane[0][4, 9]) == 0 and _held(lane, 4, 9) == (3, 0, 5, 4)
    E, e, _, _, donated, claimed = pool[:6]
    assert (int(donated), int(claimed), int(pool[9])) == (4, 0, 0)
    assert int(np.asarray(e)[5, 0]) == 1 and int(E[5][5, 0]) == _word(1, 5)


def test_a_row_without_room_leaves_its_lanes_their_frames():
    """A row of the exchange that is full takes nothing: the givers keep
    their frames for a round (a push onto their full ring waits), nothing
    is spilled for one row's sake, and the books do not move."""
    rows, cols = LANES
    full = np.zeros(LANES, np.int32)
    full[4] = cols
    E = tuple(jnp.asarray(np.where(full > 0, w + 1, 0)) for w in range(5)) + (
        jnp.asarray(np.where(full > 0, _word(0, 1), 0)),
        jnp.asarray(np.where(full > 0, 5, 0)))
    before = _lanes_with({(4, 9): [(2, 1, 5, 3), (3, 0, 5, 4)]},
                         busy=range(8))
    lane, pool = _round_of_two()(before, _pool(E, jnp.asarray(full)))
    assert int(lane[0][4, 9]) == 1
    assert _held(lane, 4, 9) == (3, 0, 5, 4)
    assert _held(lane, 4, 9, level=0) == (2, 1, 5, 3)
    assert [int(x) for x in pool[4:]] == [0, 0, 0, 0, 0, 0]
    assert np.asarray(pool[1])[:, 0].tolist() == [0, 0, 0, 0, 0, cols, 0, 0]


# What the parent of PR 56 (commit 124561d) gave on these calls: a geometric
# tree's path holds none of the pool's code, and its counters say so.
GEOMETRIC = {
    "toy": (T_TINY, 64, (1279, 1018, 5, 21, 1, 195, 318)),
    "t1_d7": (UTSParams(shape=FIXED, gen_mx=7, b0=4.0, root_seed=19), 64,
              (63914, 51124, 7, 2189, 1, 147, 254)),
    "t1_d8": (UTSParams(shape=FIXED, gen_mx=8, b0=4.0, root_seed=19), 4096,
              (257042, 205878, 8, 280, 56, 9629, 16000)),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIC))
@pytest.mark.parametrize("engine", ["vec", "pallas"])
def test_geometric_trees_count_and_step_as_on_the_parent(engine, name):
    p, target_roots, want = GEOMETRIC[name]
    r = _engine(engine, p, target_roots=target_roots)
    assert tuple(r[k] for k in (
        "nodes", "leaves", "max_depth", "steps", "refills", "roots",
        "host_seed_nodes")) == want
    assert "donated" not in r
