"""The UTS seeding on either side of its crossover (device/uts_vec.py):
the breadth-first expansion of the tree's top on the device
(``uts_seed_expand``, ``uts_seed_roots``) against the numpy levels
(``_host_level``, ``_expand_host``), which stay as the path below the
crossover and are the oracle here. Everything is an integer: states,
counts, the order of the roots and the host's counters are compared bit for
bit. Tiny trees on the CPU; they all share the ladder's lowest rung, so the
file compiles one expansion (and one more for the rung's edge)."""

import jax
import numpy as np
import pytest

from hclib_tpu.device import uts_vec as uv
from hclib_tpu.device.uts_pallas import ALIGN, uts_pallas
from hclib_tpu.ops.sha1 import sha1_block
from hclib_tpu.models.uts import (
    CYCLIC, EXPDEC, FIXED, LINEAR, T1L, T_TINY, UTSParams, count_seq,
)

NEVER = 1 << 62

# name -> (tree, target_roots): T_TINY, the trees of tests/test_uts_pallas.py
# and T1L's own top, to level 6 (4,562 nodes).
TREES = {
    "T_TINY": (T_TINY, 64),
    "fixed7": (UTSParams(shape=FIXED, gen_mx=7, b0=4.0, root_seed=19), 256),
    "linear": (UTSParams(shape=LINEAR, gen_mx=6, b0=4.0, root_seed=34), 64),
    "cyclic": (UTSParams(shape=CYCLIC, gen_mx=1, b0=6.0, root_seed=7), 8),
    "expdec": (UTSParams(shape=EXPDEC, gen_mx=3, b0=3.0, root_seed=502), 16),
    "T1L-top": (T1L, 4000),
}


def _cpu():
    return jax.devices("cpu")[0]


def _root_level(params):
    """Level 0 as ``_seed_top`` makes it."""
    w16 = [np.zeros(1, np.uint32) for _ in range(16)]
    w16[4][:] = params.root_seed
    w16[5][:] = 0x80000000
    w16[15][:] = 20 * 8
    return uv._host_level(params, np.stack(sha1_block(w16, np)), 0)


def _same_level(chip, host):
    """A device level holds the host level's numbers, and nothing else."""
    assert (chip.n, chip.leaves, chip.total) == (
        host.n, host.leaves, host.total
    )
    state = np.stack([np.asarray(s) for s in chip.state])
    counts = np.asarray(chip.counts)
    assert state.dtype == np.uint32 and counts.dtype == np.int32
    assert counts.shape[0] == uv._rung(host.n)
    assert (state[:, : host.n] == host.state).all()
    assert (counts[: host.n] == host.counts).all()
    assert not counts[host.n :].any()


@pytest.mark.parametrize("name", sorted(TREES))
def test_every_level_expands_the_same_on_the_device(name):
    params, target = TREES[name]
    level, depth, thr = _root_level(params), 0, {}
    chip = None  # the same level, as the device's own chain carried it
    while level.total and level.n < target:
        depth += 1
        nxt = uv._host_level(params, uv._expand_host(level), depth)
        # from the host level (the first device level of a seeding) ...
        _same_level(uv._expand_chip(params, level, depth, _cpu(), thr), nxt)
        # ... and from the device level before it (every later one)
        chip = uv._expand_chip(
            params, level if chip is None else chip, depth, _cpu(), thr
        )
        _same_level(chip, nxt)
        level = nxt
    assert depth >= 1


def _seeded(monkeypatch, chip_from, params, target, slack, planes):
    monkeypatch.setattr(uv, "SEED_CHIP_FROM", chip_from)
    seed, roots, result = uv._seeded(params, target, _cpu(), slack, planes)
    if roots is not None:
        roots = tuple(np.asarray(r) for r in roots)
    result.pop("seed_seconds")
    return seed, roots, result


@pytest.mark.parametrize("planes", [False, True], ids=["vec", "pallas"])
@pytest.mark.parametrize("name", sorted(TREES))
def test_roots_are_the_hosts_bit_for_bit(monkeypatch, name, planes):
    """d0, the three counters, R, and the engine's padded root arrays
    (states, counts, LPT order, zero padding, layout) with the crossover
    at 0 (every level on the device) and at never (the parent's
    ``_host_seed``)."""
    params, target = TREES[name]
    slack = 1024 + (ALIGN if planes else 0)
    seed_h, roots_h, res_h = _seeded(
        monkeypatch, NEVER, params, target, slack, planes
    )
    seed_c, roots_c, res_c = _seeded(
        monkeypatch, 0, params, target, slack, planes
    )
    assert seed_c == seed_h
    nodes, _, d0 = seed_h
    assert (res_h["seed_levels_on_chip"], res_h["seed_nodes_on_chip"]) == (
        0, 0
    )
    assert (res_c["seed_levels_on_chip"], res_c["seed_nodes_on_chip"]) == (
        d0, nodes - 1
    )
    for key in ("host_seed_nodes", "roots"):
        assert res_c[key] == res_h[key]
    R = res_h["roots"]
    assert R > 0
    for got, want in zip(roots_c, roots_h):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert (got == want).all()
    state, count = roots_h
    assert state.dtype == (np.int32 if planes else np.uint32)
    assert state.shape[1:] == count.shape
    flat = count.reshape(-1)
    assert flat.size % uv.PAD_QUANTUM == 0 and flat.size >= R + slack
    assert (flat[:R] > 0).all() and not flat[R:].any()
    assert (np.diff(flat[:R]) <= 0).all()  # LPT: biggest counts first


@pytest.mark.parametrize("over", [0, 1], ids=["at-the-edge", "one-over"])
def test_a_level_at_a_rungs_edge_and_one_node_over_it(over):
    """A level whose children fill the lowest rung exactly stays on it;
    one child more takes the next rung, whole."""
    rung = uv.SEED_RUNGS[0]
    rng = np.random.default_rng(30)
    n = 500
    counts = rng.integers(1, 6, n).astype(np.int32)
    counts[::7] = 0  # childless parents between the others, and the last
    counts[-1] = 0
    for i in np.flatnonzero(counts):  # top up to the size wanted
        counts[i] += min(
            uv.MAX_CHILDREN - counts[i], rung + over - int(counts.sum())
        )
    assert int(counts.sum()) == rung + over
    level = uv._Level(
        rng.integers(0, 1 << 32, (5, n), dtype=np.uint32), counts, n,
        int((counts == 0).sum()), rung + over,
    )
    nxt = uv._host_level(T1L, uv._expand_host(level), 3)
    chip = uv._expand_chip(T1L, level, 3, _cpu(), {})
    assert np.asarray(chip.counts).shape[0] == uv.SEED_RUNGS[over]
    _same_level(chip, nxt)
    # and the level after it, whose input sits at that rung
    _same_level(
        uv._expand_chip(T1L, chip, 4, _cpu(), {}),
        uv._host_level(T1L, uv._expand_host(nxt), 4),
    )


def test_rungs():
    assert [uv._rung(n) for n in (1, uv.SEED_RUNGS[0], uv.SEED_RUNGS[0] + 1)
            ] == [uv.SEED_RUNGS[0], uv.SEED_RUNGS[0], uv.SEED_RUNGS[1]]
    assert list(uv.SEED_RUNGS) == sorted(uv.SEED_RUNGS)
    with pytest.raises(ValueError, match="largest capacity"):
        uv._rung(uv.SEED_RUNGS[-1] + 1)


@pytest.mark.parametrize("engine", ["vec", "pallas"])
def test_a_tree_the_seeding_consumes_whole_on_the_device(monkeypatch, engine):
    """``target_roots`` beyond the tree: no roots, no launch, the exact
    counts from the device's levels alone."""
    monkeypatch.setattr(uv, "SEED_CHIP_FROM", 0)
    if engine == "vec":
        r = uv.uts_vec(T_TINY, target_roots=10**9, device=_cpu())
    else:
        r = uts_pallas(T_TINY, target_roots=10**9, device=_cpu(), interpret=True)
    nodes, leaves, depth = count_seq(T_TINY)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == (nodes, leaves, depth)
    assert (r["roots"], r["steps"], r["host_seed_nodes"]) == (0, 0, nodes)
    assert r["seed_nodes_on_chip"] == nodes - 1
    assert r["seed_levels_on_chip"] == depth
    assert "device_nodes" not in r


@pytest.mark.parametrize("chip_from", [0, NEVER], ids=["chip", "host"])
def test_engine_runs_the_same_traversal_from_either_seeding(
    monkeypatch, chip_from
):
    """Same roots, so the same steps: the XLA engine end to end."""
    monkeypatch.setattr(uv, "SEED_CHIP_FROM", chip_from)
    p = UTSParams(shape=FIXED, gen_mx=7, b0=4.0, root_seed=7)
    r = uv.uts_vec(p, target_roots=1024, device=_cpu(), stack_pad=8)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == count_seq(p)
    assert r["host_seed_nodes"] + r["device_nodes"] == r["nodes"]
    on_chip = chip_from == 0
    assert (r["seed_levels_on_chip"] > 0) == on_chip
    assert (r["seed_nodes_on_chip"] == r["host_seed_nodes"] - 1) == on_chip
    monkeypatch.setattr(uv, "SEED_CHIP_FROM", NEVER)
    ref = uv.uts_vec(p, target_roots=1024, device=_cpu(), stack_pad=8)
    assert (r["steps"], r["roots"], r["refills"]) == (
        ref["steps"], ref["roots"], ref["refills"]
    )
