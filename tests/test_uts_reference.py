"""The benchmark's plain UTS reference (benchmarks/reference/uts.py)
against hashlib, and both UTS engines against the reference: exact counts,
one kernel launch a call, and the kernel's own counters.

The engine calls use the trees, ``target_roots`` and ``stack_pad=8`` of
tests/test_uts_pallas.py and tests/test_uts_vec.py, so they compile the
two programs those files compile and nothing else."""

import functools
import hashlib
import math
import os
import struct
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import uts as ref  # noqa: E402
from hclib_tpu.device import uts_pallas as up  # noqa: E402
from hclib_tpu.device import uts_vec as uv  # noqa: E402
from hclib_tpu.models.uts import FIXED, UTSParams  # noqa: E402
from hclib_tpu.ops.sha1 import sha1_child, sha1_children_np  # noqa: E402

# name -> (the reference's tree, the engines' target_roots)
TREES = {
    "T_TINY": ({"shape": "FIXED", "gen_mx": 5, "b0": 4, "root_seed": 42}, 64),
    "deep7": ({"shape": "FIXED", "gen_mx": 7, "b0": 4, "root_seed": 19}, 256),
}
NLANES = uv.NLANES


def _digest(state5) -> bytes:
    return b"".join(struct.pack(">I", int(w[0])) for w in state5)


@pytest.mark.parametrize("seed", [0, 19, 29, 42, 2**31 - 1])
def test_reference_root_state_is_hashlibs(seed):
    want = hashlib.sha1(b"\x00" * 16 + struct.pack(">I", seed)).digest()
    assert _digest(ref.root_state(seed)) == want


@pytest.mark.parametrize("index", [0, 1, 7, 99])
def test_reference_child_state_is_hashlibs(index):
    parent = ref.root_state(29)
    want = hashlib.sha1(_digest(parent) + struct.pack(">I", index)).digest()
    got = ref.child_state(parent, np.array([index], np.uint32))
    assert _digest(got) == want


def _hashlib_levels(tree):
    """Depth-first, one node at a time, as uts.c does it: the size of
    every level and the number of leaves."""
    logq = math.log(1.0 - 1.0 / (1.0 + tree["b0"]))
    levels, leaves = [0] * (tree["gen_mx"] + 1), 0
    root = hashlib.sha1(
        b"\x00" * 16 + struct.pack(">I", tree["root_seed"])
    ).digest()
    stack = [(root, 0)]
    while stack:
        state, depth = stack.pop()
        levels[depth] += 1
        n = 0
        if depth < tree["gen_mx"]:
            r = struct.unpack(">I", state[16:])[0] & 0x7FFFFFFF
            n = min(100, int(math.floor(
                math.log(1.0 - r / 2147483648.0) / logq)))
        leaves += n == 0
        stack += [(hashlib.sha1(state + struct.pack(">I", i)).digest(),
                   depth + 1) for i in range(n)]
    return levels, leaves


@functools.lru_cache(maxsize=None)
def _reference(name):
    return ref.count_tree(TREES[name][0])


@pytest.mark.parametrize("name", sorted(TREES))
def test_reference_counts_a_tree_as_a_hashlib_dfs_does(name):
    levels, leaves = _hashlib_levels(TREES[name][0])
    got = _reference(name)
    assert got["levels"] == levels and got["leaves"] == leaves
    assert got["nodes"] == sum(levels) and got["depth"] == len(levels) - 1
    # every state but the root's and the last level's had to be hashed
    assert got["hashed_nodes"] == sum(levels[1:-1])


def test_reference_hashes_in_blocks_with_jax_numpy(monkeypatch):
    """The device form of the block hash (fixed-size blocks, padded,
    jitted jax.numpy), at a block the CPU compiles quickly."""
    import jax.numpy as jnp

    monkeypatch.setattr(ref, "BLOCK", 128)  # T_TINY's widest hashed level: 243
    assert ref.count_tree(TREES["T_TINY"][0], jnp) == _reference("T_TINY")


def test_reference_refuses_a_shape_it_does_not_write():
    with pytest.raises(NotImplementedError):
        ref.count_tree({**TREES["T_TINY"][0], "shape": "LINEAR"})


@pytest.mark.parametrize("n", [1, 7, 1000])
def test_host_seedings_in_place_hash_is_hashlibs(n):
    """ops.sha1.sha1_children_np (the host seeding's hash, no temporaries)
    against hashlib, the reference and the generic form it replaced."""
    rng = np.random.default_rng(n)
    state = rng.integers(0, 2**32, (5, 64), dtype=np.uint32)
    parent = rng.integers(0, 64, n)
    index = rng.integers(0, 100, n).astype(np.uint32)
    got = sha1_children_np(state, parent, index)
    assert got.dtype == np.uint32 and got.shape == (5, n)
    picked = list(state[:, parent])
    assert (got == np.stack(sha1_child(picked, index, np))).all()
    assert (got == np.stack(ref.child_state(picked, index))).all()
    for j in (0, n - 1):
        want = hashlib.sha1(
            _digest([[w] for w in state[:, parent[j]]])
            + struct.pack(">I", int(index[j]))
        ).digest()
        assert _digest([[w] for w in got[:, j]]) == want


# ------------------------------------------- the engines, one call each


def _params(name):
    t = TREES[name][0]
    return UTSParams(shape=FIXED, gen_mx=t["gen_mx"], b0=float(t["b0"]),
                     root_seed=t["root_seed"])


def _call(engine, name):
    cpu = jax.devices("cpu")[0]
    kw = dict(target_roots=TREES[name][1], device=cpu, stack_pad=8)
    if engine == "pallas":
        return up.uts_pallas(_params(name), interpret=True, **kw)
    return uv.uts_vec(_params(name), **kw)


_result = functools.lru_cache(maxsize=None)(_call)
ENGINE_TREES = [(e, n) for e in ("pallas", "vec") for n in sorted(TREES)]


@pytest.mark.parametrize("engine,name", ENGINE_TREES)
def test_engine_counts_what_the_reference_counts(engine, name):
    r, want = _result(engine, name), _reference(name)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == (
        want["nodes"], want["leaves"], want["depth"])
    # The level sizes the engine gives: the host counted whole levels 0 to
    # d0, the device every node below them, each exactly once.
    tops = np.cumsum(want["levels"]).tolist()
    assert r["host_seed_nodes"] in tops
    assert r["host_seed_nodes"] + r["device_nodes"] == want["nodes"]
    assert r["roots"] > 0  # the engine's device half did run


@pytest.mark.parametrize("engine,name", ENGINE_TREES)
def test_engine_counters_bound_its_node_count(engine, name):
    r = _result(engine, name)
    assert r["refills"] >= 1
    assert r["steps"] >= 1
    # a step expands at most one node a lane
    assert r["device_nodes"] <= r["steps"] * NLANES
    assert r["lane_efficiency"] == r["device_nodes"] / (r["steps"] * NLANES)
    assert r["device_seconds"] > 0 and r["seed_seconds"] > 0


@pytest.mark.parametrize("engine,module,attr", [
    ("pallas", up, "_uts_dfs_pallas"), ("vec", uv, "_uts_dfs"),
])
def test_one_call_launches_the_kernel_exactly_once(
        engine, module, attr, monkeypatch):
    real, launches = getattr(module, attr), []

    def counted(*args, **kw):
        launches.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(module, attr, counted)
    r = _call(engine, "T_TINY")
    assert len(launches) == 1
    assert r["nodes"] == _reference("T_TINY")["nodes"]


@pytest.mark.parametrize("engine", ["pallas", "vec"])
def test_a_call_takes_no_timing_reps(engine):
    fn = up.uts_pallas if engine == "pallas" else uv.uts_vec
    with pytest.raises(TypeError, match="timing_reps"):
        fn(_params("T_TINY"), timing_reps=1)
