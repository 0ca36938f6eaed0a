"""The cell ``uts-t3l`` (PR 56) at a tiny binomial tree through the Pallas
interpreter, on the CPU, run by hand with the other benchmark tests:

    python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.reference import uts_bin  # noqa: E402

CELL = "uts-t3l"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# tests/test_uts_binomial.py's tree "deep" (167 levels, 17 non-leaf root
# children for 1,024 lanes); its counts are a hashlib traversal's there.
TINY = {
    "tree": {"type": "BIN (-t 0)", "b0": 80, "q": 0.2, "m": 5,
             "root_seed": 4},
    "lanes": [8, 128], "stack_size": 2,
    "guarantees": {"nodes": 27051, "leaves": 21656, "depth": 167},
    "hashed_nodes": 27050,
}


@pytest.fixture(scope="module")
def bench():
    return run.load_json("BENCHMARK.json")


def tiny(bench, traced=False, cfg=None):
    return run.run_cell(bench, CELL, 2**31 + 56, 0.2, traced, CPU,
                        interpret=True, cfg_over={**TINY, **(cfg or {})})


def test_cell_is_correct_and_every_compared_number_is_zero(bench, capsys):
    out = tiny(bench)
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["attempted"] >= run.MIN_OPERATIONS
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    compared = {x["compared"]: x for x in lines if "compared" in x}
    assert len(compared) == 13  # nine a call, four of the reference
    assert all(x["value"] == 0 and x["limit"] == 0
               for x in compared.values())
    (reference,) = [x["reference"] for x in lines if "reference" in x]
    assert reference["nodes"] == 27051 and reference["widest_level"] == 420


def test_traced_run_reports_the_pools_counters(bench):
    # No device plane on the CPU: the readers of kernel events find
    # nothing and are left out; the spans and the counters are read.
    out = tiny(bench, traced=True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"uts_seed_ms", "uts_lane_share", "t3_donated", "t3_pool_max",
            "t3_stack_max", "t3_balance_share", "t3_spills"} <= set(m)
    assert m["t3_donated"]["value"] > 0
    assert m["t3_stack_max"]["value"] == 2  # the ring was full
    assert 17 <= m["t3_pool_max"]["value"] <= 8 * 1024
    assert m["t3_spills"]["value"] >= 0
    assert 0 < m["t3_balance_share"]["value"] < 51  # a round in 2 steps
    assert 0 < m["uts_lane_share"]["value"] < 100
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans >= {"bench:uts.seed", "bench:uts.stage", "bench:uts.run",
                     "bench:uts.readback"}


def test_control_traversal_that_stops_early(bench):
    """The configuration's control: a step budget below the steps the
    traversal takes. It raises, and a control that raises has failed."""
    control = run.load_json("benchmarks/configs/uts-t3l.json")["control"]
    assert set(control) == {"max_steps"}
    with pytest.raises(RuntimeError, match="ran out of steps"):
        tiny(bench, cfg={"max_steps": 100})


def test_a_wrong_reference_fails_as_loudly(bench):
    out = tiny(bench, cfg={"hashed_nodes": 27051})
    assert out["correct"] is False and out["failed"] == out["attempted"]


def _balance_with(monkeypatch, edit):
    """The engine with its balance round ``edit``ed: the timed path broken
    where frames change hands. ``edit(lane before, pool before, lane
    after, pool after) -> the rows' counts``."""
    from hclib_tpu.device import uts_pallas as up
    from hclib_tpu.device import uts_vec as uv

    real = uv.make_balance

    def make(*args, **kw):
        balance = real(*args, **kw)

        def edited(lane0, pool0):
            lane, pool = balance(lane0, pool0)
            return lane, (pool[0], edit(lane0, pool0, lane, pool)) + pool[2:]

        return edited

    monkeypatch.setattr(uv, "make_balance", make)
    up._uts_bin_pallas.clear_cache()
    monkeypatch.setattr(up, "_uts_bin_pallas", up._uts_bin_pallas)  # undo


def _not_correct(bench):
    """The run raises on the call's own conservation check (``donated +
    roots == claimed`` is asserted before a call returns) or comes out
    false by the driver's comparison."""
    from hclib_tpu.device import uts_pallas as up

    try:
        out = tiny(bench, cfg={"max_steps": 50000})
    except (AssertionError, RuntimeError) as e:
        return str(e)
    finally:
        up._uts_bin_pallas.clear_cache()
    assert out["correct"] is False and out["failed"] == out["attempted"]
    return "false"


def test_a_donation_that_loses_its_frame_is_not_correct(bench, monkeypatch):
    """Whenever lane (0, 0) gives its bottom frame away, the last frame
    its row was given falls off the exchange (the row's count is one
    short): the subtree under it is never counted, and one frame given was
    never taken."""
    import jax.numpy as jnp

    def lose(lane0, pool0, lane, pool):
        gave = (lane[0] < lane0[0])[0, 0]
        # what row 0 was given sits in row 1 after the turn
        return pool[1].at[1].add(-gave.astype(jnp.int32))

    _balance_with(monkeypatch, lose)
    _not_correct(bench)


def test_a_claim_that_leaves_its_frame_is_not_correct(bench, monkeypatch):
    """In the first round the starved lanes take the roots and the
    exchange keeps them too (its rows' counts are put back): the roots'
    subtrees are counted twice, nodes over."""
    import jax.numpy as jnp

    def keep(lane0, pool0, lane, pool):
        took = jnp.sum(((lane0[0] < 0) & (lane[0] >= 0)).astype(jnp.int32),
                       axis=1, keepdims=True)
        again = jnp.roll(jnp.broadcast_to(took, pool[1].shape), 1, 0)
        return jnp.where(pool0[5] == 0, pool[1] + again, pool[1])

    _balance_with(monkeypatch, keep)
    _not_correct(bench)


def test_reference_counts_t3_as_upstream_publishes_it():
    """The small binomial sample tree, 4 M hashes, once: nodes and depth
    as remembered from sample_trees.sh; the leaves are 87.5 % of the
    nodes, 1 - q, where 3,290,922 (80 %) were remembered (PERF.md)."""
    t3 = {"b0": 2000, "q": 0.124875, "m": 8, "root_seed": 42}
    got = uts_bin.count_tree(t3, np)
    assert (got["nodes"], got["leaves"], got["depth"]) == (
        4112897, 3599034, 1572)
    assert got["hashed_nodes"] == 4112896 and got["widest_level"] == 6896
