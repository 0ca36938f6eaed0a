"""``smithwaterman._cummax_lanes``, the wavefront sweep's prefix scan: the
same inclusive running maximum as ``np.maximum.accumulate``, in as many
dependent trips through the cross-lane unit as ``SCAN_STAGES`` has stages
(a row of the sweep waits on each of them; PERF.md section 6, PR 54)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from hclib_tpu.device import smithwaterman as sw

T, NEG = sw.T, sw.NEG


def scan_interpreted(x):
    """``(inclusive, exclusive)`` running maxima of ``x`` by the engine's
    scan, and the inclusive one alone must be the same plane."""
    def body(x_ref, o_ref, left_ref, alone_ref):
        o_ref[...], left_ref[...] = sw._cummax_lanes(x_ref[...], shifted=True)
        alone_ref[...] = sw._cummax_lanes(x_ref[...])

    shape = jax.ShapeDtypeStruct(x.shape, jnp.int32)
    got, left, alone = map(np.asarray, pl.pallas_call(
        body, out_shape=(shape,) * 3, interpret=True)(jnp.asarray(x)))
    assert np.array_equal(got, alone)
    return got, left


def plane(kind, rows, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(-(1 << 20), 1 << 20, (rows, T), dtype=np.int32)
    if kind == "neg_entries":  # what a masked lane and a dead slot hold
        x[rng.random((rows, T)) < 0.4] = NEG
    elif kind == "all_neg":
        x[:] = NEG
    elif kind == "max_in_lane_0":  # must reach lane 127 through every stage
        x[:, 0] = 1 << 21
    elif kind == "max_in_lane_127":  # and must wrap round to no lane
        x[:, T - 1] = 1 << 21
    elif kind == "descending":  # every window's maximum is its oldest lane
        x = np.sort(x, axis=1)[:, ::-1].copy()
    else:
        assert kind == "random"
    return x


KINDS = ["random", "neg_entries", "all_neg", "max_in_lane_0",
         "max_in_lane_127", "descending"]


@pytest.mark.parametrize("rows", [1, 8, 16])
@pytest.mark.parametrize("kind", KINDS)
def test_scan_is_the_inclusive_running_maximum(kind, rows):
    x = plane(kind, rows, seed=54 + rows)
    want = np.maximum.accumulate(x, axis=1)
    got, left = scan_interpreted(x)
    assert np.array_equal(got, want)
    # the exclusive scan is the inclusive one a lane on, NEG in lane 0
    assert np.array_equal(left[:, 1:], want[:, :-1])
    assert (left[:, 0] == NEG).all()


def test_every_stage_widens_the_window_to_the_next_stages_first_shift():
    """The windows overlap but leave no hole: a stage's shifts step by the
    window it was handed, and the last stage covers the 128 lanes."""
    window = 1
    for shifts in sw.SCAN_STAGES:
        assert shifts == tuple(range(window, window * (len(shifts) + 1),
                                     window)), (window, shifts)
        window *= len(shifts) + 1
    assert window >= T


def roll_depths(jaxpr, depths):
    """The number of ``roll``s in series behind each output of ``jaxpr``,
    given that of each input (sub-jaxprs walked, not counted as one)."""
    depth = dict(zip(jaxpr.invars, depths))

    def of(v):
        return depth.get(v, 0)  # a literal or a constant

    for eqn in jaxpr.eqns:
        ins = [of(v) for v in eqn.invars if not hasattr(v, "val")]
        sub = eqn.params.get("jaxpr")
        if sub is not None:
            outs = roll_depths(getattr(sub, "jaxpr", sub), ins)
        else:
            d = max(ins, default=0) + (eqn.primitive.name == "roll")
            outs = [d] * len(eqn.outvars)
        depth.update(zip(eqn.outvars, outs))
    return [of(v) for v in jaxpr.outvars]


@pytest.mark.parametrize("rows", [1, 16])
@pytest.mark.parametrize("shifted", [False, True])
def test_longest_chain_of_dependent_rolls_is_the_stage_count(rows, shifted):
    """A row of the sweep waits on every roll in series: a later edit must
    not lengthen the chain unseen. The exclusive scan rides the last stage
    (one roll more a shift of it, none in series)."""
    closed = jax.make_jaxpr(lambda x: sw._cummax_lanes(x, shifted))(
        jnp.zeros((rows, T), jnp.int32))
    rolls = [e for e in closed.jaxpr.eqns if e.primitive.name == "roll"]
    stages = sw.SCAN_STAGES
    assert len(rolls) == sum(map(len, stages)) + shifted * (
        len(stages[-1]) + 1)
    assert roll_depths(closed.jaxpr, [0]) == [len(stages)] * (1 + shifted)
    assert len(stages) <= 4  # the radix-2 ladder it replaced had 7


def test_roll_depths_counts_a_ladder_as_its_length():
    """The walk itself, on the ladder the scan used to be."""
    def ladder(x):
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        for sh in (1, 2, 4, 8, 16, 32, 64):
            x = jnp.maximum(
                x, jnp.where(lane >= sh, sw.pltpu.roll(x, sh, axis=1), NEG))
        return x

    closed = jax.make_jaxpr(ladder)(jnp.zeros((8, T), jnp.int32))
    assert roll_depths(closed.jaxpr, [0]) == [7]
