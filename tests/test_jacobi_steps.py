"""Time steps with tile dependences on the forasync device tier (PR 51):
``TileKernel(steps=, awaits=)`` through ``hc.forasync(..., mode=RECURSIVE,
place="device")``, held to the benchmark's plain reference
(``benchmarks/reference/jacobi.py``, which imports nothing of the program)
and to the schedule replayed on the host (``StepPlan.simulate``)."""

import numpy as np
import pytest

import hclib_tpu as hc
from benchmarks.reference import jacobi as ref
from hclib_tpu.analysis import (
    AnalysisError, certify_tile_schedule, check_tile_windows,
)
from hclib_tpu.device import workloads as wl
from hclib_tpu.device.forasync_tier import (
    Slab, StepPlan, TileKernel, make_forasync_megakernel, tile_grid,
)

TH, TW = 8, 128
R, C = wl.JAC_HR, wl.JAC_HC
NO_ROWS = ((0, 0), (0, -1), (0, 1))  # north and south left out


def _interior(grid):
    return np.asarray(grid)[0, R:-R, C:-C]


def _run(ny, nx, steps, width, seed=5, **kw):
    H, W = ny * TH, nx * TW
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps)
    g = wl.jacobi_data(H, W, seed)
    out, info = hc.forasync(
        tk, bounds, tile=tile, mode=hc.RECURSIVE, place="device",
        data={"grid": g}, width=width, **kw)
    return tk, bounds, tile, g, np.asarray(out["grid"]), info


def _want(g, steps):
    H, W = g.shape[1] - 2 * R, g.shape[2] - 2 * C
    return np.concatenate(
        [b.copy() for _, b in ref.sweeps(_interior(g), H, W, steps,
                                         band=16, threads=2)])


# (tile rows, tile columns, steps): 2 x 2 up to 8 x 4 tiles; the tall one
# is where steps overlap in a round.
CASES = [(2, 2, 1), (2, 2, 2), (3, 3, 3), (16, 2, 3), (8, 4, 8)]
# every case at width 2 and on the scalar arm; width 8 (a minute of the
# interpreter's tracing a build) on the smallest and the largest
ARMS = [(c, w) for c in CASES for w in (2, 0)] + [
    ((2, 2, 2), 8), ((8, 4, 8), 8)]


@pytest.mark.parametrize(
    "case,width", ARMS, ids=lambda v: (
        "%dx%dx%d" % v if isinstance(v, tuple) else "w%d" % v))
def test_steps_equal_the_reference_and_the_replayed_schedule(case, width):
    ny, nx, steps = case
    tk, bounds, tile, g, got, info = _run(ny, nx, steps, width)
    H, W = ny * TH, nx * TW
    assert np.array_equal(wl.jacobi_result(got, steps), _want(g, steps))
    halo = got.copy()
    halo[:, R:-R, C:-C] = 0
    assert not halo.any()  # the zero halo kept zero, in both planes
    counts = ref.loop_counts(H, W, (TH, TW), steps)
    loop = info["forasync"]
    assert info["executed"] == counts["executed"] and info["pending"] == 0
    assert not info["overflow"]
    assert (loop["tiles"], loop["splits"]) == (counts["tiles"],
                                               counts["splits"])
    assert loop["live_rows_max"] < loop["capacity"]
    if width:  # every tile of every step exactly once, through the lane
        t = info["tiers"]
        assert t["batch_tasks"] == counts["tiles"]
        assert t["scalar_tasks"] == counts["splits"]
    if steps == 1:  # today's loop: nothing awaited, nothing counted
        assert "released" not in loop
        return
    assert loop["steps"] == steps
    assert (loop["released"], loop["decrements"]) == (
        counts["released"], counts["decrements"])
    # the schedule the host replays is the one the kernel ran
    sim = StepPlan(tk, *tile_grid(bounds, tile)[:3]).simulate(width)
    for k in ("released", "decrements", "mixed_rounds", "step_skew_max",
              "live_rows_max"):
        assert loop[k] == sim[k], k
    if width:
        assert t["batch_rounds"] == sim["batch_rounds"]
        assert t["direct"] == counts["released"]  # straight onto the lane
    if case == (16, 2, 3) and width == 2:
        # no barrier: rounds that held tiles of two steps
        assert loop["mixed_rounds"] > 0 and loop["step_skew_max"] >= 1


def test_one_step_gives_stencil_loops_answers():
    H, W = 2 * TH, 3 * TW
    gin, _ = wl.stencil_data(H, W, seed=9)
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps=1)
    g = wl.jacobi_data(H, W, 0)
    g[0, R:-R, C:-C] = gin[1:H + 1, 1:W + 1]
    out, info = hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                            place="device", data={"grid": g}, width=4)
    assert np.array_equal(
        wl.jacobi_result(np.asarray(out["grid"]), 1),
        wl.stencil_reference(gin))
    assert info["forasync"]["tiles"] == 6


def test_a_table_smaller_than_one_steps_tiles_does_not_overflow():
    ny, nx, steps, width = 16, 4, 3, 4
    H, W = ny * TH, nx * TW
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps)
    live = StepPlan(tk, *tile_grid(bounds, tile)[:3]).simulate(width)[
        "live_rows_max"]
    cap = live + 2
    assert cap < ny * nx  # fewer rows than one step has tiles
    g = wl.jacobi_data(H, W, 2)
    out, info = hc.forasync(
        tk, bounds, tile=tile, mode=hc.RECURSIVE, place="device",
        data={"grid": g}, width=width, capacity=cap)
    assert not info["overflow"] and info["pending"] == 0
    assert info["forasync"]["live_rows_max"] == live < cap
    assert np.array_equal(wl.jacobi_result(np.asarray(out["grid"]), steps),
                          _want(g, steps))


def test_a_neighbour_not_awaited_gives_a_wrong_grid():
    """The check sees a missed dependence: with the tiles above and below
    left out of what a tile awaits, a tile of step 1 runs before the row
    under it has stored step 0, on the schedule the tier runs."""
    ny, nx, steps = 8, 2, 3
    H, W = ny * TH, nx * TW
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps, awaits=NO_ROWS)
    g = wl.jacobi_data(H, W, 5)
    with pytest.raises(AnalysisError, match="does not await"):
        hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                    place="device", data={"grid": g}, width=2)
    mk = make_forasync_megakernel(tk, width=2, space=(bounds, tile),
                                  verify=False)
    out, info = hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                            place="device", data={"grid": g}, width=2,
                            mk=mk)
    assert info["pending"] == 0  # every tile ran, each once
    assert info["tiers"]["batch_tasks"] == steps * ny * nx
    got = wl.jacobi_result(np.asarray(out["grid"]), steps)
    assert np.count_nonzero(got != _want(g, steps)) > 0


# ------------------------------------------------ the proofs, no kernel


def _superset_loop(H, W, steps):
    """``jacobi_loop``'s layout read as ONE aligned superset a tile,
    corners and all, with the five awaits: what the shipped loop is not."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return TileKernel(
        loads=[Slab("v", "grid", lambda a: (
            a[4] & 1, pl.ds(a[1], TH + 2 * R), pl.ds(a[2], TW + 2 * C)),
            (TH + 2 * R, TW + 2 * C))],
        stores=[Slab("o", "grid", lambda a: (
            (a[4] + 1) & 1, pl.ds(a[1] + R, TH), pl.ds(a[2] + C, TW)),
            (TH, TW))],
        compute=lambda ins: {"o": ins["v"][R:R + TH, C:C + TW]},
        data_specs={"grid": jax.ShapeDtypeStruct(
            (2, H + 2 * R, W + 2 * C), jnp.int32)},
        steps=steps, awaits=wl.JAC_AWAITS,
    )


@pytest.mark.parametrize("size", ["small", "cell"])
def test_read_before_overwrite_is_proved_on_the_shipped_loop(size):
    H, W, th, tw, steps = {
        "small": (4 * TH, 3 * TW, TH, TW, 3),
        # jacobi-dep-hbm's concrete tile space: 128 x 32 tiles, 8 steps
        "cell": (32768, 32768, 256, 1024, 8),
    }[size]
    tk, bounds, tile = wl.jacobi_loop(H, W, th, tw, steps)
    rep = check_tile_windows(tk, bounds, tile)
    assert rep.findings == []


@pytest.mark.parametrize("broken", ["rows", "columns", "corners"])
def test_the_rule_names_the_two_tiles(broken):
    H, W, steps = 4 * TH, 3 * TW, 3
    if broken == "corners":
        tk, bounds, tile = _superset_loop(H, W, steps), [H, W], [TH, TW]
    else:
        awaits = NO_ROWS if broken == "rows" else ((0, 0), (-1, 0), (1, 0))
        tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps,
                                          awaits=awaits)
    rep = check_tile_windows(tk, bounds, tile)
    (f,) = [f for f in rep.findings if f.rule == "tile-race"]
    a, b = f.witness["tile_a"], f.witness["tile_b"]
    d = (a[0] - b[0], a[1] - b[1])
    assert (f.witness["step_a"], f.witness["step_b"]) == (0, 1)
    assert d in {"rows": {(1, 0), (-1, 0)}, "columns": {(0, 1), (0, -1)},
                 "corners": {(1, 1), (1, -1), (-1, 1), (-1, -1)}}[broken]
    with pytest.raises(AnalysisError):
        rep.raise_errors()


def test_the_model_checker_honours_the_declared_awaits_and_no_more():
    H, W, steps = 4 * TH, 3 * TW, 3
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps)
    cert = certify_tile_schedule(tk, bounds, tile)
    assert (cert["status"], cert["tiles"]) == ("certified", 36)
    bad, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps, awaits=NO_ROWS)
    cert = certify_tile_schedule(bad, bounds, tile, raise_on_error=False)
    assert cert["status"] == "refused (order-dependent)"


def test_the_build_verifies_and_describes_itself():
    tk, bounds, tile = wl.jacobi_loop(4 * TH, 2 * TW, TH, TW, steps=3)
    mk = make_forasync_megakernel(tk, width=4, space=(bounds, tile),
                                  verify=True)
    assert [f for f in mk.analysis.findings if f.severity == "error"] == []
    kinds = mk.describe()["kinds"]
    assert kinds["fa_jacobi"]["dispatch"] == "batch"
    assert mk.num_values == 8 + 2 * 8 and mk.fa_plan.steps == 3


@pytest.mark.parametrize("what", ["flat", "asymmetric", "no-self",
                                  "prebuilt", "lifo", "staging"])
def test_what_the_entry_point_refuses(what):
    H, W = 2 * TH, 2 * TW
    tk, bounds, tile = wl.jacobi_loop(H, W, TH, TW, steps=2)
    g = wl.jacobi_data(H, W)
    if what == "flat":
        with pytest.raises(ValueError, match="needs mode=RECURSIVE"):
            hc.forasync(tk, bounds, tile=tile, mode=hc.FLAT,
                        place="device", data={"grid": g})
    elif what == "asymmetric":
        with pytest.raises(ValueError, match="its opposite"):
            wl.jacobi_loop(H, W, TH, TW, 2, awaits=((0, 0), (1, 0)))
    elif what == "no-self":
        with pytest.raises(ValueError, match="its own tile"):
            wl.jacobi_loop(H, W, TH, TW, 2, awaits=((1, 0), (-1, 0)))
    elif what == "prebuilt":
        other, _, _ = wl.jacobi_loop(H, W, TH, TW, steps=3)
        mk = make_forasync_megakernel(other, width=2, space=(bounds, tile))
        with pytest.raises(ValueError, match="another loop's steps"):
            hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                        place="device", data={"grid": g}, width=2, mk=mk)
    elif what == "lifo":
        with pytest.raises(ValueError, match="pops FIFO"):
            make_forasync_megakernel(tk, width=2, prefetch=False,
                                     space=(bounds, tile))
    else:
        with pytest.raises(ValueError, match="undeclared staging"):
            TileKernel(loads=[Slab("a", "g", lambda a: (), (8, 128),
                                   into="v", at=())],
                       stores=[], compute=lambda i: {}, data_specs={
                           "g": tk.data_specs["grid"]})


# ------------------------------------------------------- the reference


@pytest.mark.parametrize("shape", [(40, 256, 3, 16), (300, 384, 8, 64),
                                   (64, 512, 1, 256)],
                         ids=lambda s: "%dx%dx%d-b%d" % s)
def test_the_references_bands_equal_whole_grid_sweeps(shape):
    H, W, steps, band = shape
    g = np.random.default_rng(1).integers(0, 1 << 31, (H, W),
                                          dtype=np.int32)  # sums wrap
    got = np.concatenate(
        [b.copy() for _, b in ref.sweeps(g, H, W, steps, band=band)])
    assert np.array_equal(got, wl.jacobi_reference(g, steps))
    c, counts, errs = ref.self_check(
        g, H, W, (TH, TW), steps, ref.loop_counts(H, W, (TH, TW), steps))
    assert c == min(256, H, W) and not any(errs.values())
