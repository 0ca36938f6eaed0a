"""Lesson 14: the forasync device tier - data-parallel loops on batch lanes.

Lesson 3 ran forasync on the HOST: the loop tiles into ranges, each tile
becomes a host task, and a dist func places tiles on locales. This lesson
lowers the same construct onto the DEVICE (device/forasync_tier.py):

- **A tile IS a same-kind batch.** Every flat tile becomes one task
  descriptor of one kernel kind, so the whole loop rides the lesson-7
  batch lanes: each round fires up to ``width`` tiles through ONE tiled
  Pallas body, with the double-buffered operand prefetch loading the
  next batch's slabs under the current batch's compute.
- **The body is a slab pipeline.** A ``TileKernel`` declares operand
  slabs (windows of named HBM buffers addressed by the tile's loop
  offsets), a pure compute function on the loaded values, and output
  slabs - the tier derives the scalar-dispatch kernel, the batched body,
  and its prefetch drain from that one declaration, which is why the
  two device spellings are bit-identical by construction.
- **RECURSIVE makes the tiles on the device.** FLAT stages one
  descriptor a tile from the host, so the task table must hold every
  tile - and a table row costs a KiB of the chip's 1 MiB of SMEM.
  ``mode=hc.RECURSIVE`` stages ONE range descriptor instead: a split kind
  on the scalar tier halves the widest dimension at a tile boundary and
  spawns its halves until a half is one tile, which goes through the same
  lane and the same body. The lane fires as soon as it holds two batches,
  so the splitter is paced by its consumer and the table holds the live
  set (two batches of tiles, a range a level of the recursion) however
  many tiles the loop has: 256 tiles run through 64 rows below.
- **Time steps are dependences, not barriers.** A ``TileKernel`` that
  declares ``steps=`` and ``awaits=`` advances its grid several time
  steps in ONE call: a tile of step t+1 is made on the device when the
  last tile it awaits of step t has stored (a countdown a tile in SMEM
  that a finishing tile decrements), and goes straight onto the lane, so
  a tile of step t+1 may run while tiles of step t have not.
- **Placement is data, not code.** On a mesh, a JSON placement
  descriptor (or a classic dist func) resolved against
  ``locality_graphs/*.json`` maps each flat tile to a device, seeding
  the per-device ready rings; the machine graph also orders the steal
  scan near-neighbors-first (``steal_hop_order``), so a skewed or stale
  placement degrades into recoverable work stealing.

Env spelling for wrapper scripts: ``HCLIB_TPU_FORASYNC_WIDTH`` sets the
default batch width.
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The mesh part wants 4 virtual devices; harmless if already set wider.
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)

import numpy as np  # noqa: E402

import hclib_tpu as hc  # noqa: E402
from hclib_tpu.device.forasync_tier import run_forasync_device  # noqa: E402
from hclib_tpu.device.megakernel import C_EXECUTED  # noqa: E402
from hclib_tpu.device.workloads import (  # noqa: E402
    jacobi_data,
    jacobi_loop,
    jacobi_reference,
    jacobi_result,
    map_body,
    map_data,
    map_loop,
    map_reference,
    stencil_body,
    stencil_data,
    stencil_loop,
    stencil_reference,
)
from hclib_tpu.runtime.locality import MeshPlacement  # noqa: E402

H, W = 16, 512  # 2x4 tiles of (8, 128)


def part_one_host_vs_device():
    """The same 2D Jacobi-style stencil three ways - host forasync,
    scalar device dispatch, and the batched tile tier - bit-identical."""
    tk, bounds, tile = stencil_loop(H, W)
    gin, gout = stencil_data(H, W)
    ref = stencil_reference(gin)

    ghost = gout.copy()

    def main():
        hc.forasync(stencil_body(gin, ghost), bounds, tile=tile)

    hc.launch(main, nworkers=2)
    assert np.array_equal(ghost, ref)

    d_scalar, _ = run_forasync_device(
        tk, bounds, tile, {"gin": gin, "gout": gout.copy()}, width=0
    )
    assert np.array_equal(np.asarray(d_scalar["gout"]), ref)

    # place="device" is the forasync spelling of the same call; the body
    # is the TileKernel and the result comes back as (data, info).
    d_tile, info = hc.forasync(
        tk, bounds, tile=tile, place="device",
        data={"gin": gin, "gout": gout.copy()}, width=4,
    )
    assert np.array_equal(np.asarray(d_tile["gout"]), ref)
    t = info["tiers"]
    print(f"  stencil: {t['batch_tasks']} tiles in {t['batch_rounds']} "
          f"batch rounds, occupancy {t['batch_occupancy']:.2f}, "
          f"{t['prefetch_hits']} prefetch hits - three arms bit-identical")


def part_two_map_loop():
    """Map-style batched apply (the batched-inference shape): 1D loop,
    one (8,128) block per tile, prefetch hiding the operand loads."""
    T = 16
    tk, bounds, tile = map_loop(T)
    vin, vout = map_data(T)
    ref = map_reference(vin)

    vh = vout.copy()

    def main():
        hc.forasync(map_body(vin, vh), bounds, tile=tile)

    hc.launch(main, nworkers=2)
    assert np.array_equal(vh, ref)

    d, info = hc.forasync(
        tk, bounds, tile=tile, place="device",
        data={"vin": vin, "vout": vout.copy()}, width=8,
    )
    assert np.array_equal(np.asarray(d["vout"]), ref)
    print(f"  map: {info['tiers']['batch_tasks']} tiles, occupancy "
          f"{info['tiers']['batch_occupancy']:.2f}")


def part_two_b_recursive():
    """More tiles than table rows: FLAT refuses and names RECURSIVE,
    which makes the 256 tiles on the device through a table of 64."""
    T = 256
    tk, bounds, tile = map_loop(T)
    vin, vout = map_data(T)
    try:
        hc.forasync(tk, bounds, tile=tile, place="device", capacity=64,
                    data={"vin": vin, "vout": vout.copy()}, width=8)
    except ValueError as e:
        assert "mode=RECURSIVE" in str(e)
    else:
        raise AssertionError("256 tiles were staged into 64 rows")
    d, info = hc.forasync(
        tk, bounds, tile=tile, mode=hc.RECURSIVE, place="device",
        capacity=64, data={"vin": vin, "vout": vout.copy()}, width=8,
    )
    assert np.array_equal(np.asarray(d["vout"]), map_reference(vin))
    fa, t = info["forasync"], info["tiers"]
    assert fa["live_rows_max"] < fa["capacity"] == 64 < fa["tiles"]
    print(f"  recursive: {fa['tiles']} tiles from {fa['splits']} splits "
          f"through {fa['capacity']} rows, {fa['live_rows_max']} live at "
          f"most; {t['batch_rounds']} rounds, occupancy "
          f"{t['batch_occupancy']:.2f}, {t['prefetch_hits']} prefetch hits")


def part_two_c_time_steps():
    """The same stencil advanced four time steps in one call: two planes
    in one buffer, each tile awaiting its own and its four edge
    neighbours' tiles of the step before, no barrier between steps;
    checked against the numpy oracle applied as many times."""
    HS, WS, steps = 128, 256, 4  # 16 x 2 tiles: tall enough to overlap
    tk, bounds, tile = jacobi_loop(HS, WS, steps=steps)
    grid = jacobi_data(HS, WS, seed=14)
    d, info = hc.forasync(
        tk, bounds, tile=tile, mode=hc.RECURSIVE, place="device",
        data={"grid": grid}, width=2,
    )
    got = jacobi_result(np.asarray(d["grid"]), steps)
    want = jacobi_reference(jacobi_result(grid, 0), steps)
    assert np.array_equal(got, want)
    fa = info["forasync"]
    assert fa["released"] == (steps - 1) * 32 and fa["mixed_rounds"] > 0
    assert fa["live_rows_max"] < fa["capacity"] < fa["tiles"]
    print(f"  {steps} steps: {fa['tiles']} tiles, {fa['released']} made by "
          f"the last of their {fa['decrements']} awaited stores, "
          f"{fa['mixed_rounds']} of {info['tiers']['batch_rounds']} rounds "
          f"held tiles of several steps (at most {fa['step_skew_max']} "
          f"apart), {fa['live_rows_max']} rows live at most")


def part_three_mesh_placement():
    """Placement as data: a JSON descriptor seeds the per-device ready
    rings; the machine graph orders the steal scan; a deliberately
    skewed placement still completes exactly via stealing."""
    tk, bounds, tile = stencil_loop(H, W)
    gin, gout = stencil_data(H, W)
    ref = stencil_reference(gin)

    block = MeshPlacement.from_file(
        os.path.join(_REPO, "locality_graphs", "v5e_4.place_block.json")
    )
    print(f"  graph-derived steal scan order: {block.hop_order()} "
          "(2x2 ICI ring: hop 2 is the direct neighbor)")
    d, info = run_forasync_device(
        tk, bounds, tile, {"gin": gin, "gout": gout.copy()},
        width=4, placement=block, quantum=2, window=4,
    )
    assert np.array_equal(np.asarray(d["gout"]), ref)
    print(f"  block placement seeded {info['placement_counts']} tiles/dev")

    skew = MeshPlacement.from_file(
        os.path.join(_REPO, "locality_graphs", "v5e_4.place_skew.json")
    )
    d, info = run_forasync_device(
        tk, bounds, tile, {"gin": gin, "gout": gout.copy()},
        width=4, placement=skew, quantum=1, window=4,
    )
    assert np.array_equal(np.asarray(d["gout"]), ref)
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    assert int((per_dev > 0).sum()) > 1
    print(f"  skewed placement [8,0,0,0] executed as "
          f"{per_dev.tolist()} - recovered by locality-ordered stealing")


if __name__ == "__main__":
    print("host vs device, bit-identical:")
    part_one_host_vs_device()
    print("map loop:")
    part_two_map_loop()
    print("recursive splitting on the device:")
    part_two_b_recursive()
    print("time steps with tile dependences, one call:")
    part_two_c_time_steps()
    print("mesh placement + stealing:")
    part_three_mesh_placement()
    print("lesson 14 OK")
