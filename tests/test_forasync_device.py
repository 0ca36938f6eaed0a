"""forasync device tier (ISSUE 9): tile loops lowered onto batch lanes,
data-driven mesh placement from locality_graphs/, locality-ordered
stealing, checkpoint mid-loop, and the partial-batch starvation detector.

The acceptance spine: stencil and map-loop results bit-identical across
host forasync, scalar device dispatch, and the tile tier (single device
and the 4-device interpret mesh), with placement as data and skew
recovered by stealing.
"""

import os

import numpy as np
import pytest
from jax.experimental import pallas as pl

import hclib_tpu as hc
from hclib_tpu.device.descriptor import F_A0, TaskGraphBuilder, ring_window
from hclib_tpu.device.forasync_tier import (
    FA_SPLIT,
    FA_TILE,
    Slab,
    TileKernel,
    make_forasync_megakernel,
    place_tiles,
    run_forasync_device,
    seed_root,
    seed_tiles,
    split_plan,
    tile_args,
    tile_grid,
)
from hclib_tpu.device.megakernel import (
    C_EXECUTED,
    C_HEAD,
    C_TAIL,
    Megakernel,
)
from hclib_tpu.device.workloads import (
    batch_of,
    map_body,
    map_data,
    map_loop,
    map_reference,
    stencil_body,
    stencil_data,
    stencil_loop,
    stencil_reference,
)
from hclib_tpu.runtime.locality import (
    MeshPlacement,
    load_locality_file,
    resolve_placement,
    steal_hop_order,
)

GRAPHS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "locality_graphs",
)

# One small stencil configuration shared by every arm in this file: 8
# tiles of (8, 128) so a width-4 batch tier fires full rounds, kept tiny
# because each distinct megakernel build is an XLA compile.
H, W = 16, 512
TK, BOUNDS, TILE = stencil_loop(H, W)
GIN, GOUT0 = stencil_data(H, W)
REF = stencil_reference(GIN)
TOTAL = 8


# ------------------------------------------------------------ tiling math


def test_tile_grid_math():
    dims, tdims, counts, total = tile_grid([16, 512], [8, 128])
    assert (dims, tdims, counts, total) == (
        [(0, 16), (0, 512)], [8, 128], [2, 4], 8
    )
    # Flat order is row-major; args carry [flat, lo0, lo1, lo2].
    assert tile_args(dims, tdims, counts, 0) == [0, 0, 0, 0]
    assert tile_args(dims, tdims, counts, 5) == [5, 8, 128, 0]
    # (lo, hi) bounds offset the lo corner.
    dims2, td2, c2, t2 = tile_grid([(4, 12)], 4)
    assert tile_args(dims2, td2, c2, 1) == [1, 8, 0, 0]
    # Ragged tiling is a device-path error, not a silent clamp.
    with pytest.raises(ValueError, match="divide the bounds exactly"):
        tile_grid([10], [4])
    with pytest.raises(ValueError, match="1-3 dimensions"):
        tile_grid([2, 2, 2, 2], 1)


def test_place_arguments_validated():
    # RECURSIVE runs on the device now (the tests below); ragged bounds
    # are still refused there, in either mode.
    with pytest.raises(ValueError, match="divide the bounds exactly"):
        hc.forasync(TK, [H + 4, W], tile=TILE, mode=hc.RECURSIVE,
                    place="device", data={"gin": GIN, "gout": GOUT0})
    with pytest.raises(ValueError, match="explicit tile"):
        hc.forasync(TK, BOUNDS, place="device")
    with pytest.raises(ValueError, match="unknown forasync place"):
        hc.forasync(lambda i: None, [4], place="gpu")
    with pytest.raises(TypeError, match="place='device'"):
        hc.forasync(lambda i: None, [4], width=4)
    with pytest.raises(ValueError, match="synchronous"):
        hc.forasync(TK, BOUNDS, tile=TILE, place="device",
                    blocking=False)


# ------------------------------------------------- three-arm bit-identity


def test_stencil_three_arms_bit_identical():
    # Host forasync arm.
    ghost = GOUT0.copy()

    def main():
        hc.forasync(stencil_body(GIN, ghost), BOUNDS, tile=TILE)

    hc.launch(main, nworkers=3)
    assert np.array_equal(ghost, REF)

    # Scalar device dispatch arm (width=0: one tile per lax.switch).
    d_sc, info_sc = run_forasync_device(
        TK, BOUNDS, TILE, {"gin": GIN, "gout": GOUT0.copy()}, width=0
    )
    assert np.array_equal(np.asarray(d_sc["gout"]), ghost)
    assert info_sc["executed"] == TOTAL

    # Tile tier arm: batch lanes + double-buffered operand prefetch.
    d_bt, info_bt = run_forasync_device(
        TK, BOUNDS, TILE, {"gin": GIN, "gout": GOUT0.copy()}, width=4
    )
    assert np.array_equal(np.asarray(d_bt["gout"]), ghost)
    t = info_bt["tiers"]
    assert t["batch_tasks"] == TOTAL and t["scalar_tasks"] == 0
    assert t["batch_rounds"] > 0 and t["batch_occupancy"] == 1.0
    # The cross-round prefetch engaged: every batch past the first had
    # its operand slabs in flight one round early.
    assert t["prefetch_hits"] == TOTAL - 4


def test_map_three_arms_bit_identical():
    T = 16
    tkm, mb, mt = map_loop(T)
    vin, vout = map_data(T)
    mref = map_reference(vin)

    vh = vout.copy()

    def main():
        hc.forasync(map_body(vin, vh), mb, tile=mt)

    hc.launch(main, nworkers=2)
    assert np.array_equal(vh, mref)

    d_sc, _ = hc.forasync(
        tkm, mb, tile=mt, place="device",
        data={"vin": vin, "vout": vout.copy()}, width=0,
    )
    assert np.array_equal(np.asarray(d_sc["vout"]), mref)

    d_bt, info = hc.forasync(
        tkm, mb, tile=mt, place="device",
        data={"vin": vin, "vout": vout.copy()}, width=8,
    )
    assert np.array_equal(np.asarray(d_bt["vout"]), mref)
    assert info["tiers"]["batch_tasks"] == T
    assert info["tiers"]["batch_occupancy"] == 1.0


# --------------------------------------------------- placement as data


def test_placement_policies_counts():
    p = MeshPlacement(4, policy="block")
    assert p.counts(8) == [2, 2, 2, 2]
    assert [p.device_of(f, 8) for f in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    assert MeshPlacement(4, policy="cyclic").counts(10) == [3, 3, 2, 2]
    w = MeshPlacement(4, policy="weights", weights=[4, 2, 1, 1])
    assert w.counts(8) == [4, 2, 1, 1]
    s = MeshPlacement(4, policy="single", device=2)
    assert s.counts(8) == [0, 0, 8, 0]
    # Closed-form counts agree with the per-tile mapping (incl. a
    # zero-weight device, which owns no tiles).
    z = MeshPlacement(3, policy="weights", weights=[2, 0, 1])
    brute = [0, 0, 0]
    for f in range(9):
        brute[z.device_of(f, 9)] += 1
    assert z.counts(9) == brute and brute[1] == 0
    # dist-func spelling agrees with device_of.
    df = w.dist_func()
    assert [df(2, f, 8) for f in range(8)] == [
        w.device_of(f, 8) for f in range(8)
    ]
    with pytest.raises(ValueError, match="unknown placement policy"):
        MeshPlacement(4, policy="zigzag")
    with pytest.raises(ValueError, match="wants 4 weights"):
        MeshPlacement(4, policy="weights", weights=[1, 2])


def test_placement_descriptor_files():
    p = MeshPlacement.from_file(
        os.path.join(GRAPHS, "v5e_4.place_block.json")
    )
    assert p.ndev == 4 and p.policy == "block" and p.graph is not None
    assert p.hop_order() == [2, 1]
    skew = MeshPlacement.from_file(
        os.path.join(GRAPHS, "v5e_4.place_skew.json")
    )
    assert skew.counts(8) == [8, 0, 0, 0]
    with pytest.raises(ValueError, match="describes 4 devices"):
        resolve_placement(p, ndev=8)
    with pytest.raises(ValueError, match="'devices' or a 'graph'"):
        MeshPlacement.from_dict({"policy": "block"})
    with pytest.raises(ValueError, match="has 4 tpu locales"):
        MeshPlacement.from_dict(
            {"graph": os.path.join(GRAPHS, "v5e_4.json"), "devices": 8}
        )


def test_steal_hop_order_from_graphs():
    # 2x2 ICI ring: every hop-2 partner is a direct neighbor, half the
    # hop-1 partners are diagonal - the graph flips the default scan.
    assert steal_hop_order(os.path.join(GRAPHS, "v5e_4.json")) == [2, 1]
    g8 = load_locality_file(os.path.join(GRAPHS, "v5e_8.json"))
    order = steal_hop_order(g8)
    assert sorted(order) == [1, 2, 4]
    with pytest.raises(ValueError, match="tpu devices"):
        steal_hop_order(g8, ndev=4)
    # A 1-device roster has no hops: the descriptor hands back None so
    # runners fall back to their default instead of an empty override.
    one = MeshPlacement.from_dict(
        {"graph": os.path.join(GRAPHS, "v5e_1.json")}
    )
    assert one.ndev == 1 and one.hop_order() is None


def test_placement_swap_changes_ring_seeding():
    """Swapping the descriptor changes per-device initial tile counts as
    specified; totals are conserved (each flat tile placed exactly once)."""
    for placement, expect in [
        (MeshPlacement(4, policy="block"), [2, 2, 2, 2]),
        (MeshPlacement(4, policy="cyclic"), [2, 2, 2, 2]),
        (MeshPlacement(4, policy="weights", weights=[4, 2, 1, 1]),
         [4, 2, 1, 1]),
        (os.path.join(GRAPHS, "v5e_4.place_skew.json"), [8, 0, 0, 0]),
        (lambda ndim, flat, total: 3 - flat % 4, [2, 2, 2, 2]),
    ]:
        builders = [TaskGraphBuilder() for _ in range(4)]
        counts = place_tiles(builders, BOUNDS, TILE, placement)
        assert counts == expect, placement
        assert sum(counts) == TOTAL
        assert [b.num_tasks for b in builders] == expect
    # Block vs cyclic seed the same counts but DIFFERENT tiles: the
    # descriptor controls which flat index lands where.
    bb = [TaskGraphBuilder() for _ in range(4)]
    place_tiles(bb, BOUNDS, TILE, MeshPlacement(4, policy="block"))
    cb = [TaskGraphBuilder() for _ in range(4)]
    place_tiles(cb, BOUNDS, TILE, MeshPlacement(4, policy="cyclic"))
    bf = [r[F_A0] for r in bb[0]._rows]
    cf = [r[F_A0] for r in cb[0]._rows]
    assert bf == [0, 1] and cf == [0, 4]


# ------------------------------------------------------------- mesh arms


@pytest.fixture(scope="module")
def mesh_kernel():
    """One batch-tier megakernel + sharded runner shared by the mesh
    tests (the 4-device steal build is the expensive compile here)."""
    from hclib_tpu.device.sharded import ShardedMegakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = make_forasync_megakernel(TK, width=4, capacity=64, interpret=True)
    smk = ShardedMegakernel(mk, cpu_mesh(4, axis_name="q"),
                            migratable_fns=[FA_TILE])
    return mk, smk


def _run_mesh(smk, placement, hop_order, quantum=2):
    builders = [TaskGraphBuilder() for _ in range(4)]
    counts = place_tiles(builders, BOUNDS, TILE, placement)
    stacked = {
        "gin": np.broadcast_to(GIN, (4,) + GIN.shape).copy(),
        "gout": np.zeros((4,) + GOUT0.shape, np.int32),
    }
    _, data, info = smk.run(
        builders, data=stacked, steal=True, quantum=quantum, window=4,
        hop_order=hop_order,
    )
    gout = np.asarray(data["gout"]).sum(axis=0, dtype=np.int32)
    return counts, gout, info


def test_mesh_stencil_bit_identical_with_batch_rounds(mesh_kernel):
    _, smk = mesh_kernel
    p = MeshPlacement.from_file(
        os.path.join(GRAPHS, "v5e_4.place_block.json")
    )
    counts, gout, info = _run_mesh(smk, p, p.hop_order())
    assert counts == p.counts(TOTAL)
    assert np.array_equal(gout, REF)  # bit-identical to the single-device arms
    assert info["executed"] == TOTAL and info["pending"] == 0
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    tiers = info["tiers"]
    for d in range(4):
        if per_dev[d] > 0:
            assert tiers[d]["batch_rounds"] > 0, (d, tiers[d])
    assert sum(t["batch_tasks"] for t in tiers) == TOTAL
    assert sum(t["scalar_tasks"] for t in tiers) == 0


def test_mesh_skewed_placement_completes_by_stealing(mesh_kernel):
    """A deliberately skewed placement (every tile on device 0) still
    completes exactly: tiles are successor-free, so the locality-ordered
    steal exchange spreads them - misplacement is recoverable, not
    fatal."""
    _, smk = mesh_kernel
    skew = MeshPlacement.from_file(
        os.path.join(GRAPHS, "v5e_4.place_skew.json")
    )
    # Same quantum as the identity test so both share ONE compiled steal
    # kernel (quantum is part of the jit cache key).
    counts, gout, info = _run_mesh(smk, skew, skew.hop_order(), quantum=2)
    assert counts == [TOTAL, 0, 0, 0]
    assert np.array_equal(gout, REF)
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    assert int((per_dev > 0).sum()) > 1, per_dev.tolist()
    assert int(per_dev.sum()) == TOTAL


# ------------------------------------------------- checkpoint mid-loop


def test_checkpoint_mid_loop_resume_bit_identical():
    mk = make_forasync_megakernel(
        TK, width=4, capacity=64, interpret=True, checkpoint=True
    )
    b = TaskGraphBuilder()
    seed_tiles(b, BOUNDS, TILE)
    _, full, _ = mk.run(b, data={"gin": GIN, "gout": GOUT0.copy()})
    full_gout = np.asarray(full["gout"])
    assert np.array_equal(full_gout, REF)

    b2 = TaskGraphBuilder()
    seed_tiles(b2, BOUNDS, TILE)
    _, _, q = mk.run(
        b2, data={"gin": GIN, "gout": GOUT0.copy()}, quiesce=TOTAL // 2
    )
    assert q["quiesced"] and q["pending"] > 0
    state = q["state"]
    # Lane spill discipline: the export sees ONLY ring rows - every
    # pending tile sits in the exported ready window (a lane-resident
    # descriptor here would be invisible to restore and lose a tile).
    counts = state["counts"]
    assert state["ready"].shape == (mk.ring_len,)
    rows = ring_window(
        state["ready"], counts[C_HEAD], counts[C_TAIL]
    ).tolist()
    flats = sorted(int(state["tasks"][r][F_A0]) for r in rows)
    assert len(flats) == q["pending"] == len(set(flats))
    assert set(flats) <= set(range(TOTAL))
    # Resume runs the remainder; the final grid is bit-identical to the
    # uninterrupted run.
    _, data_r, info_r = mk.resume(state)
    assert info_r["pending"] == 0
    # C_EXECUTED stages from the exported counts, so the resumed entry
    # reports the CUMULATIVE total across the cut.
    assert info_r["executed"] == TOTAL
    assert np.array_equal(np.asarray(data_r["gout"]), full_gout)


# ------------------------------- partial-batch starvation watch item


PUMP, PTILE = 0, 1


def _pump_kernel(ctx):
    """Dynamic spawner that keeps the ready ring hot: each PUMP spawns
    one batch-routed PTILE and chains the next PUMP behind it, so under
    ring-drain-first firing the lane never holds more than one entry -
    the forasync-style dynamic-producer shape the ROADMAP lane-policy
    watch item predicts will starve partial batches."""
    d = ctx.arg(0)

    @pl.when(d > 0)
    def _():
        nxt = ctx.spawn(PUMP, [d - 1], dep_count=1, nargs=1)
        ctx.spawn(PTILE, [d], succ0=nxt, nargs=1)


def _ptile_kernel(ctx):
    ctx.set_value(0, ctx.value(0) + 1)


def test_lane_partial_age_detector_fires():
    depth = 24
    mk = Megakernel(
        kernels=[("pump", _pump_kernel), ("ptile", _ptile_kernel)],
        route={"ptile": batch_of(_ptile_kernel, width=4)},
        capacity=128, num_values=16, succ_capacity=8,
        interpret=True, trace=4096,
    )
    b = TaskGraphBuilder()
    b.add(PUMP, args=[depth])
    iv, _, info = mk.run(b)
    assert int(iv[0]) == depth
    t = info["tiers"]
    # Every tile fired as a width-1 partial batch: the detector reports
    # a long consecutive-partial streak for the PTILE lane.
    assert t["batch_tasks"] == depth and t["full_rounds"] == 0
    assert t["lane_partial_ages"][PTILE] >= 16, t
    assert t["lane_partial_age"] == t["lane_partial_ages"][PTILE]

    # The gauge rides MetricsRegistry.add_run_info beside lane_occupancy.
    reg = hc.MetricsRegistry()
    reg.add_run_info("pumped", info)
    snap = reg.snapshot()["metrics"]
    assert snap["pumped.lane_partial_age.0"] >= 16
    assert "pumped.lane_occupancy.0" in snap


def test_lane_partial_age_quiet_on_static_tiles():
    """A static tile set (the forasync lowering's shape) fires full
    batches: the detector stays at/near zero - the gauge separates
    healthy loops from starved ones instead of alarming on both."""
    mk = Megakernel(
        kernels=[("pump", _pump_kernel), ("ptile", _ptile_kernel)],
        route={"ptile": batch_of(_ptile_kernel, width=4)},
        capacity=128, num_values=16, succ_capacity=8,
        interpret=True, trace=4096,
    )
    b = TaskGraphBuilder()
    for k in range(8):
        b.add(PTILE, args=[k + 1])
    iv, _, info = mk.run(b)
    assert int(iv[0]) == 8
    t = info["tiers"]
    assert t["full_rounds"] == t["batch_rounds"] == 2
    assert t["lane_partial_age"] == 0


# --------------------------------------- resident ready-ring seeding


def test_resident_ring_seeding_follows_placement():
    """place_tiles seeds the RESIDENT runner's per-device ready rings the
    same way (placement is runner-agnostic data): with stealing disabled
    for the tile kind, each device executes exactly its seeded count."""
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    mk = Megakernel(
        kernels=[("fa_tile", _ptile_kernel)],
        capacity=64, num_values=16, succ_capacity=8, interpret=True,
    )
    rk = ResidentKernel(mk, cpu_mesh(4, axis_name="q"),
                        migratable_fns=[], window=4)
    builders = [TaskGraphBuilder() for _ in range(4)]
    counts = place_tiles(
        builders, [12], [1],
        MeshPlacement(4, policy="weights", weights=[6, 3, 2, 1]),
    )
    assert counts == [6, 3, 2, 1]
    iv, _, info = rk.run(builders, quantum=4)
    assert info["pending"] == 0
    per_dev = np.asarray(info["per_device_counts"])[:, C_EXECUTED]
    assert per_dev.tolist() == counts
    assert int(np.asarray(iv)[:, 0].sum()) == 12


# ------------------------------- RECURSIVE: tiles made on the device


def _stencil(h, w):
    tk, bounds, tile = stencil_loop(h, w)
    gin, gout = stencil_data(h, w, seed=h)
    return (tk, bounds, tile, {"gin": gin, "gout": gout}, "gout",
            stencil_reference(gin))


def _map(t):
    tk, bounds, tile = map_loop(t)
    vin, vout = map_data(t, seed=t)
    return (tk, bounds, tile, {"vin": vin, "vout": vout}, "vout",
            map_reference(vin))


def _cube():
    """A 3-D loop: block (i, j, k) of a (4, 24, 256) grid in (2, 8, 128)
    tiles, 2 x 3 x 2 of them, out = in + 1."""
    import jax
    import jax.numpy as jnp

    shape, tile = (4, 24, 256), (2, 8, 128)
    spec = jax.ShapeDtypeStruct(shape, jnp.int32)

    def window(a):
        return tuple(pl.ds(a[1 + d], tile[d]) for d in range(3))

    tk = TileKernel(
        loads=[Slab("cin", "cin", window, tile)],
        stores=[Slab("cout", "cout", window, tile)],
        compute=lambda ins: {"cout": ins["cin"] + 1},
        data_specs={"cin": spec, "cout": spec}, name="fa_cube",
    )
    cin = np.arange(np.prod(shape), dtype=np.int32).reshape(shape)
    return (tk, list(shape), list(tile),
            {"cin": cin, "cout": np.zeros(shape, np.int32)}, "cout", cin + 1)


LOOPS = {
    "stencil-2x4": lambda: _stencil(16, 512),  # power-of-two tile counts
    "stencil-3x3": lambda: _stencil(24, 384),
    "map-16": lambda: _map(16),
    "map-9": lambda: _map(9),
    "cube-2x3x2": _cube,
}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_recursive_flat_and_numpy_bit_identical(loop):
    tk, bounds, tile, data, out, want = LOOPS[loop]()
    tiles = tile_grid(bounds, tile)[3]
    got = {}
    for mode in (hc.FLAT, hc.RECURSIVE):
        d, info = hc.forasync(
            tk, bounds, tile=tile, mode=mode, place="device", width=4,
            data={k: v.copy() for k, v in data.items()},
        )
        got[mode] = np.asarray(d[out])
        splits = tiles - 1 if mode == hc.RECURSIVE else 0
        assert info["executed"] == tiles + splits and info["pending"] == 0
        t = info["tiers"]
        assert (t["batch_tasks"], t["scalar_tasks"]) == (tiles, splits)
        assert info["forasync"] == {
            "mode": mode, "tiles": tiles, "splits": splits,
            "capacity": info["forasync"]["capacity"],
            "live_rows_max": info["allocated"],
        }
        assert not info["overflow"]
        assert info["allocated"] < info["forasync"]["capacity"]
    assert np.array_equal(got[hc.FLAT], want)
    assert np.array_equal(got[hc.RECURSIVE], want)


def test_recursive_scalar_arm_and_one_tile_loop():
    # width=0: the leaves go through lax.switch like the splits.
    tk, bounds, tile, data, out, want = _stencil(16, 512)
    d, info = hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                          place="device", width=0, data=dict(data))
    assert np.array_equal(np.asarray(d[out]), want)
    assert info["executed"] == 2 * TOTAL - 1 and "tiers" not in info
    # A loop of one tile has nothing to split: the root IS the tile.
    b = TaskGraphBuilder()
    assert seed_root(b, [8, 128], [8, 128]) == 1
    assert int(b.finalize()[0][0][0]) == FA_TILE  # F_FN of row 0
    b = TaskGraphBuilder()
    assert seed_root(b, BOUNDS, TILE) == TOTAL
    row = b.finalize()[0][0]
    assert int(row[0]) == FA_SPLIT
    assert [int(x) for x in row[F_A0:F_A0 + 6]] == [0, 2, 0, 4, 0, 1]


def test_split_plan_counts():
    assert split_plan([32768, 32768], [256, 1024]) == {
        "tiles": 4096, "splits": 4095, "depth": 12}
    assert split_plan([24, 384], [8, 128]) == {
        "tiles": 9, "splits": 8, "depth": 4}
    assert split_plan([8 * 1024], [8]) == {
        "tiles": 1024, "splits": 1023, "depth": 10}
    assert split_plan([8, 128], [8, 128])["depth"] == 0


def test_256_tiles_through_a_table_of_64_rows():
    """Four times as many tiles as table rows: FLAT says so and names
    RECURSIVE, which completes with the table a quarter full."""
    tk, bounds, tile, data, out, want = _map(256)
    with pytest.raises(ValueError, match="mode=RECURSIVE"):
        hc.forasync(tk, bounds, tile=tile, place="device", width=8,
                    capacity=64, data=dict(data))
    # A compiled build is refused at construction, by the SMEM a row a
    # tile would take (nothing is compiled or run for this).
    big = map_loop(2000)
    with pytest.raises(ValueError, match="2000.*SMEM.*mode=RECURSIVE"):
        hc.forasync(big[0], big[1], tile=big[2], place="device", width=8,
                    interpret=False, data=dict(zip(("vin", "vout"),
                                                   map_data(2000))))
    d, info = hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                          place="device", width=8, capacity=64,
                          data=dict(data))
    assert np.array_equal(np.asarray(d[out]), want)
    assert not info["overflow"] and info["pending"] == 0
    fa, t = info["forasync"], info["tiers"]
    assert fa["capacity"] == 64 and fa["tiles"] == 256
    assert fa["live_rows_max"] == info["allocated"] < 32
    assert info["executed"] == 511
    assert (t["batch_tasks"], t["scalar_tasks"]) == (256, 255)
    # The lane fired at two batches, so every round was full and every
    # round but the first found its operands in flight.
    assert t["batch_rounds"] == t["full_rounds"] == 32
    assert t["prefetch_hits"] == 256 - 8 and t["age_fires"] == 0
    # The default table is sized to the live set, not to the tile count.
    assert make_forasync_megakernel(
        tk, width=8, interpret=True, space=(bounds, tile)).capacity == 64


def test_prebuilt_kernel_owns_its_mode_and_space():
    mk = make_forasync_megakernel(TK, width=4, interpret=True,
                                  space=(BOUNDS, TILE))
    assert mk.fa_space == tile_grid(BOUNDS, TILE)[:2]
    assert mk.read_only == ("gin",)
    data = {"gin": GIN, "gout": GOUT0.copy()}
    with pytest.raises(ValueError, match="disagrees with the prebuilt"):
        run_forasync_device(TK, BOUNDS, TILE, data, mk=mk)  # FLAT
    with pytest.raises(ValueError, match="disagrees with the prebuilt"):
        run_forasync_device(TK, [32, 512], TILE, data, mk=mk,
                            mode=hc.RECURSIVE)
    with pytest.raises(ValueError, match="one device"):
        run_forasync_device(TK, BOUNDS, TILE, data, mode=hc.RECURSIVE,
                            placement=MeshPlacement(2, policy="block"))
    d, info = run_forasync_device(TK, BOUNDS, TILE, data, mk=mk,
                                  mode=hc.RECURSIVE)
    assert np.array_equal(np.asarray(d["gout"]), REF)
    assert mk.stats_dict()["forasync"] == info["forasync"]


def test_recursive_checkpoint_mid_loop_resume_bit_identical():
    """The split rows are ordinary rows: a cut taken while ranges and
    tiles are both pending resumes to the uninterrupted grid."""
    mk = make_forasync_megakernel(
        TK, width=4, capacity=64, interpret=True, checkpoint=True,
        space=(BOUNDS, TILE),
    )

    def root():
        b = TaskGraphBuilder()
        seed_root(b, BOUNDS, TILE)
        return b

    _, full, info = mk.run(root(), data={"gin": GIN, "gout": GOUT0.copy()})
    assert np.array_equal(np.asarray(full["gout"]), REF)
    assert info["executed"] == 2 * TOTAL - 1
    _, _, q = mk.run(root(), data={"gin": GIN, "gout": GOUT0.copy()},
                     quiesce=5)
    assert q["quiesced"] and q["pending"] > 0
    state = q["state"]
    counts = state["counts"]
    rows = ring_window(
        state["ready"], counts[C_HEAD], counts[C_TAIL]
    ).tolist()
    kinds = sorted(int(state["tasks"][r][0]) for r in rows)
    # Everything pending is on the ring (the lane spilled), both kinds.
    assert len(rows) == q["pending"] and set(kinds) == {FA_TILE, FA_SPLIT}
    _, data_r, info_r = mk.resume(state)
    assert info_r["pending"] == 0
    assert info_r["executed"] == 2 * TOTAL - 1
    assert np.array_equal(np.asarray(data_r["gout"]), REF)


# --------------------------------- operands that live on the device


@pytest.mark.parametrize("mode", [hc.FLAT, hc.RECURSIVE])
def test_device_operands_are_kept_or_consumed(mode):
    """Megakernel.run's ownership rule through the entry point: a
    ``jax.Array`` the tile kernel only loads is read where it lies and is
    still the caller's; one it stores to is consumed and comes back."""
    import jax.numpy as jnp

    gin, gout = jnp.array(GIN), jnp.array(GOUT0)
    d, info = hc.forasync(TK, BOUNDS, tile=TILE, mode=mode, place="device",
                          width=4, data={"gin": gin, "gout": gout})
    assert d["gin"] is gin and not gin.is_deleted()
    assert np.array_equal(np.asarray(gin), GIN)
    assert gout.is_deleted() and d["gout"] is not gout
    assert np.array_equal(np.asarray(d["gout"]), REF)
    # What came back goes in again (a sweep a time step).
    d2, _ = hc.forasync(TK, BOUNDS, tile=TILE, mode=mode, place="device",
                        width=4, data=d)
    assert d2["gin"] is gin and d["gout"].is_deleted()
    assert np.array_equal(np.asarray(d2["gout"]), REF)
    # Both buffers crossed alone, so the slab held the scheduler only.
    assert info["staging"]["uploads"] == 1
    assert not [b for b in info["staging"]["slab_blocks"] if ":" in b]


def test_numpy_operands_are_untouched():
    gin, gout = GIN.copy(), GOUT0.copy()
    d, _ = hc.forasync(TK, BOUNDS, tile=TILE, mode=hc.RECURSIVE,
                       place="device", width=4,
                       data={"gin": gin, "gout": gout})
    assert np.array_equal(gin, GIN) and np.array_equal(gout, GOUT0)
    assert np.array_equal(np.asarray(d["gout"]), REF)
    assert np.array_equal(np.asarray(d["gin"]), GIN)
