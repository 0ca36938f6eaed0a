#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the runtime's main path once, compiled (``interpret=False`` stated
everywhere), through the constructors a user would call, at sizes the
benches call real, and checks every result exactly or against an
independent numpy reference. One process; it never starts a child.

    python chip_smoke.py               # one TPU chip: all phases below
    python chip_smoke.py --four-chips  # the four-chip host: the mesh phase only

Each phase prints one JSON line as it ends - sizes, ``compile_s`` (first
call minus second), ``run_s`` (second call), the checked value, the device.
Those are set-up facts, not measurements of record. The LAST line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check or exception exits non-zero with no such line, and so does
a machine where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

T1L_NODES = 102_181_082
CHOLESKY_RESIDUAL_BOUND = 1e-6  # at n=8192; the cell cholesky-8192 allows 2e-6


def emit(phase: str, dev: dict, **facts) -> None:
    print(json.dumps({"phase": phase, **facts, "device": dev}), flush=True)


def ran_compiled(info: dict) -> dict:
    """Every runner's info/result dict says how and where it ran; a phase
    whose runner took the interpreter or another platform is a failure."""
    assert info["interpret"] is False and info["platform"] == "tpu", {
        k: info.get(k) for k in ("interpret", "platform")
    }
    return {"interpret": info["interpret"], "platform": info["platform"]}


def twice(fn):
    """(result of the second call, compile_s, run_s): the first call pays
    the compile, the second is the same program again."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    run = time.perf_counter() - t0
    return out, round(max(first - run, 0.0), 3), round(run, 4)


# ------------------------------------------------------------ one chip


def phase_megakernel(dev: dict, seed: int) -> None:
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import (
        FIB, VFIB, make_fib_megakernel, make_vfib_megakernel,
    )
    from hclib_tpu.models.fib import fib_seq, task_count

    # Scalar tier: dynamic spawn/join/continuation through the 768-row
    # SMEM table (rows recycle; fib(20) is 32,836 descriptors).
    n = 20
    mk = make_fib_megakernel(768, interpret=False)

    def scalar():
        b = TaskGraphBuilder()
        b.add(FIB, args=[n], out=0)
        iv, _, info = mk.run(b)
        return int(iv[0]), info

    (value, info), compile_s, run_s = twice(scalar)
    tasks = task_count(n) + (task_count(n) - 1) // 2  # FIB nodes + SUM joins
    assert value == fib_seq(n), (value, fib_seq(n))
    assert info["executed"] == tasks, (info["executed"], tasks)
    emit("megakernel", dev, tier="scalar", fib=n, capacity=mk.capacity,
         value=value, executed=info["executed"], compile_s=compile_s,
         run_s=run_s, **ran_compiled(info))

    # Batch tier: one seed descriptor, the subtree wide over VPU lanes.
    n = 30
    vmk = make_vfib_megakernel(max_n=n + 2, interpret=False)

    def batch():
        b = TaskGraphBuilder()
        b.add(VFIB, args=[n], out=0)
        iv, _, info = vmk.run(b, fuel=1 << 30)
        return int(iv[0]), info

    (value, info), compile_s, run_s = twice(batch)
    tasks = 2 * fib_seq(n + 1) - 1  # the whole recursion tree
    assert value == fib_seq(n), (value, fib_seq(n))
    assert info["executed"] == tasks, (info["executed"], tasks)
    emit("megakernel", dev, tier="batch", fib=n, value=value,
         executed=info["executed"], compile_s=compile_s, run_s=run_s,
         **ran_compiled(info))


def phase_uts(dev: dict, seed: int) -> None:
    from hclib_tpu.device.uts_pallas import uts_pallas
    from hclib_tpu.models.uts import T1L

    lanes, roots = (64, 128), 256 * 1024
    r, compile_s, run_s = twice(lambda: uts_pallas(
        T1L, target_roots=roots, lanes=lanes, min_idle_div=32,
        interpret=False,
    ))
    assert r["nodes"] == T1L_NODES, r["nodes"]
    # run_s is the whole second call (seeding on host and chip, one launch,
    # readback); device_s is that call's one launch of the kernel.
    emit("uts", dev, tree="T1L", lanes=list(lanes), target_roots=roots,
         nodes=r["nodes"], leaves=r["leaves"], max_depth=r["max_depth"],
         compile_s=compile_s, run_s=run_s,
         device_s=round(r["device_seconds"], 4), **ran_compiled(r))


def phase_cholesky(dev: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.cholesky import (
        device_cholesky, make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    n, tile = 8192, 512
    mk = make_cholesky_megakernel(
        n // tile, interpret=False, tile=tile, fused_only=True
    )
    a = make_spd(n, seed=seed).astype(np.float32)
    (L, info), compile_s, run_s = twice(
        lambda: device_cholesky(a, interpret=False, mk=mk, tile=tile)
    )
    assert L.shape == (n, n) and np.isfinite(L).all()
    # Residual on the device at HIGHEST precision (the default bf16
    # matmul's own error would drown it), as the cell cholesky-8192.
    La, Aa = jnp.asarray(L), jnp.asarray(a)
    m = jnp.matmul(La, La.T, precision=jax.lax.Precision.HIGHEST)
    rel = float(jnp.max(jnp.abs(m - Aa)) / jnp.max(jnp.abs(Aa)))
    assert rel < CHOLESKY_RESIDUAL_BOUND, rel
    emit("cholesky", dev, n=n, tile=tile, tasks=info["executed"],
         residual=rel, bound=CHOLESKY_RESIDUAL_BOUND, compile_s=compile_s,
         run_s=run_s, **ran_compiled(info))


def sw_numpy(a: np.ndarray, b: np.ndarray):
    """``(best, last_row, last_col)`` of each pair a[k] (n) vs b[k] (m):
    the best local-alignment score (B), H's last row (B, m) and H's last
    column (B, n), by the row recurrence in plain numpy - independent of
    every engine under test. The in-row gap chain h[j] = max(t[j],
    h[j-1] - GAP) is solved with a running maximum of t[j] + j*GAP."""
    from hclib_tpu.models.smithwaterman import GAP, MATCH, MISMATCH

    B, m = b.shape
    ramp = (np.arange(m, dtype=np.int32) * GAP)[None, :]
    prev = np.zeros((B, m), np.int32)
    diag = np.zeros((B, m), np.int32)
    best = np.zeros(B, np.int32)
    last_col = np.zeros(a.shape, np.int32)
    for i in range(a.shape[1]):
        s = np.where(b == a[:, i:i + 1], MATCH, MISMATCH).astype(np.int32)
        diag[:, 1:] = prev[:, :-1]
        t = np.maximum(np.maximum(diag + s, prev - GAP), 0)
        prev = np.maximum.accumulate(t + ramp, axis=1) - ramp
        last_col[:, i] = prev[:, -1]
        np.maximum(best, prev.max(axis=1), out=best)
    return best, prev, last_col


def phase_sw(dev: dict, seed: int) -> None:
    from hclib_tpu.device.smithwaterman import device_sw_wave
    from hclib_tpu.device.sw_pallas import sw_scores_pallas

    # Fused sweep: score one 1024-long query against each of 1024 database
    # sequences of 1024 (no task graph: the throughput engine).
    B, n, m = 1024, 1024, 1024
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 4, (B, n), dtype=np.int32)
    Bs = rng.integers(0, 4, (B, m), dtype=np.int32)
    got, compile_s, run_s = twice(
        lambda: sw_scores_pallas(A, Bs, interpret=False)
    )
    want = sw_numpy(A, Bs)[0]
    assert got.shape == (B,) and np.array_equal(got, want), (
        int((got != want).sum()), "pairs differ"
    )
    # sw_scores_pallas returns bare scores: it has no info dict, and no
    # branch either - interpret=False goes straight into its pallas_call.
    emit("sw", dev, engine="fused", B=B, n=n, m=m, pairs_equal=B,
         best_score=int(got.max()), compile_s=compile_s, run_s=run_s,
         interpret=False, platform=dev["platform"])

    # Wave-DAG megakernel: ONE 8192 x 8192 alignment as a dependency graph
    # of wave chunks through the batch tier, against the same numpy DP.
    n = m = 8192
    a, b = (rng.integers(0, 4, n, dtype=np.int32) for _ in range(2))
    (score, _, info), compile_s, run_s = twice(
        lambda: device_sw_wave(a, b, interpret=False, with_h=False)
    )
    want, row, col = (x[0] for x in sw_numpy(a[None], b[None]))
    assert score == want, (score, want)
    # What the benchmark cell sw-wave-8192 holds too: every tile feeds
    # H's last row and column, the score only the best path's.
    assert np.array_equal(info["last_row"], row), "last row differs"
    assert np.array_equal(info["last_col"], col), "last column differs"
    emit("sw", dev, engine="wave-dag", n=n, m=m, score=score,
         tasks=info["executed"],
         batch_occupancy=round(info["tiers"]["batch_occupancy"], 3),
         compile_s=compile_s, run_s=run_s, **ran_compiled(info))


def phase_forasync(dev: dict, seed: int) -> None:
    import hclib_tpu as hc
    from hclib_tpu.device.workloads import (
        MAP_ADD, MAP_MUL, map_data, map_loop, stencil_data, stencil_loop,
    )

    # 1D: the map-style batched-apply loop.
    T = 64
    tk, bounds, tile = map_loop(T)
    vin, vout = map_data(T, seed=seed)

    def loop1d():
        return hc.forasync(
            tk, bounds, tile=tile, place="device", width=8,
            interpret=False, data={"vin": vin, "vout": vout.copy()},
        )

    (data, info), compile_s, run_s = twice(loop1d)
    assert np.array_equal(np.asarray(data["vout"]), vin * MAP_MUL + MAP_ADD)
    assert info["executed"] == T, info["executed"]
    emit("forasync", dev, loop="1d-map", elements=int(vin.size), tiles=T,
         equal_to_numpy=True, compile_s=compile_s, run_s=run_s,
         **ran_compiled(info))

    # 2D: the 5-point stencil over a (64, 1024) interior in (8, 128) tiles.
    H, W = 64, 1024
    tk, bounds, tile = stencil_loop(H, W)
    gin, gout = stencil_data(H, W, seed=seed)

    def loop2d():
        return hc.forasync(
            tk, bounds, tile=tile, place="device", width=8,
            interpret=False, data={"gin": gin, "gout": gout.copy()},
        )

    (data, info), compile_s, run_s = twice(loop2d)
    g = gin.astype(np.int64)
    want = (g[1:H + 1, 1:W + 1] + g[:H, 1:W + 1] + g[2:H + 2, 1:W + 1]
            + g[1:H + 1, :W] + g[1:H + 1, 2:W + 2]).astype(np.int32)
    assert np.array_equal(np.asarray(data["gout"]), want)
    tiles = (H // tile[0]) * (W // tile[1])
    assert info["executed"] == tiles, info["executed"]
    emit("forasync", dev, loop="2d-stencil", interior=[H, W],
         tile=list(tile), tiles=tiles, equal_to_numpy=True,
         compile_s=compile_s, run_s=run_s, **ran_compiled(info))

    # The same loop with its tiles made on the device: one range
    # descriptor, 63 splits on the scalar tier, the same lane and body.
    def loop2d_recursive():
        return hc.forasync(
            tk, bounds, tile=tile, mode=hc.RECURSIVE, place="device",
            width=8, interpret=False,
            data={"gin": gin, "gout": gout.copy()},
        )

    (data, info), compile_s, run_s = twice(loop2d_recursive)
    assert np.array_equal(np.asarray(data["gout"]), want)
    assert info["executed"] == 2 * tiles - 1, info["executed"]
    fa, tiers = info["forasync"], info["tiers"]
    assert (tiers["batch_tasks"], tiers["scalar_tasks"]) == (
        tiles, tiles - 1), tiers
    assert fa["live_rows_max"] < fa["capacity"] <= tiles, fa
    emit("forasync", dev, loop="2d-stencil-recursive", interior=[H, W],
         tile=list(tile), tiles=tiles, splits=fa["splits"],
         live_rows_max=fa["live_rows_max"], capacity=fa["capacity"],
         equal_to_numpy=True, compile_s=compile_s, run_s=run_s,
         **ran_compiled(info))


def phase_serve(dev: dict, seed: int) -> None:
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.telemetry import TelemetryBlock
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    def respond(ctx):  # the request: answer 3x+1, keep a running sum
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))
        ctx.set_out(ctx.arg(0) * 3 + 1)

    tenants, per_tenant, capacity = (("gold", 4), ("silver", 2),
                                     ("bronze", 1)), 1024, 320
    rng = np.random.default_rng(seed)

    def serve():
        table = TenantTable(
            [TenantSpec(t, weight=w) for t, w in tenants],
            per_tenant, egress=EgressSpec(depth=64),
        )
        mk = Megakernel(
            kernels=[("respond", respond)], capacity=capacity,
            num_values=8, succ_capacity=8, interpret=False,
        )
        sm = StreamingMegakernel(
            mk, ring_capacity=len(tenants) * per_tenant, tenants=table,
            telemetry=True,
        )
        asked = []
        for tid, _ in tenants:
            for x in rng.integers(1, 1 << 16, per_tenant):
                adm = sm.submit(tid, 0, args=[int(x)], out=1)
                assert adm, adm
                asked.append((int(x), adm.future))
        sm.close()
        b = TaskGraphBuilder()
        b.add(0, args=[0], out=1)  # the resident graph the stream joins
        iv, info = sm.run_stream(b)
        return table, sm, asked, iv, info

    (table, sm, asked, iv, info), compile_s, run_s = twice(serve)
    for x, fut in asked:
        assert fut.state == "RESULT" and fut.result(0) == 3 * x + 1, (
            x, fut.state
        )
    total = sum(x for x, _ in asked)
    assert int(iv[0]) == total, (int(iv[0]), total)
    cons = table.futures.conservation()
    assert cons["ok"] and cons["resolved"] == len(asked), cons
    assert cons["pending"] == cons["expired"] == cons["poisoned"] == 0, cons
    stats = table.stats()
    for tid, _ in tenants:
        s = stats[tid]
        assert s["accepted"] == s["completed"] == per_tenant, (tid, s)
        assert not (s["dropped"] or s["expired"] or s["rejected"]
                    or s["poisoned"]), (tid, s)
    assert info["executed"] == len(asked) + 1 and info["pending"] == 0, info
    snap = sm.telemetry_snapshot()
    hist = TelemetryBlock(snap["tele"], snap.get("ns_per_round"))
    assert hist.total() == len(asked), hist.total()
    emit("serve", dev, tenants=len(tenants), requests=len(asked),
         capacity=capacity, resolved=cons["resolved"],
         admitted=sum(s["accepted"] for s in stats.values()),
         completed=sum(s["completed"] for s in stats.values()), dropped=0,
         telemetry_retirements=hist.total(), compile_s=compile_s,
         run_s=run_s, **ran_compiled(info))


# ----------------------------------------------------------- four chips


def phase_four_chips(dev: dict, seed: int) -> None:
    """The one path that exists only across chips: a maximally skewed fib
    forest (every root on device 0) through the resident kernel on a mesh
    over the four real devices - roots migrate over ICI and each subtree
    explodes on its thief - and the same forest on one device."""
    import jax
    from jax.sharding import Mesh

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.megakernel import VBLOCK
    from hclib_tpu.device.stress import forest_resident
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel
    from hclib_tpu.models.fib import fib_seq, task_count

    roots, n, capacity = 160, 12, 640
    per_root = task_count(n) + (task_count(n) - 1) // 2
    tasks, value = roots * per_root, roots * fib_seq(n)

    mesh = Mesh(np.array(jax.devices()), ("q",))
    info, compile_s, run_s = twice(lambda: forest_resident(
        mesh, roots=roots, n=n, capacity=capacity, interpret=False,
    ))
    per_dev = np.asarray(info["per_device_counts"])[:, 5]
    assert info["executed"] == tasks and info["value"] == value, info
    assert (per_dev > 0).all(), per_dev
    assert info["input_devices"] == 4, info["input_devices"]
    emit("four_chips", dev, runner="resident-mesh", roots=roots, fib=n,
         executed=info["executed"], value=info["value"],
         per_device_executed=per_dev.tolist(), rounds=info["rounds"],
         input_devices=info["input_devices"], compile_s=compile_s,
         run_s=run_s, **ran_compiled(info))

    mk = make_fib_megakernel(
        capacity, interpret=False,
        num_values=VBLOCK * capacity + max(64, roots),
    )

    def one_device():
        b = TaskGraphBuilder()
        for r in range(roots):
            b.add(FIB, args=[n], out=r)
        iv, _, info1 = mk.run(b)
        return int(np.asarray(iv)[:roots].sum(dtype=np.int64)), info1

    (value1, info1), compile_s, run_s = twice(one_device)
    assert info1["executed"] == tasks and value1 == value, (info1, value1)
    emit("four_chips", dev, runner="one-device", roots=roots, fib=n,
         executed=info1["executed"], value=value1, compile_s=compile_s,
         run_s=run_s, **ran_compiled(info1))


ONE_CHIP = (phase_megakernel, phase_uts, phase_cholesky, phase_sw,
            phase_forasync, phase_serve)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip mesh phase and nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every random input")
    args = ap.parse_args(argv)

    from hclib_tpu.runtime.env import use_compile_cache

    cache = use_compile_cache()
    from hclib_tpu.device.megakernel import require_tpu

    dev = require_tpu()  # raises off the chip: no phase runs, no result
    # Count what the persistent cache did, from JAX's own events: a second
    # run on one machine must show hits and no misses.
    import collections

    from jax import monitoring

    events = collections.Counter()
    monitoring.register_event_listener(
        lambda name, **kw: events.update([name.rsplit("/", 1)[-1]])
    )
    if args.four_chips and dev["count"] != 4:
        raise RuntimeError(f"--four-chips needs 4 chips, JAX has {dev}")
    print(json.dumps({"phase": "start", "device": dev, "cache_dir": cache,
                      "seed": args.seed}), flush=True)
    t0 = time.perf_counter()
    for phase in (phase_four_chips,) if args.four_chips else ONE_CHIP:
        phase(dev, args.seed)
    print(json.dumps({
        "phase": "done", "wall_s": round(time.perf_counter() - t0, 1),
        "cache_hits": events["cache_hits"],
        "cache_misses": events["cache_misses"],
    }), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
