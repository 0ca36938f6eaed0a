#!/usr/bin/env python
"""Performance-regression harness.

Reference design (test/performance-regression/full-apps/): driver scripts run
each app N pinned trials with HCLIB_PROFILE_LAUNCH_BODY=1, record mean launch-
body wall time per app into dated logs (regression-logs-*/<ts>.dat, one
"<app> <mean ns>" line per app), and compare new runs against past logs.

This harness runs the suite (fib, fib-ddt, nqueens, qsort, cilksort, FFT,
UTS, Cholesky, Smith-Waterman - the BASELINE.md apps plus the BASELINE.json
configs), writes ``perf-logs/<unix_ts>.json`` with per-app mean/min/std
nanoseconds, and flags regressions against the most recent prior log.
Every run also executes the **instrument-overhead guard**: the same
spawn-storm workload with the EventLog recorder off vs on, failing when
the ratio exceeds ``--instrument-tolerance`` (default 3x; the
recorder measures ~1.2-1.8x on no-op spawn storms, but a loaded CI box
swings the denominator) - the
observability layer must never silently tax the hot path. The
**ingress-overhead guard** bounds the multi-tenant front door the same
way: tenancy-off streams compile zero new device words and stay
bit-identical to seed, and the 1-tenant enabled path is bounded vs the
plain streaming-inject baseline in the SAME run
(``--ingress-tolerance``). The **forasync-tile guard** holds the
forasync device tier's floor: the same map loop through host forasync
(scalar-spawn) and the batch-lane tile tier must stay bit-identical,
the tile tier must beat the host arm by ``--forasync-floor`` (default
2x) in the SAME run, and its batch-lane occupancy must not collapse
(``--forasync-occupancy``).

Usage:
  python tools/perf_regression.py               # full sizes, 3 trials
  python tools/perf_regression.py --quick       # tiny sizes (CI/smoke)
  python tools/perf_regression.py --trials 5 --tolerance 0.2
  python tools/perf_regression.py --multichip   # 8-device mesh at scale
Exit code 1 if any app regressed beyond tolerance vs the previous log.

``--multichip`` runs the benchmark-scale multi-device acceptance
workloads (hclib_tpu/device/stress.py) on a virtual 8-device CPU mesh:
a >=100k-task maximally-skewed fib forest through the sharded steal
runner, and the unified resident kernel (dependency-bearing migration +
remote atomics) under Mosaic-interpreter-scale load. Each run's exact
totals are asserted inside the workload; wall time and tasks/s are
recorded like any other app, and the per-device load reports are written
next to the log as ``<ts>.<name>.json`` (render them with
``python tools/timeline.py --device <file>``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _suite(quick: bool) -> List[Tuple[str, Callable[[], dict]]]:
    from hclib_tpu.models import cholesky, fft, fib, nqueens, smithwaterman, sort, uts

    if quick:
        return [
            ("fib", lambda: fib.run(18, "finish")),
            ("fib-ddt", lambda: fib.run(18, "ddf")),
            ("nqueens", lambda: nqueens.run(7)),
            ("qsort", lambda: sort.run(1 << 14, "qsort")),
            ("cilksort", lambda: sort.run(1 << 14, "cilksort")),
            ("fft", lambda: fft.run(1 << 12, threshold=1 << 10)),
            ("uts", lambda: uts.run(uts.T_TINY)),
            ("cholesky", lambda: cholesky.run(n=64, tile=32)),
            ("smithwaterman", lambda: smithwaterman.run(m=128, n=128, tile=64)),
        ]
    return [
        ("fib", lambda: fib.run(27, "finish")),
        ("fib-ddt", lambda: fib.run(24, "ddf")),
        ("nqueens", lambda: nqueens.run(11)),
        ("qsort", lambda: sort.run(1 << 21, "qsort")),
        ("cilksort", lambda: sort.run(1 << 21, "cilksort")),
        ("fft", lambda: fft.run(1 << 18)),
        ("uts", lambda: uts.run(uts.T1)),
        ("cholesky", lambda: cholesky.run(n=512, tile=64)),
        ("smithwaterman", lambda: smithwaterman.run(m=2048, n=2048, tile=256)),
    ]


def _instrument_overhead(quick: bool, trials: int) -> dict:
    """Observability-tax guard: the same spawn-storm workload with the
    EventLog recorder off vs on (min-of-N each, interleaved start so a
    machine-load drift taxes both arms). The recorder (and by policy the
    whole flight-recorder layer) must never silently tax the hot path -
    the ratio is bounded by --instrument-tolerance."""
    import hclib_tpu as hc

    ntasks = 2000 if quick else 6000

    def run_once(instr: bool) -> int:
        rt = hc.Runtime(nworkers=2, instrument=instr)

        def body():
            with hc.finish():
                for _ in range(ntasks):
                    hc.async_(lambda: None)

        t0 = time.perf_counter_ns()
        rt.run(body)
        return time.perf_counter_ns() - t0

    n = max(2, trials)
    base, instr = [], []
    for _ in range(n):
        base.append(run_once(False))
        instr.append(run_once(True))
    return {
        "base_ns": min(base),
        "instrumented_ns": min(instr),
        "ratio": min(instr) / min(base),
        "tasks": ntasks,
    }


def _ingress_overhead(quick: bool, trials: int) -> dict:
    """Multi-tenant ingress tax guard (ISSUE 8), same-run arms: the same
    injected workload through (a) the plain single-firehose stream -
    tenancy OFF compiles zero new device words (no tctl input/echo, no
    WRR poll; ``tenants=False`` overrides any env spelling) and must
    stay bit-identical to the seed path - and (b) a 1-tenant enabled
    stream, whose results must be bit-identical to (a) and whose wall
    time is bounded by --ingress-tolerance (it pays the tctl copy + one
    lane's WRR bookkeeping per round)."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel

    ntasks = 48 if quick else 160

    def mark(ctx):
        # Every task writes its OWN value slot: the cross-arm compare is
        # over the whole ivalues vector, so a dropped or misrouted ring
        # row shows up as a wrong slot even when an aggregate sum would
        # come out equal by coincidence.
        ctx.set_value(ctx.arg(1), ctx.arg(0))

    def mk():
        return Megakernel(
            kernels=[("mark", mark)], capacity=max(256, ntasks + 8),
            num_values=ntasks + 8, succ_capacity=8, interpret=True,
        )

    def run_once(tenants) -> Tuple[int, bytes]:
        sm = StreamingMegakernel(mk(), ring_capacity=max(256, ntasks),
                                 tenants=tenants)
        if tenants is False:
            assert sm.tenants is None  # zero new device words: no tctl ABI
            for i in range(ntasks):
                sm.inject(0, args=[i + 1, i + 1])
        else:
            for i in range(ntasks):
                assert sm.submit("t0", 0, args=[i + 1, i + 1])
        sm.close()
        b = TaskGraphBuilder()
        b.add(0, args=[0, 0])
        t0 = time.perf_counter_ns()
        iv, info = sm.run_stream(b)
        dt = time.perf_counter_ns() - t0
        iv = np.asarray(iv)
        expect = np.zeros(ntasks + 8, iv.dtype)
        expect[1 : ntasks + 1] = np.arange(1, ntasks + 1)
        if not np.array_equal(iv, expect):
            raise AssertionError(
                f"ingress-overhead: arm (tenants={tenants!r}) dropped "
                f"or misrouted rows: {np.flatnonzero(iv != expect)}"
            )
        if tenants is False:
            # Tenancy off = seed ABI: no tenant echo anywhere in the
            # run's surfaces.
            assert "tenants" not in info and "tenants" not in (
                sm.stats_dict()
            )
        else:
            assert info["tenants"]["t0"]["completed"] == ntasks
        return dt, iv.tobytes()

    run_once(False)  # warm both jits outside the timed arms
    run_once(1)
    n = max(2, trials)
    base, ten, values = [], [], set()
    for _ in range(n):
        dt, v = run_once(False)
        base.append(dt)
        values.add(v)
        dt, v = run_once(1)
        ten.append(dt)
        values.add(v)
    if len(values) != 1:
        raise AssertionError(
            "ingress-overhead: tenancy-on ivalues diverged from the "
            f"plain stream ({len(values)} distinct result vectors)"
        )
    return {
        "base_ns": min(base),
        "tenant_ns": min(ten),
        "ratio": min(ten) / min(base),
        "tasks": ntasks,
        "bit_identical": True,
    }


def _checkpoint_overhead(quick: bool, trials: int) -> dict:
    """Checkpoint-tax guard (ISSUE 5): the same seeded UTS megakernel
    traversal with checkpoint support off vs compiled-in-but-never-
    quiesced (min-of-N each, interleaved arms like the instrument guard).
    The quiesce word must never silently tax a run that doesn't
    checkpoint; the enabled-but-idle path is bounded by
    --checkpoint-tolerance (it pays one qctl DMA per scheduling round).
    Also measures the quiesce LAG - how far past the requested round the
    boundary landed, in tasks - which must stay within one batch width
    (the same overshoot contract fuel has).

    The third arm prices ``quiesce_stride`` (ISSUE 6): polling the qctl
    word every Nth round instead of every round must land at or below
    the per-round arm's cost (it does strictly fewer DMAs), and its
    quiesce lag may grow by at most stride-1 rounds' worth of tasks -
    both bounded here so the knob can never silently regress either
    side of its trade."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import (
        UTS_NODE, make_uts_megakernel,
    )

    kw = dict(interpret=True, max_depth=6 if quick else 8)
    STRIDE = 4

    def builder():
        b = TaskGraphBuilder()
        b.add(UTS_NODE, args=[1, 0])
        return b

    mk_off = make_uts_megakernel(**kw)
    mk_on = make_uts_megakernel(checkpoint=True, **kw)
    mk_strided = make_uts_megakernel(
        checkpoint=True, quiesce_stride=STRIDE, **kw
    )
    nodes = mk_off.run(builder())[2]["executed"]  # also warms the jit
    mk_on.run(builder())  # warm the enabled build too
    mk_strided.run(builder())
    n = max(2, trials)
    base, on, strided = [], [], []
    for _ in range(n):
        t0 = time.perf_counter_ns()
        mk_off.run(builder())
        base.append(time.perf_counter_ns() - t0)
        t0 = time.perf_counter_ns()
        mk_on.run(builder())
        on.append(time.perf_counter_ns() - t0)
        t0 = time.perf_counter_ns()
        mk_strided.run(builder())
        strided.append(time.perf_counter_ns() - t0)
    # Quiesce latency: request the cut at half the tree; the observed
    # boundary must not drift (lag in tasks) and the quiesced entry must
    # not cost more than an uninterrupted run (it does strictly less).
    at = nodes // 2
    t0 = time.perf_counter_ns()
    _, _, info_q = mk_on.run(builder(), quiesce=at)
    quiesce_ns = time.perf_counter_ns() - t0
    lag = info_q["quiesce"]["executed_at"] - at
    _, _, info_qs = mk_strided.run(builder(), quiesce=at)
    lag_s = info_qs["quiesce"]["executed_at"] - at
    return {
        "base_ns": min(base),
        "checkpoint_ns": min(on),
        "ratio": min(on) / min(base),
        "stride": STRIDE,
        "stride_ns": min(strided),
        "stride_ratio": min(strided) / min(base),
        "nodes": nodes,
        "quiesce_entry_ns": quiesce_ns,
        "quiesce_lag_tasks": int(lag),
        "stride_lag_tasks": int(lag_s),
    }


def _forasync_tile(quick: bool, trials: int) -> dict:
    """forasync-tile guard (ISSUE 9), same-run arms: the SAME map loop
    through (a) host forasync - per-tile scalar-spawn through the host
    scheduler, the reference's execution model - and (b) the device tile
    tier (batch lanes + operand prefetch). Results must be bit-identical
    and the tile tier must hold a tasks/s floor vs the scalar-spawn arm
    (--forasync-floor, default 2x; measured 8-30x on CPU interpret). A
    third arm - scalar DEVICE dispatch - is recorded informationally:
    interpret-mode walls do not show the dispatch win (the interpreter
    serializes the DMAs the lanes overlap on hardware), so the device-
    internal ratio is reported, not bounded. The lane-occupancy bound
    (--forasync-occupancy) fails if the static tile set stops filling
    its batches - the tier silently degrading to near-scalar firing."""
    import numpy as np

    import hclib_tpu as hc
    from hclib_tpu.device.forasync_tier import (
        make_forasync_megakernel, run_forasync_device,
    )
    from hclib_tpu.device.workloads import (
        map_body, map_data, map_loop, map_reference,
    )

    # Quick stays large enough that the host arm's per-index python cost
    # dominates its scheduler noise: the ratio is ~4-8x unloaded and must
    # clear the 2x floor even on a loaded CI box.
    T = 32 if quick else 64
    tk, bounds, tile = map_loop(T)
    vin, vout = map_data(T)
    ref = map_reference(vin)
    mk_tier = make_forasync_megakernel(tk, width=8, interpret=True)
    mk_scalar = make_forasync_megakernel(tk, width=0, interpret=True)

    def run_host() -> np.ndarray:
        vh = vout.copy()

        def main():
            hc.forasync(map_body(vin, vh), bounds, tile=tile)

        hc.launch(main, nworkers=4)
        return vh

    def run_dev(mk, width) -> np.ndarray:
        d, info = run_forasync_device(
            tk, bounds, tile, {"vin": vin, "vout": vout.copy()},
            width=width, mk=mk,
        )
        if width:
            run_dev.tiers = info["tiers"]
        return np.asarray(d["vout"])

    results = {run_host().tobytes(), run_dev(mk_tier, 8).tobytes(),
               run_dev(mk_scalar, 0).tobytes(), ref.tobytes()}  # + warm
    if len(results) != 1:
        raise AssertionError(
            "forasync-tile: arms diverged (host/scalar/tile results not "
            "bit-identical)"
        )
    n = max(2, trials)
    host, tier, scalar = [], [], []
    for _ in range(n):
        t0 = time.perf_counter_ns()
        run_host()
        host.append(time.perf_counter_ns() - t0)
        t0 = time.perf_counter_ns()
        run_dev(mk_tier, 8)
        tier.append(time.perf_counter_ns() - t0)
        t0 = time.perf_counter_ns()
        run_dev(mk_scalar, 0)
        scalar.append(time.perf_counter_ns() - t0)
    occ = run_dev.tiers["batch_occupancy"]
    return {
        "tiles": T,
        "host_ns": min(host),
        "tile_tier_ns": min(tier),
        "device_scalar_ns": min(scalar),
        "tier_vs_host": min(host) / min(tier),
        "tier_vs_device_scalar": min(scalar) / min(tier),
        "occupancy": occ,
        "prefetch_hits": run_dev.tiers["prefetch_hits"],
        "bit_identical": True,
    }


def _frontier_batch(quick: bool, trials: int) -> dict:
    """frontier-batch guard (ISSUE 10), same-run arms: the SAME seeded
    R-MAT BFS through (a) scalar dispatch - one EXPAND per lax.switch
    round, the bit-identity reference - and (b) the batched frontier
    tier (edge-slab prefetch + the age-triggered firing policy, on at
    its frontier default lane_max_age = 4*width). Distances must be
    bit-identical to each other AND the host reference, the batched arm
    must hold a TEPS floor against the scalar arm measured in the same
    run (--frontier-floor; interpret mode serializes the edge-slab DMAs
    the lanes overlap on hardware, so the measured ratio is ~0.5x and
    the floor prices 'never collapses'), and the batched arm's
    lane_partial_age must stay under --frontier-age-ceiling with its
    device-side max_starved_age bounded by the knob - the proof that
    the new firing policy keeps the lanes from starving while the
    frontier spawner keeps the ring hot."""
    import numpy as np

    from hclib_tpu.device.frontier import (
        Graph, _KINDS, host_bfs, make_frontier_megakernel, run_frontier,
    )
    from hclib_tpu.device.workloads import rmat_edges

    scale = 6 if quick else 8
    width = 8
    n, src, dst, w = rmat_edges(scale, efactor=8, seed=7)
    g = Graph(n, src, dst, w)
    cap = 768 if quick else 1024
    # The TIMED batched arm is untraced (tracing taxes only the batched
    # side: in-kernel TR_* emission + host ring decode - an unfair
    # thumb on the ratio); one separate traced run below supplies the
    # lane_partial_age / age-gauge readings.
    mk_b = make_frontier_megakernel(
        _KINDS["bfs"](), g, width=width, capacity=cap, interpret=True,
    )
    lane_max_age = mk_b.lane_max_age
    mk_s = make_frontier_megakernel(
        _KINDS["bfs"](), g, width=0, capacity=cap, interpret=True,
    )
    mk_tr = make_frontier_megakernel(
        _KINDS["bfs"](), g, width=width, capacity=cap, interpret=True,
        trace=4096,
    )
    ref = host_bfs(g, 0)

    def run_arm(mk):
        d, info = run_frontier("bfs", g, 0, mk=mk, interpret=True)
        run_arm.info = info
        return d

    d_b = run_arm(mk_b)
    d_tr = run_arm(mk_tr)
    info_b = run_arm.info  # the traced run's gauges
    d_s = run_arm(mk_s)
    if not np.array_equal(d_tr, ref):
        raise AssertionError(
            "frontier-batch: traced arm diverged from the host reference"
        )
    if not (np.array_equal(d_b, ref) and np.array_equal(d_s, ref)):
        raise AssertionError(
            "frontier-batch: arms diverged (scalar/batched/host BFS "
            "distances not bit-identical)"
        )
    n_tr = max(2, trials)
    b_ns, s_ns = [], []
    for _ in range(n_tr):
        t0 = time.perf_counter_ns()
        run_arm(mk_b)
        b_ns.append(time.perf_counter_ns() - t0)
        edges_b = run_arm.info["edges"]
        t0 = time.perf_counter_ns()
        run_arm(mk_s)
        s_ns.append(time.perf_counter_ns() - t0)
        edges_s = run_arm.info["edges"]
    teps_b = edges_b / (min(b_ns) / 1e9)
    teps_s = edges_s / (min(s_ns) / 1e9)
    t = info_b["tiers"]
    if t["max_starved_age"] > lane_max_age:
        raise AssertionError(
            f"frontier-batch: device starved age {t['max_starved_age']} "
            f"exceeds lane_max_age {lane_max_age} - the age trigger "
            "stopped bounding starvation"
        )
    return {
        "edges": g.m,
        "batched_teps": round(teps_b),
        "scalar_teps": round(teps_s),
        "batched_vs_scalar": teps_b / teps_s,
        "occupancy": t["batch_occupancy"],
        "age_fires": t["age_fires"],
        "max_starved_age": t["max_starved_age"],
        "lane_max_age": lane_max_age,
        "lane_partial_age": t.get("lane_partial_age", 0),
        "bit_identical": True,
    }


def _priority_tier(quick: bool, trials: int) -> dict:
    """priority-tier guard (ISSUE 15), same-run arms on the SAME seeded
    weighted R-MAT: (a) the unordered batched frontier (PR 10's
    label-correcting SSSP - the bit-identity reference), (b) the
    priority-bucketed build (TRUE delta-stepping: bucket = dist//delta,
    lowest-nonempty-first). Distances must be bit-identical to each
    other AND the host Dijkstra, and the bucketed arm must do at most
    --priority-expand-ceiling (0.8x) of the unordered arm's executed
    EXPANDs - ordered retirement is claimed as *asymptotically less
    work*, so the guard prices the work count, which interpret mode
    measures exactly (no DMA-overlap weather). A PageRank pair on the
    same graph additionally bounds the bucketed arm's peak live row
    set (info['allocated'] - the bump allocator's high-water mark) at
    --priority-live-ceiling of the unordered arm's: the bounded-
    frontier fix for the PR 10 breadth blowup."""
    import numpy as np

    from hclib_tpu.device.frontier import (
        Graph, _KINDS, host_pagerank_push, host_sssp,
        make_frontier_megakernel, run_frontier,
    )
    from hclib_tpu.device.workloads import rmat_edges

    scale = 6 if quick else 8
    width = 8
    buckets = 8
    n, src, dst, w = rmat_edges(scale, efactor=8, seed=7)
    g = Graph(n, src, dst, w)
    cap = 768 if quick else 1024
    mk_u = make_frontier_megakernel(
        _KINDS["sssp"](), g, width=width, capacity=cap, interpret=True,
    )
    mk_b = make_frontier_megakernel(
        _KINDS["sssp"](), g, width=width, capacity=cap, interpret=True,
        priority_buckets=buckets,
    )
    ref = host_sssp(g, 0)
    d_u, info_u = run_frontier("sssp", g, 0, mk=mk_u, interpret=True)
    d_b, info_b = run_frontier("sssp", g, 0, mk=mk_b, interpret=True)
    if not (np.array_equal(d_u, ref) and np.array_equal(d_b, ref)):
        raise AssertionError(
            "priority-tier: SSSP arms diverged (unordered/delta-stepping"
            "/host Dijkstra distances not bit-identical)"
        )
    # Work-count arms (deterministic - one run each IS the measurement;
    # wall time also logged for the record).
    n_tr = max(2, trials)
    u_ns, b_ns = [], []
    for _ in range(n_tr):
        t0 = time.perf_counter_ns()
        run_frontier("sssp", g, 0, mk=mk_u, interpret=True)
        u_ns.append(time.perf_counter_ns() - t0)
        t0 = time.perf_counter_ns()
        run_frontier("sssp", g, 0, mk=mk_b, interpret=True)
        b_ns.append(time.perf_counter_ns() - t0)
    teps_u = info_u["edges"] / (min(u_ns) / 1e9)
    teps_b = info_b["edges"] / (min(b_ns) / 1e9)
    # PageRank live-set arms: deep mass cascade (m0 = 1<<14) where the
    # FIFO breadth-first push balloons the live set.
    m0, reps = 1 << 14, 64
    pscale = 5 if quick else 6
    n2, s2, d2, w2 = rmat_edges(pscale, efactor=8, seed=7)
    g2 = Graph(n2, s2, d2, w2)
    twin, _ = host_pagerank_push(g2, m0=m0, reps=reps)
    r_u, pr_u = run_frontier(
        "pagerank", g2, width=width, m0=m0, reps=reps, interpret=True,
        capacity=4096,
    )
    r_b, pr_b = run_frontier(
        "pagerank", g2, width=width, m0=m0, reps=reps, interpret=True,
        capacity=4096, priority_buckets=buckets,
    )
    if not (np.array_equal(np.asarray(r_u), twin)
            and np.array_equal(np.asarray(r_b), twin)):
        raise AssertionError(
            "priority-tier: PageRank arms diverged from the integer twin"
        )
    return {
        "edges": g.m,
        "expanded_unordered": info_u["executed"],
        "expanded_bucketed": info_b["executed"],
        "expand_ratio": info_b["executed"] / info_u["executed"],
        "unordered_teps": round(teps_u),
        "bucketed_teps": round(teps_b),
        "teps_ratio": teps_b / teps_u,
        "bucket_inversions": info_b["tiers"]["bucket_inversions"],
        "pr_live_unordered": pr_u["allocated"],
        "pr_live_bucketed": pr_b["allocated"],
        "pr_live_ratio": pr_b["allocated"] / pr_u["allocated"],
        "bit_identical": True,
    }


def _program_cache(quick: bool, trials: int) -> dict:
    """Program-cache guard (ISSUE 18), same-run arms:

    (a) cold-vs-warm: two content-identical megakernel instances; the
        second instance's FIRST run must ride the process-wide program
        cache (hit asserted) and beat the cold build by
        --progcache-floor (the whole point of the cache is killing the
        trace/lower/compile tax);
    (b) cache-off bit identity: a fresh instance with
        HCLIB_TPU_PROGRAM_CACHE=0 must produce the cold arm's exact
        result bytes with the registry counters untouched;
    (c) eviction correctness: at cap=1 a second distinct program evicts
        the first; rebuilding the first misses (counted) and is
        bit-identical to its original run.
    """
    import os as _os

    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.runtime import progcache

    ntasks = 16 if quick else 48

    def mark(ctx):
        ctx.set_value(ctx.arg(1), ctx.arg(0))

    def mark2(ctx):
        ctx.set_value(ctx.arg(1), ctx.arg(0) + 1)

    def mk(body=mark):
        return Megakernel(
            kernels=[("mark", body)], capacity=max(64, ntasks + 8),
            num_values=ntasks + 8, succ_capacity=8, interpret=True,
        )

    def run_once(m) -> Tuple[int, bytes, dict]:
        b = TaskGraphBuilder()
        for i in range(ntasks):
            b.add(0, args=[i + 1, i + 1])
        t0 = time.perf_counter_ns()
        iv, _, info = m.run(b)
        dt = time.perf_counter_ns() - t0
        return dt, np.asarray(iv).tobytes(), info["program_cache"]

    saved = {
        k: _os.environ.pop(k, None)
        for k in ("HCLIB_TPU_PROGRAM_CACHE", "HCLIB_TPU_PROGRAM_CACHE_CAP")
    }
    try:
        progcache.reset()
        # (a) cold vs warm: first runs of fresh identical instances.
        cold_ns, cold_bytes, pc = run_once(mk())
        if pc["hit"]:
            raise AssertionError("program-cache: cold arm reported a hit")
        warm = []
        for _ in range(max(2, trials)):
            warm_ns, warm_bytes, pc = run_once(mk())
            if not pc["hit"]:
                raise AssertionError(
                    "program-cache: content-identical rebuild missed"
                )
            if warm_bytes != cold_bytes:
                raise AssertionError(
                    "program-cache: warm result bytes diverged"
                )
            warm.append(warm_ns)
        warm_ns = min(warm)
        # (b) cache off: bit-identical, counters untouched.
        before = progcache.cache_stats()
        _os.environ["HCLIB_TPU_PROGRAM_CACHE"] = "0"
        off_ns, off_bytes, pc = run_once(mk())
        del _os.environ["HCLIB_TPU_PROGRAM_CACHE"]
        if pc["hit"] or off_bytes != cold_bytes:
            raise AssertionError(
                "program-cache: cache-off arm hit or diverged"
            )
        if progcache.cache_stats() != before:
            raise AssertionError(
                "program-cache: cache-off arm moved the counters"
            )
        # (c) eviction correctness at cap=1.
        _os.environ["HCLIB_TPU_PROGRAM_CACHE_CAP"] = "1"
        progcache.reset()
        _, first_bytes, _ = run_once(mk())
        run_once(mk(mark2))  # distinct program: evicts the first
        if progcache.cache_stats()["evictions"] < 1:
            raise AssertionError("program-cache: cap=1 never evicted")
        _, again_bytes, pc = run_once(mk())
        if pc["hit"]:
            raise AssertionError(
                "program-cache: evicted program reported a hit"
            )
        if again_bytes != first_bytes:
            raise AssertionError(
                "program-cache: post-eviction rebuild diverged"
            )
    finally:
        for k, v in saved.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
        progcache.reset()
    return {
        "cold_ns": cold_ns,
        "warm_ns": warm_ns,
        "off_ns": off_ns,
        "speedup": cold_ns / warm_ns,
        "tasks": ntasks,
        "bit_identical": True,
        "eviction_correct": True,
    }


def _telemetry_overhead(quick: bool, trials: int) -> dict:
    """Telemetry-tax guard (ISSUE 19), same-run arms: the same
    submitted workload through (a) a telemetry-OFF egress stream and
    (b) the telemetry-ON stream. Off compiles ZERO new device words -
    asserted by lowered-text byte identity: a build forced off while
    the telemetry env knob is SET must lower to the exact text the
    env-free default build lowers to (and the enabled build must
    differ - the tele/tlat words exist only on-path). The on arm's
    result vector must be bit-identical to (a), its on-device
    histogram must account for every submitted retirement exactly,
    and its wall is bounded by --telemetry-tolerance (it pays the
    tele/tlat echo plus the branch-free log2 fold per retire)."""
    import os as _os

    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW, TaskGraphBuilder
    from hclib_tpu.device.egress import EGR_WORDS, EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.telemetry import (
        LAT_BUCKETS, LAT_WORDS, TelemetryBlock,
    )
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    ntasks = 48 if quick else 160
    cap = max(256, ntasks + 8)

    def mark(ctx):
        ctx.set_value(ctx.arg(1), ctx.arg(0))

    def mk():
        return Megakernel(
            kernels=[("mark", mark)], capacity=cap,
            num_values=ntasks + 8, succ_capacity=8, interpret=True,
        )

    def sm_new(tel):
        table = TenantTable(
            [TenantSpec("t0")], cap, clock=lambda: 0.0,
            egress=EgressSpec(depth=cap),
        )
        return StreamingMegakernel(mk(), ring_capacity=cap,
                                   tenants=table, telemetry=tel)

    def lower_text(sm) -> str:
        m = sm.mk
        b = TaskGraphBuilder()
        b.add(0, args=[0, 0])
        tasks, succ, ready, counts = b.finalize(
            capacity=m.capacity, succ_capacity=m.succ_capacity
        )
        args = [
            tasks, succ, ready, counts,
            np.zeros(m.num_values, np.int32),
            np.zeros((sm.ring_capacity, RING_ROW), np.int32),
            np.zeros(8, np.int32),
            np.zeros((len(sm.tenants), 8), np.int32),
            np.zeros((sm._egress.depth, EGR_WORDS), np.int32),
            np.zeros((sm._egress.depth, EGR_WORDS), np.int32),
            np.zeros(8, np.int32),
            np.zeros(m.capacity, np.int32),
        ]
        if sm.telemetry:
            args += [
                np.zeros((1 + len(sm.tenants), LAT_BUCKETS), np.int32),
                np.zeros((m.capacity, LAT_WORDS), np.int32),
            ]
        return sm._build(1 << 10, 64).lower(*args).as_text()

    # Off-path identity first, outside the timed arms: env knob SET
    # but constructor-forced off must be byte-identical to env-free.
    saved_env = _os.environ.pop("HCLIB_TPU_TELEMETRY", None)
    try:
        base_text = lower_text(sm_new(None))    # env-free default: off
        _os.environ["HCLIB_TPU_TELEMETRY"] = "1"
        forced_off = lower_text(sm_new(False))
        env_on = lower_text(sm_new(None))
    finally:
        if saved_env is None:
            _os.environ.pop("HCLIB_TPU_TELEMETRY", None)
        else:
            _os.environ["HCLIB_TPU_TELEMETRY"] = saved_env
    if forced_off != base_text:
        raise AssertionError(
            "telemetry-overhead: telemetry=False with the env knob set "
            "lowered DIFFERENT text than the env-free build - the off "
            "path is compiling telemetry words"
        )
    if env_on == base_text:
        raise AssertionError(
            "telemetry-overhead: the enabled build lowered the SAME "
            "text as the off build - the tele/tlat words never compiled"
        )

    def run_once(tel) -> Tuple[int, bytes]:
        sm = sm_new(tel)
        futs = []
        for i in range(ntasks):
            h = sm.submit("t0", 0, args=[i + 1, i + 1])
            assert h
            futs.append(h.future)
        sm.close()
        b = TaskGraphBuilder()
        b.add(0, args=[0, 0])
        t0 = time.perf_counter_ns()
        iv, info = sm.run_stream(b)
        dt = time.perf_counter_ns() - t0
        iv = np.asarray(iv)
        expect = np.zeros(ntasks + 8, iv.dtype)
        expect[1 : ntasks + 1] = np.arange(1, ntasks + 1)
        if not np.array_equal(iv, expect):
            raise AssertionError(
                f"telemetry-overhead: arm (telemetry={tel!r}) dropped "
                f"or misrouted rows: {np.flatnonzero(iv != expect)}"
            )
        bad = [f.state for f in futs if f.state != "RESULT"]
        if bad:
            raise AssertionError(
                f"telemetry-overhead: {len(bad)} futures unresolved "
                f"(telemetry={tel!r}): {sorted(set(bad))}"
            )
        if tel:
            snap = sm.telemetry_snapshot()
            total = TelemetryBlock(snap["tele"]).total() if snap else -1
            if total != ntasks:
                raise AssertionError(
                    "telemetry-overhead: on-device histogram counted "
                    f"{total} retirements, expected {ntasks}"
                )
        else:
            # Telemetry off = no new surfaces anywhere in the run.
            assert "telemetry" not in info
            assert sm.telemetry_snapshot() is None
        return dt, iv.tobytes()

    run_once(False)  # warm both jits outside the timed arms
    run_once(True)
    n = max(2, trials)
    base, tele, values = [], [], set()
    for _ in range(n):
        dt, v = run_once(False)
        base.append(dt)
        values.add(v)
        dt, v = run_once(True)
        tele.append(dt)
        values.add(v)
    if len(values) != 1:
        raise AssertionError(
            "telemetry-overhead: telemetry-on ivalues diverged from "
            f"the off stream ({len(values)} distinct result vectors)"
        )
    return {
        "base_ns": min(base),
        "telemetry_ns": min(tele),
        "ratio": min(tele) / min(base),
        "tasks": ntasks,
        "bit_identical": True,
        "off_text_identical": True,
    }


def _dyngraph_incremental(quick: bool, trials: int) -> dict:
    """Dynamic-graph incremental-recompute guard (ISSUE 20): phase 1
    runs the seeded SSSP to its fixpoint on the STATIC graph; phase 2
    feeds ONLY the update stream into the same megakernel, reusing
    phase 1's converged labels as the initial values - so the only
    EXPANDs it executes are the re-relaxations the splices actually
    caused. That incremental EXPAND count must stay a small fraction
    of the from-scratch run on the mutated graph, measured in the same
    process; both fixpoints are asserted bit-identical to the
    ``host_dyngraph`` mutated-graph reference. Work counts are exact
    (no timed arms), so ``trials`` is unused."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.dyngraph import (
        DG_UPDATE, INF, DynGraph, _bind_updates, _seed_builders,
        fk_data, host_dyngraph, make_dyngraph_megakernel, run_dyngraph,
    )
    from hclib_tpu.device.workloads import rmat_edges

    scale = 5 if quick else 7
    n, src_e, dst_e, w_e = rmat_edges(scale, efactor=8, seed=7)
    capacity = 512 if quick else 1024
    rng = np.random.default_rng(13)
    n_ups = 6 if quick else 16
    ups = [
        (int(u), int(v), int(w))
        for u, v, w in zip(
            rng.integers(0, n, n_ups),
            rng.integers(0, n, n_ups),
            rng.integers(1, 8, n_ups),
        )
    ]
    src = 0
    g = DynGraph(
        n, src_e, dst_e, w_e, spare_blocks=2, upd_cap=max(16, n_ups),
    )
    mk = make_dyngraph_megakernel(
        "sssp", g, width=8, capacity=capacity, interpret=True,
    )
    _bind_updates(mk, g)  # empty stream: phase 1 is the static run
    st = g.st_base
    iv0 = g.preset_values(mk.num_values, INF)
    iv0[st + src] = 0
    builders, _ = _seed_builders(
        g, "sssp", src, 1 << 14, 64, (), mk.num_values, 1,
        lambda i, tot: 0,
    )
    iv1, _, info1 = mk.run(
        builders[0], data=dict(fk_data(g, mk)), ivalues=iv0,
        fuel=1 << 22,
    )

    # Phase 2: the update stream ALONE, seeded with the converged
    # labels. Fresh data buffers (pristine spare rows) are correct -
    # phase 1 ran no splices, so its adjacency never mutated.
    for u, v, w in ups:
        g.add_update(u, v, w)
    _bind_updates(mk, g)
    b2 = TaskGraphBuilder()
    b2.reserve_values(g.num_value_slots)
    for uid, (u, v, w) in enumerate(g.updates):
        b2.add(DG_UPDATE, args=[u, v, w, uid])
    iv2, _, info2 = mk.run(
        b2, data=dict(fk_data(g, mk)), ivalues=np.asarray(iv1),
        fuel=1 << 22,
    )
    rows = np.asarray(iv2, np.int64)
    res_incr = rows[st : st + n].astype(np.int64)
    flags = rows[g.flag_base : g.flag_base + g.upd_cap]
    applied = int((flags != 0).sum())
    ref = np.asarray(host_dyngraph("sssp", g), np.int64)
    if not np.array_equal(res_incr, ref):
        raise AssertionError(
            "dyngraph-incremental: the update-only rerun's fixpoint "
            "diverged from the mutated-graph reference"
        )

    # From-scratch arm: the same storm raced with the traversal on a
    # fresh graph - everything recomputes. The prebuilt megakernel is
    # reusable (identical (n, kind, st_base) layout stamp).
    g2 = DynGraph(
        n, src_e, dst_e, w_e, spare_blocks=2, upd_cap=max(16, n_ups),
    )
    res_full, info_full = run_dyngraph(
        "sssp", g2, src, updates=ups, capacity=capacity,
        interpret=True, mk=mk,
    )
    if not np.array_equal(np.asarray(res_full, np.int64), ref):
        raise AssertionError(
            "dyngraph-incremental: the from-scratch arm diverged from "
            "the mutated-graph reference"
        )
    incr_expands = int(info2["executed"]) - len(ups)
    full_expands = int(info_full["executed"]) - len(ups)
    return {
        "incr_expands": incr_expands,
        "full_expands": full_expands,
        "expand_ratio": incr_expands / max(full_expands, 1),
        "static_expands": int(info1["executed"]),
        "updates": len(ups),
        "updates_applied": applied,
        "bit_identical": True,
    }


def _latest_log(log_dir: str, quick: bool) -> Dict[str, dict]:
    """Most recent log of the SAME size class (quick vs full): comparing
    tiny smoke inputs against full-size baselines is meaningless in either
    direction."""
    if not os.path.isdir(log_dir):
        return {}
    for name in sorted(
        (f for f in os.listdir(log_dir) if f.endswith(".json")),
        reverse=True,
    ):
        with open(os.path.join(log_dir, name)) as f:
            try:
                log = json.load(f)
            except ValueError:
                continue
        # Skip non-harness JSONs sharing the directory (per-workload
        # info side files, clock logs): only real logs carry "apps".
        if not isinstance(log, dict) or "apps" not in log:
            continue
        if bool(log.get("quick")) == quick:
            return log.get("apps", {})
    return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="tiny inputs (smoke)")
    ap.add_argument("--multichip", action="store_true",
                    help="also run the 8-device mesh acceptance workloads")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional slowdown vs previous log")
    ap.add_argument("--instrument-tolerance", type=float, default=3.0,
                    help="max instrument=True slowdown ratio (the "
                    "flight-recorder/EventLog overhead guard)")
    ap.add_argument("--ingress-tolerance", type=float, default=3.0,
                    help="max enabled(1-tenant)/plain-stream wall ratio "
                         "for the ingress-overhead guard (interpret-mode "
                         "walls swing; results must be bit-identical "
                         "regardless)")
    ap.add_argument("--checkpoint-tolerance", type=float, default=3.0,
                    help="max checkpoint-enabled-but-idle slowdown ratio "
                    "(the quiesce-word overhead guard; the off path is "
                    "compiled out entirely)")
    ap.add_argument("--mesh-batch-floor", type=float, default=0.5,
                    help="mesh-batch-dispatch guard: minimum batched "
                    "forest-steal tasks/s as a fraction of the scalar-"
                    "mesh arm measured in the same run (interpret-mode "
                    "wall time is weather-prone, so the floor price is "
                    "'never collapses', not 'always faster')")
    ap.add_argument("--mesh-batch-occupancy", type=float, default=0.5,
                    help="mesh-batch-dispatch guard: minimum per-device "
                    "batch-slot occupancy (from tstats) on devices that "
                    "fired batch rounds - a collapse means the mesh "
                    "stopped exposing same-kind width to the tier")
    ap.add_argument("--forasync-floor", type=float, default=2.0,
                    help="forasync-tile guard: minimum tile-tier tasks/s "
                    "as a multiple of the host scalar-spawn arm measured "
                    "in the same run (measured 8-30x; 2x is the collapse "
                    "floor)")
    ap.add_argument("--forasync-occupancy", type=float, default=0.8,
                    help="forasync-tile guard: minimum batch-lane "
                    "occupancy of the static tile set (near 1.0 by "
                    "construction; a drop means the tier stopped "
                    "batching the loop)")
    ap.add_argument("--frontier-floor", type=float, default=0.25,
                    help="frontier-batch guard: minimum batched-frontier "
                    "TEPS as a fraction of the scalar-dispatch arm "
                    "measured in the same run. Interpret mode SERIALIZES "
                    "the edge-slab DMAs the lanes overlap on hardware "
                    "(the PR 9 forasync finding), so the batched arm "
                    "measures ~0.5x here while the dispatch win is a "
                    "hardware number - the floor prices 'never "
                    "collapses', not 'faster under the interpreter'")
    ap.add_argument("--frontier-age-ceiling", type=float, default=8,
                    help="frontier-batch guard: maximum lane_partial_age "
                    "(consecutive-partial-fire streak, rounds) on the "
                    "batched BFS arm - the age-triggered firing policy "
                    "keeps it near zero; a climb means lanes are "
                    "starving again")
    ap.add_argument("--priority-expand-ceiling", type=float, default=0.8,
                    help="priority-tier guard: maximum executed-EXPAND "
                         "ratio of delta-stepping SSSP over the "
                         "unordered label-correcting arm on the same "
                         "seeded weighted R-MAT (the ISSUE 15 "
                         "ordered-work dividend; measured ~0.7x at "
                         "scale 8, delta = w_max/8)")
    ap.add_argument("--priority-live-ceiling", type=float, default=0.8,
                    help="priority-tier guard: maximum peak-live-row "
                         "ratio of bounded-frontier PageRank over the "
                         "FIFO breadth-first arm (measured ~0.4-0.6x "
                         "at m0=1<<14 - the live-set blowup fix)")
    ap.add_argument("--progcache-floor", type=float, default=3.0,
                    help="program-cache guard: minimum cold/warm "
                         "first-build speedup for a content-identical "
                         "second instance (the compile-tax kill)")
    ap.add_argument("--telemetry-tolerance", type=float, default=1.3,
                    help="max telemetry-on/off wall ratio for the "
                         "telemetry-overhead guard (the tele/tlat echo "
                         "plus per-retire histogram fold; results must "
                         "be bit-identical and the off path must lower "
                         "byte-identical text regardless)")
    ap.add_argument("--dyngraph-expand-ceiling", type=float, default=0.5,
                    help="dyngraph-incremental guard: maximum "
                         "incremental-EXPAND count of the update-only "
                         "rerun as a fraction of the from-scratch run "
                         "on the mutated graph (the ISSUE 20 "
                         "incremental-recompute dividend; the rerun "
                         "re-expands only what the splices actually "
                         "invalidated)")
    ap.add_argument("--log-dir", default=os.path.join(
        os.path.dirname(__file__), "..", "perf-logs"))
    ap.add_argument("--apps", default="", help="comma-separated subset")
    args = ap.parse_args(argv)

    if args.multichip:
        # Must land before jax initializes: the mesh workloads need the
        # CPU backend with 8 virtual devices.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
        from hclib_tpu.runtime.env import use_compile_cache

        use_compile_cache()

    wanted = {a for a in args.apps.split(",") if a}
    prev = _latest_log(args.log_dir, args.quick)
    results: Dict[str, dict] = {}
    failures: List[str] = []

    for name, fn in _suite(args.quick):
        if wanted and name not in wanted:
            continue
        times_ns = []
        for _ in range(args.trials):
            t0 = time.perf_counter_ns()
            fn()  # each run() self-checks its result
            times_ns.append(time.perf_counter_ns() - t0)
        mean = sum(times_ns) / len(times_ns)
        results[name] = {
            "mean_ns": mean,
            "min_ns": min(times_ns),
            "trials": len(times_ns),
        }
        line = f"{name:15s} mean {mean / 1e6:10.2f} ms  min {min(times_ns) / 1e6:10.2f} ms"
        if name in prev:
            ratio = mean / prev[name]["mean_ns"]
            line += f"  vs prev {ratio:5.2f}x"
            if ratio > 1 + args.tolerance:
                failures.append(f"{name}: {ratio:.2f}x slower than previous log")
                line += "  REGRESSED"
        print(line, flush=True)

    if not wanted or "instrument-overhead" in wanted:
        try:
            ov = _instrument_overhead(args.quick, args.trials)
        except Exception as e:
            print(f"instrument-overhead FAILED: {e}", file=sys.stderr)
            failures.append(f"instrument-overhead: failed ({e})")
        else:
            results["instrument-overhead"] = ov
            line = (
                f"{'instrument-overhead':15s} ratio {ov['ratio']:5.2f}x "
                f"({ov['instrumented_ns'] / 1e6:.1f} ms vs "
                f"{ov['base_ns'] / 1e6:.1f} ms, {ov['tasks']} tasks)"
            )
            if ov["ratio"] > args.instrument_tolerance:
                failures.append(
                    f"instrument-overhead: instrument=True is "
                    f"{ov['ratio']:.2f}x slower (bound "
                    f"{args.instrument_tolerance:.2f}x) - the recorder is "
                    "taxing the hot path"
                )
                line += "  REGRESSED"
            print(line, flush=True)

    if not wanted or "ingress-overhead" in wanted:
        try:
            io = _ingress_overhead(args.quick, args.trials)
        except Exception as e:
            print(f"ingress-overhead FAILED: {e}", file=sys.stderr)
            failures.append(f"ingress-overhead: failed ({e})")
        else:
            results["ingress-overhead"] = io
            line = (
                f"{'ingress-overhead':15s} ratio {io['ratio']:5.2f}x "
                f"({io['tenant_ns'] / 1e6:.1f} ms 1-tenant vs "
                f"{io['base_ns'] / 1e6:.1f} ms plain, {io['tasks']} "
                f"tasks, bit-identical)"
            )
            if io["ratio"] > args.ingress_tolerance:
                failures.append(
                    f"ingress-overhead: the 1-tenant front door is "
                    f"{io['ratio']:.2f}x slower than the plain stream "
                    f"(bound {args.ingress_tolerance:.2f}x) - the WRR "
                    "poll is taxing the round loop"
                )
                line += "  REGRESSED"
            print(line, flush=True)

    if not wanted or "checkpoint-overhead" in wanted:
        try:
            co = _checkpoint_overhead(args.quick, args.trials)
        except Exception as e:
            print(f"checkpoint-overhead FAILED: {e}", file=sys.stderr)
            failures.append(f"checkpoint-overhead: failed ({e})")
        else:
            results["checkpoint-overhead"] = co
            line = (
                f"{'checkpoint-overhead':15s} ratio {co['ratio']:5.2f}x "
                f"(stride-{co['stride']} {co['stride_ratio']:5.2f}x; "
                f"{co['checkpoint_ns'] / 1e6:.1f} ms vs "
                f"{co['base_ns'] / 1e6:.1f} ms, {co['nodes']} nodes; "
                f"quiesce lag {co['quiesce_lag_tasks']} tasks, strided "
                f"{co['stride_lag_tasks']})"
            )
            if co["ratio"] > args.checkpoint_tolerance:
                failures.append(
                    f"checkpoint-overhead: checkpoint=True (idle) is "
                    f"{co['ratio']:.2f}x slower (bound "
                    f"{args.checkpoint_tolerance:.2f}x) - the quiesce "
                    "word is taxing the round loop"
                )
                line += "  REGRESSED"
            if co["stride_ratio"] > args.checkpoint_tolerance:
                # The stride knob exists to CUT the enabled-idle tax; a
                # strided build pricier than the bound means the poll
                # skip is broken, not just slow.
                failures.append(
                    f"checkpoint-overhead: quiesce_stride={co['stride']} "
                    f"(idle) is {co['stride_ratio']:.2f}x slower (bound "
                    f"{args.checkpoint_tolerance:.2f}x) - the strided "
                    "poll is not skipping DMAs"
                )
                line += "  STRIDE-REGRESSED"
            if co["quiesce_lag_tasks"] > 8:
                failures.append(
                    f"checkpoint-overhead: quiesce landed "
                    f"{co['quiesce_lag_tasks']} tasks past the requested "
                    "round - the boundary latency contract (<= one batch "
                    "width) regressed"
                )
                line += "  LAG-REGRESSED"
            if co["stride_lag_tasks"] > 8 + co["stride"] - 1:
                failures.append(
                    f"checkpoint-overhead: strided quiesce landed "
                    f"{co['stride_lag_tasks']} tasks past the requested "
                    f"round (contract: one batch width + stride-1 = "
                    f"{8 + co['stride'] - 1})"
                )
                line += "  STRIDE-LAG-REGRESSED"
            print(line, flush=True)

    if not wanted or "forasync-tile" in wanted:
        try:
            fa = _forasync_tile(args.quick, args.trials)
        except Exception as e:
            print(f"forasync-tile FAILED: {e}", file=sys.stderr)
            failures.append(f"forasync-tile: failed ({e})")
        else:
            results["forasync-tile"] = fa
            line = (
                f"{'forasync-tile':15s} tier vs host "
                f"{fa['tier_vs_host']:5.2f}x (vs device-scalar "
                f"{fa['tier_vs_device_scalar']:5.2f}x, occupancy "
                f"{fa['occupancy']:.2f}, {fa['tiles']} tiles, "
                "bit-identical)"
            )
            if fa["tier_vs_host"] < args.forasync_floor:
                failures.append(
                    f"forasync-tile: tile tier is only "
                    f"{fa['tier_vs_host']:.2f}x the host scalar-spawn arm "
                    f"(floor {args.forasync_floor:.2f}x) - the device "
                    "tier collapsed"
                )
                line += "  REGRESSED"
            if fa["occupancy"] < args.forasync_occupancy:
                failures.append(
                    f"forasync-tile: batch-lane occupancy "
                    f"{fa['occupancy']:.2f} under bound "
                    f"{args.forasync_occupancy:.2f} - the tile loop "
                    "stopped batching"
                )
                line += "  OCC-REGRESSED"
            print(line, flush=True)

    if not wanted or "frontier-batch" in wanted:
        try:
            fb = _frontier_batch(args.quick, args.trials)
        except Exception as e:
            print(f"frontier-batch FAILED: {e}", file=sys.stderr)
            failures.append(f"frontier-batch: failed ({e})")
        else:
            results["frontier-batch"] = fb
            line = (
                f"{'frontier-batch':15s} batched/scalar "
                f"{fb['batched_vs_scalar']:5.2f}x "
                f"({fb['batched_teps']:,} vs {fb['scalar_teps']:,} TEPS, "
                f"occupancy {fb['occupancy']:.2f}, partial age "
                f"{fb['lane_partial_age']}, {fb['age_fires']} age fires, "
                f"starved age {fb['max_starved_age']}<="
                f"{fb['lane_max_age']}, bit-identical)"
            )
            if fb["batched_vs_scalar"] < args.frontier_floor:
                failures.append(
                    f"frontier-batch: batched frontier is "
                    f"{fb['batched_vs_scalar']:.2f}x the scalar arm "
                    f"(floor {args.frontier_floor:.2f}x) - the frontier "
                    "tier collapsed"
                )
                line += "  REGRESSED"
            if fb["lane_partial_age"] > args.frontier_age_ceiling:
                failures.append(
                    f"frontier-batch: lane_partial_age "
                    f"{fb['lane_partial_age']} over ceiling "
                    f"{args.frontier_age_ceiling:.0f} - the firing "
                    "policy stopped bounding lane starvation"
                )
                line += "  AGE-REGRESSED"
            print(line, flush=True)

    if not wanted or "priority-tier" in wanted:
        try:
            pt = _priority_tier(args.quick, args.trials)
        except Exception as e:
            print(f"priority-tier FAILED: {e}", file=sys.stderr)
            failures.append(f"priority-tier: failed ({e})")
        else:
            results["priority-tier"] = pt
            line = (
                f"{'priority-tier':15s} expand "
                f"{pt['expand_ratio']:5.2f}x "
                f"({pt['expanded_bucketed']} vs "
                f"{pt['expanded_unordered']} EXPANDs, teps "
                f"{pt['teps_ratio']:.2f}x, pr live "
                f"{pt['pr_live_ratio']:.2f}x "
                f"({pt['pr_live_bucketed']} vs "
                f"{pt['pr_live_unordered']} rows), "
                f"{pt['bucket_inversions']} inversions, bit-identical)"
            )
            if pt["expand_ratio"] > args.priority_expand_ceiling:
                failures.append(
                    f"priority-tier: delta-stepping executed "
                    f"{pt['expand_ratio']:.2f}x the label-correction "
                    f"EXPAND count (ceiling "
                    f"{args.priority_expand_ceiling:.2f}x) - ordered "
                    "retirement stopped cutting re-relaxation"
                )
                line += "  EXPAND-REGRESSED"
            if pt["pr_live_ratio"] > args.priority_live_ceiling:
                failures.append(
                    f"priority-tier: bounded-frontier PageRank peak "
                    f"live set is {pt['pr_live_ratio']:.2f}x the "
                    f"unordered arm (ceiling "
                    f"{args.priority_live_ceiling:.2f}x) - the "
                    "magnitude-band ordering stopped bounding the "
                    "frontier"
                )
                line += "  LIVE-REGRESSED"
            print(line, flush=True)

    if not wanted or "program-cache" in wanted:
        try:
            pg = _program_cache(args.quick, args.trials)
        except Exception as e:
            print(f"program-cache FAILED: {e}", file=sys.stderr)
            failures.append(f"program-cache: failed ({e})")
        else:
            results["program-cache"] = pg
            line = (
                f"{'program-cache':15s} warm "
                f"{pg['speedup']:5.2f}x "
                f"({pg['cold_ns']/1e6:.1f}ms cold vs "
                f"{pg['warm_ns']/1e6:.1f}ms warm first build, "
                f"off {pg['off_ns']/1e6:.1f}ms, bit-identical, "
                f"eviction-correct)"
            )
            if pg["speedup"] < args.progcache_floor:
                failures.append(
                    f"program-cache: warm first build only "
                    f"{pg['speedup']:.2f}x faster than cold (floor "
                    f"{args.progcache_floor:.2f}x) - the cache "
                    "stopped killing the compile tax"
                )
                line += "  REGRESSED"
            print(line, flush=True)

    if not wanted or "telemetry-overhead" in wanted:
        try:
            to = _telemetry_overhead(args.quick, args.trials)
        except Exception as e:
            print(f"telemetry-overhead FAILED: {e}", file=sys.stderr)
            failures.append(f"telemetry-overhead: failed ({e})")
        else:
            results["telemetry-overhead"] = to
            line = (
                f"{'telemetry-overhead':15s} ratio {to['ratio']:5.2f}x "
                f"({to['telemetry_ns'] / 1e6:.1f} ms on vs "
                f"{to['base_ns'] / 1e6:.1f} ms off, {to['tasks']} "
                f"tasks, bit-identical, off-text-identical)"
            )
            if to["ratio"] > args.telemetry_tolerance:
                failures.append(
                    f"telemetry-overhead: the telemetry plane is "
                    f"{to['ratio']:.2f}x slower than the off stream "
                    f"(bound {args.telemetry_tolerance:.2f}x) - the "
                    "histogram fold is taxing the round loop"
                )
                line += "  REGRESSED"
            print(line, flush=True)

    if not wanted or "dyngraph-incremental" in wanted:
        try:
            dy = _dyngraph_incremental(args.quick, args.trials)
        except Exception as e:
            print(f"dyngraph-incremental FAILED: {e}", file=sys.stderr)
            failures.append(f"dyngraph-incremental: failed ({e})")
        else:
            results["dyngraph-incremental"] = dy
            line = (
                f"{'dyngraph-incr':15s} expand "
                f"{dy['expand_ratio']:5.2f}x "
                f"({dy['incr_expands']} incremental vs "
                f"{dy['full_expands']} from-scratch EXPANDs, "
                f"{dy['updates_applied']}/{dy['updates']} splices, "
                "bit-identical)"
            )
            if dy["expand_ratio"] > args.dyngraph_expand_ceiling:
                failures.append(
                    f"dyngraph-incremental: the update-only rerun "
                    f"re-expanded {dy['expand_ratio']:.2f}x the "
                    f"from-scratch EXPAND count (ceiling "
                    f"{args.dyngraph_expand_ceiling:.2f}x) - "
                    "incremental recompute stopped paying for itself"
                )
                line += "  REGRESSED"
            print(line, flush=True)

    ts = int(time.time())
    if args.multichip:
        from hclib_tpu.device import stress

        fs_kw = (
            stress.FOREST_STEAL_QUICK if args.quick
            else stress.FOREST_STEAL_BENCH
        )
        mc = [
            ("mc-forest-steal", lambda: stress.forest_steal(**fs_kw)),
            # The batched arm of the SAME workload (ISSUE 7): fib fires
            # through per-device lanes between steal rounds; its rate and
            # occupancy feed the mesh-batch-dispatch guard below, which
            # is why both arms share the one config dict.
            ("mc-forest-steal-batch", lambda: stress.forest_steal(
                batch_width=8, **fs_kw
            )),
            ("mc-unified-resident", lambda: stress.unified_load(
                ndev=8,
                n=8 if args.quick else 10,
                fadds=8 if args.quick else 32,
                capacity=256 if args.quick else 1024,
            )),
        ]
        os.makedirs(args.log_dir, exist_ok=True)
        for name, fn in mc:
            if wanted and name not in wanted:
                continue
            try:
                info = fn()  # exact totals asserted inside
            except Exception as e:
                print(f"{name:20s} FAILED: {e}", file=sys.stderr)
                failures.append(f"{name}: failed ({e})")
                continue
            rate = info["tasks_per_sec"]
            results[name] = {
                "rate": rate, "unit": "tasks/s",
                "tasks": info["tasks"], "seconds": info["seconds"],
                "devices_used": info["devices_used"],
                "imbalance": round(info["imbalance"], 3),
            }
            line = (
                f"{name:20s} {info['tasks']:>8,} tasks in "
                f"{info['seconds']:7.2f} s  ({rate:12,.0f} tasks/s, "
                f"{info['devices_used']} devices, imbalance "
                f"{info['imbalance']:.2f}x)"
            )
            if "min_occupancy" in info:
                results[name]["min_occupancy"] = round(
                    info["min_occupancy"], 3
                )
                results[name]["mean_occupancy"] = round(
                    info["mean_occupancy"], 3
                )
                results[name]["spilled"] = info["spilled"]
                line += (
                    f"  occ {info['mean_occupancy']:.2f} "
                    f"(min {info['min_occupancy']:.2f}), "
                    f"{info['spilled']} lane spills"
                )
            with open(os.path.join(
                    args.log_dir, f"{ts}.{name}.json"), "w") as f:
                json.dump(info, f, indent=1)
            if name in prev and "rate" in prev[name]:
                ratio = rate / prev[name]["rate"]
                line += f"  vs prev {ratio:5.2f}x"
                if ratio < 1 - args.tolerance:
                    failures.append(
                        f"{name}: {1/ratio:.2f}x slower than previous log"
                    )
                    line += "  REGRESSED"
            print(line, flush=True)

        # mesh-batch-dispatch guard (ISSUE 7): the batched forest-steal
        # arm must hold a tasks/s floor against the scalar arm measured
        # in the SAME run (no cross-run weather), and its per-device
        # lane occupancy must not collapse - either failing means the
        # mesh multiplier silently regressed.
        sc = results.get("mc-forest-steal")
        bt = results.get("mc-forest-steal-batch")
        if sc and bt and "rate" in sc and "rate" in bt:
            ratio = bt["rate"] / sc["rate"]
            occ = bt.get("min_occupancy", 0.0)
            results["mesh-batch-dispatch"] = {
                "batch_vs_scalar": round(ratio, 3),
                "min_occupancy": occ,
            }
            line = (
                f"{'mesh-batch-dispatch':20s} batched/scalar "
                f"{ratio:5.2f}x  min occupancy {occ:.2f}"
            )
            if ratio < args.mesh_batch_floor:
                failures.append(
                    f"mesh-batch-dispatch: batched forest-steal is "
                    f"{ratio:.2f}x the scalar mesh (floor "
                    f"{args.mesh_batch_floor:.2f}x) - the mesh batch "
                    "tier collapsed"
                )
                line += "  REGRESSED"
            if occ < args.mesh_batch_occupancy:
                failures.append(
                    f"mesh-batch-dispatch: min per-device occupancy "
                    f"{occ:.2f} under bound "
                    f"{args.mesh_batch_occupancy:.2f} - the mesh stopped "
                    "exposing same-kind width to the lanes"
                )
                line += "  OCC-REGRESSED"
            print(line, flush=True)

    os.makedirs(args.log_dir, exist_ok=True)
    out_path = os.path.join(args.log_dir, f"{ts}.json")
    with open(out_path, "w") as f:
        json.dump({"quick": args.quick, "apps": results}, f, indent=1)
    print(f"log written: {out_path}")
    if failures:
        print("REGRESSIONS:\n  " + "\n  ".join(failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
