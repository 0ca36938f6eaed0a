"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline: UTS tree-search throughput (nodes/sec) of the fused Pallas DFS
engine on the canonical T1L tree (BASELINE.json's north-star workload),
compared against this repo's C++ native work-stealing runtime on the local
CPU (the measured baseline BASELINE.md calls for; the reference publishes no
reusable numbers).

Every mode runs compiled on a TPU and nowhere else: ``main()`` checks the
platform first (``require_tpu``), every arm states ``interpret=False``, and
every result line names the device it ran on. A phase that fails fails the
run: there is no fallback headline, engine, tree or backend.

Secondary numbers (fib megakernel tasks/sec vs Python-host and native
baselines, Cholesky GFLOP/s) go to stderr so the stdout contract stays a
single JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Set by main() from require_tpu(): {"platform", "kind", "count"}.
DEVICE = None


def emit(result: dict) -> None:
    """The stdout contract: one JSON line per result, naming the device."""
    print(json.dumps({**result, "device": DEVICE}), flush=True)


def _mesh(ndev: int):
    """A 1-D mesh over the first ``ndev`` REAL devices; asking for more
    than the host has is an error, never a virtual CPU mesh."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < ndev:
        raise RuntimeError(
            f"this arm needs {ndev} chips and JAX has {len(devs)}"
        )
    return Mesh(np.array(devs[:ndev]), ("q",))


# --------------------------------------------------------- wall budget
# The headline runs FIRST and its JSON line flushes the moment it exists;
# every later section start is gated on the time remaining, so the bench
# truncates itself (logged SKIP) instead of being killed mid-number.
# HCLIB_TPU_BENCH_BUDGET_S overrides the default wall budget.

# Armed by main(): other consumers of these bench functions (notably
# tools/perf_regression.py --device, whose whole-suite wall time easily
# exceeds one bench budget) must not have their trials truncated by a
# clock that started at module import.
_T0 = None


def _budget_s() -> float:
    from hclib_tpu.runtime.env import env_float

    return env_float("HCLIB_TPU_BENCH_BUDGET_S", 780.0)


def _remaining() -> float:
    if _T0 is None:
        return float("inf")
    return _budget_s() - (time.monotonic() - _T0)


def section(name: str, est_s: float, fn):
    """Run one bench section if ~est_s seconds fit in the remaining wall
    budget (a section that does not fit is logged as SKIP and returns
    None). A section that RAISES fails the whole run: the exception
    propagates and bench.py exits non-zero."""
    left = _remaining()
    if left < est_s:
        log(f"SKIP {name}: {left:.0f}s of budget left, ~{est_s:.0f}s needed")
        return None
    return fn()


def _chol_ceiling_pct(gflops: float) -> float:
    """Achieved f32-effective GFLOP/s as a percentage of the 3-pass f32
    ceiling: every f32-accurate GEMM costs 3 bf16 MXU passes, so the
    ceiling is the chip's published bf16 peak / 3 (``DEVICE_TABLE``, keyed
    by device_kind; a chip that is not in it is an error)."""
    import jax

    from hclib_tpu.device.megakernel import device_row

    peak = device_row(jax.devices()[0].device_kind)["bf16_tflops"]
    return 100.0 * gflops / (peak * 1000.0 / 3.0)


def trials_of(name: str, fn, trials: int) -> dict:
    """Run ``fn`` (-> value, higher better) ``trials`` times back to back
    and return {median, best, n_trials, n_used, spread} over the trials
    that were not sheared (see ``_slope_or_sheared``); logs each one."""
    values = []
    for t in range(trials):
        if t and _remaining() < 0:
            log(f"  {name}: wall budget exhausted after {t} trials")
            break
        v = fn()
        log(f"  {name} trial {t}: {v:.4g}")
        values.append(v)
    used = [v for v in values if v > 0]
    if not used:
        raise RuntimeError(f"{name}: every one of {len(values)} trials "
                           "was sheared")
    s = {
        "median": float(np.median(used)),
        "best": max(used),
        "n_trials": len(values),
        "n_used": len(used),
        "spread": round(max(used) / min(used), 2),
    }
    log(f"{name}: median {s['median']:.4g} / best {s['best']:.4g} "
        f"({s['n_used']}/{s['n_trials']} trials, spread {s['spread']}x)")
    return s


# Reps gaps under this are host-clock jitter, not measurement: any slope
# computed from them is nonsense (observed: 7e12 tasks/s from a near-zero
# denominator). trials_of() drops the -1.0 sentinel - ONE policy for
# every slope bench here.
_SHEAR_GAP_S = 5e-3


def _slope_or_sheared(gap_seconds: float, units: float) -> float:
    """units/sec over a reps gap, or the sheared-trial sentinel."""
    if gap_seconds < _SHEAR_GAP_S:
        return -1.0
    return units / gap_seconds


def _slope_harness(mk, builder, expect_value, fuel, reps_pair, label):
    """Shared steady-state harness: re-run the staged graph R times inside
    one kernel launch for two R values; per-task cost is the slope between
    them, which cancels launch, staging and readback. The warm-up call's
    value slot 0 is asserted against ``expect_value``. Each timed leg ends
    by reading the executed count back to the host, which the slope needs
    and which cannot return before the kernel has. Returns a zero-arg
    trial callable (-> tasks/sec)."""
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.megakernel import C_EXECUTED

    tasks, succ, ring, counts = builder.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )

    def fresh():
        return jax.block_until_ready([
            jax.device_put(jnp.asarray(x))
            for x in (tasks, succ, ring, counts,
                      np.zeros(mk.num_values, np.int32))
        ])

    jits = {}
    for reps in reps_pair:
        jits[reps] = mk._build(fuel, reps=reps)
        outs = jits[reps](*fresh())  # compile + warm
        assert int(np.asarray(outs[3])[0]) == expect_value, f"{label} wrong"

    def one_trial():
        points = []
        for reps in reps_pair:
            args = fresh()
            t0 = time.perf_counter()
            outs = jits[reps](*args)
            n = int(np.asarray(outs[2])[C_EXECUTED])
            dt = time.perf_counter() - t0
            points.append((dt, n))
        (d1, n1), (d2, n2) = points
        return _slope_or_sheared(d2 - d1, n2 - n1)

    return one_trial


def _graph_slope_trial(jits, fresh, reps_pair, units_per_graph):
    """Two-reps slope over a pre-staged megakernel graph -> units/sec.

    The shared machinery of the Cholesky and SW-wave benches (the
    fib benches use _slope_harness, which also owns graph STAGING): run
    the compiled reps-variants on fresh device buffers (staged before the
    clock starts), read the executed count back, and return
    units_per_graph over the per-graph slope, with the shared shear guard
    (_slope_or_sheared)."""
    import jax

    from hclib_tpu.device.megakernel import C_EXECUTED

    r1, r2 = reps_pair

    def one_trial():
        t = {}
        for r in reps_pair:
            args = jax.block_until_ready(fresh())
            t0 = time.perf_counter()
            outs = jits[r](*args)
            _ = int(np.asarray(outs[2])[C_EXECUTED])
            t[r] = time.perf_counter() - t0
        return _slope_or_sheared(
            t[r2] - t[r1], units_per_graph * (r2 - r1)
        )

    return one_trial


def bench_device_vfib():
    """Steady-state batch-dispatch (vector tier) throughput: the fib(30)
    graph (2,692,537 tasks - the whole recursion tree, lane-level work
    stealing balancing the lanes) under the shared slope harness."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import VFIB, make_vfib_megakernel

    # 100 reps between the two points ~= 270M tasks ~= 100-190 ms of
    # kernel time: a (2,12) pair produced 7e12 "tasks/s" from an 11 ms
    # gap, which is why the shear guard exists.
    n, reps_pair = 30, (10, 110)
    mk = make_vfib_megakernel(max_n=n + 2, interpret=False)
    b = TaskGraphBuilder()
    b.add(VFIB, args=[n], out=0)
    one_trial = _slope_harness(
        mk, b, 832040, 1 << 30, reps_pair, f"device vfib({n})"
    )
    s = trials_of("fib batch-dispatch tier", one_trial, trials=3)
    log(f"device fib batch-dispatch steady-state: "
        f"{1e9/s['median']:.2f} ns/task -> {s['median']/1e6:,.1f}M tasks/s "
        f"median (best {s['best']/1e6:,.1f}M)")
    return s["median"]


def bench_device_fib():
    """Steady-state scalar-tier megakernel throughput: the fib(12) task
    graph (697 dynamic tasks: spawns, joins, continuation passing) under
    the shared slope harness (the resident scheduler never exits between
    reps)."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    mk = make_fib_megakernel(768, interpret=False)
    b = TaskGraphBuilder()
    b.add(FIB, args=[12], out=0)  # 697 tasks, fits the SMEM table
    one_trial = _slope_harness(
        mk, b, 144, 1 << 22, (100, 2000), "device fib"
    )
    s = trials_of("fib scalar tier", one_trial, trials=3)
    log(f"device fib steady-state: {1e9/s['median']:.0f} ns/task -> "
        f"{s['median']:,.0f} tasks/s median (best {s['best']:,.0f})")
    return s["median"]


def bench_host_fib(n: int = 20):
    from hclib_tpu.models import fib

    r = fib.run(n, variant="finish")
    log(f"host fib({n}): {r['tasks']} tasks in {r['seconds']*1000:.0f} ms "
        f"-> {r['tasks_per_sec']:,.0f} tasks/s")
    return r["tasks_per_sec"]


def bench_native_fib(n: int = 27):
    """The strongest CPU baseline: this repo's C++ work-stealing runtime."""
    from hclib_tpu.native import NativeRuntime

    with NativeRuntime() as rt:
        t0 = time.perf_counter()
        v = rt.fib(n)
        dt = time.perf_counter() - t0
        tasks = rt.executed
    rate = tasks / dt
    log(f"native C++ fib({n}) = {v}: {tasks} tasks in {dt*1000:.0f} ms "
        f"-> {rate:,.0f} tasks/s ({rt.nworkers} workers)")
    return rate


def bench_device_sw():
    """Secondary: batched Smith-Waterman GCUPS via the fused Pallas sweep
    (device/sw_pallas.py). The rate is the slope between two query
    lengths, which cancels the per-call launch cost."""
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.sw_pallas import _sw_pallas

    rng = np.random.default_rng(1)
    B, m = 1024, 1024
    bt = jax.device_put(jnp.asarray(rng.integers(0, 4, (m, B)), jnp.int32))
    ats = {}
    for n in (256, 2048):
        ats[n] = jax.device_put(
            jnp.asarray(rng.integers(0, 4, (n, B)), jnp.int32)
        )
        _sw_pallas(ats[n], bt, block_b=256,
                   interpret=False).block_until_ready()

    def one_trial():
        # Both lengths timed back-to-back inside ONE trial; each leg
        # dispatches K calls and waits ONCE for the last (they run in
        # order on the one device), so single-call host jitter (a leg is
        # 4-35 ms of compute) does not dominate the 2-point slope.
        K = 8
        t = {}
        for n in (256, 2048):
            out = None
            t0 = time.perf_counter()
            for _ in range(K):
                out = _sw_pallas(ats[n], bt, block_b=256, interpret=False)
            out.block_until_ready()
            t[n] = (time.perf_counter() - t0) / K
        return B * m * (2048 - 256) / (t[2048] - t[256]) / 1e9

    s = trials_of("SW pallas GCUPS", one_trial, trials=3)
    log(f"device SW [pallas]: B={B} m={m}, {s['median']:.0f} GCUPS median "
        f"(best {s['best']:.0f})")
    return s["median"]


def bench_device_sw_wave(trials: int = 3):
    """Secondary: GCUPS of the wave-batched SW tile-DAG engine
    (device/smithwaterman.py device_sw_wave - wave chunks chained by REAL
    dependencies through the megakernel scheduler, unlike the fused
    sw_pallas sweep which has no task graph). Scoring mode (with_h=False)
    so the measured rate is the DP itself, not H-matrix writeback. Slope
    harness over reps cancels launch and staging."""
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.smithwaterman import (
        T as SWT,
        build_sw_wave_graph,
        make_sw_wave_megakernel,
        sw_wave_buffers,
    )
    from hclib_tpu.models.smithwaterman import random_seq

    n = m = 8192
    nt = n // SWT
    mk = make_sw_wave_megakernel(nt, nt, interpret=False, with_h=False)
    builder = build_sw_wave_graph(nt, nt)
    a, b_ = random_seq(n, 5), random_seq(m, 6)
    tasks, succ, ring, counts = builder.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    bufs = sw_wave_buffers(a, b_)
    host = (
        tasks, succ, ring, counts, np.zeros(mk.num_values, np.int32),
        bufs["aseq"], bufs["bseq"], bufs["bot"], bufs["right"],
    )

    def fresh():
        return [jax.device_put(jnp.asarray(x)) for x in host]

    reps_pair = (2, 12)
    jits = {r: mk._build(1 << 22, reps=r) for r in reps_pair}
    score = None
    outs = None
    for r in reps_pair:
        outs = jits[r](*fresh())  # compile + warm
        score = int(np.asarray(outs[3])[0])  # best alignment score
    # Correctness gate: the wave DAG's best score must match the
    # independent batched-scan XLA engine on the same pair (a different
    # algorithmic formulation of the same DP, no megakernel involved).
    from hclib_tpu.device.sw_vec import sw_score_one

    ref = sw_score_one(np.asarray(a), np.asarray(b_))
    assert score == ref, (score, ref)
    log(f"device SW [wave-DAG]: score {score} matches the scan engine")
    # Batched-dispatch tier counters (guarded by tools/perf_regression.py
    # so the occupancy the speedup rests on never floats free).
    global LAST_SW_WAVE_TIERS
    LAST_SW_WAVE_TIERS = tiers = mk.decode_tier_stats(
        np.asarray(outs[4 + len(mk.data_specs)])
    )
    log(
        f"device SW [wave-DAG]: batch occupancy "
        f"{tiers['batch_occupancy']:.2f} ({tiers['batch_rounds']} rounds x "
        f"width {tiers['batch_width']}, {tiers['prefetch_hits']} prefetch "
        f"hits, {tiers['full_rounds']} full rounds)"
    )

    one_trial = _graph_slope_trial(jits, fresh, reps_pair, n * m / 1e9)
    s = trials_of("SW wave-DAG GCUPS", one_trial, trials)
    log(
        f"device SW [wave-DAG]: {n}x{m} grid, {builder.num_tasks} chunk "
        f"tasks, {s['median']:.1f} GCUPS median (best {s['best']:.1f})"
    )
    return s["median"]


def bench_device_cholesky(
    trials: int = 4,
    n: int = 8192,
    residual_bound: float = 1e-6,
):
    """In-kernel tiled-Cholesky throughput: a DDF DAG of 512x512 MXU
    tiles (column-fused TRSM streams + row-fused trailing updates over
    PRE-SPLIT bf16 operands, double-buffered DMA) - hundreds of
    heterogeneous tasks sustained by the resident scheduler, not a toy
    graph. One fresh factorization is residual-checked on-device first
    (||LL^T - A||_max / ||A||_max < ``residual_bound``, measured with a
    HIGHEST-precision matmul - the default bf16 matmul's own error would
    drown the signal); throughput then comes from the steady-state slope
    harness (re-run the staged graph R times inside one kernel launch;
    per-graph cost = slope between two R values, cancelling launch and
    staging); the number of record is the median over the trials.

    Two sizes ship (fused-graph task counts): n=8192 (151 tasks;
    residual gated < 1e-6, the reference-parity bar) and n=16384 (559
    tasks; the f32 accumulation error over 2x the update steps lands
    ~1.5e-6, gated < 2e-6 and reported - the POTRF/TRSM serial fraction
    amortizes, so this is the peak-utilization row)."""
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.cholesky import (
        build_cholesky_graph,
        cholesky_buffers,
        device_cholesky,
        make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    # 512 tiles flip the GEMMs compute-bound (arithmetic intensity ts/8
    # flops/byte); 1024 tiles measured slower (POTRF block algebra grows
    # faster than the DMA savings).
    tile = 512
    nt = n // tile
    # fused-only capacity: at nt=32 the unfused task table would overflow
    # the 1 MB SMEM budget (~32 B per descriptor word in SMEM windows).
    mk = make_cholesky_megakernel(
        nt, interpret=False, tile=tile, fused_only=True
    )
    a = make_spd(n).astype(np.float32)

    # Correctness gate on the REAL size (reference keeps a checked result,
    # test/cholesky/run.sh): factor once fresh, residual on-device.
    L, _ = device_cholesky(a, interpret=False, mk=mk, tile=tile)
    La = jax.device_put(jnp.asarray(L))
    Aa = jax.device_put(jnp.asarray(a))
    m = jnp.matmul(La, La.T, precision=jax.lax.Precision.HIGHEST)
    rel = float(jnp.max(jnp.abs(m - Aa)) / jnp.max(jnp.abs(Aa)))
    assert rel < residual_bound, (
        f"cholesky n={n} residual {rel:.2e} >= {residual_bound:g}"
    )
    log(f"device cholesky n={n}: residual {rel:.2e} (< {residual_bound:g})")
    del L, La, Aa, m

    b = build_cholesky_graph(nt)
    tasks, succ, ring, counts = b.finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    bufs = cholesky_buffers(a, nt, tile)
    host = (
        tasks, succ, ring, counts, np.zeros(8, np.int32),
        bufs["tiles"], bufs["linvsp"], bufs["lsp"],
    )

    def fresh():
        # input_output_aliases donate the inputs; every call needs fresh
        # device buffers.
        return [jax.device_put(jnp.asarray(x)) for x in host]

    reps_pair = (5, 45) if n <= 8192 else (2, 12)
    jits = {r: mk._build(1 << 22, reps=r) for r in reps_pair}
    ntasks = 0
    for r in reps_pair:
        outs = jits[r](*fresh())  # compile + warm
        ntasks = int(np.asarray(outs[2])[5]) // r

    one_trial = _graph_slope_trial(jits, fresh, reps_pair, n**3 / 3.0 / 1e9)
    s = trials_of(f"cholesky n={n} ({ntasks} tasks)", one_trial, trials)
    # Physics context for the number: every f32-accurate GEMM costs 3 bf16
    # MXU passes, so the achievable ceiling is the published bf16 peak / 3
    # - report achieved utilization against THAT, plus the
    # bf16-equivalent MXU rate.
    log(
        f"device cholesky: {s['median']/1e3:.1f} TF f32-effective = "
        f"{_chol_ceiling_pct(s['median']):.0f}% of the 3-pass f32 ceiling "
        f"(published bf16 peak / 3 passes); bf16-equivalent MXU rate "
        f"{3.0 * s['median']/1e3:.1f} TF"
    )
    return s["median"]


def emit_trace_artifacts(log_dir: str = "perf-logs"):
    """--trace artifact emission: one traced megakernel run + one
    instrumented host run, folded into a MetricsRegistry snapshot
    (JSON + Prometheus text) and a merged Perfetto file under
    ``log_dir`` - the machine-readable observability bundle of a bench
    round (budget-gated like every other section)."""
    import hclib_tpu as hc
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.tracebuf import trace_to_jsonable
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel
    from hclib_tpu.runtime.metrics import MetricsRegistry

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        import timeline
    finally:
        sys.path.pop(0)

    os.makedirs(log_dir, exist_ok=True)
    ts = int(time.time())

    # Device: the fib megakernel with the flight recorder on.
    mk = make_fib_megakernel(768, trace=1024, interpret=False)
    b = TaskGraphBuilder()
    b.add(FIB, args=[12], out=0)
    iv, _, dev_info = mk.run(b)
    assert int(iv[0]) == 144

    # Host: an instrumented + metrics-enabled runtime.
    rt = hc.Runtime(nworkers=2, instrument=True, metrics=True)

    def body():
        with hc.finish():
            for _ in range(200):
                hc.async_(lambda: None)

    rt.run(body)
    dump = rt.event_log.dump(log_dir)

    reg = rt.metrics or MetricsRegistry()
    reg.add_run_info("device_fib", dev_info)
    snap = reg.snapshot()
    mpath = os.path.join(log_dir, f"trace_{ts}.metrics.json")
    with open(mpath, "w") as f:
        f.write(reg.to_json(snap))
    with open(os.path.join(log_dir, f"trace_{ts}.prom"), "w") as f:
        f.write(reg.to_prometheus(snap))
    tpath = os.path.join(log_dir, f"trace_{ts}.trace.json")
    with open(tpath, "w") as f:
        json.dump(trace_to_jsonable(dev_info["trace"]), f)
    ppath = os.path.join(log_dir, f"trace_{ts}.perfetto.json")
    doc = timeline.export_perfetto(
        ppath, dump_path=dump, traces=[dev_info["trace"]]
    )
    log(
        f"trace artifacts: {len(doc['traceEvents'])} perfetto events -> "
        f"{ppath}; metrics -> {mpath}; device trace -> {tpath}; "
        f"host dump -> {dump}"
    )
    return ppath


T1_NODES = 4130071
T1L_NODES = 102181082

# Last bench_device_sw_wave run's batched-tier counters (occupancy,
# prefetch hits), for tools/perf_regression.py.
LAST_SW_WAVE_TIERS: dict = {}


def bench_native_uts():
    """CPU baseline for the headline: C++ runtime on UTS T1 (same node rate
    as T1L, 50x faster to run)."""
    from hclib_tpu.models.uts import T1
    from hclib_tpu.native import NativeRuntime

    with NativeRuntime() as rt:
        t0 = time.perf_counter()
        nodes, leaves, depth = rt.uts(T1.shape, T1.gen_mx, T1.b0, T1.root_seed)
        dt = time.perf_counter() - t0
    assert nodes == T1_NODES, nodes
    rate = nodes / dt
    log(f"native C++ UTS T1: {nodes} nodes in {dt:.2f}s -> {rate:,.0f} nodes/s "
        f"({rt.nworkers} workers)")
    return rate


def bench_device_uts():
    """Headline: fused-Pallas vectorized-DFS UTS on the canonical T1L tree
    (102,181,082 nodes; BASELINE.json's north-star workload; uts_pallas.py,
    the whole traversal resident on-core). Returns (rate, tree_label,
    statistic_tag). One engine, one tree: a failure raises."""
    from hclib_tpu.device.uts_pallas import uts_pallas
    from hclib_tpu.models.uts import T1L

    # Empirically best single-chip config (v5e): 8192 lanes as (64,128)
    # planes, ~240k subtree roots (deep enough that the shared root queue
    # bounds imbalance by one small subtree), refill threshold nlanes/32.
    lanes, roots, div, trials = (64, 128), 256 * 1024, 32, 7
    holder = {}

    def one_trial():
        r = uts_pallas(T1L, target_roots=roots, lanes=lanes,
                       min_idle_div=div, interpret=False)
        assert r["nodes"] == T1L_NODES, r["nodes"]
        assert r["interpret"] is False and r["platform"] == "tpu", r
        holder["r"] = r
        return r["nodes_per_sec"]

    one_trial()  # a call is one launch, and the first of a shape compiles
    s = trials_of("UTS T1L [pallas]", one_trial, trials)
    stat = f"median-of-{s['n_used']}"
    r = holder["r"]
    log(f"device UTS T1L [pallas]: {r['nodes']} nodes, "
        f"{s['median']/1e6:.1f}M nodes/s (lane eff "
        f"{100.0 * r['lane_efficiency']:.0f}%, statistic {stat})")
    return s["median"], "T1L", stat


def bench_checkpoint():
    """Checkpoint/restore cost of record (ISSUE 5): quiesce latency,
    bundle size, and save/restore wall time for the seeded UTS traversal
    and the Cholesky factor, written to perf-logs/<ts>.checkpoint.json.
    Compiled, on the chip - the
    numbers that matter operationally are the QUIESCE latency (how long a
    preemption notice stalls before the state is exportable) and the
    BUNDLE size (what a preemption window must flush to disk)."""
    import tempfile

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import make_uts_megakernel
    from hclib_tpu.runtime.checkpoint import (
        restore_megakernel, snapshot_megakernel,
    )

    out = {}

    def uts_builder():
        b = TaskGraphBuilder()
        b.add(0, args=[1, 0])  # UTS_NODE root
        return b

    def one(name, make_mk, builder, data_of):
        mk_plain = make_mk(False)
        full = mk_plain.run(builder(), data=data_of())[2]
        mk = make_mk(True)
        mk.run(builder(), data=data_of())  # warm the checkpoint build
        at = max(1, full["executed"] // 2)
        t0 = time.perf_counter()
        _, _, info_q = mk.run(builder(), data=data_of(), quiesce=at)
        quiesce_s = time.perf_counter() - t0
        bundle = snapshot_megakernel(mk, info_q)
        d = tempfile.mkdtemp(prefix=f"hclib-bench-ckpt-{name}-")
        stats = bundle.save(d)
        t0 = time.perf_counter()
        _, _, info_r = restore_megakernel(d, make_mk(True))
        restore_s = time.perf_counter() - t0
        assert info_r["executed"] == full["executed"], (name, info_r)
        row = {
            "executed": full["executed"],
            "checkpoint_at": info_q["quiesce"]["executed_at"],
            "quiesce_entry_s": round(quiesce_s, 4),
            "bundle_bytes": stats["bundle_bytes"],
            "save_s": stats["save_s"],
            "restore_s": round(restore_s, 4),
        }
        out[name] = row
        log(f"checkpoint [{name}]: quiesced at "
            f"{row['checkpoint_at']}/{row['executed']} tasks in "
            f"{row['quiesce_entry_s'] * 1e3:.1f} ms, bundle "
            f"{row['bundle_bytes'] / 1024:.0f} KiB "
            f"(save {row['save_s'] * 1e3:.1f} ms, restore+drain "
            f"{row['restore_s'] * 1e3:.1f} ms)")

    one(
        "uts",
        lambda ck: make_uts_megakernel(checkpoint=ck, interpret=False),
        uts_builder,
        lambda: None,
    )

    from hclib_tpu.device.cholesky import (
        build_cholesky_graph, cholesky_buffers, make_cholesky_megakernel,
    )
    from hclib_tpu.models.cholesky import make_spd

    nt = 4
    a = make_spd(nt * 128).astype(np.float32)
    one(
        "cholesky",
        lambda ck: make_cholesky_megakernel(
            nt, checkpoint=ck, interpret=False
        ),
        lambda: build_cholesky_graph(nt),
        lambda: cholesky_buffers(a, nt),
    )

    # Durable-store arms (ISSUE 17). Schema under out["store"]:
    #   publish_fsync_s / publish_nofsync_s - median save() wall time
    #     (stage + hash + atomic rename [+ fsync]) for the UTS bundle;
    #   cold_load_clean_s - load_latest() on a healthy 3-gen store;
    #   cold_load_healing_s - load_latest() with the 2 NEWEST gens
    #     corrupt (2 quarantine moves + sha walk before the valid gen);
    #   bundle_bytes - the payload all arms move.
    # Every arm logs its own line as it lands, so a timeout kill
    # (rc=124) still leaves the completed numbers in the transcript.
    import shutil

    from hclib_tpu.runtime.checkpoint import BundleStore

    mk = make_uts_megakernel(checkpoint=True)
    _, _, info_q = mk.run(uts_builder(), quiesce=8)
    bundle = snapshot_megakernel(mk, info_q)
    store_row = {}

    def publish(fsync, trials=5):
        times = []
        for _ in range(trials):
            d = tempfile.mkdtemp(prefix="hclib-bench-store-")
            st = BundleStore(d, keep=3, fsync=fsync)
            t0 = time.perf_counter()
            st.save(bundle)
            times.append(time.perf_counter() - t0)
            shutil.rmtree(d, ignore_errors=True)
        return round(sorted(times)[len(times) // 2], 4)

    store_row["publish_fsync_s"] = publish(True)
    store_row["publish_nofsync_s"] = publish(False)
    log(f"store publish: {store_row['publish_fsync_s'] * 1e3:.1f} ms "
        f"fsync'd / {store_row['publish_nofsync_s'] * 1e3:.1f} ms fast "
        f"(atomic-rename generational save)")

    def cold_load(corrupt_newest):
        d = tempfile.mkdtemp(prefix="hclib-bench-store-")
        st = BundleStore(d, keep=3, fsync=False)
        for _ in range(3):
            st.save(bundle)
        for g in st.generations()[-corrupt_newest:] if corrupt_newest else []:
            npz = os.path.join(st.path_of(g), "state.npz")
            blob = open(npz, "rb").read()
            with open(npz, "wb") as f:
                f.write(blob[:-4] + b"\xff" * 4)
        reader = BundleStore(d, fsync=False)
        t0 = time.perf_counter()
        got = reader.load_latest()
        dt = time.perf_counter() - t0
        assert len(reader.faults) == corrupt_newest
        assert got.diff(bundle)["equal"]
        shutil.rmtree(d, ignore_errors=True)
        return round(dt, 4)

    store_row["cold_load_clean_s"] = cold_load(0)
    store_row["cold_load_healing_s"] = cold_load(2)
    stats = bundle.save(tempfile.mkdtemp(prefix="hclib-bench-store-"))
    store_row["bundle_bytes"] = stats["bundle_bytes"]
    out["store"] = store_row
    log(f"store cold load_latest: "
        f"{store_row['cold_load_clean_s'] * 1e3:.1f} ms clean / "
        f"{store_row['cold_load_healing_s'] * 1e3:.1f} ms healing past "
        f"2 quarantined generations "
        f"({store_row['bundle_bytes'] / 1024:.0f} KiB bundle)")

    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.checkpoint.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    log(f"checkpoint bench written: {path}")
    return out


def bench_autoscale():
    """Elastic-autoscaling cost of record (ISSUE 6): resize latency
    (quiesced state -> resumable state across a reshard) and tasks/s
    sustained THROUGH scale events, for an autoscaled UTS mesh that
    scales 2 -> 4 under backlog and back in on the idle tail. Written to
    perf-logs/<ts>.autoscale.json. The mesh is real chips, so this arm
    needs the four-chip host (``_mesh`` refuses otherwise)."""
    import hclib_tpu as hc
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel

    def make_kernel(ndev):
        mk = make_uts_megakernel(max_depth=7, interpret=False,
                                 checkpoint=True)
        return ResidentKernel(
            mk, _mesh(ndev), migratable_fns=[UTS_NODE], window=4,
            homed=False,
        )

    builders = [TaskGraphBuilder() for _ in range(2)]
    for d in range(2):
        for r in range(8):
            builders[d].add(UTS_NODE, args=[d * 8 + r + 1, 0])
    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(
        make_kernel,
        hc.AutoscalerPolicy(min_devices=1, max_devices=4,
                            scale_out_backlog=4.0, scale_in_backlog=1.0,
                            hysteresis=1, cooldown=1),
        slice_rounds=8, metrics=reg,
    )
    t0 = time.perf_counter()
    iv, _, info = asc.run(builders, quantum=8)
    wall = time.perf_counter() - t0
    resizes = [e for e in info["scale_events"]
               if e["from_ndev"] != e["to_ndev"]]
    out = {
        "executed": info["executed"],
        "wall_s": round(wall, 4),
        "tasks_per_sec": round(info["executed"] / max(wall, 1e-9)),
        "slices": len(info["scale_events"]),
        "resizes": [
            {
                "kind": e["kind"], "from": e["from_ndev"],
                "to": e["to_ndev"],
                "resize_latency_s": e["resize_latency_s"],
            }
            for e in resizes
        ],
        "ndev_final": info["ndev_final"],
    }
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.autoscale.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    lat = [r["resize_latency_s"] for r in out["resizes"]
           if r["resize_latency_s"] is not None]
    if lat:
        log(f"autoscale: {info['executed']} tasks through "
            f"{len(resizes)} resize(s) at {out['tasks_per_sec']:,} "
            f"tasks/s; resize latency {max(lat) * 1e3:.1f} ms max")
    else:
        log(f"autoscale: {info['executed']} tasks at "
            f"{out['tasks_per_sec']:,} tasks/s, no resizes fired")
    log(f"autoscale bench written: {path}")
    return out


def _bench_tenants_mesh(weights: dict, per_tenant: int) -> dict:
    """The MESH arm (ISSUE 13): the same 3-lane roster spanning a
    4-device front door (MeshTenantTable routing + the numpy WRR
    reference model - the executable spec of the in-kernel poll;
    interpret mode serializes the DMAs, so the model is the honest
    host-side price), riding ONE live reshard cut 4 -> 2 mid-stream
    (the scale event). Reports aggregate tasks/s and per-tenant
    p50/p99 admission-to-complete latency ACROSS the event, plus the
    cut's own latency - the serving-latency seed direction 1 inherits."""
    import numpy as np

    from hclib_tpu.device.descriptor import RING_ROW
    from hclib_tpu.device.tenants import (
        MeshTenantTable, TenantSpec, wrr_poll_reference,
    )

    # Region sized so each tenant's rows fit one lane region even at
    # the 2-device trough (the lifetime budget resets at the cut).
    region = -(-per_tenant // (2 * 8)) * 8 + 16
    specs = [TenantSpec(t, weight=w, queue_capacity=4 * per_tenant)
             for t, w in weights.items()]
    table = MeshTenantTable(specs, 4, region)
    rings = np.zeros((4, len(specs) * region, RING_ROW), np.int32)

    def drive(tbl, rg, polls, start):
        tctl = tbl.pump(rg)
        for r in range(start, start + polls):
            for d in range(tbl.ndev):
                wrr_poll_reference(rg[d], tctl[d], region, r, 1 << 20)
        tbl.absorb(tctl)

    def raw_latencies(tbl):
        out = {tid: [] for tid in weights}
        for i, tid in enumerate(weights):
            for t in tbl.tables:
                out[tid].extend(t._lanes[i].latencies)
        return out

    t0 = time.perf_counter()
    total = 0
    for tid in weights:
        for _ in range(per_tenant):
            assert table.submit(tid, 0, args=[1])
            total += 1
    rnd = 0
    drive(table, rings, 4, rnd)
    rnd += 4
    lat_pre = raw_latencies(table)
    done_pre = {t: s["completed"] for t, s in table.stats().items()}
    t_cut = time.perf_counter()
    table, _ = table.reshard(rings, 2)
    resize_s = time.perf_counter() - t_cut
    rings = np.zeros((2, len(specs) * region, RING_ROW), np.int32)
    for r in range(1024):
        drive(table, rings, 2, rnd)
        rnd += 2
        if table.drained():
            break
    wall = time.perf_counter() - t0
    assert table.drained(), "mesh tenant bench wedged"
    snap = table.stats()
    assert sum(s["completed"] for s in snap.values()) == total
    lat_post = raw_latencies(table)
    detail = {}
    for tid in weights:
        xs = sorted(lat_pre[tid] + lat_post[tid])
        pct = (lambda p, xs=xs:
               xs[min(len(xs) - 1, int(p * len(xs)))] if xs else 0.0)
        detail[tid] = {
            "weight": weights[tid],
            "completed": int(snap[tid]["completed"]),
            "completed_before_cut": int(done_pre[tid]),
            "p50_latency_s": round(pct(0.50), 6),
            "p99_latency_s": round(pct(0.99), 6),
        }
    return {
        "ndev": "4->2",
        "tasks": total,
        "tasks_per_sec": round(total / max(wall, 1e-9), 1),
        "wall_s": round(wall, 4),
        "resize_latency_s": round(resize_s, 6),
        "wrr_rounds": rnd,
        "per_tenant": detail,
    }


def bench_tenants(quick: bool = False) -> None:
    """Multi-tenant ingress cost of record (ISSUE 8 + the ISSUE 13 mesh
    arm): a 3-lane weighted front door (4:2:1) over the compiled
    streaming kernel, plus the same roster spanning a 4-device mesh
    front door across a live reshard cut. The headline JSON - aggregate
    admitted tasks/s through the WRR poll, single-device AND mesh -
    prints (and flushes) FIRST, rc=124-proofed like every other
    headline; per-tenant tasks/s and p50/p99 admission-to-complete
    latency go to stderr and perf-logs/<ts>.tenants.json."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.tenants import TenantSpec

    per_tenant = 40 if quick else 150
    weights = {"gold": 4, "silver": 2, "bronze": 1}

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    mk = Megakernel(
        kernels=[("bump", bump)], capacity=3 * per_tenant + 64,
        num_values=8, succ_capacity=8, interpret=False,
    )
    sm = StreamingMegakernel(
        mk, ring_capacity=3 * max(per_tenant, 64),
        tenants=[TenantSpec(t, weight=w) for t, w in weights.items()],
    )
    # The mesh arm runs first (host-model, milliseconds) so its
    # aggregate lands in the rc=124-proofed headline line.
    mesh = _bench_tenants_mesh(weights, per_tenant)
    total = 0
    for tid in weights:
        for i in range(per_tenant):
            assert sm.submit(tid, 0, args=[1])
            total += 1
    sm.close()
    b = TaskGraphBuilder()
    b.add(0, args=[0])
    t0 = time.perf_counter()
    iv, info = sm.run_stream(b)
    wall = time.perf_counter() - t0
    assert int(iv[0]) == total
    rate = total / max(wall, 1e-9)
    headline = {
        "bench": "tenant_ingress",
        "tenants": len(weights),
        "tasks": total,
        "tasks_per_sec": round(rate, 1),
        "wall_s": round(wall, 4),
        "mesh_tasks_per_sec": mesh["tasks_per_sec"],
        "mesh_resize_latency_s": mesh["resize_latency_s"],
    }
    emit(headline)  # headline FIRST, always
    detail = {}
    for tid in weights:
        ten = info["tenants"][tid]
        lat = sm.tenants.latency_stats(tid)
        detail[tid] = {
            "weight": weights[tid],
            "completed": ten["completed"],
            "tasks_per_sec": round(ten["completed"] / max(wall, 1e-9), 1),
            "p50_latency_s": round(lat.get("p50_s", 0.0), 4),
            "p99_latency_s": round(lat.get("p99_s", 0.0), 4),
        }
        log(f"tenant [{tid}] w={weights[tid]}: "
            f"{detail[tid]['completed']} tasks "
            f"({detail[tid]['tasks_per_sec']:,} tasks/s), "
            f"admission-to-complete p50 "
            f"{detail[tid]['p50_latency_s'] * 1e3:.1f} ms / p99 "
            f"{detail[tid]['p99_latency_s'] * 1e3:.1f} ms")
    for tid, row in mesh["per_tenant"].items():
        log(f"mesh tenant [{tid}] w={row['weight']}: "
            f"{row['completed']} tasks across the 4->2 cut "
            f"({row['completed_before_cut']} pre-cut), "
            f"admission-to-complete p50 "
            f"{row['p50_latency_s'] * 1e3:.2f} ms / p99 "
            f"{row['p99_latency_s'] * 1e3:.2f} ms")
    log(f"mesh arm: {mesh['tasks']} tasks at "
        f"{mesh['tasks_per_sec']:,} tasks/s across a 4->2 reshard "
        f"({mesh['resize_latency_s'] * 1e3:.2f} ms cut)")
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.tenants.json")
    with open(path, "w") as f:
        json.dump({**headline, "per_tenant": detail, "mesh": mesh},
                  f, indent=1)
    log(f"tenant ingress bench written: {path}")


def _bench_serve_stream(per_tenant: int) -> dict:
    """The DEVICE arm of the serving bench: 3 lanes through the compiled
    streaming kernel with the completion mailbox ON -
    every request rides submit() -> egress mailbox -> Future.result(),
    so the rate prices the whole request/response loop (admission, WRR
    install, in-kernel retirement publish, host drain, ledger resolve),
    not just ingress. The telemetry plane (ISSUE 19) rides the same
    run: the on-device histogram's p50/p99 (rounds -> seconds via the
    entry epoch bracket) report beside the host-stamped quantiles - the
    agreement the acceptance holds to one log2 bucket."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.egress import EgressSpec
    from hclib_tpu.device.inject import StreamingMegakernel
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.telemetry import TelemetryBlock
    from hclib_tpu.device.tenants import TenantSpec, TenantTable

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    names = ("gold", "silver", "bronze")
    region = max(64, per_tenant)
    table = TenantTable(
        [TenantSpec(t, weight=w) for t, w in
         zip(names, (4, 2, 1))],
        region, egress=EgressSpec(depth=64),
    )
    mk = Megakernel(
        kernels=[("bump", bump)], capacity=3 * per_tenant + 64,
        num_values=8, succ_capacity=8, interpret=False,
    )
    sm = StreamingMegakernel(mk, ring_capacity=3 * region,
                             tenants=table, telemetry=True)
    futs = []
    t0 = time.perf_counter()
    for tid in names:
        for i in range(per_tenant):
            adm = sm.submit(tid, 0, args=[1])
            assert adm, adm
            futs.append(adm.future)
    sm.close()
    b = TaskGraphBuilder()
    b.add(0, args=[0])
    sm.run_stream(b)
    lats = sorted(f.latency_s() for f in futs)
    wall = time.perf_counter() - t0
    assert all(f.state == "RESULT" for f in futs)
    cons = table.futures.conservation()
    assert cons["ok"] and cons["resolved"] == len(futs), cons
    pct = (lambda p: lats[min(len(lats) - 1, int(p * len(lats)))])
    out = {
        "requests": len(futs),
        "req_per_sec": round(len(futs) / max(wall, 1e-9), 1),
        "wall_s": round(wall, 4),
        "p50_latency_s": round(pct(0.50), 6),
        "p99_latency_s": round(pct(0.99), 6),
    }
    snap = sm.telemetry_snapshot()
    if snap is not None:
        blk = TelemetryBlock(snap["tele"], snap.get("ns_per_round"))
        out["hist_requests"] = blk.total()
        out["hist_rounds"] = snap["rounds"]
        for q, key in ((0.50, "hist_p50"), (0.99, "hist_p99")):
            r = blk.quantile(q)
            if r is not None:
                out[f"{key}_rounds"] = r
            s = blk.quantile_s(q)
            if s is not None:
                out[f"{key}_latency_s"] = round(s, 6)
    return out


def bench_serve(quick: bool = False) -> None:
    """Request/response serving loop cost of record (ISSUE 16): a
    3-tenant weighted roster (4:2:1) submitting through the futures
    face of a 4-device mesh front door with per-device completion
    mailboxes (WRR reference model + HostMailbox - the executable spec
    of the in-kernel poll/publish), riding ONE live reshard cut 4 -> 2
    with futures in flight (preempt -> reattach on the shared ledger).
    The headline JSON - aggregate requests/s plus p50/p99
    submit-to-result latency ACROSS the scale event - prints (and
    flushes) FIRST, rc=124-proofed like every other headline; the
    device arm (the compiled stream with the mailbox on) and
    per-tenant lines go to stderr budget-gated.

    perf-logs/<ts>.serve.json schema::

        {"bench": "serve", "device": {...}, "tenants": 3,
         "requests": int,            # total accepted submits
         "req_per_sec": float,       # aggregate, across the cut
         "wall_s": float,
         "p50_latency_s": float,     # submit-to-RESULT, ACROSS the cut
         "p99_latency_s": float,     #   (reattached futures keep their
         "resize_latency_s": float,  #    original submit timestamp)
         "reattached": int,          # futures that rode the cut
         "ndev": "4->2",
         "per_tenant": {tenant: {"weight": int, "requests": int,
                                 "p50_latency_s": float,
                                 "p99_latency_s": float}},
         "conservation": {...},      # FutureTable.conservation()
         "stream": {...} | null}     # device arm (same latency keys)
    """
    from hclib_tpu.device.descriptor import RING_ROW, TEN_TOKEN
    from hclib_tpu.device.egress import EgressSpec, HostMailbox
    from hclib_tpu.device.tenants import (
        MeshTenantTable, TenantSpec, wrr_poll_reference,
    )

    per_tenant = 40 if quick else 200
    weights = {"gold": 4, "silver": 2, "bronze": 1}
    region = -(-per_tenant // (2 * 8)) * 8 + 16
    spec = EgressSpec(depth=32)
    specs = [TenantSpec(t, weight=w, queue_capacity=4 * per_tenant)
             for t, w in weights.items()]
    table = MeshTenantTable(specs, 4, region, egress=spec)
    futures = table.futures
    rings = np.zeros((4, len(specs) * region, RING_ROW), np.int32)
    # Client view: token -> (tenant, submit time, latest Future). The
    # submit stamp is OURS so a reattached future's latency still spans
    # the cut (the ledger restamps t_submit at reattach).
    client = {}

    def drive(tbl, rg, polls, start):
        boxes = [HostMailbox(spec, park_cap=len(specs) * region)
                 for _ in range(tbl.ndev)]
        tctl = tbl.pump(rg)
        for r in range(start, start + polls):
            for d in range(tbl.ndev):
                rows = wrr_poll_reference(
                    rg[d], tctl[d], region, r, 1 << 20
                )
                boxes[d].publish([
                    (int(row[TEN_TOKEN]), 0, 0, 0, 1) for row in rows
                ])
        tbl.absorb(tctl)
        for box in boxes:
            box.drain(futures=futures)

    def submit_half(tbl):
        n = 0
        for tid in weights:
            for _ in range(per_tenant // 2):
                adm = tbl.submit(tid, 0, args=[1])
                assert adm, adm
                client[adm.future.token] = (
                    tid, time.monotonic(), adm.future
                )
                n += 1
        return n

    t0 = time.perf_counter()
    total = submit_half(table)
    rnd = 0
    drive(table, rings, 4, rnd)
    rnd += 4
    # THE scale event: export preempts in-flight futures; the resized
    # mesh shares the SAME ledger, so every resume token reattaches.
    t_cut = time.perf_counter()
    state = table.export_state(rings)
    preempted = [(tok, f.resume_token)
                 for tok, (_, _, f) in client.items()
                 if f.state == "PREEMPTED"]
    table = table.resized(2)
    table.resume_from(state)
    for tok, rt in preempted:
        tid, ts, _ = client[tok]
        client[tok] = (tid, ts, table.reattach(rt))
    resize_s = time.perf_counter() - t_cut
    rings = np.zeros((2, len(specs) * region, RING_ROW), np.int32)
    total += submit_half(table)
    for r in range(1024):
        drive(table, rings, 2, rnd)
        rnd += 2
        if table.drained():
            break
    wall = time.perf_counter() - t0
    assert table.drained(), "serve bench wedged"
    cons = futures.conservation()
    assert cons["ok"] and cons["resolved"] == total, cons
    by_tenant = {t: [] for t in weights}
    for tok, (tid, ts, f) in client.items():
        assert f.state == "RESULT", (tok, f.state)
        by_tenant[tid].append(f.t_done - ts)
    lats = sorted(x for xs in by_tenant.values() for x in xs)
    pct = (lambda p, xs: xs[min(len(xs) - 1, int(p * len(xs)))])
    headline = {
        "bench": "serve",
        "tenants": len(weights),
        "requests": total,
        "req_per_sec": round(total / max(wall, 1e-9), 1),
        "wall_s": round(wall, 4),
        "p50_latency_s": round(pct(0.50, lats), 6),
        "p99_latency_s": round(pct(0.99, lats), 6),
        "resize_latency_s": round(resize_s, 6),
        "reattached": len(preempted),
        "ndev": "4->2",
    }
    emit(headline)  # headline FIRST, always
    detail = {}
    for tid, xs in by_tenant.items():
        xs.sort()
        detail[tid] = {
            "weight": weights[tid],
            "requests": len(xs),
            "p50_latency_s": round(pct(0.50, xs), 6),
            "p99_latency_s": round(pct(0.99, xs), 6),
        }
        log(f"serve tenant [{tid}] w={weights[tid]}: {len(xs)} "
            f"requests across the 4->2 cut, submit-to-result p50 "
            f"{detail[tid]['p50_latency_s'] * 1e3:.2f} ms / p99 "
            f"{detail[tid]['p99_latency_s'] * 1e3:.2f} ms")
    log(f"serve mesh arm: {total} requests at "
        f"{headline['req_per_sec']:,} req/s across a 4->2 reshard "
        f"({resize_s * 1e3:.2f} ms cut, {len(preempted)} futures "
        f"reattached)")
    stream = section(
        "serve device arm", 120,
        lambda: _bench_serve_stream(20 if quick else 50),
    )
    if stream:
        log(f"serve device arm (compiled stream, mailbox on): "
            f"{stream['requests']} requests at "
            f"{stream['req_per_sec']:,} req/s, submit-to-result p50 "
            f"{stream['p50_latency_s'] * 1e3:.1f} ms / p99 "
            f"{stream['p99_latency_s'] * 1e3:.1f} ms")
        if "hist_p99_latency_s" in stream:
            log(f"serve device histograms (on-device, "
                f"{stream['hist_rounds']} rounds): p50 "
                f"{stream['hist_p50_latency_s'] * 1e3:.1f} ms / p99 "
                f"{stream['hist_p99_latency_s'] * 1e3:.1f} ms from "
                f"{stream['hist_requests']} tracked retirements")
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.serve.json")
    with open(path, "w") as f:
        json.dump({**headline, "per_tenant": detail,
                   "conservation": cons, "stream": stream},
                  f, indent=1)
    log(f"serve bench written: {path}")


def bench_forasync(quick: bool = False) -> None:
    """forasync device tier cost of record (ISSUE 9): the 2D Jacobi-style
    stencil and the map-style batched-apply loop through the tile tier
    (batch lanes + double-buffered operand prefetch). The headline JSON -
    combined tiles/s across both loops - prints (and flushes) FIRST,
    rc=124-proofed like every other headline; per-tile-size occupancy /
    prefetch lines go to stderr budget-gated, and the full detail lands
    in perf-logs/<ts>.forasync.json."""
    import numpy as np

    from hclib_tpu.device.forasync_tier import run_forasync_device
    from hclib_tpu.device.workloads import (
        map_data, map_loop, map_reference, stencil_data, stencil_loop,
        stencil_reference,
    )

    H, W = (16, 512) if quick else (64, 1024)
    T = 16 if quick else 64
    tk_s, bounds_s, tile_s = stencil_loop(H, W)
    gin, gout = stencil_data(H, W)
    ref_s = stencil_reference(gin)
    tk_m, bounds_m, tile_m = map_loop(T)
    vin, vout = map_data(T)
    ref_m = map_reference(vin)

    def arm(tk, bounds, tile, data, ref, out_name, width):
        from hclib_tpu.device.forasync_tier import make_forasync_megakernel

        # One megakernel reused across warm + timed runs: the timed arm
        # measures the steady-state tile rate, not the XLA compile.
        mk = make_forasync_megakernel(tk, width=width, interpret=False)
        d, info = run_forasync_device(
            tk, bounds, tile, dict(data), width=width, mk=mk
        )  # warm the jit
        t0 = time.perf_counter()
        d, info = run_forasync_device(
            tk, bounds, tile, dict(data), width=width, mk=mk
        )
        wall = time.perf_counter() - t0
        assert np.array_equal(np.asarray(d[out_name]), ref), "wrong result"
        return info, wall

    info_s, wall_s = arm(
        tk_s, bounds_s, tile_s, {"gin": gin, "gout": gout}, ref_s,
        "gout", 8,
    )
    info_m, wall_m = arm(
        tk_m, bounds_m, tile_m, {"vin": vin, "vout": vout}, ref_m,
        "vout", 8,
    )
    tiles_s = info_s["executed"]
    tiles_m = info_m["executed"]
    rate_s = tiles_s / max(wall_s, 1e-9)
    rate_m = tiles_m / max(wall_m, 1e-9)
    headline = {
        "bench": "forasync_tile_tier",
        "tasks": tiles_s + tiles_m,
        "tasks_per_sec": round(
            (tiles_s + tiles_m) / max(wall_s + wall_m, 1e-9), 1
        ),
        "stencil_tasks_per_sec": round(rate_s, 1),
        "map_tasks_per_sec": round(rate_m, 1),
        "stencil_occupancy": round(
            info_s["tiers"]["batch_occupancy"], 3
        ),
        "map_occupancy": round(info_m["tiers"]["batch_occupancy"], 3),
    }
    emit(headline)  # headline FIRST, always
    log(f"forasync stencil: {tiles_s} tiles ({H}x{W}/8x128) at "
        f"{rate_s:,.0f} tiles/s, occupancy "
        f"{info_s['tiers']['batch_occupancy']:.2f}, "
        f"{info_s['tiers']['prefetch_hits']} prefetch hits")
    log(f"forasync map: {tiles_m} tiles at {rate_m:,.0f} tiles/s, "
        f"occupancy {info_m['tiers']['batch_occupancy']:.2f}, "
        f"{info_m['tiers']['prefetch_hits']} prefetch hits")

    # Per-tile-size sweep (stderr, budget-gated): occupancy + prefetch
    # behavior as the batch width changes - the knob a workload tunes.
    detail = {"widths": {}}

    def sweep():
        for width in (2, 4, 8):
            d, info = run_forasync_device(
                tk_m, bounds_m, tile_m, {"vin": vin, "vout": vout.copy()},
                width=width,
            )
            t = info["tiers"]
            detail["widths"][width] = {
                "occupancy": round(t["batch_occupancy"], 3),
                "batch_rounds": t["batch_rounds"],
                "prefetch_hits": t["prefetch_hits"],
            }
            log(f"forasync width={width}: occupancy "
                f"{t['batch_occupancy']:.2f}, {t['batch_rounds']} rounds, "
                f"{t['prefetch_hits']} prefetch hits")

    section("forasync width sweep", 60, sweep)
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.forasync.json")
    with open(path, "w") as f:
        json.dump({**headline, **detail}, f, indent=1)
    log(f"forasync bench written: {path}")


def bench_graph(quick: bool = False) -> None:
    """Graph-analytics frontier tier cost of record (ISSUE 10): BFS,
    delta-stepping-style SSSP, and push PageRank over a seeded
    R-MAT-style graph through the batch-lane frontier tier (edge-slab
    prefetch + the age-triggered firing policy). The headline JSON -
    combined traversed-edges/s (TEPS) - prints (and flushes) FIRST,
    rc=124-proofed like every other headline; per-kernel TEPS /
    occupancy / lane_partial_age lines go to stderr budget-gated, and
    the full detail lands in perf-logs/<ts>.graph.json.

    perf-logs schema (<ts>.graph.json): the headline fields (metric/
    value/unit, per-kernel ``*_teps``, ``sssp_delta_teps`` +
    ``sssp_delta_expand_ratio`` - the ISSUE 15 ordered-work dividend,
    executed EXPANDs of the bucketed arm over the unordered arm's)
    merged with ``kernels.<kind>`` rows: edges / relaxations / tasks /
    elapsed_s / occupancy / age_fires / max_starved_age /
    bucket_fires / bucket_inversions (the last two zero on unbucketed
    arms), plus ``traced_bfs`` gauges."""
    import numpy as np

    from hclib_tpu.device.frontier import (
        Graph, host_bfs, host_pagerank_push, host_sssp,
        make_frontier_megakernel, run_frontier, _KINDS,
    )
    from hclib_tpu.device.workloads import rmat_edges

    scale = 6 if quick else 9
    n, src, dst, w = rmat_edges(scale, efactor=8, seed=7)
    g = Graph(n, src, dst, w)
    width = 8
    # PageRank mass/threshold sized so the push's FIFO-lane breadth (the
    # live descriptor set is the mass frontier, not a DFS spine) fits
    # the table, which must itself fit SMEM (Megakernel.check_smem
    # admits ~950 rows beside this graph's vertex table).
    m0, reps = 1 << 9, 64  # 426 live rows at scale 9; 1 << 10 overflows 896
    capacity = 768

    def arm(kind):
        fk = _KINDS[kind](reps=reps) if kind == "pagerank" else _KINDS[kind]()
        mk = make_frontier_megakernel(
            fk, g, width=width, capacity=capacity, interpret=False,
        )
        kw = dict(m0=m0, reps=reps, capacity=capacity, interpret=False, mk=mk)
        res, info = run_frontier(kind, g, 0, **kw)  # warm the jit
        t0 = time.perf_counter()
        res, info = run_frontier(kind, g, 0, **kw)
        wall = time.perf_counter() - t0
        ref = {
            "bfs": lambda: host_bfs(g, 0),
            "sssp": lambda: host_sssp(g, 0),
            "pagerank": lambda: host_pagerank_push(g, m0=m0, reps=reps)[0],
        }[kind]()
        assert np.array_equal(np.asarray(res, np.int64), ref), (
            f"{kind}: device result diverged from the host reference"
        )
        return info, wall

    arms = {}
    edges_total = 0.0
    wall_total = 0.0
    for kind in ("bfs", "sssp", "pagerank"):
        info, wall = arm(kind)
        arms[kind] = (info, wall)
        edges_total += info["edges"]
        wall_total += wall

    # Delta-stepping arm (ISSUE 15): the SAME seeded SSSP through the
    # priority-bucket tier - the headline addition is the executed-
    # EXPAND ratio vs the unordered arm just measured (ordered
    # retirement = asymptotically less work; distances asserted
    # bit-identical) plus its own TEPS.
    def delta_arm():
        fk = _KINDS["sssp"]()
        mk = make_frontier_megakernel(
            fk, g, width=width, capacity=capacity, interpret=False,
            priority_buckets=8,
        )
        kw = dict(capacity=capacity, interpret=False, mk=mk)
        run_frontier("sssp", g, 0, **kw)  # warm the jit
        t0 = time.perf_counter()
        res, info = run_frontier("sssp", g, 0, **kw)
        wall = time.perf_counter() - t0
        assert np.array_equal(np.asarray(res), host_sssp(g, 0)), (
            "sssp-delta: bucketed distances diverged from Dijkstra"
        )
        return info, wall

    dinfo, dwall = delta_arm()
    expand_ratio = dinfo["executed"] / max(arms["sssp"][0]["executed"], 1)
    headline = {
        "metric": f"graph frontier traversal throughput (BFS+SSSP+"
        f"PageRank, R-MAT scale {scale}, {g.m} edges, batched "
        f"frontier width {width})",
        "value": round(edges_total / max(wall_total, 1e-9)),
        "unit": "TEPS",
        "bfs_teps": round(arms["bfs"][0]["edges"] / max(arms["bfs"][1], 1e-9)),
        "sssp_teps": round(
            arms["sssp"][0]["edges"] / max(arms["sssp"][1], 1e-9)
        ),
        "pagerank_teps": round(
            arms["pagerank"][0]["edges"] / max(arms["pagerank"][1], 1e-9)
        ),
        # Priority tier (delta-stepping SSSP, priority_buckets=8):
        # the work-count dividend is the schedule-proof number
        # (the EXPAND ratio is an exact count).
        "sssp_delta_teps": round(dinfo["edges"] / max(dwall, 1e-9)),
        "sssp_delta_expand_ratio": round(expand_ratio, 4),
    }
    emit(headline)  # headline FIRST, always
    detail = {"kernels": {}}
    arms["sssp_delta"] = (dinfo, dwall)
    for kind, (info, wall) in arms.items():
        t = info.get("tiers", {})
        detail["kernels"][kind] = {
            "edges": info["edges"],
            "relaxations": info["relaxations"],
            "tasks": info["executed"],
            "elapsed_s": wall,
            "occupancy": round(t.get("batch_occupancy", 0.0), 3),
            "age_fires": t.get("age_fires", 0),
            "max_starved_age": t.get("max_starved_age", 0),
            # Priority-tier counters (zeros on unbucketed arms).
            "bucket_fires": t.get("bucket_fires", 0),
            "bucket_inversions": t.get("bucket_inversions", 0),
        }
        log(f"graph {kind}: {info['edges']} edges in {wall:.3f}s "
            f"({info['edges'] / max(wall, 1e-9):,.0f} TEPS), occupancy "
            f"{t.get('batch_occupancy', 0.0):.2f}, {t.get('age_fires', 0)} "
            f"age fires (max starved age {t.get('max_starved_age', 0)})")
    log(f"graph sssp-delta: {dinfo['executed']} EXPANDs vs "
        f"{arms['sssp'][0]['executed']} unordered "
        f"({expand_ratio:.2f}x), {dinfo['edges']} edges in {dwall:.3f}s")

    # Traced BFS round (stderr, budget-gated): the lane_partial_age
    # gauge - bounded by the age-triggered firing policy - plus per-lane
    # occupancy off the flight recorder.
    def traced():
        _, info = run_frontier(
            "bfs", g, 0, width=width, capacity=capacity, interpret=False,
            trace=4096,
        )
        t = info["tiers"]
        detail["traced_bfs"] = {
            "lane_partial_age": t.get("lane_partial_age", 0),
            "age_fires": t.get("age_fires", 0),
            "max_starved_age": t.get("max_starved_age", 0),
            "occupancy": round(t.get("batch_occupancy", 0.0), 3),
        }
        log(f"graph traced bfs: lane_partial_age "
            f"{t.get('lane_partial_age', 0)}, max starved age "
            f"{t.get('max_starved_age', 0)} (bounded by the "
            "age-triggered firing policy)")

    section("graph traced round", 90, traced)
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.graph.json")
    with open(path, "w") as f:
        json.dump({**headline, **detail}, f, indent=1)
    log(f"graph bench written: {path}")


def bench_dyngraph(quick: bool = False) -> None:
    """Dynamic-graph service cost of record (ISSUE 20): a concurrent
    UPDATE storm + QUERY stream against the mutable blocked-CSR
    adjacency, raced with the BFS/SSSP traversals on the batched
    frontier tier - the incremental fixpoint asserted bit-identical to
    the from-scratch host reference ON THE MUTATED GRAPH. The headline
    JSON - updates applied per second, with the concurrent traversal's
    query TEPS riding along - prints (and flushes) FIRST, rc=124-proofed
    like every other headline; per-kind splice/query lines go to stderr
    budget-gated and the full detail lands in
    perf-logs/<ts>.dyngraph.json.

    perf-logs schema (<ts>.dyngraph.json): the headline fields (metric/
    value/unit, ``updates_per_sec`` / ``query_teps`` /
    ``queries_per_sec``) merged with ``kernels.<kind>`` rows: edges /
    relaxations / tasks / updates_applied / dropped / spare_in_use /
    queries / elapsed_s."""
    import numpy as np

    from hclib_tpu.device.dyngraph import (
        DynGraph, host_dyngraph, make_dyngraph_megakernel, run_dyngraph,
    )
    from hclib_tpu.device.workloads import rmat_edges

    scale = 5 if quick else 7
    n, src_e, dst_e, w_e = rmat_edges(scale, efactor=8, seed=7)
    width = 8
    capacity = 512 if quick else 768
    rng = np.random.default_rng(11)
    n_ups = 8 if quick else 24
    ups = [
        (int(u), int(v), int(w))
        for u, v, w in zip(
            rng.integers(0, n, n_ups),
            rng.integers(0, n, n_ups),
            rng.integers(1, 8, n_ups),
        )
    ]
    queries = [int(q) for q in rng.integers(0, n, 4)]

    def arm(kind):
        # Fresh graph per arm: the update stream registers on it and
        # the spare rows mutate in-run.
        g = DynGraph(
            n, src_e, dst_e, w_e, spare_blocks=2,
            upd_cap=max(16, n_ups),
        )
        mk = make_dyngraph_megakernel(
            kind, g, width=width, capacity=capacity, interpret=False,
        )
        kw = dict(
            updates=ups, queries=queries, capacity=capacity,
            interpret=False, mk=mk,
        )
        run_dyngraph(kind, g, 0, **kw)  # warm the jit (mutates nothing
        g = DynGraph(                   # host-side; rebuild regardless)
            n, src_e, dst_e, w_e, spare_blocks=2,
            upd_cap=max(16, n_ups),
        )
        t0 = time.perf_counter()
        res, info = run_dyngraph(kind, g, 0, **dict(kw, mk=mk))
        wall = time.perf_counter() - t0
        assert np.array_equal(
            np.asarray(res, np.int64),
            np.asarray(host_dyngraph(kind, g), np.int64),
        ), f"{kind}: incremental fixpoint diverged from the mutated-graph"
        return info, wall

    arms = {}
    ups_total = edges_total = wall_total = 0.0
    q_total = 0
    for kind in ("bfs", "sssp"):
        info, wall = arm(kind)
        arms[kind] = (info, wall)
        ups_total += info["updates_applied"]
        edges_total += info["edges"]
        q_total += info["queries"]
        wall_total += wall

    headline = {
        "metric": f"dynamic-graph update+query service throughput "
        f"(BFS+SSSP, R-MAT scale {scale}, {len(src_e)} static edges, "
        f"{n_ups} updates, {len(queries)} queries, batched width "
        f"{width})",
        "value": round(ups_total / max(wall_total, 1e-9)),
        "unit": "updates/sec",
        "updates_per_sec": round(ups_total / max(wall_total, 1e-9)),
        "query_teps": round(edges_total / max(wall_total, 1e-9)),
        "queries_per_sec": round(q_total / max(wall_total, 1e-9)),
    }
    emit(headline)  # headline FIRST, always
    detail = {"kernels": {}}
    for kind, (info, wall) in arms.items():
        detail["kernels"][kind] = {
            "edges": info["edges"],
            "relaxations": info["relaxations"],
            "tasks": info["executed"],
            "updates_applied": info["updates_applied"],
            "dropped": info["dropped"],
            "spare_in_use": info["spare_in_use"],
            "queries": info["queries"],
            "elapsed_s": wall,
        }
        log(f"dyngraph {kind}: {info['updates_applied']} splices "
            f"({info['dropped']} dropped, {info['spare_in_use']} spare "
            f"blocks), {info['queries']} queries, {info['edges']} edges "
            f"in {wall:.3f}s, bit-identical to the mutated-graph "
            "reference")

    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.dyngraph.json")
    with open(path, "w") as f:
        json.dump({**headline, **detail}, f, indent=1)
    log(f"dyngraph bench written: {path}")


def bench_bnb(quick: bool = False) -> None:
    """Branch-and-bound cost of record (ISSUE 15): best-first 0/1
    knapsack on the priority-bucket tier vs the unordered batched arm,
    same seeded instance, optimum asserted equal to the independent
    host DP in both. The headline JSON - best-first expanded nodes/s
    plus the expanded-node ratio (priority IS the speedup here) -
    prints (and flushes) FIRST, rc=124-proofed like every other
    headline; per-arm node/prune lines go to stderr budget-gated and
    the full detail lands in perf-logs/<ts>.bnb.json.

    perf-logs schema (<ts>.bnb.json): the headline fields (metric/
    value/unit, ``expand_ratio`` = best-first executed nodes over
    unordered, ``optimum``) merged with ``arms.<name>`` rows:
    executed / pruned / leaves / elapsed_s / occupancy /
    bucket_fires / bucket_inversions."""
    from hclib_tpu.device.bnb import (
        host_knapsack_opt, make_bnb_megakernel, make_knapsack, run_bnb,
    )

    n_items = 12 if quick else 16
    kp = make_knapsack(n_items, seed=5)
    opt = host_knapsack_opt(kp)
    width = 4
    arms = {}
    for name, buckets in (("unordered", 0), ("best_first", 8)):
        mk = make_bnb_megakernel(
            kp, width=width, priority_buckets=buckets, interpret=False,
        )
        run_bnb(kp, mk=mk, interpret=False)  # warm the jit
        t0 = time.perf_counter()
        best, info = run_bnb(kp, mk=mk, interpret=False)
        wall = time.perf_counter() - t0
        assert best == opt, (
            f"bnb {name}: incumbent {best} != DP optimum {opt}"
        )
        arms[name] = (info, wall)
    bi, bw = arms["best_first"]
    ui, _uw = arms["unordered"]
    ratio = bi["executed"] / max(ui["executed"], 1)
    headline = {
        "metric": f"branch-and-bound best-first search ({n_items}-item "
        f"knapsack, priority buckets over the batch lanes)",
        "value": round(bi["executed"] / max(bw, 1e-9)),
        "unit": "nodes/sec",
        "optimum": opt,
        "expand_ratio": round(ratio, 4),
        "pruned_best_first": bi["pruned"],
        "pruned_unordered": ui["pruned"],
    }
    emit(headline)  # headline FIRST, always
    detail = {"arms": {}}
    for name, (info, wall) in arms.items():
        t = info.get("tiers", {})
        detail["arms"][name] = {
            "executed": info["executed"],
            "pruned": info["pruned"],
            "leaves": info["leaves"],
            "elapsed_s": wall,
            "occupancy": round(t.get("batch_occupancy", 0.0), 3),
            "bucket_fires": t.get("bucket_fires", 0),
            "bucket_inversions": t.get("bucket_inversions", 0),
        }
        log(f"bnb {name}: {info['executed']} nodes ({info['pruned']} "
            f"pruned, {info['leaves']} leaves) in {wall:.3f}s")
    log(f"bnb best-first expanded {ratio:.2f}x the unordered node "
        f"count (optimum {opt} proven by both)")
    logdir = os.path.join(os.path.dirname(__file__), "perf-logs")
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, f"{int(time.time())}.bnb.json")
    with open(path, "w") as f:
        json.dump({**headline, **detail}, f, indent=1)
    log(f"bnb bench written: {path}")


def bench_multichip(quick: bool = False) -> None:
    """Forest-steal through the sharded steal runner on a mesh over every
    REAL chip of this host (the four-chip host; one chip is refused),
    BATCHED arm first (ISSUE 7): the batched tasks/s headline JSON prints
    (and flushes) before anything else can eat the budget, then
    per-device occupancy/prefetch lines and the scalar-mesh comparison go
    to stderr, budget-gated."""
    import jax

    from hclib_tpu.device import stress

    ndev = len(jax.devices())
    if ndev < 2:
        raise RuntimeError("--multichip needs the four-chip host")
    kw = dict(stress.FOREST_STEAL_QUICK if quick
              else stress.FOREST_STEAL_BENCH)
    # The shared config is sized for the 8-virtual-device interpreter
    # guard; on chips the table must fit SMEM.
    kw.update(ndev=ndev, capacity=640, mesh=_mesh(ndev), interpret=False)
    binfo = stress.forest_steal(batch_width=8, **kw)
    emit({
        "metric": f"forest-steal mesh throughput (batched dispatch, "
        f"{ndev} devices, {kw['roots']}x fib({kw['n']}))",
        "value": round(binfo["tasks_per_sec"]),
        "unit": "tasks/sec",
        "tasks": binfo["tasks"],
        "mean_occupancy": round(binfo["mean_occupancy"], 3),
        "devices_used": binfo["devices_used"],
    })
    for d, t in enumerate(binfo["tiers"]):
        log(
            f"device {d}: occupancy {t['batch_occupancy']:.2f} "
            f"({t['batch_rounds']} batch rounds, {t['batch_tasks']} "
            f"batched + {t['scalar_tasks']} scalar tasks, "
            f"{t['prefetch_hits']} prefetch hits, {t['spilled']} lane "
            f"spills)"
        )
    out = {"batched": {k: v for k, v in binfo.items() if k != "trace"}}
    sinfo = section(
        "scalar-mesh baseline", 180,
        lambda: stress.forest_steal(**kw),
    )
    if sinfo:
        mult = binfo["tasks_per_sec"] / sinfo["tasks_per_sec"]
        log(
            f"mesh batch dispatch vs scalar mesh: {mult:.2f}x "
            f"({binfo['tasks_per_sec']:,.0f} vs "
            f"{sinfo['tasks_per_sec']:,.0f} tasks/s)"
        )
        out["scalar"] = dict(sinfo)
        out["batch_vs_scalar"] = mult
    os.makedirs("perf-logs", exist_ok=True)
    path = os.path.join("perf-logs", f"{int(time.time())}.multichip.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=float)
    log(f"multichip log written: {path}")


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="hclib_tpu benchmark driver")
    ap.add_argument(
        "--trace", action="store_true",
        help="also emit per-section metrics JSON + a Perfetto trace "
        "under perf-logs/ (budget-gated like the other sections)",
    )
    ap.add_argument(
        "--checkpoint", action="store_true",
        help="also measure checkpoint/restore cost (quiesce latency + "
        "bundle size for UTS and Cholesky) plus the durable-store arms "
        "(save-publish latency fsync'd/fast, cold load_latest clean and "
        "healing past 2 quarantined generations) into perf-logs/ "
        "(budget-gated like the other sections)",
    )
    ap.add_argument(
        "--autoscale", action="store_true",
        help="also measure elastic-autoscaling cost (resize latency + "
        "tasks/s through a scale event) into perf-logs/ "
        "(budget-gated like the other sections)",
    )
    ap.add_argument(
        "--tenants", action="store_true",
        help="multi-tenant ingress mode: 3 weighted lanes through the "
        "streaming front door; the aggregate tasks/s headline prints "
        "FIRST (stdout JSON), per-tenant rates + p50/p99 admission-to-"
        "complete latency to stderr and perf-logs/<ts>.tenants.json; "
        "replaces the single-device suite for this run",
    )
    ap.add_argument(
        "--serve", action="store_true",
        help="request/response serving mode: 3 weighted tenants "
        "submitting through the futures face of a 4-device mesh front "
        "door with completion mailboxes, across ONE live 4->2 reshard "
        "with futures reattached; the req/s + p50/p99 submit-to-result "
        "latency headline prints FIRST (stdout JSON), the device arm "
        "and per-tenant lines to stderr and perf-logs/<ts>.serve.json; "
        "replaces the single-device suite for this run",
    )
    ap.add_argument(
        "--forasync", action="store_true",
        help="forasync device-tier mode: stencil + map-loop tiles/s "
        "through the batch-lane tile tier; the combined tasks/s headline "
        "prints FIRST (stdout JSON), per-tile-size occupancy/prefetch "
        "lines to stderr and perf-logs/<ts>.forasync.json; replaces the "
        "single-device suite for this run",
    )
    ap.add_argument(
        "--graph", nargs="?", const="static", default=None,
        metavar="ARM",
        help="graph-analytics mode: BFS/SSSP/PageRank traversed-edges/s "
        "(TEPS) through the batched frontier tier on a seeded R-MAT "
        "graph; the combined TEPS headline prints FIRST (stdout JSON), "
        "per-kernel TEPS/occupancy/lane_partial_age to stderr and "
        "perf-logs/<ts>.graph.json; replaces the single-device suite "
        "for this run. '--graph dyngraph' runs the dynamic-graph arm "
        "instead: a concurrent update storm + queries against the "
        "mutable adjacency, updates/s + query TEPS headline, detail to "
        "perf-logs/<ts>.dyngraph.json",
    )
    ap.add_argument(
        "--bnb", action="store_true",
        help="branch-and-bound mode: best-first knapsack search on the "
        "priority-bucket tier; the expanded-nodes/s headline (plus the "
        "expanded-node ratio vs the unordered arm) prints FIRST "
        "(stdout JSON), per-arm node/prune lines to stderr and "
        "perf-logs/<ts>.bnb.json; replaces the single-device suite for "
        "this run",
    )
    ap.add_argument(
        "--multichip", action="store_true",
        help="mesh mode (every chip of the four-chip host): the "
        "batched forest-steal tasks/s "
        "headline prints FIRST (stdout JSON), then per-device "
        "occupancy/prefetch lines and the scalar-mesh comparison "
        "(stderr); replaces the single-device suite for this run",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="tiny inputs (CI smoke; affects --multichip and --tenants)",
    )
    args = ap.parse_args(argv)
    from hclib_tpu.runtime.env import use_compile_cache

    use_compile_cache()
    from hclib_tpu.device.megakernel import require_tpu

    global _T0, DEVICE
    DEVICE = require_tpu()  # every mode runs on the chip or not at all
    log(f"device: {DEVICE}")
    _T0 = time.monotonic()  # arm the wall budget for THIS driver run
    if args.tenants:
        bench_tenants(quick=args.quick)
        return
    if args.serve:
        bench_serve(quick=args.quick)
        return
    if args.forasync:
        bench_forasync(quick=args.quick)
        return
    if args.graph == "dyngraph":
        bench_dyngraph(quick=args.quick)
        return
    if args.graph:
        bench_graph(quick=args.quick)
        return
    if args.bnb:
        bench_bnb(quick=args.quick)
        return
    if args.multichip:
        bench_multichip(quick=args.quick)
        return
    # ---- headline FIRST: the stdout JSON line exists (and is flushed)
    # before any secondary section can eat the budget. A failure here, or
    # in any section below, raises: the run exits non-zero.
    native_uts_rate = bench_native_uts()
    device_uts_rate, tree, uts_stat = bench_device_uts()
    emit({
        "metric": f"UTS {tree} tree-search throughput (fused Pallas "
        f"vectorized DFS, 1 TPU core)",
        "value": round(device_uts_rate),
        "unit": "nodes/sec",
        "vs_baseline": round(device_uts_rate / native_uts_rate, 2),
        "statistic": uts_stat,
    })

    # ---- secondaries (stderr only), budget-gated, priority order: the
    # dispatch-tier numbers under acceptance tracking come first.
    sw_wave = section("sw wave-DAG", 90, bench_device_sw_wave)
    chol8k = section("cholesky n=8192", 150, bench_device_cholesky)
    host_rate = section("host fib", 30, bench_host_fib)
    native_fib_rate = section("native fib", 45, bench_native_fib)
    device_fib_rate = section(
        "device fib scalar tier", 60, bench_device_fib
    )
    if host_rate and device_fib_rate:
        line = (
            f"fib megakernel (scalar tier) vs python host: "
            f"{device_fib_rate / host_rate:.1f}x"
        )
        if native_fib_rate:
            line += (
                f"; vs native C++: {device_fib_rate / native_fib_rate:.2f}x"
            )
        log(line)
    vfib_rate = section("device fib batch tier", 90, bench_device_vfib)
    if host_rate and vfib_rate:
        line = (
            f"fib megakernel (batch-dispatch tier) vs python host: "
            f"{vfib_rate / host_rate:.0f}x"
        )
        if native_fib_rate:
            line += f"; vs native C++: {vfib_rate / native_fib_rate:.1f}x"
        log(line)
    section("sw pallas (fused ceiling)", 90, bench_device_sw)
    # The peak-utilization size (POTRF/TRSM amortized over 8x the GEMM
    # work); its residual bound reflects f32 accumulation over twice the
    # update steps - reported, not hidden.
    section(
        "cholesky n=16384", 200,
        lambda: bench_device_cholesky(trials=3, n=16384, residual_bound=2e-6),
    )
    if args.trace:
        section("trace artifacts", 60, emit_trace_artifacts)
    if args.checkpoint:
        section("checkpoint/restore", 120, bench_checkpoint)
    if args.autoscale:
        section("elastic autoscale", 120, bench_autoscale)
    if sw_wave:
        log(f"wave-DAG SW final: {sw_wave:.1f} GCUPS median")
    if chol8k is not None:
        log(f"cholesky n=8192 final: {_chol_ceiling_pct(chol8k):.0f}% "
            f"of the 3-pass ceiling")


if __name__ == "__main__":
    main()
