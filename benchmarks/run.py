#!/usr/bin/env python3
"""One run of one benchmark cell, in one process:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, inputs from the seed, kernels, one warm operation that
compiles) is timed as ``setup_s``; then the cell's operation is repeated
back to back until ``--seconds`` have passed; then, outside the window,
what those operations produced is compared with the plain reference. The
last line of stdout is the result, one JSON object. ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` traces a few operations with
the JAX profiler and reports its per-layer metrics, the device's busy time
and a breakdown. Off a TPU, on a device kind peaks.json does not list, or
with fewer chips than the cell asks for, it exits non-zero with no result.
"""

import time

T_START = time.monotonic()  # process start, as near as Python reads it

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MIN_OPERATIONS = 3
GAP_NS = 50e3  # idle gaps shorter than this are not attributed to a span


def load_json(*rel: str) -> dict:
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def reports(metric: dict, cell: str, bench: dict) -> bool:
    """Whether ``cell`` reports ``metric``: it is listed under the
    metric's ``workloads``, or the metric has no such key and the cell
    reports the end-to-end metric it moves (every cell, for an end-to-end
    metric without the key)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" not in metric:
        return True
    return reports(find(bench["end_to_end"], metric["moves"], "metric"),
                   cell, bench)


def use_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if that is
    set, else at ``<checkout>/.jax_cache``: a fixed path, because the path
    is part of the key. Everything is cached, however quick to compile."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def device_record(chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; raises unless it is a TPU of a kind
    peaks.json lists, with at least the cell's chips."""
    import jax

    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rec["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {rec}")
    if rec["kind"] not in peaks:
        raise RuntimeError(f"device kind {rec['kind']!r} has no row in "
                           "benchmarks/peaks.json")
    if rec["count"] < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX has {rec}")
    return rec


class Compilations:
    """Counts JAX's own monitoring events from ``reset()`` on: requests to
    the compile cache, its hits and misses, and backend compilations."""

    def __init__(self):
        from jax import monitoring

        self.n = collections.Counter()
        monitoring.register_event_listener(
            lambda name, **kw: self.n.update([name.rsplit("/", 1)[-1]])
        )
        monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.n.update(
                [name.rsplit("/", 1)[-1]]
            )
        )

    def reset(self):
        self.n.clear()

    def counts(self) -> dict:
        return {k: self.n[k] for k in (
            "compile_requests_use_cache", "cache_hits", "cache_misses",
            "backend_compile_duration",
        )}


def memory_peak_bytes(chips: int) -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:chips]
    )


def breakdown(tr: dict, chips: int, t0: float, t1: float):
    """The device operations that took most time, and the idle time of the
    traced window by the benchmark span that covers it."""
    from benchmarks import trace

    ops_s = collections.Counter()
    gaps_s = collections.Counter()
    for chip in range(chips):
        evs = tr["device"].get(chip, [])
        for name, s, e in evs:
            if t0 <= s < t1:  # the trace names an op by its whole HLO line
                ops_s[name.split(" = ")[0][:80]] += (e - s) / 1e9 / chips
        idle = trace.idle_by_span(evs, tr["host"], t0, t1, GAP_NS)
        for name, ns in idle.items():
            gaps_s[name] += ns / 1e9 / chips
    return {"device_ops": [list(x) for x in ops_s.most_common(10)],
            "idle_gaps": [list(x) for x in gaps_s.most_common(10)]}


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             traced: bool, dev: dict, interpret: bool = False,
             cfg_over: dict = None, mix_over: dict = None) -> dict:
    """Everything after the look for a chip. ``interpret`` and the two
    overrides are for benchmarks/tests only (a tiny size on the CPU);
    the command line cannot set them."""
    import jax

    from benchmarks import reduce, trace, traffic

    cell = find(bench["workloads"], workload, "workload")
    centry = find(bench["configs"], cell["config"], "configuration")
    cfg = {**load_json(centry["file"]), **(cfg_over or {})}
    mix = {**traffic.load(ROOT, cell["traffic"]), **(mix_over or {})}
    peaks = load_json("benchmarks", "peaks.json").get(dev["kind"], {})
    driver = importlib.import_module(f"benchmarks.drivers.{cfg['driver']}")
    comp = Compilations()

    def operation():
        rec = driver.operation(state)
        want = {"interpret": interpret,
                "platform": "cpu" if interpret else "tpu"}
        got = {k: rec[k] for k in want}
        if got != want:  # never the interpreter or another platform
            raise RuntimeError(f"the operation ran as {got}, not {want}")
        return rec

    state = driver.setup(cfg, mix, seed, interpret)
    mem_inputs = memory_peak_bytes(cell["chips"])  # before the program ran
    operation()  # warm: the one call that compiles
    setup_s = time.monotonic() - T_START
    comp.reset()

    tr = None
    if traced:
        operation()  # steady before the trace starts
        tdir = os.path.join(ROOT, ".bench_trace", f"{workload}.{os.getpid()}")
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the host spans are the benchmark's
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    w0 = time.monotonic()
    records = []
    try:
        while True:
            with jax.profiler.TraceAnnotation("bench:window"):
                records.append(operation())
            done = len(records)
            if traced and done >= mix["trace_ops"]:
                break
            if (not traced and done >= MIN_OPERATIONS
                    and time.monotonic() - w0 >= seconds):
                break
        window_s = time.monotonic() - w0
    finally:
        if traced:
            jax.profiler.stop_trace()
    in_window = comp.counts()
    mem = memory_peak_bytes(cell["chips"])
    if traced:
        tr = trace.read(tdir)
        shutil.rmtree(tdir, ignore_errors=True)

    failed, compared = driver.check(state, records)
    walls = sorted(r["wall_s"] for r in records)
    print(json.dumps({"compilations_in_window": in_window,
                      "memory_peak_bytes_after_inputs": mem_inputs,
                      "operations": len(records), "window_s": window_s,
                      "operation_wall_s": [walls[0], walls[len(walls) // 2],
                                           walls[-1]]}))
    for name, value, limit in compared:
        print(json.dumps({"compared": name, "value": value,
                          "limit": limit}))

    run = reduce.Run(cfg=cfg, records=records, window_s=window_s,
                     peaks=peaks, trace=tr)
    metrics = {}
    for m in bench["per_layer" if traced else "end_to_end"]:
        if not reports(m, workload, bench):
            continue
        if m["name"] == "setup_s":
            value = setup_s
        else:
            spec = load_json("benchmarks", "metrics", m["name"] + ".json")
            value = reduce.reducer(spec["reducer"])(run, **spec["args"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {**dev, "memory_peak_bytes": mem}
    out = {
        "correct": failed == 0,
        "attempted": sum(r["attempted"] for r in records),
        "failed": failed, "metrics": metrics, "device": device,
    }
    if traced:
        win = [e for e in tr["host"] if e[0] == "bench:window"]
        t0, t1 = win[0][1], win[-1][2]
        chips = cell["chips"]
        device["busy_s"] = sum(
            trace.busy_ns(tr["device"].get(c, []), t0, t1)
            for c in range(chips)
        ) / chips / 1e9
        device["window_s"] = (t1 - t0) / 1e9
        out["breakdown"] = breakdown(tr, chips, t0, t1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = load_json("BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    cache = use_compile_cache()
    dev = device_record(cell["chips"], load_json("benchmarks", "peaks.json"))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "seconds": args.seconds, "trace": args.trace,
                      "cache_dir": cache, "device": dev}), flush=True)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), dev)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
