"""Lesson 10: observability and the auto-routed fast path.

Three production-facing features close the tour:

1. **Tracing and reports.** The runtime records per-worker START/END task
   events into binary double-buffered logs (the reference's instrument
   framework, but LIVE - the reference's recorder is stubbed,
   reference src/hclib-instrument.c:211-252), and exposes worker counters
   incl. the steal matrix as a dict. ``tools/timeline.py`` renders both:
   a density timeline (one row per worker, shade = busy fraction) and a
   load/steal report - the analogue of the reference's tools/timeline.py
   station.

2. **Auto-routing to the batch-dispatch tier.** A recursive,
   reduction-shaped task family (lesson 7) can be named in
   ``Megakernel(route=...)``: tasks of that kernel NAME then run as
   whole subtrees across the VPU lanes instead of one ~100 ns descriptor
   at a time, while the rest of the DAG stays on the scalar tier -
   dependencies, value slots, and counts all behave identically.

3. **The device flight recorder.** ``Megakernel(trace=N)`` compiles a
   fixed-width trace ring into the scheduler's round loop
   (device/tracebuf.py): every dispatch is a record, the host brackets
   the launch with its wall clock, and ``tools/timeline.py --perfetto``
   merges host events + device rounds into one zoomable timeline.
"""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import hclib_tpu as hc


def part_one_tracing(tmpdir: str) -> None:
    rt = hc.Runtime(nworkers=4, instrument=True)

    def body():
        with hc.finish():
            for _ in range(60):
                hc.async_(lambda: time.sleep(0.0005))

    rt.run(body)
    dump = rt.event_log.dump(tmpdir)
    stats = rt.stats_dict()

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import timeline

    text = timeline.render_dump(dump)
    assert "per-worker timeline" in text and "task" in text
    print(text.split("\n\n")[1])  # the timeline block
    report = timeline.render_stats(stats)
    assert "executed=" in report
    print(report)
    executed = sum(w["executed"] for w in stats["workers"])
    assert executed >= 61, executed
    print(f"traced {executed} tasks across {stats['nworkers']} workers\n")


def part_two_routing() -> None:
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.vector_engine import fib_spec
    from hclib_tpu.device.workloads import _fib_kernel, _sum_kernel

    def report(ctx):
        ctx.set_value(1, ctx.value(0) * 10)

    mk = Megakernel(
        kernels=[
            ("fib", _fib_kernel),   # the scalar semantic definition
            ("sum", _sum_kernel),
            ("report", report),
        ],
        # Route the 'fib' FAMILY to the vector tier: its whole recursion
        # tree expands across the lanes from one descriptor.
        route={"fib": fib_spec(max_n=16, lanes=(1, 8))},
        capacity=32,
        num_values=16,
        succ_capacity=16,
        interpret=True,
    )
    b = TaskGraphBuilder()
    t0 = b.add(0, args=[14], out=0)     # routed: 1219-node subtree
    b.add(2, deps=[t0])                 # scalar successor reads its out
    b.reserve_values(2)
    iv, _, info = mk.run(b)
    assert iv[0] == 377 and iv[1] == 3770
    assert info["executed"] > 1000      # the tree, not 2 descriptors
    assert info["allocated"] == 2       # ...from just 2 descriptor rows
    print(
        f"auto-routed fib(14): {info['executed']} tasks expanded on the "
        f"vector tier from {info['allocated']} descriptors; "
        f"result {iv[0]}, scalar successor saw {iv[1]}"
    )


def part_three_flight_recorder(tmpdir: str) -> None:
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.tracebuf import TR_FIRE_SCALAR, records_of
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    # trace=256: a 256-record ring rides out of the kernel; every
    # scheduler round appends records from INSIDE the device loop.
    mk = make_fib_megakernel(256, interpret=True, trace=256)
    b = TaskGraphBuilder()
    b.add(FIB, args=[10], out=0)
    iv, _, info = mk.run(b)
    assert int(iv[0]) == 55
    ring = info["trace"]["rings"][0]
    fires = records_of(info["trace"], TR_FIRE_SCALAR)
    # Overflow is counted, never fatal: the ring keeps the LAST records.
    print(
        f"flight recorder: {ring['written']} records written "
        f"({ring['dropped']} dropped past the {ring['capacity']}-record "
        f"ring), {len(fires)} scalar dispatch fires kept"
    )
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import timeline

    out = os.path.join(tmpdir, "lesson10.perfetto.json")
    doc = timeline.export_perfetto(out, traces=[info["trace"]])
    assert len(doc["traceEvents"]) > 0
    print(
        f"perfetto: {len(doc['traceEvents'])} events -> {out} "
        "(open at https://ui.perfetto.dev)\n"
    )


def main() -> None:
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        part_one_tracing(d)
        part_three_flight_recorder(d)
    part_two_routing()
    print("lesson 10 OK")


if __name__ == "__main__":
    main()
