"""Lesson 12: elastic autoscaling - serving through a preempt storm.

Lesson 11 survived ONE preemption. Production serving faces storms of
them - plus chip death and load swings - and the autoscaler
(runtime/autoscaler.py) is the control loop that rides them: it slices
a resident mesh into bounded runs (quiesce is the slicing mechanism),
observes each slice through the MetricsRegistry-shaped signals, and
live-reshapes the mesh via quiesce -> ``CheckpointBundle.reshard(M)``
-> resume:

- **scale out** when ready backlog per device stays high (hysteresis:
  N consecutive slices, so one spiky slice never resizes);
- **scale in** when the mesh idles (plus a post-resize cooldown - the
  no-flap guarantee);
- **evacuate** a quarantined chip immediately (fault recovery must not
  wait out a flap guard) - reshard around it before the watchdog
  escalates;
- **checkpoint, then stop** on a preemption notice, resumable at any
  mesh size.

Every decision is a typed ``ScaleEvent``: in ``Autoscaler.events``, in
the MetricsRegistry (``autoscale.*``), and as a TR_SCALE record that
``Autoscaler.trace_info()`` exposes for the Perfetto timeline.

The policy is a PURE function of observations - this lesson drives it
headless first (no mesh, runs on any jax), then runs the real
autoscaled mesh when the Mosaic interpret mode is available.
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The mesh part wants virtual CPU devices (no-op without Mosaic).
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import hclib_tpu as hc


def part_one_policy_headless() -> None:
    """The decision function, no mesh attached: hysteresis, cooldown,
    and the evacuation fast path."""
    policy = hc.AutoscalerPolicy(
        min_devices=1, max_devices=8,
        scale_out_backlog=16.0, scale_in_backlog=2.0,
        hysteresis=2, cooldown=1,
    )
    # A single hot slice holds (streak 1/2); a SUSTAINED backlog scales.
    hot = hc.Observation(ndev=2, backlog=[40, 40])
    for expect_kind in ("hold", "scale_out"):
        target, kind, reason = policy.decide(hot)
        print(f"  hot slice -> {kind} (target {target}): {reason}")
        assert kind == expect_kind, (kind, expect_kind)
    # Cooldown right after the resize: even a hot observation holds.
    target, kind, _ = policy.decide(hc.Observation(4, [40] * 4))
    assert kind == "hold" and target == 4
    print("  post-resize slice -> hold (cooldown): no flapping")
    # Evacuation bypasses both gates: a quarantined chip reshard-around
    # happens at the FIRST observation naming it.
    target, kind, reason = policy.decide(
        hc.Observation(4, [5, 5, 5, 0], quarantined=[3])
    )
    assert kind == "evacuate" and target == 2, (kind, target)
    print(f"  dead chip -> {kind} to {target} devices: {reason}")


def part_two_events_and_telemetry() -> None:
    """ScaleEvents are data: metrics counters + a host flight-recorder
    ring in the same ABI device traces use."""
    from hclib_tpu.device.tracebuf import TR_SCALE, records_of

    reg = hc.MetricsRegistry()
    asc = hc.Autoscaler(
        lambda ndev: (_ for _ in ()).throw(RuntimeError("unused")),
        hc.AutoscalerPolicy(),
        metrics=reg,
    )
    asc._event(hc.ScaleEvent("scale_out", 0, 2, 4, "demo backlog"))
    asc._event(hc.ScaleEvent("evacuate", 1, 4, 2, "demo dead chip",
                             resize_latency_s=0.012))
    snap = reg.snapshot()["metrics"]
    assert snap["autoscale.scale_out.count"] == 1.0
    assert snap["autoscale.evacuate.last.to_ndev"] == 2.0
    recs = records_of(asc.trace_info(), TR_SCALE)
    assert len(recs) == 2
    frm, to = int(recs[1][2]) >> 8, int(recs[1][2]) & 0xFF
    print(f"  {len(recs)} TR_SCALE records; last: {frm} -> {to} "
          "(feed asc.trace_info() to tools/timeline.py --perfetto)")


def part_three_autoscaled_mesh() -> None:
    """The real loop: a 2-device UTS mesh scales in on its idle tail,
    totals exact across the resize."""
    import numpy as np

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.workloads import UTS_NODE, make_uts_megakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    def make_kernel(ndev):
        mk = make_uts_megakernel(max_depth=5, interpret=True,
                                 checkpoint=True)
        return ResidentKernel(
            mk, cpu_mesh(ndev, axis_name="q"),
            migratable_fns=[UTS_NODE], window=4, homed=False,
        )

    def builders(ndev):
        bs = [TaskGraphBuilder() for _ in range(ndev)]
        for d in range(ndev):
            bs[d].add(UTS_NODE, args=[d + 1, 0])
        return bs

    iv_f, _, info_f = make_kernel(2).run(builders(2), quantum=8,
                                         max_rounds=1 << 14)
    total = int(np.asarray(iv_f)[:, 0].sum())
    asc = hc.Autoscaler(
        make_kernel,
        hc.AutoscalerPolicy(min_devices=1, max_devices=2,
                            scale_out_backlog=1e9, scale_in_backlog=2.0,
                            hysteresis=1, cooldown=0),
        slice_rounds=8,
    )
    iv, _, info = asc.run(builders(2), quantum=8)
    assert int(np.asarray(iv)[:, 0].sum()) == total
    kinds = [e["kind"] for e in info["scale_events"]]
    print(f"  {info['executed']} tasks, events {kinds}, final mesh "
          f"{info['ndev_final']} device(s), totals exact ({total})")


def part_four_quiesce_stride() -> None:
    """The poll-every-N-rounds knob: checkpoint builds re-read the
    quiesce word from HBM each round by default; quiesce_stride=N
    amortizes that DMA N-fold for at most N-1 rounds of extra latency
    (perf_regression's checkpoint-overhead guard bounds both sides)."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.workloads import (
        UTS_NODE, device_uts_mk, make_uts_megakernel,
    )

    kw = dict(max_depth=7, interpret=True)
    nodes, _ = device_uts_mk(**kw)
    mk = make_uts_megakernel(checkpoint=True, quiesce_stride=4, **kw)
    b = TaskGraphBuilder()
    b.add(UTS_NODE, args=[1, 0])
    _, _, info = mk.run(b, quiesce=nodes // 2)
    assert info["quiesced"] is True
    iv, _, done = mk.resume(info["state"])
    assert int(iv[0]) == nodes
    print(f"  stride-4 build: cut at {info['quiesce']['executed_at']} "
          f"(requested {nodes // 2}), resumed to {nodes} nodes - exact")


if __name__ == "__main__":
    print("policy, headless:")
    part_one_policy_headless()
    print("telemetry:")
    part_two_events_and_telemetry()
    print("autoscaled mesh:")
    part_three_autoscaled_mesh()
    print("quiesce stride:")
    part_four_quiesce_stride()
    print("lesson 12 OK")
