"""Elastic autoscaling: a metrics-driven quiesce -> reshard -> resume
control loop over the resident mesh.

PRs 2, 4, and 5 built the three ingredients - device fault detection +
quarantine (``DeviceFaultPlan``, heartbeat quarantine masks), the
``MetricsRegistry``, and ``CheckpointBundle`` + ``reshard(M)`` - and this
module is their production composition: a host controller that keeps a
resident mesh serving through preemption, chip death, and load swings
without losing the task graph. SURVEY.md notes the HClib reference has
*no elastic recovery, no checkpointing*; this is where the rebuild
overtakes the paper rather than reproducing it.

Control model (one **slice** per loop iteration):

1. Run the mesh for a bounded slice: ``rk.run(..., quiesce=slice_rounds)``
   makes every device stop popping at round ``slice_rounds`` and exit in
   lockstep with its live scheduler state (the PR 5 clean-cut quiesce) -
   or exit normally if the workload drained first.
2. Observe: per-device ready backlog, pending, executed delta,
   inject-ring backlog, and the quarantine masks from ``fault_stats``
   fold into an :class:`Observation`.
3. Decide: :class:`AutoscalerPolicy` is a PURE decision function with
   hysteresis (a resize needs ``hysteresis`` consecutive over/under-
   threshold observations) and a post-resize ``cooldown`` (slices during
   which no further resize fires) - so the controller never flaps, and
   the policy is unit-testable with synthetic observations, no mesh
   required.
4. Act: a resize snapshots the quiesced state
   (``snapshot_resident``), re-homes it with ``CheckpointBundle.
   reshard(M)`` (totals conserved; the PR 2 dead-chip semantics), builds
   the M-device kernel, and resumes mid-graph. **Evacuation** is the
   fault-driven special case: any chip named in a survivor's quarantine
   mask is resharded around immediately (no hysteresis, no cooldown
   gate) - the controller beats the watchdog's escalation to it.
5. Record: every decision is a typed :class:`ScaleEvent` - appended to
   ``Autoscaler.events``, recorded in the :class:`MetricsRegistry`
   (``autoscale.*``), and emitted as a ``TR_SCALE`` record that
   ``Autoscaler.trace_info()`` exposes in the flight-recorder ABI, so
   ``tools/timeline.py --perfetto`` renders scale events beside device
   rounds on one timeline.

Preemption composes: when ``resilience.preempt_requested()`` turns true
between slices (SIGTERM via ``install_preempt_handler``, the
``HCLIB_TPU_PREEMPT`` env, or ``fire_preempt``), the controller saves
the current quiesced state as an on-disk bundle (``checkpoint_dir``) and
returns with ``info['preempted'] = True`` - checkpoint, then stop; a
later ``Autoscaler.run(resume_bundle=...)`` (any mesh size the policy
picks) continues the graph.

Off-path cost: none. The autoscaler is a host-side composition - it
spawns no threads, compiles nothing into kernels, and a mesh run outside
it is byte-identical to PR 5 behavior (asserted in
tests/test_autoscaler.py).
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from . import resilience
from .checkpoint import (
    BundleStore,
    CheckpointBundle,
    CheckpointError,
    snapshot_resident,
)

__all__ = [
    "Observation",
    "ScaleEvent",
    "AutoscalerPolicy",
    "Autoscaler",
]

# ScaleEvent.kind -> TR_SCALE b-word code, derived from the one SC_*
# table (device/tracebuf.py SC_NAMES; timeline.py labels from the same
# table, so codes, kinds, and rendered names cannot drift apart).
# tracebuf imports only numpy at module scope, so this is host-safe.
from ..device.tracebuf import SC_NAMES as _SC_NAMES  # noqa: E402

_KIND_CODES = {
    name.replace(" ", "_"): code for code, name in _SC_NAMES.items()
}


def _pof2_floor(n: int) -> int:
    """Largest power of two <= n (0 for n < 1)."""
    n = int(n)
    if n < 1:
        return 0
    return 1 << (n.bit_length() - 1)


class Observation:
    """One control slice's view of the mesh - everything the policy may
    read. Built from a quiesced run's ``info`` by the controller, or
    constructed directly in policy unit tests.

    ``tenants`` (mesh-tenancy runs): the per-tenant pressure feed -
    ``{tid: {backlog, in_flight, ring_residue, expired, budget, ...}}``
    (``MeshTenantTable.pressure()`` is the canonical producer). The
    policy reads deadline-budget DRAIN (expired deltas between
    consecutive observations) and the strand set (tenants with
    in-flight / ring-resident rows a scale-in would disturb) off it."""

    __slots__ = (
        "ndev", "backlog", "pending", "executed_delta", "inject_backlog",
        "quarantined", "slice_s", "tenants", "latency_pressure",
    )

    def __init__(
        self,
        ndev: int,
        backlog: Sequence[int],
        pending: int = 0,
        executed_delta: int = 0,
        inject_backlog: int = 0,
        quarantined: Sequence[int] = (),
        slice_s: float = 0.0,
        tenants: Optional[Dict[str, Dict[str, float]]] = None,
        latency_pressure: float = 0.0,
    ) -> None:
        self.ndev = int(ndev)
        self.backlog = [int(b) for b in backlog]
        self.pending = int(pending)
        self.executed_delta = int(executed_delta)
        self.inject_backlog = int(inject_backlog)
        self.quarantined = tuple(sorted(set(int(q) for q in quarantined)))
        self.slice_s = float(slice_s)
        self.tenants = tenants
        # Max burn rate across the SLO engine's windows (runtime/slo.py
        # SloEstimator.latency_pressure(); 0.0 when no SLO is
        # configured or the feed is absent - the rung is then dead).
        self.latency_pressure = float(latency_pressure)

    @property
    def stranded_tenants(self) -> List[str]:
        """Tenants a scale-in would disturb mid-flight: nonzero
        in-flight quota or ring residue (host backlog re-homes freely;
        published-but-unconsumed rows are the strand risk)."""
        if not self.tenants:
            return []
        return sorted(
            tid for tid, s in self.tenants.items()
            if float(s.get("in_flight", 0)) > 0
            or float(s.get("ring_residue", 0)) > 0
        )

    @property
    def backlog_per_device(self) -> float:
        """Mean READY backlog per device (+ any unconsumed inject rows):
        the actionable-work pressure the thresholds compare against.
        ``pending`` also counts dependency-blocked rows, which no amount
        of extra devices could run - deliberately not the signal."""
        if self.ndev <= 0:
            return 0.0
        return (sum(self.backlog) + self.inject_backlog) / self.ndev

    @classmethod
    def from_info(
        cls, ndev: int, info: Dict[str, Any], executed_before: int,
        slice_s: float,
        tenants: Optional[Dict[str, Dict[str, float]]] = None,
        latency_pressure: float = 0.0,
    ) -> "Observation":
        from ..device.megakernel import C_HEAD, C_TAIL

        counts = np.asarray(info["per_device_counts"])
        backlog = (counts[:, C_TAIL] - counts[:, C_HEAD]).tolist()
        quarantined = sorted({
            q for f in info.get("fault_stats", []) for q in f["quarantined"]
        })
        inj = 0
        ic = info.get("inject_ctl")
        if ic is not None:
            ic = np.asarray(ic)
            inj = int((ic[:, 0] - ic[:, 2]).sum())
        return cls(
            ndev=ndev, backlog=backlog, pending=int(info["pending"]),
            executed_delta=int(info["executed"]) - int(executed_before),
            inject_backlog=inj, quarantined=quarantined, slice_s=slice_s,
            tenants=tenants, latency_pressure=latency_pressure,
        )


class ScaleEvent:
    """One typed controller decision (every slice produces exactly one).

    ``kind``: ``scale_out`` | ``scale_in`` | ``evacuate`` | ``hold`` |
    ``checkpoint`` (preemption cut) | ``finish`` (workload drained).
    ``resize_latency_s`` is the full quiesced-state -> resumable-state
    cost of a resize (snapshot + reshard + state rebuild;
    tests/test_autoscaler.py holds it to the event). ``cache_hit`` (resizes only):
    whether the target shape's program was already warm in the
    process-wide program cache (runtime/progcache.py), i.e. the resume
    pays zero trace/lower/compile work.
    """

    __slots__ = (
        "kind", "slice_idx", "t_ns", "from_ndev", "to_ndev", "reason",
        "backlog", "pending", "executed", "resize_latency_s",
        "cache_hit",
    )

    def __init__(
        self, kind: str, slice_idx: int, from_ndev: int, to_ndev: int,
        reason: str, backlog: int = 0, pending: int = 0, executed: int = 0,
        resize_latency_s: Optional[float] = None,
        cache_hit: Optional[bool] = None,
    ) -> None:
        if kind not in _KIND_CODES:
            raise ValueError(f"unknown ScaleEvent kind {kind!r}")
        self.kind = kind
        self.slice_idx = int(slice_idx)
        self.t_ns = time.monotonic_ns()
        self.from_ndev = int(from_ndev)
        self.to_ndev = int(to_ndev)
        self.reason = str(reason)
        self.backlog = int(backlog)
        self.pending = int(pending)
        self.executed = int(executed)
        self.resize_latency_s = resize_latency_s
        self.cache_hit = cache_hit

    @property
    def resized(self) -> bool:
        return self.from_ndev != self.to_ndev

    def as_dict(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in self.__slots__}
        return d

    def record(self, t: Optional[int] = None) -> List[int]:
        """The TR_SCALE flight-recorder row ([tag, t, a, b]): ``t``
        defaults to the control-slice index (callers spanning several
        run()s pass an ordinal instead - ring timebases must be
        monotonic), a packs (from << 8) | to, b the kind."""
        from ..device.tracebuf import TR_SCALE

        return [
            TR_SCALE, self.slice_idx if t is None else int(t),
            (self.from_ndev << 8) | self.to_ndev,
            _KIND_CODES[self.kind],
        ]

    def __repr__(self) -> str:
        arrow = (
            f" {self.from_ndev}->{self.to_ndev}" if self.resized else ""
        )
        return (
            f"<ScaleEvent {self.kind}{arrow} slice={self.slice_idx} "
            f"({self.reason})>"
        )


class AutoscalerPolicy:
    """The pure decision function: observation in, (target, kind, reason)
    out. Hysteresis and cooldown are the no-flap machinery:

    - scale OUT when mean ready backlog per device stays >=
      ``scale_out_backlog`` for ``hysteresis`` consecutive slices - OR
      (the LIVE-DELTA signal, ISSUE 13) when it RISES by >=
      ``scale_out_delta`` per slice while the executed rate is not
      rising, for the same streak: a storm is caught while it builds,
      not after it crosses the level threshold;
    - scale IN when it stays <= ``scale_in_backlog`` (and nothing is
      queued on the inject rings) for ``hysteresis`` slices - but NEVER
      while it would strand a tenant's in-flight quota or ring residue
      (``obs.tenants``): the refusal is a typed ``strand_hold`` event,
      and the streak stays armed so a drained mesh shrinks at the very
      next slice;
    - DEADLINE PRESSURE bypasses hysteresis AND cooldown: a tenant
      whose deadline budget drains by >= ``tenant_pressure`` (fraction
      of its budget) within one slice triggers an immediate
      ``deadline_out`` scale-out - the controller must beat the
      watchdog's strike ladder (budget exhaustion cancels the lane) to
      the punch, so this path has no flap guard, only the post-resize
      cooldown it sets;
    - SLO BURN (ISSUE 19) rides the same no-guard lane: an observation
      whose ``latency_pressure`` (max multi-window burn rate from
      ``runtime/slo.py``) reaches ``slo_burn`` triggers an immediate
      ``slo_out`` scale-out - the latency ladder's earliest rung,
      firing before tail latency converts into deadline-budget drain;
    - EVACUATION bypasses both too: a quarantined chip is resharded
      around at the first observation that names it - fault recovery
      must not wait out a flap guard. The target drops to the largest
      power of two that fits the survivors (the hypercube hop schedule
      is pof2-only).

    Thresholds default from ``HCLIB_TPU_AUTOSCALE_OUT`` /
    ``HCLIB_TPU_AUTOSCALE_IN`` (tasks per device),
    ``HCLIB_TPU_AUTOSCALE_OUT_DELTA`` (tasks per device per slice) and
    ``HCLIB_TPU_AUTOSCALE_TENANT_PRESSURE`` (budget fraction per
    slice; the new knobs raise on malformed text). The instance is
    stateful (streak/cooldown counters + the previous slice's levels
    the deltas difference against): use one per controlled mesh.
    """

    def __init__(
        self,
        min_devices: int = 1,
        max_devices: int = 8,
        scale_out_backlog: Optional[float] = None,
        scale_in_backlog: Optional[float] = None,
        hysteresis: int = 2,
        cooldown: int = 2,
        scale_out_delta: Optional[float] = None,
        tenant_pressure: Optional[float] = None,
        slo_burn: Optional[float] = None,
    ) -> None:
        if min_devices < 1 or _pof2_floor(min_devices) != min_devices:
            raise ValueError(
                f"min_devices must be a power of two >= 1, got {min_devices}"
            )
        if _pof2_floor(max_devices) != max_devices:
            raise ValueError(
                f"max_devices must be a power of two, got {max_devices}"
            )
        if max_devices < min_devices:
            raise ValueError("max_devices < min_devices")
        if hysteresis < 1:
            raise ValueError("hysteresis must be >= 1")
        if cooldown < 0:
            raise ValueError("cooldown must be >= 0")
        self.min_devices = int(min_devices)
        self.max_devices = int(max_devices)

        def _envf(name: str, default: float) -> float:
            from .env import env_float

            return env_float(name, default, malformed=default)

        self.scale_out_backlog = (
            _envf("HCLIB_TPU_AUTOSCALE_OUT", 32.0)
            if scale_out_backlog is None else float(scale_out_backlog)
        )
        self.scale_in_backlog = (
            _envf("HCLIB_TPU_AUTOSCALE_IN", 2.0)
            if scale_in_backlog is None else float(scale_in_backlog)
        )
        if self.scale_in_backlog >= self.scale_out_backlog:
            raise ValueError(
                f"scale_in_backlog ({self.scale_in_backlog}) must be < "
                f"scale_out_backlog ({self.scale_out_backlog}): an "
                "overlapping band would oscillate by construction"
            )
        # The live-delta knobs (new in ISSUE 13) parse with RAISE
        # semantics: a typo'd threshold must not silently change the
        # elasticity policy.
        from .env import env_float

        self.scale_out_delta = (
            env_float("HCLIB_TPU_AUTOSCALE_OUT_DELTA", 8.0)
            if scale_out_delta is None else float(scale_out_delta)
        )
        if self.scale_out_delta <= 0:
            raise ValueError(
                f"scale_out_delta must be > 0, got {self.scale_out_delta}"
            )
        self.tenant_pressure = (
            env_float("HCLIB_TPU_AUTOSCALE_TENANT_PRESSURE", 0.25)
            if tenant_pressure is None else float(tenant_pressure)
        )
        if not 0 < self.tenant_pressure <= 1:
            raise ValueError(
                f"tenant_pressure must be in (0, 1], got "
                f"{self.tenant_pressure} (it is a fraction of the "
                "tenant's deadline budget drained per slice)"
            )
        # The SLO burn rung (ISSUE 19): raise semantics for the same
        # reason as the live-delta knobs.
        self.slo_burn = (
            env_float("HCLIB_TPU_SLO_BURN", 2.0)
            if slo_burn is None else float(slo_burn)
        )
        if self.slo_burn <= 0:
            raise ValueError(
                f"slo_burn must be > 0, got {self.slo_burn}"
            )
        self.hysteresis = int(hysteresis)
        self.cooldown = int(cooldown)
        self._out_streak = 0
        self._in_streak = 0
        self._cooling = 0
        # Previous-slice levels the delta signals difference against
        # (None until the first observation lands).
        self._prev_per_dev: Optional[float] = None
        self._prev_rate: Optional[float] = None
        self._prev_expired: Optional[Dict[str, float]] = None

    def reset(self) -> None:
        self._out_streak = self._in_streak = self._cooling = 0
        self._prev_per_dev = self._prev_rate = None
        self._prev_expired = None

    def _resized(self) -> None:
        self._out_streak = self._in_streak = 0
        self._cooling = self.cooldown

    def _roll_deltas(self, obs: Observation):
        """Advance the previous-slice levels and return this slice's
        delta signals: (backlog_delta, rate_delta, worst_drain,
        worst_tenant). Every decide() path must pass through here
        exactly once, or the deltas would stretch across skipped
        slices."""
        per_dev = obs.backlog_per_device
        rate = (
            obs.executed_delta / obs.slice_s if obs.slice_s > 0 else None
        )
        backlog_delta = (
            None if self._prev_per_dev is None
            else per_dev - self._prev_per_dev
        )
        rate_delta = (
            None if rate is None or self._prev_rate is None
            else rate - self._prev_rate
        )
        drain, worst = 0.0, None
        if obs.tenants:
            prev = self._prev_expired
            for tid, s in obs.tenants.items():
                budget = float(s.get("budget") or 0)
                if budget <= 0:
                    continue
                if prev is None:
                    # First observation: no baseline, no drain - a
                    # resumed deployment's cumulative expiry count must
                    # not read as a fresh storm.
                    continue
                d = (
                    float(s.get("expired", 0)) - prev.get(tid, 0.0)
                ) / budget
                if d > drain:
                    drain, worst = d, tid
        self._prev_per_dev = per_dev
        if rate is not None:
            self._prev_rate = rate
        if obs.tenants is not None:
            self._prev_expired = {
                tid: float(s.get("expired", 0))
                for tid, s in obs.tenants.items()
            }
        return backlog_delta, rate_delta, drain, worst

    def decide(self, obs: Observation):
        """-> (target_ndev, kind, reason). ``target == obs.ndev`` means
        hold (kind names why)."""
        backlog_delta, rate_delta, drain, worst = self._roll_deltas(obs)
        # Fault first: reshard around quarantined chips immediately.
        if obs.quarantined:
            survivors = obs.ndev - len(obs.quarantined)
            target = max(self.min_devices, _pof2_floor(survivors))
            if target < obs.ndev:
                self._resized()
                return (
                    target, "evacuate",
                    f"quarantined chip(s) {list(obs.quarantined)}: "
                    f"{survivors} survivors -> {target} devices",
                )
            return (
                obs.ndev, "hold",
                f"quarantined {list(obs.quarantined)} but already at "
                f"min_devices={self.min_devices} (watchdog owns this)",
            )
        # Deadline pressure next, BEFORE the cooldown gate: a tenant
        # burning its budget must scale out before the watchdog's
        # strike ladder (budget exhaustion -> lane cancel) fires, and a
        # flap guard is exactly the latency that would lose that race.
        if (
            drain >= self.tenant_pressure
            and obs.ndev < self.max_devices
        ):
            target = min(obs.ndev * 2, self.max_devices)
            self._resized()
            return (
                target, "deadline_out",
                f"tenant {worst!r} deadline budget draining "
                f"({drain:.0%}/slice >= {self.tenant_pressure:.0%}): "
                "scale out before the watchdog strikes",
            )
        # SLO burn (ISSUE 19) shares the no-flap-guard contract: a
        # breaching burn rate means the latency error budget drains
        # NOW, and the scale-out must land before the tail breaches
        # hard enough to trip the deadline-budget rung above (or the
        # watchdog behind it). Only the post-resize cooldown it sets
        # gates repeats.
        if (
            obs.latency_pressure >= self.slo_burn
            and obs.ndev < self.max_devices
        ):
            target = min(obs.ndev * 2, self.max_devices)
            self._resized()
            return (
                target, "slo_out",
                f"latency burn {obs.latency_pressure:.2f} >= "
                f"{self.slo_burn:g}: SLO error budget draining",
            )
        if self._cooling > 0:
            self._cooling -= 1
            return obs.ndev, "hold", f"cooldown ({self._cooling + 1} left)"
        per_dev = obs.backlog_per_device
        hot_level = per_dev >= self.scale_out_backlog
        # The delta arm: backlog RISING while the executed rate is not -
        # extra devices will absorb the rise; a rising rate means the
        # mesh is still ramping and levels should decide.
        hot_delta = (
            backlog_delta is not None
            and backlog_delta >= self.scale_out_delta
            and (rate_delta is None or rate_delta <= 0)
        )
        if (hot_level or hot_delta) and obs.ndev < self.max_devices:
            self._out_streak += 1
            self._in_streak = 0
            if self._out_streak >= self.hysteresis:
                target = min(obs.ndev * 2, self.max_devices)
                self._resized()
                why = (
                    f"backlog {per_dev:.1f}/dev >= "
                    f"{self.scale_out_backlog:g}"
                    if hot_level else
                    f"backlog rising {backlog_delta:+.1f}/dev/slice >= "
                    f"{self.scale_out_delta:g} with rate flat"
                )
                return (
                    target, "scale_out",
                    f"{why} for {self.hysteresis} slices",
                )
            return (
                obs.ndev, "hold",
                f"backlog high ({per_dev:.1f}/dev"
                + (f", {backlog_delta:+.1f}/slice" if hot_delta else "")
                + f"), streak {self._out_streak}/{self.hysteresis}",
            )
        if (
            per_dev <= self.scale_in_backlog
            and obs.inject_backlog == 0
            and obs.ndev > self.min_devices
        ):
            self._in_streak += 1
            self._out_streak = 0
            if self._in_streak >= self.hysteresis:
                stranded = obs.stranded_tenants
                if stranded:
                    # Typed refusal, streak left armed: the mesh shrinks
                    # at the first slice whose residue has drained.
                    self._in_streak = self.hysteresis
                    return (
                        obs.ndev, "strand_hold",
                        f"scale-in refused: would strand in-flight "
                        f"rows of tenant(s) {stranded}",
                    )
                target = max(obs.ndev // 2, self.min_devices)
                self._resized()
                return (
                    target, "scale_in",
                    f"backlog {per_dev:.1f}/dev <= "
                    f"{self.scale_in_backlog:g} for "
                    f"{self.hysteresis} slices",
                )
            return (
                obs.ndev, "hold",
                f"backlog low ({per_dev:.1f}/dev), streak "
                f"{self._in_streak}/{self.hysteresis}",
            )
        self._out_streak = self._in_streak = 0
        return obs.ndev, "hold", f"backlog {per_dev:.1f}/dev in band"


class Autoscaler:
    """The control loop. ``make_kernel(ndev)`` builds the ResidentKernel
    for a mesh size (its Megakernel MUST be built ``checkpoint=True`` -
    the quiesce word is the slicing mechanism); the same kernel-table
    shape must come back for every size (restore validates it). A
    factory that places meshes on REAL devices should also accept a
    ``quarantined=`` keyword (a frozenset of evacuated flat device ids,
    cumulative across the deployment) and build the mesh around those
    chips - the controller passes it whenever the factory's signature
    admits it, so a later scale-out cannot resurrect a chip it already
    evacuated. (The interpret-mode tests, whose devices are virtual,
    ignore it.)

    ``slice_rounds`` is the control interval in exchange rounds: each
    slice runs at most that many rounds, then quiesces for an
    observation. ``metrics`` (a MetricsRegistry) receives every decision
    under ``autoscale`` plus a live gauge source ``autoscale.state``
    (call ``close()`` to unregister it when retiring a controller whose
    registry outlives it); ``checkpoint_dir`` arms the preemption path
    (the quiesced state is saved there when a preemption notice arrives
    between slices).

    A resize the bundle refuses (per-device data buffers, waits whose
    satisfier sits in unexported host residue, an overfull target)
    downgrades to a hold - the mesh keeps serving on its current size
    and resize attempts back off for ``policy.cooldown`` slices -
    instead of killing the loop.

    No controller thread: the loop runs on the calling thread, slicing
    the mesh via quiesce - the off-path (not using this class) is
    exactly PR 5 behavior.
    """

    def __init__(
        self,
        make_kernel: Callable[..., Any],
        policy: Optional[AutoscalerPolicy] = None,
        *,
        slice_rounds: int = 64,
        max_slices: int = 1 << 10,
        metrics=None,
        checkpoint_dir: Optional[str] = None,
    ) -> None:
        if slice_rounds < 1:
            raise ValueError("slice_rounds must be >= 1")
        self.make_kernel = make_kernel
        self.policy = policy or AutoscalerPolicy()
        self.slice_rounds = int(slice_rounds)
        self.max_slices = int(max_slices)
        self.metrics = metrics
        if checkpoint_dir is None:
            # The env-configured store root arms the preemption path
            # without a code change (HCLIB_TPU_CKPT_DIR).
            from .env import env_str

            checkpoint_dir = env_str("HCLIB_TPU_CKPT_DIR")
        self.checkpoint_dir = checkpoint_dir
        self._store: Optional[BundleStore] = None
        self.events: List[ScaleEvent] = []
        self.ndev: Optional[int] = None
        self.quarantined: frozenset = frozenset()
        self._kernels: Dict[Any, Any] = {}
        self._refusal_backoff = 0
        self._t0_ns = self._t1_ns = time.monotonic_ns()
        if metrics is not None:
            metrics.register("autoscale.state", self._gauges)

    def close(self) -> None:
        """Retire the controller: unregister the live gauge source so a
        long-lived registry does not keep this instance (and its cached
        compiled kernels) alive."""
        if self.metrics is not None:
            self.metrics.unregister("autoscale.state")

    # -- wiring --

    def _gauges(self) -> Dict[str, Any]:
        by_kind: Dict[str, int] = {}
        for e in self.events:
            by_kind[e.kind] = by_kind.get(e.kind, 0) + 1
        return {
            "ndev": self.ndev or 0,
            "events": len(self.events),
            "resizes": sum(1 for e in self.events if e.resized),
            **{f"kind.{k}": v for k, v in by_kind.items()},
        }

    def _kernel_for(self, ndev: int):
        key = (ndev, self.quarantined)
        rk = self._kernels.get(key)
        if rk is None:
            # Factories that accept quarantined= get the cumulative
            # evacuation history, so a scale-out after an evacuation
            # builds around the dead chips instead of resurrecting them.
            import inspect

            try:
                params = inspect.signature(self.make_kernel).parameters
                takes_q = "quarantined" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()
                )
            except (TypeError, ValueError):
                takes_q = False
            rk = (
                self.make_kernel(ndev, quarantined=self.quarantined)
                if takes_q else self.make_kernel(ndev)
            )
            if not getattr(rk.mk, "checkpoint", False):
                raise ValueError(
                    "Autoscaler needs make_kernel(ndev) to build its "
                    "Megakernel with checkpoint=True: quiesce is the "
                    "control-slice mechanism"
                )
            if rk.ndev != ndev:
                raise ValueError(
                    f"make_kernel({ndev}) returned a {rk.ndev}-device "
                    "kernel"
                )
            self._kernels[key] = rk
        return rk

    def _bundle_store(self) -> Optional[BundleStore]:
        """The durable store rooted at ``checkpoint_dir`` (lazily built
        so an unused dir knob costs nothing): the preempt hook WRITES
        THROUGH it - generational publish, crash-safe, retention-pruned
        - instead of scattering loose timestamped bundle dirs."""
        if self._store is None and self.checkpoint_dir:
            self._store = BundleStore(
                self.checkpoint_dir, metrics=self.metrics
            )
        return self._store

    def _event(self, ev: ScaleEvent) -> ScaleEvent:
        self.events.append(ev)
        self._t1_ns = time.monotonic_ns()
        if self.metrics is not None:
            rec = {
                k: v for k, v in ev.as_dict().items()
                if isinstance(v, (int, float)) and v is not None
            }
            self.metrics.record_event(f"autoscale.{ev.kind}", rec)
        return ev

    def trace_info(self) -> Dict[str, Any]:
        """The controller's decisions in the flight-recorder ABI (one
        host ring of TR_SCALE records; the timebase is the event
        ordinal, monotonic even across several run()s on one
        controller) - feed it to ``tools/timeline.py --perfetto`` (or
        ``export_perfetto(traces=[...])``) next to device traces."""
        from ..device.tracebuf import host_trace_info

        return host_trace_info(
            [e.record(t=i) for i, e in enumerate(self.events)],
            self._t0_ns, max(self._t1_ns, self._t0_ns + 1),
        )

    # -- the loop --

    def run(
        self,
        builders: Optional[Sequence[Any]] = None,
        *,
        resume_bundle=None,
        data: Optional[Dict[str, np.ndarray]] = None,
        ivalues: Optional[np.ndarray] = None,
        waits: Optional[Sequence[Sequence]] = None,
        inject_rows: Optional[Sequence[Sequence]] = None,
        quantum: int = 8,
        max_rounds: int = 1 << 14,
        tenant_table=None,
    ):
        """Serve ``builders`` (one per starting device) - or continue a
        saved ``resume_bundle`` (a resident CheckpointBundle, a bundle
        dir, a ``BundleStore`` - or a store ROOT dir, walked with the
        self-healing ``load_latest``) - to completion under the policy.
        Returns ``(ivalues, data, info)`` of the final slice, with
        ``info['scale_events']`` (every typed
        decision) and ``info['ndev_final']`` attached; a preemption
        notice instead returns early with ``info['preempted'] = True``
        and (with ``checkpoint_dir``) ``info['bundle_path']``.

        Result contract across resizes: per-device accumulator slots and
        executed counters fold by sum at every reshard (the
        ``migratable_fns`` contract), so summed ivalues and executed
        totals are invariant - the storm soak asserts them bit-equal to
        an uninterrupted run's.

        ``tenant_table`` (mesh-tenancy runs, device/tenants.py): the
        ``MeshTenantTable`` fronting the mesh. It is passed through to
        every slice's ``rk.run`` (the table pumps/absorbs the per-device
        rings + tctl blocks), its ``pressure()`` feed rides every
        Observation (so the policy sees per-tenant backlog and
        deadline-budget drain), and a resize swaps in a fresh
        ``resized(M)`` table - lane state rides the resharded bundle,
        never the table object, so per-tenant counts conserve across
        every cut by the same mechanism the single-device stream uses."""
        if (builders is None) == (resume_bundle is None):
            raise ValueError(
                "run() wants exactly one of builders= or resume_bundle="
            )
        run_base = len(self.events)  # this run's slice of the event log
        if run_base == 0:
            self._t0_ns = time.monotonic_ns()
        if resume_bundle is not None:
            if isinstance(resume_bundle, CheckpointBundle):
                b = resume_bundle
            elif isinstance(resume_bundle, BundleStore):
                # Self-healing restore: the newest generation that
                # validates (corrupt ones quarantined); unrecoverable
                # stores raise so the caller poisons futures instead
                # of hanging.
                b = resume_bundle.load_latest()
            elif isinstance(resume_bundle, str) and not os.path.exists(
                os.path.join(resume_bundle, "manifest.json")
            ):
                # A directory that is not itself a bundle is a STORE
                # root (what checkpoint_dir now writes): walk its
                # generations. Covers empty/missing dirs too - the
                # store raises its every-fault diagnostic.
                b = BundleStore(
                    resume_bundle, metrics=self.metrics
                ).load_latest()
            else:
                b = CheckpointBundle.load(resume_bundle)
            if b.kind != "resident":
                raise CheckpointError(
                    f"Autoscaler.run got a {b.kind!r} bundle"
                )
            ndev = int(b.meta.get("ndev", b.arrays["tasks"].shape[0]))
            target = min(
                max(ndev, self.policy.min_devices), self.policy.max_devices
            )
            if target != ndev:
                try:
                    b = b.reshard(target)
                    ndev = target
                except CheckpointError:
                    # The bundle cannot legally re-home into the policy
                    # band (data buffers, host-residue waits, overfull
                    # target): resume at its original size and let the
                    # policy resize later, instead of dying at restart.
                    pass
            state: Optional[Dict[str, Any]] = b.state()
        else:
            ndev = len(builders)
            state = None
        self.ndev = ndev
        rk = self._kernel_for(ndev)
        executed_before = 0
        iv = data_o = info = None
        tkw = {} if tenant_table is None else {
            "tenant_table": tenant_table
        }
        for slice_idx in range(self.max_slices):
            t0 = time.monotonic()
            if state is None:
                iv, data_o, info = rk.run(
                    builders, data=data, ivalues=ivalues, waits=waits,
                    inject_rows=inject_rows, quantum=quantum,
                    max_rounds=max_rounds, quiesce=self.slice_rounds,
                    **tkw,
                )
            else:
                iv, data_o, info = rk.run(
                    resume_state=state, quantum=quantum,
                    max_rounds=max_rounds, quiesce=self.slice_rounds,
                    **tkw,
                )
            slice_s = time.monotonic() - t0
            if not info.get("quiesced"):
                # Drained (or aborted): the loop's terminal state.
                self._event(ScaleEvent(
                    "finish", slice_idx, rk.ndev, rk.ndev,
                    "aborted" if info.get("aborted") else
                    "workload drained",
                    pending=int(info["pending"]),
                    executed=int(info["executed"]),
                ))
                break
            obs = Observation.from_info(
                rk.ndev, info, executed_before, slice_s,
                tenants=(
                    None if tenant_table is None
                    else tenant_table.pressure()
                ),
            )
            executed_before = int(info["executed"])
            if self.metrics is not None:
                # The slice's run info lands in the registry (minus the
                # state arrays), so dashboards read the same backlog /
                # fault / tier signals the policy just decided on.
                self.metrics.add_run_info(
                    "autoscale.slice",
                    {k: v for k, v in info.items() if k != "state"},
                )
            if resilience.preempt_requested():
                # Checkpoint, then stop - the PR 5 preemption semantics,
                # now holding the WHOLE autoscaled deployment.
                bundle = snapshot_resident(rk, info)
                path = None
                store = self._bundle_store()
                if store is not None:
                    gen = store.save(bundle)
                    path = store.path_of(gen)
                    info["bundle_generation"] = gen
                self._event(ScaleEvent(
                    "checkpoint", slice_idx, rk.ndev, rk.ndev,
                    "preemption notice: checkpointed and stopped",
                    backlog=sum(obs.backlog), pending=obs.pending,
                    executed=executed_before,
                ))
                info["preempted"] = True
                info["bundle"] = bundle
                if path:
                    info["bundle_path"] = path
                break
            target, kind, reason = self.policy.decide(obs)
            if (
                self._refusal_backoff > 0
                and target != rk.ndev
                and kind != "evacuate"
            ):
                # A recent resize was refused by the bundle; keep
                # serving on the current size until the backoff drains
                # (retrying every slice would pay a futile snapshot +
                # reshard each time). EVACUATION is exempt - the
                # no-gates contract: a dead chip reshard-around is
                # attempted at every observation that names it.
                self._refusal_backoff -= 1
                target, kind = rk.ndev, "hold"
                reason = f"resize backoff after refusal ({reason})"
            if target != rk.ndev:
                t0r = time.monotonic()
                try:
                    bundle = snapshot_resident(rk, info).reshard(target)
                except CheckpointError as e:
                    # The quiesced state cannot legally re-home (data
                    # buffers, pending waits, overfull target): serving
                    # beats dying - downgrade to a hold that names the
                    # refusal and back off further attempts.
                    self._refusal_backoff = max(1, self.policy.cooldown)
                    state = info["state"]
                    self._event(ScaleEvent(
                        "hold", slice_idx, obs.ndev, obs.ndev,
                        f"{kind} {obs.ndev}->{target} refused: {e}",
                        backlog=sum(obs.backlog), pending=obs.pending,
                        executed=executed_before,
                    ))
                else:
                    self._refusal_backoff = 0  # a legal resize resets it
                    if kind == "evacuate":
                        self.quarantined = self.quarantined | frozenset(
                            obs.quarantined
                        )
                    rk = self._kernel_for(target)
                    # Before the next slice triggers the (re)build:
                    # warm means the target shape's program is already
                    # in this kernel's jit table or the process-wide
                    # program cache, so the resume traces nothing.
                    cache_hit = rk.program_cached(
                        quantum=quantum, max_rounds=max_rounds,
                    )
                    state = bundle.state()
                    self.ndev = target
                    if tenant_table is not None:
                        # Fresh table, same roster: residue + counters
                        # ride the resharded bundle state, which the
                        # next slice's run feeds to resume_from.
                        tenant_table = tenant_table.resized(target)
                        tkw = {"tenant_table": tenant_table}
                    self._event(ScaleEvent(
                        kind, slice_idx, obs.ndev, target, reason,
                        backlog=sum(obs.backlog), pending=obs.pending,
                        executed=executed_before,
                        resize_latency_s=round(
                            time.monotonic() - t0r, 6
                        ),
                        cache_hit=cache_hit,
                    ))
            else:
                state = info["state"]
                self._event(ScaleEvent(
                    kind, slice_idx, obs.ndev, obs.ndev, reason,
                    backlog=sum(obs.backlog), pending=obs.pending,
                    executed=executed_before,
                ))
        else:
            from .resilience import StallError

            raise StallError(
                f"autoscaler exceeded max_slices={self.max_slices} with "
                f"{info['pending'] if info else '?'} pending",
                stats={"events": [e.as_dict() for e in self.events]},
            )
        self._t1_ns = time.monotonic_ns()
        # THIS run's decisions only: a controller reused across runs
        # (checkpoint -> resume_bundle) keeps the full log in
        # self.events / trace_info(), but per-run consumers (bench,
        # the storm assertions) must not see a previous run's events.
        info["scale_events"] = [
            e.as_dict() for e in self.events[run_base:]
        ]
        info["ndev_final"] = rk.ndev
        if self.metrics is not None:
            self.metrics.record("autoscale", self._gauges())
        return iv, data_o, info
