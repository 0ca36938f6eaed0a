"""Central registry for ``HCLIB_TPU_*`` environment variables.

Every knob the package reads from the process environment is declared
here ONCE - name, type, default, and one-line doc - and read through the
typed accessors below. The registry is what makes the env surface
auditable: ``tools/lint.py`` forbids raw ``os.environ`` access to
``HCLIB_TPU_*`` names outside this module and cross-checks that every
name mentioned anywhere in the tree has a registry row, and the README
environment table renders from ``registry_table()``.

Parsing conventions (the PR 8 rule: a typo must not silently change
behavior):

- ``env_int`` / ``env_float`` raise ``ValueError`` naming the variable
  on malformed text unless the call site passes ``malformed=`` (a few
  legacy knobs deliberately degrade - e.g. ``HCLIB_TPU_TRACE=junk``
  enables default-capacity tracing rather than aborting a run the env
  owner never wrote).
- ``env_bool``: unset, empty, and ``"0"`` are False; anything else is
  True (the HCLIB_TPU_METRICS convention).
- ``env_flag``: any nonempty string is True - the legacy
  ``bool(os.environ.get(...))`` truthiness some older knobs keep for
  compatibility (``HCLIB_TPU_STATS=0`` enables stats; documented wart).

Accessors refuse unregistered names so a new knob cannot be added
without a doc row.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import Optional, Tuple

__all__ = [
    "EnvVar",
    "REGISTRY",
    "env_raw",
    "env_set",
    "env_flag",
    "env_bool",
    "env_int",
    "env_float",
    "env_str",
    "registry_table",
    "use_compile_cache",
]


@dataclass(frozen=True)
class EnvVar:
    name: str
    kind: str       # int | float | bool | flag | str | list
    default: str    # human-readable default, for the doc table
    doc: str
    legacy: Tuple[str, ...] = field(default_factory=tuple)


def _v(name, kind, default, doc, legacy=()):
    return EnvVar(name, kind, default, doc, tuple(legacy))


# One row per knob. Keys are the canonical names; legacy aliases are
# consulted (in order) when the canonical name is unset.
REGISTRY = {
    e.name: e
    for e in [
        # -- host runtime (runtime/scheduler.py) --
        _v("HCLIB_TPU_WORKERS", "int", "cpu_count",
           "host worker threads", legacy=("HCLIB_WORKERS",)),
        _v("HCLIB_TPU_LOCALITY_FILE", "str", "generated",
           "locality-graph JSON path", legacy=("HCLIB_LOCALITY_FILE",)),
        _v("HCLIB_TPU_STATS", "flag", "off",
           "per-worker scheduler stats"),
        _v("HCLIB_TPU_INSTRUMENT", "flag", "off",
           "host event log (runtime/instrument.py)",
           legacy=("HCLIB_INSTRUMENT",)),
        _v("HCLIB_TPU_TIMER", "flag", "off",
           "per-worker state timer"),
        _v("HCLIB_TPU_WATCHDOG_S", "float", "0 (off)",
           "stall watchdog period, seconds",
           legacy=("HCLIB_TPU_WATCHDOG",)),
        _v("HCLIB_TPU_WATCHDOG_ESCALATE", "bool", "on",
           "watchdog report->dump->cancel ladder (0 = report only)"),
        _v("HCLIB_TPU_WATCHDOG_CHECKPOINT", "bool", "off",
           "watchdog strike-2 rung fires preempt hooks (checkpoint)"),
        _v("HCLIB_TPU_METRICS", "bool", "off",
           "MetricsRegistry on the Runtime"),
        _v("HCLIB_TPU_DUMP_DIR", "str", ".",
           "EventLog dump directory"),
        # -- resilience / preemption (runtime/resilience.py) --
        _v("HCLIB_TPU_PREEMPT", "bool", "off",
           "wrapper-script preemption request (no-signal spelling)"),
        _v("HCLIB_TPU_CREDIT_TIMEOUT", "int", "2",
           "steal-credit starvation window, exchange rounds"),
        _v("HCLIB_TPU_HEARTBEAT_TIMEOUT", "int", "2",
           "dead-chip heartbeat window, exchange rounds"),
        # -- autoscaler (runtime/autoscaler.py) --
        _v("HCLIB_TPU_AUTOSCALE_OUT", "float", "32",
           "scale-out backlog threshold, tasks/device"),
        _v("HCLIB_TPU_AUTOSCALE_IN", "float", "2",
           "scale-in backlog threshold, tasks/device"),
        _v("HCLIB_TPU_AUTOSCALE_OUT_DELTA", "float", "8",
           "scale-out backlog RISE threshold, tasks/device/slice "
           "(the live-delta signal; malformed text raises)"),
        _v("HCLIB_TPU_AUTOSCALE_TENANT_PRESSURE", "float", "0.25",
           "deadline-budget drain fraction per slice that triggers an "
           "immediate deadline_out scale-out (malformed text raises)"),
        # -- durable checkpoint store (runtime/checkpoint.py) --
        _v("HCLIB_TPU_CKPT_DIR", "str", "unset",
           "BundleStore root directory: default_store() and the "
           "autoscaler's preempt hook write generations under it"),
        _v("HCLIB_TPU_CKPT_KEEP", "int", "3",
           "BundleStore retention: generations kept after each "
           "publish (>= 1; malformed text raises)"),
        _v("HCLIB_TPU_CKPT_FSYNC", "bool", "on",
           "fsync bundle members and directories at publish "
           "(0 = fast mode for tests; crash-safety not guaranteed)"),
        # -- device megakernel (device/megakernel.py) --
        _v("HCLIB_TPU_TRACE", "int", "0 (off)",
           "flight-recorder ring capacity (1 = default capacity)"),
        _v("HCLIB_TPU_CHECKPOINT", "bool", "off",
           "compile the quiesce protocol into schedulers"),
        _v("HCLIB_TPU_QUIESCE_STRIDE", "int", "1",
           "poll the quiesce word every Nth round"),
        _v("HCLIB_TPU_LANE_MAX_AGE", "int", "0 (off)",
           "age-triggered lane firing policy threshold, rounds"),
        _v("HCLIB_TPU_PRIORITY_BUCKETS", "int", "0 (off)",
           "priority-bucket dispatch tier: bucket rings per batch "
           "lane, popped lowest-nonempty-first (2..8; malformed or "
           "out-of-range text raises)"),
        _v("HCLIB_TPU_VERIFY", "bool", "off; on under pytest",
           "build-time static verifier (hclib_tpu.analysis; 0 forces "
           "off, nonzero forces on)"),
        # -- program cache (runtime/progcache.py) --
        _v("HCLIB_TPU_PROGRAM_CACHE", "bool", "on",
           "process-wide content-keyed program cache: jitted "
           "executables shared across content-identical builds "
           "(byte-identical programs; 0 forces off)"),
        _v("HCLIB_TPU_PROGRAM_CACHE_CAP", "int", "256",
           "program-cache LRU entry bound (>= 1; malformed or "
           "non-positive text raises)"),
        # -- model checker (hclib_tpu/analysis: explore.py / model.py) --
        _v("HCLIB_TPU_MODEL_DEPTH", "int", "64",
           "bounded-interleaving explorer depth bound, actions per "
           "path (malformed text raises)"),
        _v("HCLIB_TPU_MODEL_BUDGET_S", "float", "20",
           "bounded-interleaving explorer wall budget, seconds; an "
           "exhausted budget flags the result incomplete (malformed "
           "text raises)"),
        _v("HCLIB_TPU_MODEL_PERMS", "int", "3",
           "schedule-independence certification: permuted pop orders "
           "checked per claim (malformed text raises)"),
        # -- dispatch tiers --
        _v("HCLIB_TPU_FORASYNC_WIDTH", "int", "8",
           "default forasync device-tier batch width"),
        # -- multi-tenant ingress (device/tenants.py) --
        _v("HCLIB_TPU_TENANTS", "int", "0 (off)",
           "enable N equal tenant lanes on streaming runs"),
        _v("HCLIB_TPU_MESH_TENANTS", "int", "0 (off)",
           "enable N equal tenant lanes on resident inject meshes "
           "(shares the per-lane WEIGHTS/RATE/BURST/INFLIGHT/"
           "DEADLINE_S knobs above; malformed text raises)"),
        _v("HCLIB_TPU_TENANT_WEIGHTS", "list", "unset",
           "per-lane WRR weights, e.g. 4,2,1 (implies lane count)"),
        _v("HCLIB_TPU_TENANT_RATE", "float", "unset",
           "per-lane token-bucket refill rate, submits/s"),
        _v("HCLIB_TPU_TENANT_BURST", "float", "rate",
           "per-lane token-bucket capacity"),
        _v("HCLIB_TPU_TENANT_INFLIGHT", "float", "unset",
           "per-lane in-flight admission budget (whole number)"),
        _v("HCLIB_TPU_TENANT_DEADLINE_S", "float", "unset",
           "per-lane default admission deadline, seconds"),
        # -- completion-mailbox egress (device/egress.py) --
        _v("HCLIB_TPU_EGRESS_DEPTH", "int", "0 (off)",
           "completion-mailbox ring depth, rows; enables submit "
           "futures on tenant runs (malformed text raises)"),
        _v("HCLIB_TPU_EGRESS_BACKOFF_S", "float", "0.05",
           "Future.result() bounded-backoff poll cap, seconds "
           "(malformed text raises)"),
        # -- live telemetry plane (device/telemetry.py, runtime/slo.py) --
        _v("HCLIB_TPU_TELEMETRY", "bool", "off",
           "compile the live telemetry plane into egress-enabled "
           "streams: per-request lifecycle stamps + on-device latency "
           "histograms, scrapeable mid-run (0 forces off)"),
        _v("HCLIB_TPU_TELEMETRY_POLL_S", "float", "0.05",
           "TelemetryPoller snapshot interval, seconds (malformed "
           "text raises)"),
        _v("HCLIB_TPU_SLO_QUANTILE", "float", "0.99",
           "SLO objective quantile for the burn-rate engine, in "
           "(0, 1] (malformed text raises)"),
        _v("HCLIB_TPU_SLO_OBJECTIVE_ROUNDS", "int", "unset",
           "SLO latency objective, scheduler rounds: requests over "
           "this are burn-budget violations (malformed text raises)"),
        _v("HCLIB_TPU_SLO_BURN", "float", "2.0",
           "burn-rate threshold that fires the slo_out scale-out "
           "(max over windows; malformed text raises)"),
        _v("HCLIB_TPU_SLO_WINDOWS_S", "str", "60,300",
           "comma-separated burn-rate window lengths, seconds "
           "(malformed text raises)"),
        # -- dynamic graph service (device/dyngraph.py) --
        _v("HCLIB_TPU_DYNGRAPH_SPARE_BLOCKS", "int", "2",
           "spare edge blocks pre-allocated per vertex for in-kernel "
           "edge splices (>= 1; malformed or non-positive text "
           "raises)"),
        _v("HCLIB_TPU_DYNGRAPH_UPDATE_PRIORITY", "int", "0",
           "bucket ring the UPDATE kind routes into on priority-"
           "bucketed dyngraph builds (0 = highest, fires before "
           "queries; clipped into [0, priority_buckets); malformed "
           "text raises)"),
        # -- native C++ runtime (read by getenv in native/, not here) --
        _v("HCLIB_TPU_AFFINITY", "str", "none",
           "native worker CPU pinning: strided | chunked | none",
           legacy=("HCLIB_AFFINITY",)),
        # -- harnesses --
        _v("HCLIB_TPU_BIG_TESTS", "flag", "off",
           "opt into hardware-scale test variants (any nonempty value)"),
    ]
}


def _lookup(name: str) -> Optional[str]:
    """Raw environment text for a registered name: canonical first,
    then legacy aliases. An EMPTY canonical value falls through to the
    aliases (the pre-registry ``get(new) or get(old)`` idiom, where
    ``HCLIB_TPU_WORKERS= cmd`` wrapper lines must not mask a set
    legacy name); if every spelling is empty-or-unset, the first empty
    is returned (set-but-empty stays observable to ``env_raw``
    callers), else None."""
    try:
        var = REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name} is not in the hclib_tpu env registry "
            "(runtime/env.py): add a row with its type and doc line"
        ) from None
    first_empty: Optional[str] = None
    for spelling in (var.name,) + var.legacy:
        v = os.environ.get(spelling)
        if v:
            return v
        if v is not None and first_empty is None:
            first_empty = v
    return first_empty


def env_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    v = _lookup(name)
    return default if v is None else v


def env_set(name: str) -> bool:
    """Variable present AND nonempty (any text, including '0')."""
    return bool(_lookup(name))


def env_flag(name: str) -> bool:
    """Legacy truthiness: any nonempty string is True ('0' included)."""
    return bool(_lookup(name))


def env_bool(name: str, default: bool = False) -> bool:
    """Unset -> default; ''/'0' -> False; anything else -> True."""
    v = _lookup(name)
    if v is None:
        return default
    return v not in ("", "0")


def _parse(name: str, conv, malformed):
    v = _lookup(name)
    if not v:
        return None
    try:
        return conv(v)
    except (TypeError, ValueError):
        if malformed == "raise":
            raise ValueError(
                f"{name}={v!r} must be {'an int' if conv is int else 'a number'}"
            ) from None
        return malformed


def env_int(name: str, default: Optional[int] = None, *,
            malformed="raise") -> Optional[int]:
    """Int value; unset/empty -> ``default``. Malformed text raises
    (naming the variable) unless ``malformed=`` supplies a fallback."""
    v = _parse(name, int, malformed)
    return default if v is None else v


def env_float(name: str, default: Optional[float] = None, *,
              malformed="raise") -> Optional[float]:
    v = _parse(name, float, malformed)
    return default if v is None else v


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    v = _lookup(name)
    return default if not v else v


def registry_table():
    """(name, kind, default, doc) rows for README / tooling, sorted."""
    return [
        (v.name, v.kind, v.default, v.doc)
        for _, v in sorted(REGISTRY.items())
    ]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set that is the
    directory and nothing is changed; where it is not, the cache goes to
    ``<checkout>/.jax_cache`` - a fixed path, because the path is part
    of the cache key and a directory that moves never hits. Entry points
    (``chip_smoke.py``, ``__graft_entry__``,
    ``tools/perf_regression.py``) and ``tests/conftest.py`` call this
    first; no other code names a cache directory."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            ".jax_cache",
        )
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d  # children inherit
        if "jax" in sys.modules:  # imported before us: env was read
            sys.modules["jax"].config.update(
                "jax_compilation_cache_dir", d
            )
    return d
