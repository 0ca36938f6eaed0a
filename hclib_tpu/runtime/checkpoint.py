"""Checkpoint/restore: preemption-tolerant snapshot and elastic resume of
the persistent megakernel.

A resident kernel that runs for minutes is exactly what TPU preemption
kills: a SIGTERM or maintenance event used to lose the whole task graph,
and the only mitigations were abort-and-rerun (the abort words, README
"Device faults") and post-mortem traces (the flight recorder). This module
is the missing robustness layer:

- **Quiesce** (device side, compiled in only with
  ``Megakernel(checkpoint=True)`` - the DeviceFaultPlan discipline): a
  host-writable quiesce word rides next to the abort word and is polled
  inside every round loop (megakernel sched, streaming-inject ctl[5],
  resident-mesh ctl word [1] folded into the termination collective). On
  observing it, workers stop popping at the next round boundary - per-kind
  batch lanes spill to the ready ring, in-flight prefetches drain, the
  resident mesh keeps its exchange rounds until the wire is empty (sent ==
  recv, outboxes drained) - and the kernel returns with its LIVE scheduler
  state through the aliased outputs: task table, ready ring, counters,
  value heap, tier counters, fault/trace cursors.

- **Bundle** (this module): ``CheckpointBundle`` serializes that exported
  state plus the host-held descriptor metadata into a versioned on-disk
  artifact - a directory holding ``state.npz`` (the arrays) and
  ``manifest.json`` (magic, version, kind, kernel-table names, capacities,
  mesh dims, sha256 of the npz) - integrity-checked on load.

- **Restore**: ``restore_megakernel`` / ``restore_stream`` /
  ``restore_resident`` validate the manifest against a freshly-built
  (same-code) runner and relaunch MID-GRAPH: the re-entry stages all value
  slots and rebuilds row free stacks from completion tombstones (the
  sharded steal loop's re-entrant discipline), so for a deterministic
  workload *checkpoint at round k + restore + run to completion* is
  bit-identical to the uninterrupted run (asserted in
  tests/test_checkpoint.py under interpret mode).

- **Elastic resume** (``CheckpointBundle.reshard``): a resident-mesh
  bundle taken on N chips restores onto M != N chips by re-homing the
  per-chip queues host-side - the same task-conservation semantics as the
  PR 2 dead-chip re-homing path (link-free migratable rows move whole;
  totals conserved), applied at rest instead of over ICI. Rows that cannot
  re-home (successor links, homed-migration proxies, dynamic out slots)
  are refused with a diagnostic naming the offending row.

- **Preemption wiring** (``checkpoint_on_preempt``): SIGTERM (via
  ``resilience.install_preempt_handler``), the ``HCLIB_TPU_PREEMPT`` env,
  or the watchdog's optional checkpoint rung
  (``HCLIB_TPU_WATCHDOG_CHECKPOINT``) fire registered preemption hooks;
  binding a stream quiesces it so the driving ``run_stream`` returns a
  restorable snapshot instead of losing the graph - checkpoint, then stop.

- **Durable store** (``BundleStore``): a generational on-disk store of
  bundles with crash-safe publish (stage to a temp dir, fsync, atomic
  rename, generation pointer written LAST - a torn save is never
  visible), bounded retention (``keep=K`` generations), and
  self-healing restore: ``load_latest()`` walks generations
  newest-first, quarantines torn/corrupt/version-mismatched ones aside
  with a typed ``BundleFault`` report (metrics-counted, TR_CKPT-traced
  via the CK_* subcodes), and resumes from the newest generation that
  validates. An unrecoverable store raises so the caller can poison
  outstanding futures through the serving degradation ladder instead of
  hanging. The autoscaler's preempt hook writes through it.

Caveats (stated, not hidden): host-side tasks and help-first host
execution are NOT captured - the bundle holds device scheduler state only,
so checkpoint the device layer and re-enter the host program idempotently
(the same caveat class as ``help_finish``'s documented timeout limit).
Resharding a bundle whose live rows carry successor links or per-device
data buffers is refused; exported wait tables RE-HOME across mesh sizes
(the parked rows deal with their waits as one unit), with the refusal
narrowed to waits whose satisfier sits in unexported host residue
(``meta['host_residue']`` - the puts target the OLD device coordinates,
so they must be re-issued against the resumed mesh before a resize).
"""

from __future__ import annotations

import contextlib
import hashlib
import io
import json
import os
import shutil
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import resilience

__all__ = [
    "BUNDLE_VERSION",
    "BundleFault",
    "BundleStore",
    "CheckpointBundle",
    "CheckpointError",
    "default_store",
    "snapshot_megakernel",
    "snapshot_stream",
    "snapshot_resident",
    "restore_megakernel",
    "restore_stream",
    "restore_resident",
    "checkpoint_on_preempt",
]

MAGIC = "hclib-tpu-checkpoint"
BUNDLE_VERSION = 1

# state dict keys serialized for every kind (data buffers ride as
# ``data/<name>`` entries; the stream kind adds ``ring_rows`` - plus the
# per-tenant ``tctl``/``tstats`` counter blocks when the front door runs
# tenant lanes, and the per-row submit-token table ``etok`` when the
# completion-mailbox egress runs (device/egress.py; tokens of
# installed-but-unretired rows survive the cut so their futures resolve
# after resume) - the resident kind adds its exported wait table and -
# when injecting - the per-device ring residue + cursor words. A
# telemetry-enabled stream (device/telemetry.py) adds the echoed
# histogram/gauge block ``tele`` and per-row stamp table ``tlat`` so
# the round timebase and per-tenant latency totals stay cumulative
# across the cut).
_STATE_KEYS = ("tasks", "succ", "ready", "counts", "ivalues")
_OPT_KEYS = (
    "ring_rows", "waits", "ictl", "tctl", "tstats", "etok",
    "tele", "tlat",
)

# Descriptor-word indices, bound once (descriptor ABI, device/descriptor).
from ..device.descriptor import (  # noqa: E402
    DESC_WORDS,
    F_CSR_N,
    F_DEP,
    F_FN,
    F_HOME,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    ring_len,
)


class CheckpointError(RuntimeError):
    """A bundle failed validation: corrupt artifact, version mismatch, or
    a restore target whose configuration contradicts the manifest."""


def _fsync_dir(path: str) -> None:
    """fsync a DIRECTORY so a just-renamed entry survives power loss
    (the rename itself is atomic; its durability needs the parent
    flushed). Best-effort: some filesystems refuse O_RDONLY dir fds."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _kernel_meta(mk) -> Dict[str, Any]:
    m = {
        "kernel_names": list(mk.kernel_names),
        "capacity": int(mk.capacity),
        "num_values": int(mk.num_values),
        "succ_capacity": int(mk.succ_capacity),
        "data_specs": {
            k: {"shape": list(s.shape), "dtype": str(np.dtype(s.dtype))}
            for k, s in mk.data_specs.items()
        },
    }
    # Dynamic-graph builds stamp their layout (plain ints + the bound
    # update stream) into the manifest: reshard's canonical-rebuild path
    # keys off ``meta['dyngraph']`` (device/dyngraph.reshard_dyngraph).
    dg = getattr(mk, "_dyngraph", None)
    if dg is not None:
        m["dyngraph"] = {
            k: (list(map(list, v)) if k == "updates" else v)
            for k, v in dg.items()
        }
    return m


def _kind_classes(mk) -> Dict[str, str]:
    """Build-time migratability classification for the bundle manifest
    (hclib_tpu.analysis; memoized on the megakernel) - ``reshard``
    reads it back for upfront whole-program diagnostics. Best-effort:
    a kernel table the shim cannot interpret classes 'unknown'."""
    try:
        from ..analysis import classify_megakernel

        return dict(classify_megakernel(mk))
    except Exception:  # noqa: BLE001 - manifest enrichment only
        return {}


def _kernel_table_diff(mine: List[str], theirs: List[str]) -> str:
    """Positional diff of two kernel tables (the ``diff()``-style
    context a table mismatch error carries): F_FN words index by
    POSITION, so 'same names, different order' is the silent-wrong-
    kernel hazard and the per-position story is what fixes it."""
    lines = []
    for i in range(max(len(mine), len(theirs))):
        a = mine[i] if i < len(mine) else "<absent>"
        b = theirs[i] if i < len(theirs) else "<absent>"
        if a != b:
            lines.append(f"[{i}] {a!r} here != {b!r} in the bundle")
    return "; ".join(lines)


def _where(bundle_or_meta) -> str:
    """Location context for a diagnostic: the bundle's source path and
    store generation when it came off disk, empty for in-memory ones."""
    src = getattr(bundle_or_meta, "source_path", None)
    gen = getattr(bundle_or_meta, "generation", None)
    if src is None and gen is None:
        return ""
    parts = []
    if src is not None:
        parts.append(str(src))
    if gen is not None:
        parts.append(f"generation {gen}")
    return f" ({', '.join(parts)})"


def _check_kernel_meta(mk, meta: Dict[str, Any], where: str = "") -> None:
    """The restore target must be the SAME program shape the bundle was
    taken from: descriptor F_FN words index the kernel table by position,
    so a renamed/reordered table would silently run the wrong kernels.
    ``where`` carries the bundle's path/generation into every error."""
    mine = _kernel_meta(mk)
    if mine["kernel_names"] != meta.get("kernel_names"):
        detail = _kernel_table_diff(
            list(mine["kernel_names"]),
            list(meta.get("kernel_names") or []),
        )
        raise CheckpointError(
            f"restore target mismatch{where}: the kernel_names table "
            f"differs positionally - {detail} - rebuild the megakernel "
            "exactly as checkpointed (names, order, capacities)"
        )
    for key in ("capacity", "num_values", "succ_capacity"):
        if mine[key] != meta.get(key):
            raise CheckpointError(
                f"restore target mismatch{where}: {key} is {mine[key]!r} "
                f"here but {meta.get(key)!r} in the bundle - rebuild the "
                "megakernel exactly as checkpointed (names, order, "
                "capacities)"
            )
    if set(mine["data_specs"]) != set(meta.get("data_specs", {})):
        raise CheckpointError(
            f"restore target mismatch{where}: data buffers "
            f"{sorted(mine['data_specs'])} != bundle "
            f"{sorted(meta.get('data_specs', {}))}"
        )


class CheckpointBundle:
    """One checkpoint: ``kind`` ("megakernel" | "stream" | "resident"),
    ``meta`` (the JSON manifest body) and ``arrays`` (flat name ->
    np.ndarray; data buffers under ``data/<name>``)."""

    def __init__(self, kind: str, meta: Dict[str, Any],
                 arrays: Dict[str, np.ndarray]) -> None:
        self.kind = kind
        self.meta = meta
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}
        # Provenance, set by load()/BundleStore: every later diagnostic
        # (version/program mismatch, reshard refusal) names WHERE the
        # offending artifact lives instead of just what is wrong.
        self.source_path: Optional[str] = None
        self.generation: Optional[int] = None

    # ---- state <-> arrays ----

    @staticmethod
    def _flatten_state(state: Dict[str, Any],
                       meta: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Flatten a runner state dict into named arrays. Extension
        dtypes numpy cannot round-trip through npz (bfloat16 data
        buffers save as raw ``|V2`` void and reload unusable) are stored
        as same-width unsigned views with the true dtype recorded in
        ``meta['dtypes']`` - ``state()`` views them back bit-exactly."""
        arrays = {k: np.asarray(state[k]) for k in _STATE_KEYS}
        for k in _OPT_KEYS:
            if k in state:
                arrays[k] = np.asarray(state[k])
        for name, buf in (state.get("data") or {}).items():
            arrays[f"data/{name}"] = np.asarray(buf)
        dtypes: Dict[str, str] = {}
        for k, v in arrays.items():
            if v.dtype.kind not in "biufc":
                dtypes[k] = str(v.dtype)
                arrays[k] = v.view(f"u{v.dtype.itemsize}")
        if dtypes:
            meta["dtypes"] = dtypes
        return arrays

    def _restore_dtype(self, key: str, arr: np.ndarray) -> np.ndarray:
        name = (self.meta.get("dtypes") or {}).get(key)
        if name is None:
            return arr.copy()
        import ml_dtypes  # noqa: F401  (registers bfloat16 & friends)

        return arr.view(np.dtype(name)).copy()

    def state(self) -> Dict[str, Any]:
        """The resumable state dict (what ``Megakernel.resume`` /
        ``run_stream(resume_state=)`` / ``run(resume_state=)`` take)."""
        st: Dict[str, Any] = {
            k: self._restore_dtype(k, self.arrays[k]) for k in _STATE_KEYS
        }
        for k in _OPT_KEYS:
            if k in self.arrays:
                st[k] = self.arrays[k].copy()
        st["data"] = {
            k.split("/", 1)[1]: self._restore_dtype(k, v)
            for k, v in self.arrays.items()
            if k.startswith("data/")
        }
        return st

    # ---- persistence ----

    def save(self, path: str, metrics=None, fsync: bool = False,
             fault_plan=None) -> Dict[str, Any]:
        """Write the bundle as a directory: ``state.npz`` +
        ``manifest.json`` (magic, version, kind, meta, npz sha256).
        Returns {bundle_bytes, save_s, sha256}; with ``metrics`` (a
        MetricsRegistry) the stats are recorded under "checkpoint".
        ``fsync=True`` flushes both members and the directory (the
        BundleStore publish discipline); ``fault_plan`` routes the
        bytes through the chaos disk sites (torn write, bit flip,
        missing/truncated manifest) for the durability soak."""
        t0 = time.monotonic()
        os.makedirs(path, exist_ok=True)
        buf = io.BytesIO()
        np.savez(buf, **self.arrays)
        blob = buf.getvalue()
        sha = hashlib.sha256(blob).hexdigest()
        if fault_plan is not None:
            blob = fault_plan.on_bundle_blob(blob)
        npz_path = os.path.join(path, "state.npz")
        with open(npz_path, "wb") as f:
            f.write(blob)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        manifest = {
            "magic": MAGIC,
            "version": BUNDLE_VERSION,
            "kind": self.kind,
            "created_unix": time.time(),
            "sha256": sha,
            "meta": self.meta,
        }
        text = json.dumps(manifest, indent=1, sort_keys=True)
        if fault_plan is not None:
            text = fault_plan.on_manifest_text(text)
        if text is not None:  # a chaos-dropped manifest never lands
            with open(os.path.join(path, "manifest.json"), "w") as f:
                f.write(text)
                if fsync:
                    f.flush()
                    os.fsync(f.fileno())
        if fsync:
            _fsync_dir(path)
        stats = {
            "bundle_bytes": len(blob),
            "save_s": round(time.monotonic() - t0, 6),
            "sha256": sha,
        }
        if metrics is not None:
            rec = {"bundle_bytes": stats["bundle_bytes"],
                   "save_s": stats["save_s"]}
            for k in ("quiesce_latency_s", "quiesce_round", "executed_at"):
                if k in self.meta and self.meta[k] is not None:
                    rec[k] = self.meta[k]
            metrics.record("checkpoint", rec)
        return stats

    @classmethod
    def load(cls, path: str,
             generation: Optional[int] = None) -> "CheckpointBundle":
        """Load + integrity-check a saved bundle. Raises CheckpointError
        on a missing/foreign manifest, a version from the future, or an
        npz whose sha256 disagrees with the manifest (bit rot, truncated
        copy, tampering). Every error names the offending file path -
        and the store generation, when ``generation`` is passed (as
        ``BundleStore`` does) - so a multi-generation post-mortem
        points at ONE artifact, not "some bundle somewhere"."""
        gen = "" if generation is None else f" (generation {generation})"
        man_path = os.path.join(path, "manifest.json")
        npz_path = os.path.join(path, "state.npz")
        try:
            with open(man_path) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointError(
                f"unreadable checkpoint manifest {man_path}{gen}: {e}"
            )
        if manifest.get("magic") != MAGIC:
            raise CheckpointError(
                f"{man_path}{gen} is not a {MAGIC} bundle "
                f"(magic={manifest.get('magic')!r})"
            )
        try:
            version = int(manifest.get("version", -1))
        except (TypeError, ValueError):
            version = -1  # a mangled field is a corrupt manifest
        if version != BUNDLE_VERSION:
            raise CheckpointError(
                f"bundle version {manifest.get('version')!r} in "
                f"{man_path}{gen} != supported {BUNDLE_VERSION}: "
                "re-checkpoint with this build or restore with the "
                "build that wrote it"
            )
        try:
            with open(npz_path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise CheckpointError(
                f"unreadable checkpoint state {npz_path}{gen}: {e}"
            )
        sha = hashlib.sha256(blob).hexdigest()
        if sha != manifest.get("sha256"):
            raise CheckpointError(
                f"checkpoint state corrupt: sha256 {sha[:12]}... != "
                f"manifest {str(manifest.get('sha256'))[:12]}... "
                f"({npz_path}{gen})"
            )
        try:
            with np.load(io.BytesIO(blob)) as z:
                arrays = {k: z[k] for k in z.files}
        except (OSError, ValueError, KeyError) as e:
            # A blob that hashes right but will not parse (a manifest
            # re-stamped over a torn npz) is corrupt, not a crash.
            raise CheckpointError(
                f"unparseable checkpoint state {npz_path}{gen}: {e}"
            )
        b = cls(manifest["kind"], manifest.get("meta", {}), arrays)
        b.source_path = path
        b.generation = generation
        return b

    # ---- elastic resume (resident mesh only) ----

    def reshard(self, ndev_new: int) -> "CheckpointBundle":
        """Re-home a resident-mesh bundle's per-chip queues onto
        ``ndev_new`` devices (N -> M re-sharding) - checkpoint-time
        elasticity with the PR 2 dead-chip re-homing semantics: only
        link-free migratable rows move (whole, conserving the pending
        total), dealt round-robin; per-device accumulator value slots
        fold by SUM into the new devices' symmetric host regions (the
        ``ShardedMegakernel.migratable_fns`` contract: migratable kernels
        write accumulate-style slots the host combines) and executed
        counters fold the same way, so executed + pending totals are
        conserved exactly. Refused with a diagnostic when any live row
        carries successor links / a home-link / a dynamic out slot, or
        when the kernel has per-device data buffers (no generic fold
        exists for those).

        Exported wait tables RE-HOME: a wait-parked row (its dep
        counter holds exactly one bump per wait parked on it) moves as
        ONE UNIT with all its waits - parked rows group per channel and
        deal round-robin onto the new roster, allocated but NOT in the
        ready ring, with the wait entries rewritten to the new (device,
        row) coordinates; wait counts and per-channel need sums are
        conserved exactly. Needs stay in their export rebasing (arrival
        counters restart at zero on every resume), and host puts issued
        AFTER the resume target the resumed roster, so re-homed waits
        fire exactly as on the original mesh. The one refusal left:
        waits whose satisfier sits in unexported host residue
        (``meta['host_residue']``, declared at snapshot time) - those
        puts were aimed at the OLD coordinates, so the whole-program
        diagnostic names every stranded channel and the fix (re-issue
        the residue on the original size, or drain it first)."""
        from ..device.megakernel import (
            C_ALLOC, C_EXECUTED, C_PENDING, C_VALLOC,
        )

        if self.kind != "resident":
            raise CheckpointError(
                f"reshard applies to resident-mesh bundles, not {self.kind}"
            )
        try:
            ndev_new = int(ndev_new)
        except (TypeError, ValueError):
            raise CheckpointError(
                f"reshard wants an integer device count, got {ndev_new!r}"
            )
        tasks = self.arrays["tasks"]
        counts = self.arrays["counts"]
        ivalues = self.arrays["ivalues"]
        ndev, cap, _ = tasks.shape
        if ndev_new < 1 or (ndev_new & (ndev_new - 1)):
            raise CheckpointError(
                f"reshard wants a power-of-two device count >= 1, got "
                f"{ndev_new} (the resident mesh's hypercube hop schedule "
                "is pof2-only; an evacuation drops to the next pof2 "
                "below the survivor count)"
            )
        if self.meta.get("dyngraph"):
            # Mutable-adjacency bundles DO carry per-device data buffers
            # (the spliced block rows) - but their layout stamp gives
            # reshard what the generic path lacks: a canonical rebuild
            # (static rows + union-applied updates in uid order) every
            # new device can share. Delegate wholesale; the dyngraph
            # merge owns its own eligibility/conservation story.
            from ..device.dyngraph import reshard_dyngraph

            return reshard_dyngraph(self, ndev_new)
        if any(k.startswith("data/") for k in self.arrays):
            raise CheckpointError(
                "reshard cannot re-home per-device data buffers: restore "
                "onto the original mesh size, or drain and re-partition "
                "at the application level"
            )
        waits = self.arrays.get("waits")
        # Parse the exported wait table into parked[(d, row)] ->
        # [(chan, need), ...]. Needs are already rebased (need minus the
        # old device's arrival count at export), and resume restarts
        # every arrival counter at zero, so a re-homed entry means the
        # same thing on ANY roster: "this row fires after `need` more
        # puts on `chan` reach its device". The only waits that cannot
        # re-home are those whose remaining puts sit in unexported host
        # residue - the caller aimed them at the OLD (device, row)
        # coordinates (declared via ``meta['host_residue']``:
        # {channel name: outstanding put count}).
        parked: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        if waits is not None:
            warr = np.asarray(waits)
            chan_names = list(self.meta.get("channels") or [])

            def _chan(ch: int) -> str:
                return (
                    repr(chan_names[ch])
                    if 0 <= ch < len(chan_names) else f"id {ch}"
                )

            residue = {
                str(k): int(v)
                for k, v in dict(self.meta.get("host_residue") or {}).items()
                if int(v) > 0
            }
            stranded: Dict[int, List[Tuple[int, int, int]]] = {}
            for d in range(warr.shape[0]):
                for i in range(int(warr[d, 0, 0])):
                    ch, need, row = (int(x) for x in warr[d, 1 + i])
                    name = (
                        chan_names[ch]
                        if 0 <= ch < len(chan_names) else None
                    )
                    if need > 0 and name is not None and name in residue:
                        stranded.setdefault(ch, []).append((d, row, need))
                    parked.setdefault((d, row), []).append((ch, need))
            if stranded:
                # Whole-program refusal (the ISSUE 12 discipline): one
                # error names EVERY stranded channel, its wait and
                # residue counts, and the fix - not the first wait hit.
                per_chan = [
                    f"channel {_chan(ch)}: {len(ws)} wait(s) needing "
                    f"{sum(n for _d, _r, n in ws)} more arrival(s) vs "
                    f"{residue[chan_names[ch]]} unexported host put(s)"
                    for ch, ws in sorted(stranded.items())
                ]
                d0, r0, n0 = stranded[min(stranded)][0]
                raise CheckpointError(
                    f"reshard: "
                    f"{sum(len(ws) for ws in stranded.values())} pending "
                    f"wait(s) on {len(stranded)} channel(s) have their "
                    f"satisfier in unexported host residue "
                    f"({'; '.join(per_chan)}); e.g. device {d0} row {r0} "
                    f"still needs {n0} arrival(s) - the outstanding puts "
                    "target the original (device, row) coordinates, so "
                    "resume on the original mesh size and re-issue (or "
                    "drain) the residue before resizing"
                )
        V = ivalues.shape[1]
        va = int(counts[:, C_VALLOC].max())
        # Whole-program eligibility scan (ISSUE 12): instead of refusing
        # at the FIRST offending row, collect every violation, fold it
        # per kernel kind, and - when the bundle carries the build-time
        # ``kind_classes`` classification (Megakernel.describe() /
        # hclib_tpu.analysis) - lead the diagnostic with the per-kind
        # story, so one error names everything that must drain before a
        # resize instead of a row-by-row whack-a-mole.
        kind_names = list(self.meta.get("kernel_names") or [])
        kind_classes = dict(self.meta.get("kind_classes") or {})
        violations: List[Tuple[int, int, int, str]] = []
        live_rows: List[np.ndarray] = []
        parked_rows: List[Tuple[int, int, np.ndarray]] = []
        for d in range(ndev):
            alloc = int(counts[d][C_ALLOC])
            for i in range(alloc):
                row = tasks[d, i]
                if int(row[F_DEP]) == -1:
                    continue  # tombstone (completed/exported)
                bad = None
                nwaits = len(parked.get((d, i), ()))
                dep = int(row[F_DEP])
                if dep != nwaits:
                    # A wait-parked row carries exactly one dep bump per
                    # wait parked on it (the export contract); anything
                    # else is a real dependency the deal cannot re-home.
                    bad = (
                        f"a dependency counter {dep} != its "
                        f"{nwaits} parked wait(s)"
                        if nwaits else "a nonzero dependency counter"
                    )
                elif (
                    int(row[F_SUCC0]) != NO_TASK
                    or int(row[F_SUCC1]) != NO_TASK
                    or int(row[F_CSR_N]) != 0
                ):
                    bad = "successor links"
                elif int(row[F_HOME]) >= 0:
                    bad = "a migration home-link"
                elif int(row[F_OUT]) >= va:
                    bad = f"a dynamic out slot ({int(row[F_OUT])} >= {va})"
                if bad is not None:
                    violations.append((d, i, int(row[F_FN]), bad))
                    continue
                if nwaits:
                    parked_rows.append((d, i, row.copy()))
                else:
                    live_rows.append(row.copy())
        if violations:
            by_kind: Dict[int, int] = {}
            for _d, _i, fn, _bad in violations:
                by_kind[fn] = by_kind.get(fn, 0) + 1
            kinds = []
            for fn, n in sorted(by_kind.items()):
                name = (
                    kind_names[fn]
                    if 0 <= fn < len(kind_names) else f"id {fn}"
                )
                cls = kind_classes.get(str(name))
                kinds.append(
                    f"{name!r}"
                    + (f" [{cls}]" if cls else "")
                    + f": {n} row(s)"
                )
            d0, i0, _fn0, bad0 = violations[0]
            raise CheckpointError(
                f"reshard: {len(violations)} live row(s) across "
                f"{ndev} device(s) are not link-free "
                f"({'; '.join(kinds)}); e.g. device {d0} row {i0} "
                f"carries {bad0}; only ready link-free rows re-home "
                "across mesh sizes (quiesce drains dependent subgraphs "
                "first, or restore onto the original mesh size)"
            )
        pend_total = int(counts[:, C_PENDING].sum())
        if pend_total != len(live_rows) + len(parked_rows):
            raise CheckpointError(
                f"reshard conservation check failed: {pend_total} pending "
                f"!= {len(live_rows)} live + {len(parked_rows)} "
                "wait-parked rows - the bundle is not a clean quiesce "
                "snapshot"
            )
        parts: List[List[np.ndarray]] = [[] for _ in range(ndev_new)]
        for i, row in enumerate(live_rows):
            parts[i % ndev_new].append(row)
        # Wait-parked rows re-home as UNITS - each row moves with every
        # wait parked on it. Deterministic order (first-wait channel,
        # then original coordinates) grouped per channel, then dealt
        # round-robin, so a channel's waiters spread across the new
        # roster the same way on every run.
        park_parts: List[List[Tuple[int, int, np.ndarray]]] = [
            [] for _ in range(ndev_new)
        ]
        park_order = sorted(
            parked_rows,
            key=lambda e: (min(ch for ch, _n in parked[(e[0], e[1])]),
                           e[0], e[1]),
        )
        for k, entry in enumerate(park_order):
            park_parts[k % ndev_new].append(entry)
        for j, p in enumerate(parts):
            if len(p) + len(park_parts[j]) > cap:
                # The M=1 (and any aggressive scale-in) failure mode:
                # the folded backlog must still fit each survivor's
                # task table. Diagnose with the numbers that fix it.
                total = len(live_rows) + len(parked_rows)
                raise CheckpointError(
                    f"reshard {ndev} -> {ndev_new}: device {j} would "
                    f"hold {len(p) + len(park_parts[j])} rows > capacity "
                    f"{cap} ({total} live+parked rows total); scale in "
                    f"less aggressively (>= {-(-total // cap)} "
                    "devices) or rebuild with a larger capacity"
                )
        tasks_new = np.zeros((ndev_new, cap, DESC_WORDS), np.int32)
        ready_new = np.full((ndev_new, ring_len(cap)), NO_TASK, np.int32)
        counts_new = np.zeros((ndev_new, 8), np.int32)
        ivalues_new = np.zeros((ndev_new, V), np.int32)
        waits_new = None
        if waits is not None:
            warr = np.asarray(waits)
            max_w = warr.shape[1] - 1
            waits_new = np.zeros(
                (ndev_new,) + warr.shape[1:], np.int32
            )
        for j, p in enumerate(parts):
            for i, row in enumerate(p):
                tasks_new[j, i] = row
                ready_new[j, i] = i
            n = len(p)
            # Parked rows land AFTER the ready rows: allocated (and
            # counted pending) but NOT in the ready ring - resume's
            # no-bump restage leaves their dep counters holding the
            # wait bumps, exactly the exported shape.
            for k, (od, orow, row) in enumerate(park_parts[j]):
                slot = n + k
                tasks_new[j, slot] = row
                if waits_new is not None:
                    for ch, need in parked[(od, orow)]:
                        w = int(waits_new[j, 0, 0])
                        if w >= max_w:
                            raise CheckpointError(
                                f"reshard {ndev} -> {ndev_new}: device "
                                f"{j} would park > {max_w} wait(s); "
                                "scale in less aggressively or raise "
                                "max_waits"
                            )
                        waits_new[j, 1 + w] = (ch, need, slot)
                        waits_new[j, 0, 0] = w + 1
            total_j = n + len(park_parts[j])
            counts_new[j][0] = 0  # head
            counts_new[j][1] = n  # tail (ready ring: live rows only)
            counts_new[j][C_ALLOC] = total_j
            counts_new[j][C_PENDING] = total_j
            counts_new[j][C_VALLOC] = va
        # Fold the old devices' accumulator host regions and executed
        # counters mod M: column-wise sums (what the host combines at the
        # end) are conserved exactly.
        for d in range(ndev):
            j = d % ndev_new
            ivalues_new[j][:va] += ivalues[d][:va]
            counts_new[j][C_EXECUTED] += int(counts[d][C_EXECUTED])
        scap = self.arrays["succ"].shape[1]
        succ_new = np.full((ndev_new, scap), NO_TASK, np.int32)
        arrays = {
            "tasks": tasks_new, "succ": succ_new, "ready": ready_new,
            "counts": counts_new, "ivalues": ivalues_new,
        }
        if waits_new is not None:
            warr = np.asarray(waits)
            # Post-deal conservation: total wait count and per-channel
            # need sums must survive the re-home exactly.
            if int(waits_new[:, 0, 0].sum()) != int(warr[:, 0, 0].sum()):
                raise CheckpointError(
                    "reshard wait conservation check failed: "
                    f"{int(waits_new[:, 0, 0].sum())} re-homed wait(s) "
                    f"!= {int(warr[:, 0, 0].sum())} exported"
                )
            need_old: Dict[int, int] = {}
            need_new: Dict[int, int] = {}
            for arr, acc in ((warr, need_old), (waits_new, need_new)):
                for d in range(arr.shape[0]):
                    for i in range(int(arr[d, 0, 0])):
                        ch, need, _row = (int(x) for x in arr[d, 1 + i])
                        acc[ch] = acc.get(ch, 0) + need
            if need_old != need_new:
                raise CheckpointError(
                    "reshard wait conservation check failed: per-channel "
                    f"need sums diverged ({need_old} -> {need_new})"
                )
            arrays["waits"] = waits_new
        if "ring_rows" in self.arrays:
            # Inject-ring residue re-homes like the task rows: injected
            # descriptors are link-free by construction (inject refuses
            # dep_count != 0), so the rows are location-free and deal
            # round-robin; the consumed cursor was already folded into
            # the packed-from-zero representation at export.
            rr = np.asarray(self.arrays["ring_rows"])
            ic = np.asarray(self.arrays["ictl"])
            R = rr.shape[1]
            residue = [
                rr[d, i]
                for d in range(rr.shape[0])
                for i in range(int(ic[d, 0]))
            ]
            rr_new = np.zeros((ndev_new, R) + rr.shape[2:], np.int32)
            ic_new = np.zeros((ndev_new, 8), np.int32)
            ic_new[:, 1] = ic[:, 1].max() if ic.size else 0  # close flag
            for i, row in enumerate(residue):
                j = i % ndev_new
                slot = ic_new[j, 0]
                if slot >= R:
                    raise CheckpointError(
                        f"reshard {ndev} -> {ndev_new}: device {j} would "
                        f"hold > {R} inject-ring residue rows "
                        f"({len(residue)} total); scale in less "
                        "aggressively or raise ring_capacity"
                    )
                rr_new[j, slot] = row
                ic_new[j, 0] = slot + 1
            if int(ic_new[:, 0].sum()) != len(residue):
                raise CheckpointError(
                    "reshard ring conservation check failed"
                )
            arrays["ring_rows"] = rr_new
            arrays["ictl"] = ic_new
        # Mesh-tenancy counter blocks (aggregate (T, 8) tctl/tstats,
        # MeshTenantTable.export_state): device-count-free by
        # construction, so a reshard passes them through untouched -
        # per-tenant accepted/completed/expired totals are conserved
        # across N -> M exactly like the tagged residue rows above.
        for k in ("tctl", "tstats"):
            if k in self.arrays:
                arrays[k] = np.asarray(self.arrays[k]).copy()
        meta = dict(self.meta)
        meta["ndev"] = ndev_new
        meta["resharded_from"] = int(ndev)
        return CheckpointBundle("resident", meta, arrays)

    def diff(self, other: "CheckpointBundle") -> Dict[str, Any]:
        """Structural comparison of two bundles, for the bit-identity
        assertions the storm tests make: returns ``{'equal': bool,
        'kind': ..., 'only_self': [...], 'only_other': [...],
        'mismatched': {key: {n, max_abs}}}``. Arrays compare bit-exactly
        (shape + values); dtype views are compared raw (two bundles of
        the same build store identically)."""
        only_self = sorted(set(self.arrays) - set(other.arrays))
        only_other = sorted(set(other.arrays) - set(self.arrays))
        mismatched: Dict[str, Any] = {}
        for k in sorted(set(self.arrays) & set(other.arrays)):
            a, b = self.arrays[k], other.arrays[k]
            if a.shape != b.shape or a.dtype != b.dtype:
                mismatched[k] = {
                    "shape": [list(a.shape), list(b.shape)],
                    "dtype": [str(a.dtype), str(b.dtype)],
                }
                continue
            if not np.array_equal(a, b):
                av = a.astype(np.int64) if a.dtype.kind in "biu" else a
                bv = b.astype(np.int64) if b.dtype.kind in "biu" else b
                d = np.abs(av - bv)
                mismatched[k] = {
                    "n": int((av != bv).sum()),
                    "max_abs": float(d.max()),
                }
        return {
            "equal": not (only_self or only_other or mismatched)
            and self.kind == other.kind,
            "kind": [self.kind, other.kind],
            "only_self": only_self,
            "only_other": only_other,
            "mismatched": mismatched,
        }


# ---------------------------------------------------------- durable store

@dataclass
class BundleFault:
    """One generation ``BundleStore.load_latest`` could not use: typed
    so chaos harnesses (and operators) can assert on WHAT failed, not
    parse message text. ``reason`` is one of ``torn`` (manifest missing
    or unparseable - the mid-save crash signature), ``corrupt`` (sha256
    or npz-payload mismatch - bit rot), ``version`` (format from a
    different build), ``foreign`` (not a bundle at all)."""

    generation: int
    path: str
    reason: str
    error: str


def _classify_fault(msg: str) -> str:
    low = msg.lower()
    if "magic" in low:
        return "foreign"
    if "sha256" in low or "unparseable" in low:
        return "corrupt"  # payload damage (flip/truncation past the sha)
    if "version" in low:
        return "version"
    if "manifest" in low or "missing" in low:
        return "torn"  # the mid-save crash signature: no valid manifest
    return "corrupt"


class BundleStore:
    """Generational on-disk store of ``CheckpointBundle``s with
    crash-safe publish and self-healing restore.

    Layout under ``root``::

        gen-000001/          one published generation (a bundle dir)
        gen-000002/
        CURRENT              newest generation number (a hint, not an
                             authority - load_latest() walks the dirs)
        quarantine/          generations load_latest() refused, moved
                             aside with their fault recorded

    Publish discipline (the crash-safety invariant): ``save`` stages
    the bundle into ``.tmp-gen-N`` (members written and - with
    ``fsync`` on - flushed to disk), fsyncs the staging dir, then
    atomically renames it to ``gen-N`` and fsyncs ``root``; the
    ``CURRENT`` pointer is rewritten LAST (tmp + rename). A crash at
    ANY byte of that sequence leaves either the previous store state or
    the new generation - never a visible torn bundle
    (``analysis/explore.py``'s ``BundleStoreModel`` certifies the
    ordering over every crash x concurrent-load interleaving).

    Restore discipline (self-healing): ``load_latest`` walks
    generations NEWEST-FIRST; one that fails validation is moved to
    ``quarantine/`` with a typed ``BundleFault`` appended to
    ``self.faults`` (metrics ``checkpoint.quarantined``, trace
    CK_QUARANTINE), and the walk continues - the newest generation that
    validates wins (``checkpoint.fallback`` when it was not the newest
    on disk). An EMPTY walk raises ``CheckpointError`` listing every
    fault so the caller can poison outstanding futures through the
    serving degradation ladder instead of hanging on a resume that will
    never come.

    Knobs: ``keep`` (default ``HCLIB_TPU_CKPT_KEEP``, 3) bounds
    retention - older generations are pruned after each publish;
    ``fsync`` (default ``HCLIB_TPU_CKPT_FSYNC``, on) trades crash
    durability for speed in tests; ``fault_plan`` routes the PR 13 disk
    chaos sites (torn blob, bit flip, manifest loss, preempt mid-save /
    mid-restore) through the store for ``chaos_soak --durability``.
    """

    def __init__(self, root: str, keep: Optional[int] = None,
                 fsync: Optional[bool] = None, metrics=None,
                 fault_plan=None) -> None:
        from . import env as _env

        self.root = str(root)
        if keep is None:
            keep = _env.env_int("HCLIB_TPU_CKPT_KEEP", 3)
        self.keep = int(keep)
        if self.keep < 1:
            raise CheckpointError(
                f"BundleStore keep={self.keep} must be >= 1 (retention "
                "of zero generations would unpublish every save)"
            )
        if fsync is None:
            fsync = _env.env_bool("HCLIB_TPU_CKPT_FSYNC", True)
        self.fsync = bool(fsync)
        self.metrics = metrics
        self.fault_plan = fault_plan
        self.faults: List[BundleFault] = []
        # Host-emitted TR_CKPT records ([tag, ordinal, -(1+CK_*), gen]);
        # trace_info() brackets them for tools/timeline.py.
        self.events: List[List[int]] = []
        self._t0_ns = time.monotonic_ns()
        os.makedirs(self.root, exist_ok=True)

    # -- internals ----------------------------------------------------

    def _trace(self, code: int, generation: int) -> None:
        from ..device import tracebuf as tb

        self.events.append(
            [tb.TR_CKPT, len(self.events), -(1 + code), int(generation)]
        )

    def _count(self, name: str, **fields) -> None:
        if self.metrics is not None:
            self.metrics.record_event(name, fields)

    def path_of(self, generation: int) -> str:
        return os.path.join(self.root, f"gen-{int(generation):06d}")

    def generations(self) -> List[int]:
        """Published generation numbers, ascending (staging and
        quarantine dirs excluded)."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        for n in names:
            if n.startswith("gen-"):
                try:
                    out.append(int(n[4:]))
                except ValueError:
                    continue
        return sorted(out)

    # -- publish ------------------------------------------------------

    def save(self, bundle: CheckpointBundle) -> int:
        """Publish ``bundle`` as the next generation; returns its
        number. Crash-safe per the class docstring: an interruption
        anywhere in here leaves the staging dir invisible to
        ``load_latest`` and the store at its previous state."""
        from ..device import tracebuf as tb

        gens = self.generations()
        gen = (gens[-1] + 1) if gens else 1
        tmp = os.path.join(self.root, f".tmp-gen-{gen}")
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        bundle.save(tmp, fsync=self.fsync, fault_plan=self.fault_plan)
        if self.fault_plan is not None:
            # The preempt-mid-save chaos site: fires BEFORE the rename,
            # so an injected kill proves a staged-but-unpublished save
            # is invisible.
            self.fault_plan.on_store_publish()
        os.rename(tmp, self.path_of(gen))
        if self.fsync:
            _fsync_dir(self.root)
        # Pointer LAST, and only ever to a published generation: a
        # torn pointer is harmless because load_latest treats it as a
        # hint, never an authority.
        cur_tmp = os.path.join(self.root, ".tmp-CURRENT")
        with open(cur_tmp, "w") as f:
            f.write(f"{gen}\n")
            if self.fsync:
                f.flush()
                os.fsync(f.fileno())
        os.rename(cur_tmp, os.path.join(self.root, "CURRENT"))
        if self.fsync:
            _fsync_dir(self.root)
        for old in self.generations()[:-self.keep]:
            shutil.rmtree(self.path_of(old), ignore_errors=True)
        self._trace(tb.CK_SAVE, gen)
        self._count("checkpoint.save", generation=gen,
                    kept=len(self.generations()))
        return gen

    # -- restore ------------------------------------------------------

    def _quarantine(self, gen: int, err: CheckpointError) -> BundleFault:
        from ..device import tracebuf as tb

        qdir = os.path.join(self.root, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        src = self.path_of(gen)
        dst = os.path.join(qdir, f"gen-{gen:06d}")
        if os.path.exists(dst):
            shutil.rmtree(dst, ignore_errors=True)
        try:
            shutil.move(src, dst)
        except OSError:
            dst = src  # refuse-to-move is not refuse-to-heal
        fault = BundleFault(
            generation=gen, path=dst,
            reason=_classify_fault(str(err)), error=str(err),
        )
        self.faults.append(fault)
        self._trace(tb.CK_QUARANTINE, gen)
        self._count("checkpoint.quarantined", generation=gen)
        return fault

    def load_latest(self) -> CheckpointBundle:
        """Newest generation that VALIDATES (sha256, magic, version,
        parseable members) - quarantining the ones that don't. Raises
        ``CheckpointError`` naming every fault when no generation
        survives; the caller owns poisoning outstanding futures through
        the degradation ladder (``FutureTable.poison``) at that point."""
        from ..device import tracebuf as tb

        if self.fault_plan is not None:
            # Preempt-mid-restore chaos site: a retried load_latest
            # must be idempotent (quarantine moves are re-entrant).
            self.fault_plan.on_store_restore()
        gens = self.generations()
        walked: List[BundleFault] = []
        newest = gens[-1] if gens else 0
        for gen in reversed(gens):
            try:
                b = CheckpointBundle.load(self.path_of(gen),
                                          generation=gen)
            except CheckpointError as e:
                walked.append(self._quarantine(gen, e))
                continue
            if gen != newest:
                self._trace(tb.CK_FALLBACK, gen)
                self._count("checkpoint.fallback", generation=gen,
                            newest=newest, quarantined=len(walked))
            self._trace(tb.CK_LOAD, gen)
            self._count("checkpoint.load", generation=gen)
            return b
        self._trace(tb.CK_POISON, newest)
        self._count("checkpoint.poison", generations=len(gens))
        detail = "; ".join(
            f"gen {f.generation}: {f.reason} ({f.error})" for f in walked
        ) or "the store holds no generations"
        raise CheckpointError(
            f"BundleStore at {self.root!r} is unrecoverable - no "
            f"generation validates ({detail}); poison outstanding "
            "futures through the degradation ladder and cold-start"
        )

    def trace_info(self) -> Dict[str, Any]:
        """trace_info-shaped dict of the store's host-emitted TR_CKPT
        records, mergeable by ``tools/timeline.py`` (the autoscaler's
        ``host_trace_info`` contract)."""
        from ..device.tracebuf import host_trace_info

        return host_trace_info(
            self.events or np.zeros((0, 4), np.int64),
            self._t0_ns, time.monotonic_ns(),
        )


def default_store(**kw) -> Optional[BundleStore]:
    """The env-configured store (``HCLIB_TPU_CKPT_DIR``), or None when
    the knob is unset - so callers can write ``store = default_store()``
    and gate their preempt hooks on it."""
    from . import env as _env

    root = _env.env_str("HCLIB_TPU_CKPT_DIR")
    if not root:
        return None
    return BundleStore(root, **kw)


# --------------------------------------------------------------- snapshot

def _require_quiesced(info: Dict[str, Any], what: str) -> Dict[str, Any]:
    if not info.get("quiesced") or "state" not in info:
        raise CheckpointError(
            f"{what}: the run info carries no quiesced state - pass "
            "quiesce= (or call .quiesce()) so the kernel exports its "
            "scheduler state at a round boundary"
        )
    return info["state"]


def snapshot_megakernel(mk, info: Dict[str, Any],
                        meta: Optional[Dict[str, Any]] = None
                        ) -> CheckpointBundle:
    """Bundle a quiesced ``Megakernel.run/resume`` info dict."""
    state = _require_quiesced(info, "snapshot_megakernel")
    m = _kernel_meta(mk)
    m.update(info.get("quiesce") or {})
    m.update(meta or {})
    return CheckpointBundle(
        "megakernel", m, CheckpointBundle._flatten_state(state, m)
    )


def snapshot_stream(sm, info: Dict[str, Any],
                    meta: Optional[Dict[str, Any]] = None
                    ) -> CheckpointBundle:
    """Bundle a quiesced ``StreamingMegakernel.run_stream`` return."""
    state = _require_quiesced(info, "snapshot_stream")
    m = _kernel_meta(sm.mk)
    m["ring_capacity"] = int(sm.ring_capacity)
    m["quiesce_latency_s"] = info.get("quiesce_latency_s")
    m["quiesce_round"] = info.get("quiesce_observed_round")
    m.update(meta or {})
    # After the user meta: the roster is what restore_stream's
    # mismatch guard validates - a descriptive meta={'tenants': ...}
    # must not clobber (or counterfeit) it.
    if getattr(sm, "tenants", None) is not None:
        m["tenants"] = list(sm.tenants.ids)
    else:
        m.pop("tenants", None)
    return CheckpointBundle(
        "stream", m, CheckpointBundle._flatten_state(state, m)
    )


def snapshot_resident(rk, info: Dict[str, Any],
                      meta: Optional[Dict[str, Any]] = None
                      ) -> CheckpointBundle:
    """Bundle a quiesced ``ResidentKernel.run`` info dict."""
    state = _require_quiesced(info, "snapshot_resident")
    m = _kernel_meta(rk.mk)
    m["kind_classes"] = _kind_classes(rk.mk)
    m["ndev"] = int(rk.ndev)
    m["dims"] = [int(d) for d in rk.dims]
    m["quiesce_round"] = max(
        f["quiesce_round"] for f in info["fault_stats"]
    )
    m.update(meta or {})
    # After the user meta (as snapshot_stream): the roster is what
    # restore_resident's mismatch guard validates; the channel-name
    # table is what reshard's wait re-homing diagnostics (and the
    # meta['host_residue'] refusal) key on, so a descriptive meta=
    # must not counterfeit it either.
    if getattr(rk, "chan_id", None):
        m["channels"] = [
            name for name, _cid in
            sorted(rk.chan_id.items(), key=lambda kv: kv[1])
        ]
    if getattr(rk, "tenant_specs", None):
        m["tenants"] = [s.id for s in rk.tenant_specs]
    else:
        m.pop("tenants", None)
    return CheckpointBundle(
        "resident", m, CheckpointBundle._flatten_state(state, m)
    )


# ---------------------------------------------------------------- restore

def _as_bundle(bundle_or_path) -> CheckpointBundle:
    if isinstance(bundle_or_path, CheckpointBundle):
        return bundle_or_path
    return CheckpointBundle.load(bundle_or_path)


def restore_megakernel(bundle_or_path, mk, fuel: int = 1 << 22,
                       quiesce=None):
    """Validate + relaunch a megakernel bundle mid-graph on ``mk`` (built
    exactly as checkpointed, ``checkpoint=True`` not required unless you
    pass ``quiesce=`` to re-checkpoint). Returns (ivalues, data, info) of
    the continued run."""
    b = _as_bundle(bundle_or_path)
    if b.kind != "megakernel":
        raise CheckpointError(
            f"restore_megakernel got a {b.kind!r} bundle"
        )
    _check_kernel_meta(mk, b.meta, where=_where(b))
    return mk.resume(b.state(), fuel=fuel, quiesce=quiesce)


def restore_stream(bundle_or_path, sm, **run_stream_kw):
    """Validate + resume a stream bundle on ``sm`` (a StreamingMegakernel
    whose Megakernel matches the manifest). The residue rows re-publish
    on the fresh ring; the stream starts OPEN - inject()/close() as
    usual, or close() first for drain-and-exit semantics."""
    b = _as_bundle(bundle_or_path)
    if b.kind != "stream":
        raise CheckpointError(f"restore_stream got a {b.kind!r} bundle")
    _check_kernel_meta(sm.mk, b.meta, where=_where(b))
    # Tenant roster must match EXACTLY (ids AND order): residue rows and
    # the tctl/tstats counter blocks are keyed by lane index, so a
    # same-count reordered roster would silently credit one tenant's
    # work and quotas to another.
    want = b.meta.get("tenants")
    have = None if getattr(sm, "tenants", None) is None else (
        sm.tenants.ids
    )
    if (want or None) != (have or None):
        raise CheckpointError(
            f"tenant roster mismatch: bundle carries {want!r}, the "
            f"target stream has {have!r} (ids and order must match - "
            "lane state is keyed by index)"
        )
    return sm.run_stream(resume_state=b.state(), **run_stream_kw)


def restore_resident(bundle_or_path, rk, quantum: int = 64,
                     max_rounds: int = 1 << 14, quiesce=None,
                     tenant_table=None):
    """Validate + relaunch a resident-mesh bundle on ``rk``. A mesh-size
    mismatch re-homes the queues automatically (``reshard`` - totals
    conserved; see its docstring for the eligibility rules). A
    tenant-enabled bundle needs a fresh ``tenant_table`` matching the
    roster - residue re-deals into its lanes. Returns (ivalues, data,
    info) of the continued run."""
    b = _as_bundle(bundle_or_path)
    if b.kind != "resident":
        raise CheckpointError(f"restore_resident got a {b.kind!r} bundle")
    _check_kernel_meta(rk.mk, b.meta, where=_where(b))
    # Tenant roster must match EXACTLY (ids AND order) - lane state is
    # keyed by index, as on the stream restore path.
    want = b.meta.get("tenants")
    have = (
        [s.id for s in rk.tenant_specs]
        if getattr(rk, "tenant_specs", None) else None
    )
    if (want or None) != (have or None):
        raise CheckpointError(
            f"tenant roster mismatch: bundle carries {want!r}, the "
            f"target mesh has {have!r} (ids and order must match - "
            "lane state is keyed by index)"
        )
    if int(b.meta.get("ndev", rk.ndev)) != rk.ndev:
        b = b.reshard(rk.ndev)
    kw = {} if tenant_table is None else {"tenant_table": tenant_table}
    return rk.run(
        resume_state=b.state(), quantum=quantum, max_rounds=max_rounds,
        quiesce=quiesce, **kw,
    )


# ------------------------------------------------------------- preemption

@contextlib.contextmanager
def checkpoint_on_preempt(stream, after_executed: int = 0):
    """Bind a running stream's checkpoint trigger to process preemption:
    SIGTERM (after ``resilience.install_preempt_handler()``), the
    ``HCLIB_TPU_PREEMPT`` env, or the watchdog's checkpoint rung
    (``HCLIB_TPU_WATCHDOG_CHECKPOINT=1``) quiesce the stream - the
    driving run_stream returns with ``info['quiesced']=True`` and the
    caller saves the bundle (checkpoint, then stop). Register-then-replay:
    a preemption that fired BEFORE this binding still checkpoints.

    ::

        with checkpoint_on_preempt(sm):
            iv, info = sm.run_stream(b, ...)
        if info.get("quiesced"):
            snapshot_stream(sm, info).save(path)
    """

    def hook() -> None:
        stream.quiesce(after_executed)

    resilience.register_preempt_hook(hook)
    try:
        yield
    finally:
        resilience.unregister_preempt_hook(hook)
