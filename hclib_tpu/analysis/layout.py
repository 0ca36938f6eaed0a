"""Word-layout consistency: ONE table, cross-checked against every
module that hard-codes part of the shared device ABI.

The descriptor ABI (descriptor.py), the ring-row transport words
(tenants.py / inject.py / resident.py), the batch-tier counter rows
(megakernel.py), and the checkpoint export key set (checkpoint.py) all
agree on word positions only by convention; this table is the
convention, and ``check_layout`` is the build-time assertion that no
module drifted. The witness of a violation is the word's name plus the
two disagreeing values - the exact edit to make.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from .findings import ERROR, AnalysisReport

__all__ = ["LAYOUT", "check_layout"]

# word name -> (expected value, module paths that must agree). A module
# listed here must expose the attribute with exactly this value.
LAYOUT = {
    # descriptor ABI (device/descriptor.py)
    "DESC_WORDS": (16, ("hclib_tpu.device.descriptor",
                        "hclib_tpu.runtime.checkpoint")),
    "NO_TASK": (-1, ("hclib_tpu.device.descriptor",)),
    "F_FN": (0, ("hclib_tpu.device.descriptor",)),
    "F_DEP": (1, ("hclib_tpu.device.descriptor",)),
    "F_SUCC0": (2, ("hclib_tpu.device.descriptor",)),
    "F_SUCC1": (3, ("hclib_tpu.device.descriptor",)),
    "F_CSR_OFF": (4, ("hclib_tpu.device.descriptor",)),
    "F_CSR_N": (5, ("hclib_tpu.device.descriptor",)),
    "F_A0": (6, ("hclib_tpu.device.descriptor",)),
    "F_OUT": (12, ("hclib_tpu.device.descriptor",)),
    "F_HOME": (13, ("hclib_tpu.device.descriptor",)),
    "F_HROW": (14, ("hclib_tpu.device.descriptor",)),
    "F_VMASK": (15, ("hclib_tpu.device.descriptor",)),
    # injection-ring transport words: every module that stamps or reads
    # them must share the descriptor-side canonical home.
    "RING_ROW": (256, ("hclib_tpu.device.descriptor",
                       "hclib_tpu.device.inject",
                       "hclib_tpu.device.resident")),
    "TEN_ID": (16, ("hclib_tpu.device.descriptor",)),
    "TEN_EXPIRED": (17, ("hclib_tpu.device.descriptor",)),
    "TEN_DEADLINE_MS": (18, ("hclib_tpu.device.descriptor",)),
    "TEN_TOKEN": (19, ("hclib_tpu.device.descriptor",)),
    "TEN_ADMIT_ROUND": (20, ("hclib_tpu.device.descriptor",)),
    # completion-mailbox EGR row ABI (device/egress.py, ISSUE 16): the
    # host drain, the numpy executable spec, and the in-kernel publish
    # path (device/inject.py) all index these words; the ectl cursor
    # block (EC_*) rides beside them like the inject ctl row.
    "EGR_STATUS": (0, ("hclib_tpu.device.egress",)),
    "EGR_TOKEN": (1, ("hclib_tpu.device.egress",)),
    "EGR_TEN": (2, ("hclib_tpu.device.egress",)),
    "EGR_FN": (3, ("hclib_tpu.device.egress",)),
    "EGR_SLOT": (4, ("hclib_tpu.device.egress",)),
    "EGR_VALUE": (5, ("hclib_tpu.device.egress",)),
    "EGR_T_ADMIT": (6, ("hclib_tpu.device.egress",)),
    "EGR_T_SPANS": (7, ("hclib_tpu.device.egress",)),
    "EGR_WORDS": (8, ("hclib_tpu.device.egress",)),
    "EC_WRITE": (0, ("hclib_tpu.device.egress",)),
    "EC_CONSUMED": (1, ("hclib_tpu.device.egress",)),
    "EC_PARKED": (2, ("hclib_tpu.device.egress",)),
    "EC_PARK_COUNT": (3, ("hclib_tpu.device.egress",)),
    "EC_PARK_HEAD": (4, ("hclib_tpu.device.egress",)),
    "EC_INFLIGHT": (5, ("hclib_tpu.device.egress",)),
    # tctl ABI (one 8-word control row per tenant lane, device/tenants):
    # the host pump, the single-device stream poll, the resident-mesh
    # WRR poll, and the numpy reference model all index these words -
    # one drifted cursor slot would silently corrupt every lane.
    "TC_TAIL": (0, ("hclib_tpu.device.tenants",
                    "hclib_tpu.device.inject",
                    "hclib_tpu.device.resident")),
    "TC_CONSUMED": (1, ("hclib_tpu.device.tenants",
                        "hclib_tpu.device.inject",
                        "hclib_tpu.device.resident")),
    "TC_WEIGHT": (2, ("hclib_tpu.device.tenants",
                      "hclib_tpu.device.inject",
                      "hclib_tpu.device.resident")),
    "TC_PAUSE": (3, ("hclib_tpu.device.tenants",
                     "hclib_tpu.device.inject",
                     "hclib_tpu.device.resident")),
    "TC_EXPIRED": (4, ("hclib_tpu.device.tenants",
                       "hclib_tpu.device.inject",
                       "hclib_tpu.device.resident")),
    "TC_INSTALLED": (5, ("hclib_tpu.device.tenants",
                         "hclib_tpu.device.inject",
                         "hclib_tpu.device.resident")),
    "TC_DROPPED": (6, ("hclib_tpu.device.tenants",
                       "hclib_tpu.device.inject",
                       "hclib_tpu.device.resident")),
    # tstats ABI (host-side cumulative counters serialized per tenant
    # into checkpoint bundles).
    "TS_ACCEPTED": (0, ("hclib_tpu.device.tenants",)),
    "TS_REJECTED": (1, ("hclib_tpu.device.tenants",)),
    "TS_EXPIRED_HOST": (2, ("hclib_tpu.device.tenants",)),
    "TS_POISONED": (3, ("hclib_tpu.device.tenants",)),
    "TS_DROPPED": (4, ("hclib_tpu.device.tenants",)),
    "TS_THROTTLED": (5, ("hclib_tpu.device.tenants",)),
    "TS_QUARANTINED": (6, ("hclib_tpu.device.tenants",)),
    # batch-tier counter/state rows (device/megakernel.py)
    "TS_WORDS": (15, ("hclib_tpu.device.megakernel",)),
    # rows spawned straight onto a lane (spawn-time routing)
    "TS_DIRECT": (14, ("hclib_tpu.device.megakernel",)),
    # re-armed dispatches (ctx.become) and retirements that took
    # retire()'s slow region: the two tier words every build writes, and
    # the re-arm scratch's three fixed words behind them.
    "TS_BECAME": (12, ("hclib_tpu.device.megakernel",)),
    "TS_WALKED": (13, ("hclib_tpu.device.megakernel",)),
    "RA_BECAME": (0, ("hclib_tpu.device.megakernel",)),
    "RA_WALKED": (1, ("hclib_tpu.device.megakernel",)),
    "RA_MARK": (2, ("hclib_tpu.device.megakernel",)),
    "LS_WORDS": (8, ("hclib_tpu.device.megakernel",)),
    "LS_AGE": (5, ("hclib_tpu.device.megakernel",)),
    # priority-bucket tier words (ISSUE 15): the static bucket-ring
    # cap and the two tstats counters the bucketed scheduler writes.
    # The bucket id itself rides the DESCRIPTOR's own arg words
    # (BatchSpec.priority is a pure function of them - see the routing
    # site in megakernel.py), so there is no bucket transport word to
    # pin: residue re-buckets on resume/reshard by construction.
    "BK_MAX": (8, ("hclib_tpu.device.megakernel",)),
    "TS_BUCKET_FIRES": (10, ("hclib_tpu.device.megakernel",)),
    "TS_INVERSIONS": (11, ("hclib_tpu.device.megakernel",)),
    "QC_FLAG": (0, ("hclib_tpu.device.megakernel",)),
    "QC_AFTER": (1, ("hclib_tpu.device.megakernel",)),
    "C_EXECUTED": (5, ("hclib_tpu.device.megakernel",)),
    "C_ROUNDS": (7, ("hclib_tpu.device.megakernel",)),
    # live-telemetry word ABI (device/telemetry.py, ISSUE 19): the
    # per-row stamp table (tlat), the gauge row (TG_*), and the
    # histogram width the kernel fold, the host wrapper, and the
    # reconciliation tests all index.
    "LAT_ADMIT": (0, ("hclib_tpu.device.telemetry",)),
    "LAT_INSTALL": (1, ("hclib_tpu.device.telemetry",)),
    "LAT_FIRE": (2, ("hclib_tpu.device.telemetry",)),
    "LAT_WORDS": (4, ("hclib_tpu.device.telemetry",)),
    "LAT_BUCKETS": (16, ("hclib_tpu.device.telemetry",)),
    "TG_ROUNDS": (0, ("hclib_tpu.device.telemetry",)),
    "TG_INSTALLS": (1, ("hclib_tpu.device.telemetry",)),
    "TG_RETIRES": (2, ("hclib_tpu.device.telemetry",)),
    "TG_PARKED": (3, ("hclib_tpu.device.telemetry",)),
    "TG_BACKLOG": (4, ("hclib_tpu.device.telemetry",)),
    "TG_ENTRIES": (5, ("hclib_tpu.device.telemetry",)),
    "TG_WORDS": (8, ("hclib_tpu.device.telemetry",)),
    # dynamic-graph service ABI (device/dyngraph.py, ISSUE 20): the
    # UPDATE/QUERY kernel-table positions (EXPAND keeps frontier.py's
    # FR_EXPAND=0) and the counter value slots the splice ledger bumps -
    # the reshard merge, the serving pump, and the conservation asserts
    # all index these words. The per-vertex spare-region layout itself
    # is a pure function stamped per build (mk._dyngraph) and checked
    # structurally by races.check_splice, not a process constant.
    "DG_UPDATE": (1, ("hclib_tpu.device.dyngraph",)),
    "DG_QUERY": (2, ("hclib_tpu.device.dyngraph",)),
    "V_UPDATES": (2, ("hclib_tpu.device.dyngraph",)),
    "V_FREE": (3, ("hclib_tpu.device.dyngraph",)),
    "V_DROPPED": (4, ("hclib_tpu.device.dyngraph",)),
    "V_QUERIES": (5, ("hclib_tpu.device.dyngraph",)),
    "TR_SPLICE": (21, ("hclib_tpu.device.tracebuf",)),
}

# checkpoint.py's export key sets: resharding and restore key on these
# literal names riding the bundle npz.
_CKPT_STATE_KEYS = ("tasks", "succ", "ready", "counts", "ivalues")
_CKPT_OPT_KEYS = (
    "ring_rows", "waits", "ictl", "tctl", "tstats", "etok",
    "tele", "tlat",
)

_cache: Optional[AnalysisReport] = None


def check_layout(report: Optional[AnalysisReport] = None,
                 suppress: Sequence[str] = (),
                 force: bool = False) -> AnalysisReport:
    """Cross-check LAYOUT against the live modules (memoized: the
    constants cannot change within a process, so every megakernel
    construction after the first reuses the verdict)."""
    global _cache
    if _cache is not None and not force and report is None and not suppress:
        return _cache
    import importlib

    report = report or AnalysisReport(suppress)
    rows: List[Tuple[str, str, int, int]] = []
    for word, (expected, modules) in LAYOUT.items():
        for modname in modules:
            mod = importlib.import_module(modname)
            actual = getattr(mod, word, None)
            if actual != expected:
                rows.append((word, modname, expected, actual))
    for word, modname, expected, actual in rows:
        report.add(
            "layout", ERROR, None,
            f"layout word {word} disagrees: table says {expected}, "
            f"{modname} has {actual}",
            word=word, module=modname, expected=expected, actual=actual,
        )
    # Structural invariants that no single constant captures.
    from ..device import descriptor as d
    from ..device import megakernel as m

    if not (d.DESC_WORDS <= d.TEN_ID < d.TEN_EXPIRED
            < d.TEN_DEADLINE_MS < d.TEN_TOKEN
            < d.TEN_ADMIT_ROUND < d.RING_ROW):
        report.add(
            "layout", ERROR, None,
            "ring-row transport words must sit beyond the descriptor "
            f"ABI and inside the padded row: DESC_WORDS={d.DESC_WORDS} "
            f"<= TEN_ID={d.TEN_ID} < TEN_EXPIRED={d.TEN_EXPIRED} < "
            f"TEN_DEADLINE_MS={d.TEN_DEADLINE_MS} < "
            f"TEN_TOKEN={d.TEN_TOKEN} < "
            f"TEN_ADMIT_ROUND={d.TEN_ADMIT_ROUND} < "
            f"RING_ROW={d.RING_ROW} violated",
            word="TEN_ID",
        )
    from ..device import egress as e

    if not (e.EGR_STATUS < e.EGR_TOKEN < e.EGR_TEN < e.EGR_FN
            < e.EGR_SLOT < e.EGR_VALUE < e.EGR_T_ADMIT
            < e.EGR_T_SPANS < e.EGR_WORDS
            and 0 <= e.EC_WRITE < e.EC_CONSUMED < e.EC_PARKED
            < e.EC_PARK_COUNT < e.EC_PARK_HEAD < e.EC_INFLIGHT < 8):
        report.add(
            "layout", ERROR, None,
            "completion-mailbox words violate the transport-word "
            f"ordering invariant: EGR {e.EGR_STATUS},{e.EGR_TOKEN},"
            f"{e.EGR_TEN},{e.EGR_FN},{e.EGR_SLOT},{e.EGR_VALUE},"
            f"{e.EGR_T_ADMIT},{e.EGR_T_SPANS} must "
            f"ascend below EGR_WORDS={e.EGR_WORDS} and the EC cursor "
            "words must ascend inside the 8-word ectl row",
            word="EGR_STATUS",
        )
    from ..device import telemetry as t

    if not (0 <= t.LAT_ADMIT < t.LAT_INSTALL < t.LAT_FIRE < t.LAT_WORDS
            and t.TG_ROUNDS < t.TG_INSTALLS < t.TG_RETIRES
            < t.TG_PARKED < t.TG_BACKLOG < t.TG_ENTRIES
            < t.TG_WORDS <= t.LAT_BUCKETS):
        report.add(
            "layout", ERROR, None,
            "telemetry words violate the ordering invariant: the LAT "
            f"stamps ({t.LAT_ADMIT},{t.LAT_INSTALL},{t.LAT_FIRE}) must "
            f"ascend below LAT_WORDS={t.LAT_WORDS}, and the TG gauge "
            f"words must ascend below TG_WORDS={t.TG_WORDS} which must "
            f"fit the LAT_BUCKETS={t.LAT_BUCKETS}-wide gauge row",
            word="LAT_ADMIT",
        )
    if not (m.LS_AGE < m.LS_WORDS
            and m.TS_MAX_AGE < m.TS_BUCKET_FIRES
            < m.TS_INVERSIONS < m.TS_BECAME < m.TS_WALKED < m.TS_DIRECT
            < m.TS_WORDS):
        report.add(
            "layout", ERROR, None,
            "lane/tier state words exceed their declared row widths "
            "(or the bucket-tier counters overlap the age words)",
            word="LS_WORDS",
        )
    from ..device import dyngraph as dg
    from ..device import frontier as fr

    if not (fr.V_EDGES < fr.V_RELAX < dg.V_UPDATES < dg.V_FREE
            < dg.V_DROPPED < dg.V_QUERIES < fr.VT_BASE
            and fr.FR_EXPAND < dg.DG_UPDATE < dg.DG_QUERY):
        report.add(
            "layout", ERROR, None,
            "dynamic-graph counter slots must ascend between the "
            f"frontier counters and the vertex table (V_EDGES="
            f"{fr.V_EDGES} < V_RELAX={fr.V_RELAX} < V_UPDATES="
            f"{dg.V_UPDATES} < V_FREE={dg.V_FREE} < V_DROPPED="
            f"{dg.V_DROPPED} < V_QUERIES={dg.V_QUERIES} < VT_BASE="
            f"{fr.VT_BASE}), and the service kinds must follow EXPAND "
            f"in the kernel table (FR_EXPAND={fr.FR_EXPAND} < "
            f"DG_UPDATE={dg.DG_UPDATE} < DG_QUERY={dg.DG_QUERY})",
            word="V_UPDATES",
        )
    from ..runtime import checkpoint as c

    if tuple(c._STATE_KEYS) != _CKPT_STATE_KEYS:
        report.add(
            "layout", ERROR, None,
            f"checkpoint state keys drifted: {c._STATE_KEYS} != "
            f"{_CKPT_STATE_KEYS}",
            word="_STATE_KEYS", actual=tuple(c._STATE_KEYS),
        )
    if tuple(c._OPT_KEYS) != _CKPT_OPT_KEYS:
        report.add(
            "layout", ERROR, None,
            f"checkpoint optional keys drifted: {c._OPT_KEYS} != "
            f"{_CKPT_OPT_KEYS}",
            word="_OPT_KEYS", actual=tuple(c._OPT_KEYS),
        )
    if report.findings == [] and not suppress:
        _cache = report
    return report
