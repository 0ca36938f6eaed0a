"""Batch-slot race detection + prefetch-protocol conformance.

Two spellings, matching how the kernels declare themselves:

- **Slab-declared kernels** (``TileKernel``): the store windows are pure
  Python ``index(args)`` callables, so ``check_tile_windows`` evaluates
  them CONCRETELY over the whole tile space and proves pairwise
  disjointness - the witness of a violation is the two colliding tile
  coordinates and their windows. This is the strong, whole-loop result
  (any two ready tiles can share a batch round).

- **Raw batch bodies** (any ``BatchSpec``): ``check_batch_spec``
  abstract-interprets the body once with the recording shim over a
  slot-distinct synthetic batch and checks (a) per-slot DMA store
  windows into data buffers are pairwise disjoint, (b) per-slot value
  writes hit disjoint slots, (c) every DMA wait matches a start, (d)
  with a prefetch announced, the residual (unwaited) starts are EXACTLY
  what ``drain`` retires. A body the shim cannot run yields one
  ``shim-unsupported`` info finding instead of false alarms.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .findings import ERROR, INFO, WARN, AnalysisReport
from .shim import (
    BodyTrace, ShimUnsupported, run_batch_body, run_drain,
)

__all__ = [
    "boxes_overlap",
    "check_batch_spec",
    "check_splice",
    "check_tile_windows",
]


def boxes_overlap(a, b) -> bool:
    """Axis-aligned boxes ((start, stop) per axis) intersect; shorter
    box = full range on the missing trailing axes."""
    n = max(len(a), len(b))
    for i in range(n):
        lo_a, hi_a = a[i] if i < len(a) else (0, 1 << 62)
        lo_b, hi_b = b[i] if i < len(b) else (0, 1 << 62)
        if hi_a <= lo_b or hi_b <= lo_a:
            return False
    return True


# ------------------------------------------------------- tile windows


import weakref

# Clean verdicts memoized per (TileKernel instance, bounds, tile):
# run_forasync_device re-proves on every call otherwise (repeated bench
# / mesh runs over one kernel), and the proof is O(tiles x stores)
# Python. Only CLEAN results cache - a violation raises at the caller
# and re-deriving its witness is the cheap path.
_tile_clean: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def check_tile_windows(tk, bounds, tile,
                       report: Optional[AnalysisReport] = None,
                       suppress: Sequence[str] = ()) -> AnalysisReport:
    """Prove every pair of tiles of one forasync loop stores disjoint
    windows (per store slab/buffer) by concrete evaluation over the
    whole tile space. Witness: the two colliding tile coordinates.

    A loop of several steps (``tk.steps`` > 1) is also held to the other
    half, between every step and the next (``_check_step_windows``): a
    tile of step t whose window overlaps a window of a tile of step t+1
    on one buffer, one of the two a store, is among the tiles that tile
    awaits. That is read-before-overwrite (a load of step t under a store
    of step t+1) and written-before-read (a store of step t under a load
    of step t+1) at once. Witness: the two tiles, their steps and the
    two windows."""
    from ..device.forasync_tier import tile_args, tile_grid

    report = report or AnalysisReport(suppress)
    key = (repr(tuple(bounds)), repr(tuple(tile) if not isinstance(
        tile, int) else (tile,)))
    try:
        if key in _tile_clean.get(tk, ()):
            return report
    except TypeError:
        pass
    dims, tile_dims, counts, total = tile_grid(bounds, tile)
    # buffer -> list of (box, flat, los)
    per_buffer: Dict[str, List[Tuple[Any, int, Tuple[int, ...]]]] = {}
    from .shim import _norm_box

    stepped = tk.steps > 1
    for flat in range(total):
        args = tile_args(dims, tile_dims, counts, flat) + [0] * stepped
        for s in tk.stores:
            try:
                idx = s.index(tuple(args))
            except Exception as e:  # noqa: BLE001
                report.add(
                    "shim-unsupported", INFO, tk.name,
                    f"store slab {s.name!r} index not concretely "
                    f"evaluable: {e}",
                )
                return report
            shape = tuple(tk.data_specs[s.data].shape)
            box = _norm_box(shape, idx)
            per_buffer.setdefault(s.data, []).append(
                (box, flat, tuple(args[1:1 + len(dims)]))
            )
    for buf, wins in per_buffer.items():
        # Sweep in first-axis order so disjoint layouts exit near-linearly.
        wins.sort(key=lambda w: w[0][0] if w[0] else (0, 0))
        active: List[Tuple[Any, int, Tuple[int, ...]]] = []
        for box, flat, los in wins:
            lo0 = box[0][0] if box else 0
            active = [w for w in active if (w[0][0][1] if w[0] else 1 << 62)
                      > lo0]
            for obox, oflat, olos in active:
                if boxes_overlap(box, obox):
                    report.add(
                        "tile-race", ERROR, tk.name,
                        f"tiles {olos} and {los} store overlapping "
                        f"windows of buffer {buf!r}",
                        buffer=buf, tile_a=olos, tile_b=los,
                        window_a=obox, window_b=box,
                        flat_a=oflat, flat_b=flat,
                    )
                    return report  # one witness is enough
            active.append((box, flat, los))
    if stepped and not _check_step_windows(
            tk, dims, tile_dims, counts, report):
        return report
    try:
        _tile_clean.setdefault(tk, set()).add(key)
    except TypeError:
        pass
    return report


def _check_step_windows(tk, dims, tile_dims, counts, report) -> bool:
    """``check_tile_windows``' rule between consecutive steps; False
    where it added a finding. Every window of every tile of every step
    is evaluated; two steps whose windows repeat an earlier pair's (a
    two-plane layout alternates) are not compared again."""
    import numpy as np

    from ..device.forasync_tier import tile_args
    from .shim import _norm_box

    total = int(np.prod(counts))
    coords = np.array(list(np.ndindex(*counts)))
    awaited = {tuple(o) for o in tk.awaits}

    def windows(step: int):
        # buffer -> (lo[n, k], hi[n, k], flat[n], is_store[n], slab name)
        per: Dict[str, List] = {}
        for flat in range(total):
            args = tuple(tile_args(dims, tile_dims, counts, flat) + [step])
            for s, st in [(s, False) for s in tk.loads] + [
                    (s, True) for s in tk.stores]:
                box = _norm_box(tuple(tk.data_specs[s.data].shape),
                                s.index(args))
                per.setdefault(s.data, []).append((box, flat, st, s.name))
        return {
            buf: (np.array([[a for a, _ in w[0]] for w in ws]),
                  np.array([[b for _, b in w[0]] for w in ws]),
                  np.array([w[1] for w in ws]),
                  np.array([w[2] for w in ws]),
                  [w[3] for w in ws])
            for buf, ws in per.items()
        }

    def sig(wins) -> int:
        return hash(tuple(
            (buf, lo.tobytes(), hi.tobytes()) for buf, (lo, hi, *_)
            in sorted(wins.items())
        ))

    seen = set()
    try:
        prev = windows(0)
    except Exception as e:  # noqa: BLE001
        report.add("shim-unsupported", INFO, tk.name,
                   f"slab index not concretely evaluable: {e}")
        return False
    for step in range(1, tk.steps):
        cur = windows(step)
        pair = (sig(prev), sig(cur))
        if pair not in seen:
            seen.add(pair)
            for buf in set(prev) & set(cur):
                alo, ahi, aflat, ast, aname = prev[buf]
                blo, bhi, bflat, bst, bname = cur[buf]
                # Sweep the axis along which step t's windows start at
                # the most places: a window of step t+1 can only meet
                # those that start inside its own extent widened by the
                # longest of them, a short run of the sorted starts.
                ax = max(range(alo.shape[1]),
                         key=lambda d: len(np.unique(alo[:, d])))
                order = np.argsort(alo[:, ax], kind="stable")
                starts = alo[order, ax]
                reach = int((ahi[:, ax] - alo[:, ax]).max())
                for j in range(len(bflat)):
                    cand = order[
                        np.searchsorted(starts, blo[j, ax] - reach, "right"):
                        np.searchsorted(starts, bhi[j, ax], "left")]
                    hit = np.all((alo[cand] < bhi[j])
                                 & (blo[j] < ahi[cand]), axis=1)
                    hit &= ast[cand] | bst[j]
                    for i in cand[hit]:
                        x, y = coords[aflat[i]], coords[bflat[j]]
                        if tuple(int(v) for v in x - y) in awaited:
                            continue
                        what = ("stores over what" if bst[j] and not ast[i]
                                else "touches what")
                        verb = "stores" if ast[i] else "loads"
                        report.add(
                            "tile-race", ERROR, tk.name,
                            f"tile {tuple(int(v) for v in y)} of step "
                            f"{step} {what} tile "
                            f"{tuple(int(v) for v in x)} of step "
                            f"{step - 1} {verb} in buffer {buf!r} (slabs "
                            f"{bname[j]!r} / {aname[i]!r}) and does not "
                            f"await it (awaits {sorted(awaited)})",
                            buffer=buf,
                            tile_a=tuple(int(v) for v in x),
                            tile_b=tuple(int(v) for v in y),
                            step_a=step - 1, step_b=step,
                            window_a=tuple(zip(alo[i].tolist(),
                                               ahi[i].tolist())),
                            window_b=tuple(zip(blo[j].tolist(),
                                               bhi[j].tolist())),
                        )
                        return False  # one witness is enough
        prev = cur
    return True


# -------------------------------------------------------- batch bodies


def _slot_of_box(box, width: int) -> Optional[int]:
    """Best-effort slot attribution of a window: which synthetic slot's
    arg stride the first nonzero start coordinate falls under."""
    from .shim import ARG_STRIDE

    for lo, _hi in box:
        if lo >= ARG_STRIDE:
            s = lo // ARG_STRIDE - 1
            return s if 0 <= s < width else None
    return None


def check_batch_spec(name: str, fid: int, spec, data_specs, scratch_specs,
                     report: Optional[AnalysisReport] = None,
                     suppress: Sequence[str] = (),
                     ctx_hook=None) -> AnalysisReport:
    """Run the four shim-based checks over one routed BatchSpec (see
    module docstring). ``suppress`` composes with the spec's own
    ``verify_suppress`` annotation (a per-rule opt-out the spec owner
    writes next to the deliberate violation)."""
    sup = tuple(suppress) + tuple(getattr(spec, "verify_suppress", ()))
    if report is not None:
        sup = sup + tuple(report._suppress)
        sub = AnalysisReport(sup)
    else:
        report = sub = AnalysisReport(sup)
    try:
        t = run_batch_body(
            spec, fid, data_specs, scratch_specs,
            prefetch_count=0, ctx_hook=ctx_hook,
        )
    except ShimUnsupported as e:
        sub.add(
            "shim-unsupported", INFO, name,
            f"batch body not abstractly interpretable ({e}); "
            "slot-race and prefetch-protocol checks skipped",
        )
    else:
        _check_round_trace(name, spec, t, sub)
        if spec.prefetch:
            _check_prefetch(name, fid, spec, data_specs, scratch_specs,
                            sub)
    if sub is not report:
        report.extend(sub)
    return report


def _check_round_trace(name: str, spec, t: BodyTrace,
                       report: AnalysisReport) -> None:
    # (c) wait/start matching within a round with nothing announced.
    # A trace with truncated / arg-bounded loops is an UNDER-
    # approximation, but only around the truncation points (the seq
    # marks where skipped iterations would have emitted): an unmatched
    # START demotes only when a truncated window sits AFTER it (the
    # missing wait could be in the skipped iterations - the cholesky
    # pipelined row stream), an unmatched WAIT only when one sits
    # BEFORE it (the missing start could). Findings whose whole
    # matching window was observed exactly stay errors - a blanket
    # demotion would let an exact-window protocol bug ride along with
    # one unrelated arg-dependent loop.
    uw, us = t.unmatched_waits(), t.unmatched_starts()
    marks = t.approx_marks
    dem_w = [w for w in uw if any(m < w.seq for m in marks)]
    dem_s = [s for s in us if any(m > s.seq for m in marks)]
    if dem_w or dem_s:
        report.add(
            "shim-unsupported", INFO, name,
            f"{t.approx_loops} loop(s) ran truncated (arg-dependent "
            f"bounds); {len(dem_s)} start(s)/{len(dem_w)} wait(s) "
            "left unmatched inside the truncated windows - DMA "
            "protocol not verifiable for those events (exact-window "
            "events still check)",
        )
        uw = [w for w in uw if w not in dem_w]
        us = [s for s in us if s not in dem_s]
    for w in uw:
        report.add(
            "prefetch-protocol", ERROR, name,
            f"DMA wait with no matching start: {w.src[0]} -> "
            f"{w.dst[0]}{list(w.dst[1])}",
            dst=w.dst, sem=w.sem,
        )
    for s in us:
        report.add(
            "prefetch-protocol", ERROR, name,
            "DMA start never waited in a round with no prefetch "
            f"announced (it would outlive the batch's completions): "
            f"{s.src[0]} -> {s.dst[0]}{list(s.dst[1])}",
            dst=s.dst, sem=s.sem,
        )
    # (a) per-slot store windows into data buffers pairwise disjoint.
    stores = [e for e in t.starts() if e.dst_kind == "data"]
    for a, b in itertools.combinations(stores, 2):
        if a.dst[0] != b.dst[0]:
            continue
        if boxes_overlap(a.dst[1], b.dst[1]):
            sa = _slot_of_box(a.dst[1], spec.width)
            sb = _slot_of_box(b.dst[1], spec.width)
            if sa is not None and sa == sb:
                continue  # one slot touching its own window twice
            report.add(
                "batch-race", ERROR, name,
                f"two batch slots store overlapping windows of "
                f"{a.dst[0]!r} "
                f"(slots {sa} and {sb}: the slab index ignores the "
                "slot's descriptor)",
                buffer=a.dst[0], window_a=a.dst[1], window_b=b.dst[1],
                slot_a=sa, slot_b=sb,
            )
            return
    # (b) per-slot value-slot writes disjoint. A BLIND overwrite of a
    # slot another batch slot already wrote is the copy-paste bug (the
    # second writer's result is independent of the first, so one slot's
    # output is silently lost); a read-modify-write chain (the slot
    # READ the value after the earlier write, before its own) is the
    # legitimate sequential-accumulator pattern - batch bodies run
    # their slots in order, so in-SMEM accumulation is well-defined.
    last_write: Dict[int, Tuple[int, int]] = {}  # vs -> (slot, seq)
    for slot, vs, seq in sorted(t.value_writes, key=lambda w: w[2]):
        if slot is None:
            last_write[vs] = (-1, seq)
            continue
        prev = last_write.get(vs)
        if prev is not None and prev[0] not in (slot, -1):
            read_between = any(
                rvs == vs and rslot in (slot, None)
                and prev[1] < rseq < seq
                for rslot, rvs, rseq in t.value_reads
            )
            if not read_between:
                report.add(
                    "batch-race", ERROR, name,
                    f"batch slots {prev[0]} and {slot} both write value "
                    f"slot {vs}, and slot {slot} never read it first "
                    "(blind overwrite: one slot's output is lost)",
                    value_slot=vs, slot_a=prev[0], slot_b=slot,
                )
                return
        last_write[vs] = (slot, seq)
    # Overreach: next-batch reads beyond the announced count (announced
    # 0 here, so ANY next read is unguarded).
    for s, pfc in t.next_reads:
        report.add(
            "prefetch-protocol", WARN, name,
            f"reads prospective next-batch slot {s} with only {pfc} "
            "announced (guard next_arg/next_idx with "
            "pl.when(s < ctx.prefetch_count))",
            slot=s, announced=pfc,
        )
        break


# ----------------------------------------------------- splice protocol


def check_splice(mk, report: Optional[AnalysisReport] = None,
                 suppress: Sequence[str] = ()) -> AnalysisReport:
    """The dynamic-graph splice protocol (device/dyngraph.py; builds
    stamped ``mk._dyngraph``). Three rules:

    1. NO lane of a dyngraph build runs the cross-round prefetch: a
       prefetched edge slab could race the write-back of the same block
       row by an UPDATE in the current round (rule ``splice-protocol``).
    2. The spare-region wiring is exact: ``spare_base + n * spare`` rows
       of spares behind the static rows must equal the stamped block
       total AND the ``indices`` buffer's leading dim - a mismatch means
       splices write past the buffer or EXPANDs read phantom blocks.
    3. Abstract-interpret the UPDATE batch body (recording shim) and
       require every DMA store into a data buffer to be either a
       READ-MODIFY-WRITE (the same window was DMA-read earlier in the
       trace - the tail-append spelling) or target a row at/above
       ``spare_base`` - the BLIND-OVERWRITE EXEMPTION: the append
       cursor owns fresh spare rows uniquely, so building the row whole
       in VMEM and storing it without a prior read is legal THERE and
       only there. A blind store into a static row is the data-loss
       spelling (it would clobber live edges) and is refused.
    """
    dg = getattr(mk, "_dyngraph", None)
    report = report or AnalysisReport(suppress)
    if dg is None:
        return report
    # (1) prefetch off on every routed lane.
    for fid, spec in mk.batch_specs:
        if spec.prefetch:
            report.add(
                "splice-protocol", ERROR, mk.kernel_names[fid],
                "dyngraph build routes a lane WITH cross-round "
                "prefetch: a prefetched edge slab can race an UPDATE's "
                "block write-back in the same round - build dyngraph "
                "megakernels with prefetch off on every kind",
                fid=fid,
            )
    # (2) spare-region bounds wiring.
    total = dg["spare_base"] + dg["n"] * dg["spare"]
    rows = tuple(mk.data_specs["indices"].shape)[0]
    if total != dg["total_blocks"] or rows != dg["total_blocks"]:
        report.add(
            "splice-protocol", ERROR, "dg_update",
            f"spare-region bounds disagree: spare_base {dg['spare_base']}"
            f" + n {dg['n']} * spare {dg['spare']} = {total}, stamped "
            f"total_blocks {dg['total_blocks']}, indices rows {rows} - "
            "splices would write past the adjacency (or EXPANDs read "
            "phantom rows)",
            computed=total, stamped=dg["total_blocks"], rows=rows,
        )
    # (3) blind-overwrite exemption scoped to the spare region.
    upd_fid = None
    for fid, spec in mk.batch_specs:
        if mk.kernel_names[fid] == "dg_update":
            upd_fid = fid
            upd_spec = spec
    if upd_fid is None:
        return report  # scalar build: no routed body to interpret
    try:
        t = run_batch_body(
            upd_spec, upd_fid, mk.data_specs, mk.scratch_specs,
            prefetch_count=0,
        )
    except ShimUnsupported as e:
        report.add(
            "shim-unsupported", INFO, "dg_update",
            f"splice body not abstractly interpretable ({e}); "
            "blind-overwrite scoping not verifiable",
        )
        return report
    spare_base = int(dg["spare_base"])
    for ev in t.dma:
        if ev.op != "start" or ev.dst_kind != "data":
            continue
        row_lo = ev.dst[1][0][0] if ev.dst[1] else 0
        if row_lo >= spare_base:
            continue  # the exemption: fresh spare rows are owned
        rmw = any(
            o.op == "start" and o.seq < ev.seq and o.src[0] == ev.dst[0]
            and boxes_overlap(o.src[1], ev.dst[1])
            for o in t.dma
        )
        if not rmw:
            report.add(
                "splice-protocol", ERROR, "dg_update",
                f"blind DMA store into STATIC block row {row_lo} of "
                f"{ev.dst[0]!r} (< spare_base {spare_base}) with no "
                "prior read of that window: static rows hold live "
                "edges - append via read-modify-write, or target the "
                "spare region the append cursor owns",
                buffer=ev.dst[0], window=ev.dst[1],
                spare_base=spare_base,
            )
    return report


def _check_prefetch(name: str, fid: int, spec, data_specs, scratch_specs,
                    report: AnalysisReport) -> None:
    """(d): announce a prefetch of k, collect the body's residual
    starts, and require drain() to retire exactly those."""
    k = min(2, spec.width)
    try:
        tb = run_batch_body(
            spec, fid, data_specs, scratch_specs, prefetch_count=k,
        )
    except ShimUnsupported as e:
        report.add(
            "shim-unsupported", INFO, name,
            f"prefetch pass not interpretable ({e})",
        )
        return
    residual = tb.unmatched_starts()
    if not residual:
        if not tb.dma:
            # A compute-only body that opted into prefetch pops (FIFO
            # lane order) without any operand DMA: the protocol is
            # vacuously satisfied - nothing to issue, nothing to drain.
            pass
        elif tb.approx_loops:
            report.add(
                "shim-unsupported", INFO, name,
                "prefetch pass ran with truncated arg-dependent loops "
                "and left no residual starts; start-count conformance "
                "not verifiable",
            )
        else:
            report.add(
                "prefetch-protocol", ERROR, name,
                f"the tier announced a prefetch of {k} next-batch "
                "descriptors but the body issued no residual DMA starts "
                "(a prefetch body MUST issue exactly the starts the tier "
                "announces)",
                announced=k,
            )
        return
    # Which operand half did the prefetch target? The scheduler records
    # LS_PF_BUF = 1 - buf; the shim ran the body with buf=0.
    try:
        td = run_drain(
            spec, fid, data_specs, scratch_specs, prefetched=k, buf=1,
        )
    except ShimUnsupported as e:
        report.add(
            "shim-unsupported", INFO, name,
            f"drain not interpretable ({e})",
        )
        return
    approx = bool(tb.approx_loops or td.approx_loops)
    open_ = [s.triple() for s in residual]
    for w in td.dma:
        if w.op != "wait":
            continue
        if w.triple() in open_:
            open_.remove(w.triple())
        elif approx:
            report.add(
                "shim-unsupported", INFO, name,
                "drain/body DMA sets disagree under truncated "
                "arg-dependent loops; conformance not verifiable",
            )
            return
        else:
            report.add(
                "prefetch-protocol", ERROR, name,
                "drain waits a copy the body never started "
                f"(start-count mismatch): {w.src[0]} -> "
                f"{w.dst[0]}{list(w.dst[1])}",
                dst=w.dst, sem=w.sem, announced=k,
            )
            return
    for s in open_:
        if approx:
            report.add(
                "shim-unsupported", INFO, name,
                "residual prefetch start not drained under truncated "
                "arg-dependent loops; conformance not verifiable",
            )
            return
        report.add(
            "prefetch-protocol", ERROR, name,
            "prefetch DMA start never drained (the scheduler's exit "
            f"path would leave it in flight): {s[0][0]} -> "
            f"{s[1][0]}{list(s[1][1])}",
            src=s[0], dst=s[1], sem=s[2], announced=k,
        )
        return
