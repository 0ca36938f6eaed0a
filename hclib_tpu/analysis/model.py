"""Schedule-independence certification (the ``schedule-independence``
rule) - the third model-checker analysis.

The frontier traversals (BFS/SSSP as monotone label correction,
PageRank as conserved integer mass) and the forasync tile loops claim
their results are independent of execution order - that claim is what
lets "bit-identical across scalar dispatch, batched tier, and the
mesh" hold with no ordering machinery, and what makes their rows
migratable/reshardable without replay. This module CHECKS the claim
instead of trusting the docstring: run the kernel's abstract body
(the same relax/compute trace the device executes, host-side over
concrete numpy state) to the fixpoint under K permuted pop orders and
prove the final state identical. Identical -> a certificate surfaced in
``Megakernel.describe()`` beside the reshard classification; divergent
-> certification is REFUSED with both schedules in the diagnostic (an
``AnalysisError`` whose witness carries the two pop orders and the
first differing word).

Like every hclint analysis this is host-only composition - no Pallas
build, no Mosaic - and lazy: builders stamp ``mk.si_claim`` at
construction for free, and the certification runs on demand
(describe(), tools/hclint.py, the CI step), memoized per claim.

A certificate is evidence over K orders of a seeded configuration, not
a proof over all schedules - which is exactly the exactness contract
the runtime leans on (the acceptance suites then pin bit-identity on
the real schedules). K rides ``HCLIB_TPU_MODEL_PERMS``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..runtime.env import env_int
from .findings import ERROR, AnalysisReport
from .shim import BodyTrace, FakeRef, _norm_box, _patched

__all__ = [
    "certify_claim",
    "certify_bnb_schedule",
    "certify_frontier_schedule",
    "certify_tile_schedule",
]

RULE = "schedule-independence"

# Tile spaces above this are not concretely simulated K times at
# describe() time (the certificate would cost more than the build);
# hclint's curated spaces sit far below it.
TILE_SPACE_CAP = 4096
# Fixpoint step cap: a (buggy) diverging claim terminates the
# certification instead of the process.
STEP_CAP = 200_000

_frontier_cache: Dict[Tuple, Dict[str, Any]] = {}

import weakref  # noqa: E402

_tile_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _perms() -> int:
    return max(2, env_int("HCLIB_TPU_MODEL_PERMS", 3))


def _np_index(box) -> Tuple:
    return tuple(slice(lo, hi) for lo, hi in box)


def _fill(shape, dtype, salt: int) -> np.ndarray:
    """Deterministic synthetic buffer contents (iota + salt, wrapped
    small so int dtypes never overflow under arithmetic bodies)."""
    n = int(np.prod(shape)) if shape else 1
    base = (np.arange(n, dtype=np.int64) * 7 + salt * 13) % 97
    return base.reshape(shape).astype(dtype)


def _finding_jsonable(f) -> List[Dict[str, Any]]:
    return [f.to_jsonable()]


def _schedule_witness(order: Sequence, cap: int = 16) -> List:
    out = [list(map(int, np.atleast_1d(o))) if not np.isscalar(o)
           else int(o) for o in list(order)[:cap]]
    if len(order) > cap:
        out.append(f"... {len(order) - cap} more")
    return out


# ------------------------------------------------------------ tiles


def certify_tile_schedule(tk, bounds, tile, *,
                          perms: Optional[int] = None, seed: int = 0,
                          report: Optional[AnalysisReport] = None,
                          raise_on_error: bool = True) -> Dict[str, Any]:
    """Certify one forasync tile loop: execute every tile's
    load->compute->store pipeline concretely over synthetic buffers in
    K permuted orders; identical final buffers = certified. A tile
    whose LOADS overlap another tile's STORES is order-dependent (the
    in-place-stencil bug class) and diverges concretely - refused with
    the two schedules.

    A loop of several steps (``tk.steps`` > 1) is certified over its
    (step, tile) nodes in K random orders that honour what it DECLARES
    it awaits, and no more: a tile that reads or overwrites what a tile
    it does not await stores or reads diverges between two of them."""
    from ..device.forasync_tier import StepPlan, tile_args, tile_grid

    perms = _perms() if perms is None else int(perms)
    key = (repr(tuple(bounds)),
           repr(tuple(tile) if not isinstance(tile, int) else (tile,)),
           perms, seed)
    cached = _tile_cache.get(tk)
    if cached is not None and key in cached:
        return cached[key]
    dims, tile_dims, counts, total = tile_grid(bounds, tile)
    steps = tk.steps
    plan = StepPlan(tk, dims, tile_dims, counts) if steps > 1 else None
    cert: Dict[str, Any] = {
        "claim": "forasync-tiles", "kernel": tk.name,
        "tiles": total * steps, "orders": perms,
    }
    if total * steps > TILE_SPACE_CAP:
        cert["status"] = f"unverified (tile space {total * steps} > cap)"
        return cert

    def run_order(order) -> Dict[str, np.ndarray]:
        bufs = {
            name: _fill(tuple(spec.shape), np.dtype(spec.dtype), si)
            for si, (name, spec) in enumerate(sorted(
                tk.data_specs.items()
            ))
        }
        for node in order:
            step, flat = divmod(int(node), total)
            args = tuple(tile_args(dims, tile_dims, counts, flat)
                         + [step] * (steps > 1))
            ins = {}
            for s in tk.loads:
                box = _norm_box(bufs[s.data].shape, s.index(args))
                got = bufs[s.data][_np_index(box)].copy()
                if s.into is None:
                    ins[s.name] = got
                else:  # a window of a shared staging buffer
                    stage = ins.setdefault(s.into, np.zeros(
                        tk.staging[s.into], got.dtype))
                    stage[_np_index(_norm_box(stage.shape, s.at))] = got
            outs = tk.compute(ins)
            for s in tk.stores:
                box = _norm_box(bufs[s.data].shape, s.index(args))
                bufs[s.data][_np_index(box)] = np.asarray(outs[s.name])
        return bufs

    rng = np.random.default_rng(seed)
    orders = [list(range(total * steps))]  # step by step: always legal
    for _ in range(perms - 1):
        orders.append(list(rng.permutation(total)) if steps == 1
                      else _awaiting_order(plan, rng))
    ref = run_order(orders[0])
    for k in range(1, perms):
        got = run_order(orders[k])
        for name in sorted(ref):
            if not np.array_equal(ref[name], got[name]):
                diff = np.argwhere(
                    np.asarray(ref[name]) != np.asarray(got[name])
                )[0]
                report = report or AnalysisReport()
                f = report.add(
                    RULE, ERROR, tk.name,
                    f"tile loop {tk.name!r} is order-DEPENDENT: buffer "
                    f"{name!r} diverges at {tuple(int(i) for i in diff)} "
                    "between two pop orders (a tile reads a window "
                    "another tile stores); certification refused",
                    buffer=name, index=tuple(int(i) for i in diff),
                    schedule_a=_schedule_witness(orders[0]),
                    schedule_b=_schedule_witness(orders[k]),
                    value_a=ref[name][tuple(diff)],
                    value_b=got[name][tuple(diff)],
                )
                cert["status"] = "refused (order-dependent)"
                # Only THIS refusal rides the certificate (the caller's
                # report may hold unrelated program findings).
                cert["findings"] = _finding_jsonable(f)
                if raise_on_error:
                    report.raise_errors()
                return cert
    cert["status"] = "certified"
    if cached is None:
        try:
            _tile_cache[tk] = {key: cert}
        except TypeError:
            pass
    else:
        cached[key] = cert
    return cert


def _awaiting_order(plan, rng) -> List[int]:
    """One random order of a stepped loop's nodes (``step * total +
    flat``) in which every tile comes after the tiles of the step before
    that it awaits (``StepPlan.near``), drawn a ready node at a time."""
    total, steps = plan.total, plan.steps
    near = [plan.near(c) for c in np.ndindex(*plan.counts)]
    left = {(s, f): len(near[f])
            for s in range(1, steps) for f in range(total)}
    ready = [(0, f) for f in range(total)]
    order = []
    while ready:
        step, flat = ready.pop(int(rng.integers(len(ready))))
        order.append(step * total + flat)
        if step + 1 < steps:
            for n in near[flat]:  # symmetric: the tiles that await me
                left[step + 1, n] -= 1
                if not left[step + 1, n]:
                    ready.append((step + 1, n))
    return order


# --------------------------------------------------------- frontier


class _AbsFrontierCtx:
    """The concrete-interpretation context one frontier task body runs
    against: real numpy ivalues behind a FakeRef (so ``pl.when`` /
    ``fori_loop`` patched by the shim evaluate concretely) and a spawn
    sink feeding the worklist."""

    def __init__(self, iv: np.ndarray, sink: List[Tuple[int, ...]]):
        self.ivalues = FakeRef("abs:ivalues", "smem", backing=iv)
        self._sink = sink

    def spawn(self, fn, args=(), nargs=None, **kw) -> int:
        self._sink.append(
            tuple(int(np.asarray(a)) for a in args)
        )
        return 0


def _small_graph(seed: int):
    from ..device.frontier import Graph
    from ..device.workloads import rmat_edges

    n, src, dst, w = rmat_edges(4, efactor=4, seed=seed + 11)
    return Graph(n, src, dst, w)


def certify_frontier_schedule(kind: str, *, reps: int = 64,
                              perms: Optional[int] = None, seed: int = 0,
                              buckets: int = 0, delta: int = 1,
                              report: Optional[AnalysisReport] = None,
                              raise_on_error: bool = True,
                              fk=None, graph=None) -> Dict[str, Any]:
    """Certify one frontier traversal kind: run its relax body (the
    SAME ``_relax_block`` loop both dispatch spellings trace) to the
    fixpoint over a small seeded R-MAT graph under K permuted worklist
    pop orders, and prove the per-vertex state identical. With
    ``buckets`` (a priority-bucketed build's claim, ISSUE 15) one extra
    order is the BUCKETED pop - always take a lowest-bucket entry, via
    the host spelling of the device priority function
    (frontier.priority_bucket) - so the priority tier's pop order is
    certified against the same fixpoint as the random permutations.
    ``fk``/``graph`` override the defaults (the order-dependent-refusal
    tests pass a planted kernel)."""
    from ..device.frontier import _KINDS, priority_bucket, seed_frontier

    perms = _perms() if perms is None else int(perms)
    custom = fk is not None or graph is not None
    key = ("frontier", kind, reps, perms, seed, buckets, delta)
    if not custom and key in _frontier_cache:
        return _frontier_cache[key]
    g = graph if graph is not None else _small_graph(seed)
    if fk is None:
        if kind not in _KINDS:
            raise ValueError(f"unknown frontier kind {kind!r}")
        fk = _KINDS[kind](reps=reps) if kind == "pagerank" else (
            _KINDS[kind]()
        )
    fk.st_base = g.st_base
    m0 = 1 << 12
    seeds = seed_frontier(None, g, kind, src=0, m0=m0, reps=reps)
    cert: Dict[str, Any] = {
        "claim": "frontier", "kind": kind,
        "orders": perms + (1 if buckets else 0),
        "vertices": g.n, "seeds": len(seeds),
        **({"buckets": int(buckets), "delta": int(delta)}
           if buckets else {}),
    }

    def run_order(perm_seed: int):
        from ..device.frontier import _pr_seed_rank

        iv = g.preset_values(g.num_value_slots, fk.state0).astype(
            np.int64
        )
        if kind in ("bfs", "sssp"):
            iv[g.st_base] = 0
        elif kind == "pagerank":
            iv[g.st_base : g.st_base + g.n] = _pr_seed_rank(g, m0, reps)
        wl: List[Tuple[int, ...]] = list(seeds)
        rng = np.random.default_rng(seed * 1000 + max(perm_seed, 0))
        schedule: List[Tuple[int, ...]] = []
        steps = 0
        trace = BodyTrace()
        with _patched(trace):
            while wl:
                steps += 1
                if steps > STEP_CAP:
                    return None, schedule, steps
                if perm_seed == 0:
                    i = 0
                elif perm_seed == -1:
                    # The bucketed pop order: lowest clipped bucket
                    # first (FIFO within a bucket) - exactly what the
                    # device's bucket-major drain retires.
                    i = int(np.argmin([
                        min(priority_bucket(kind, c, delta=delta,
                                            reps=reps), buckets - 1)
                        for _v, _b, c, _c in wl
                    ]))
                else:
                    i = int(rng.integers(len(wl)))
                v, blk, carry, cnt = wl.pop(i)
                schedule.append((v, blk, carry, cnt))
                ctx = _AbsFrontierCtx(iv, wl)
                fk._relax_block(
                    ctx,
                    lambda e, blk=blk: int(g.indices[blk][int(e)]),
                    (lambda e, blk=blk: int(g.weights[blk][int(e)]))
                    if fk.weighted else None,
                    carry,
                    cnt,
                )
        return iv[g.st_base : g.st_base + g.n].copy(), schedule, steps

    ref, sched0, steps0 = run_order(0)
    if ref is None:
        cert["status"] = f"unverified (fixpoint > {STEP_CAP} steps)"
        return cert
    cert["tasks"] = steps0
    order_ids = list(range(1, perms)) + ([-1] if buckets else [])
    for k in order_ids:
        got, schedk, _ = run_order(k)
        if got is None:
            cert["status"] = f"unverified (fixpoint > {STEP_CAP} steps)"
            return cert
        if not np.array_equal(ref, got):
            v = int(np.argwhere(ref != got)[0][0])
            report = report or AnalysisReport()
            f = report.add(
                RULE, ERROR, fk.name,
                f"frontier kind {fk.name!r} is order-DEPENDENT: vertex "
                f"{v} fixpoint diverges ({int(ref[v])} vs {int(got[v])})"
                " between two pop orders; certification refused - the "
                "two divergent schedules ride the witness",
                vertex=v, value_a=int(ref[v]), value_b=int(got[v]),
                schedule_a=_schedule_witness(sched0),
                schedule_b=_schedule_witness(schedk),
            )
            cert["status"] = "refused (order-dependent)"
            cert["findings"] = _finding_jsonable(f)
            if raise_on_error:
                report.raise_errors()
            return cert
    cert["status"] = "certified"
    if not custom:
        _frontier_cache[key] = cert
    return cert


# ---------------------------------------------------------- dyngraph

_dyngraph_cache: Dict[Tuple, Dict[str, Any]] = {}


def certify_dyngraph_schedule(kind: str, *, reps: int = 64,
                              buckets: int = 0,
                              updates: Sequence[Tuple[int, int, int]] = (),
                              perms: Optional[int] = None, seed: int = 0,
                              report: Optional[AnalysisReport] = None,
                              raise_on_error: bool = True,
                              graph=None) -> Dict[str, Any]:
    """Certify a dynamic-graph claim (device/dyngraph.py): the mutated
    fixpoint is independent of how splices interleave with frontier
    expansion. Runs the host incremental twin (same splice rule - spare
    bounds, drop mirror - same relax) over a small seeded R-MAT
    ``DynGraph`` carrying the claim's update stream, under K permuted
    op-pool orders PLUS the two adversarial extremes (every update
    before any expansion, and after all initial expansion), and proves
    every fixpoint equal to the FROM-SCRATCH reference on the mutated
    graph (bfs/sssp, bit-identity) or total mass conserved exactly
    (pagerank - the result is schedule-dependent by design; the
    certificate claims conservation, which is what the serving tier
    promises). Update endpoints fold into the model graph's vertex
    range - the certificate is about the SPLICE PROTOCOL, not the
    caller's instance (the frontier discipline)."""
    from ..device.dyngraph import (
        DynGraph, host_dyngraph, host_incremental,
        host_incremental_pagerank,
    )

    perms = _perms() if perms is None else int(perms)
    ups = tuple(
        (int(u), int(v), max(int(w), 0)) for u, v, w in updates
    )
    custom = graph is not None
    key = ("dyngraph", kind, reps, perms, seed, buckets, ups)
    if not custom and key in _dyngraph_cache:
        return _dyngraph_cache[key]
    if graph is None:
        from ..device.workloads import rmat_edges

        n, src, dst, w = rmat_edges(4, efactor=4, seed=seed + 11)
        graph = DynGraph(n, src, dst, w, spare_blocks=2,
                         upd_cap=max(len(ups), 1) + 1)
    for u, v, w in ups:
        graph.add_update(u % graph.n, v % graph.n, w)
    cert: Dict[str, Any] = {
        "claim": "dyngraph", "kind": kind,
        "updates": len(graph.updates), "vertices": graph.n,
        **({"buckets": int(buckets)} if buckets else {}),
    }
    rng = np.random.default_rng(seed * 1000 + 7)
    m0 = 1 << 12

    if kind == "pagerank":
        rank0, _ = host_incremental_pagerank(graph, m0=m0, reps=reps)
        total = int(rank0.sum())
        cert["mass"] = total
    elif kind in ("bfs", "sssp"):
        ref = host_dyngraph(kind, graph, src=0)
    else:
        raise ValueError(
            f"unknown dyngraph kind {kind!r} (bfs|sssp|pagerank)"
        )

    def order_list(tag):
        if kind == "pagerank":
            rank, _ = host_incremental_pagerank(
                graph, m0=m0, reps=reps, order=tag
            )
            return rank
        return host_incremental(kind, graph, src=0, order=tag)

    # Pool size as the twins build it.
    if kind == "pagerank":
        npool = sum(
            1
            for v in range(graph.n)
            for _u in graph.adj[v]
            if _pr_survives(graph, v, m0, reps)
        ) + len(graph.updates)
    else:
        npool = 1 + len(graph.updates)
    idx = np.arange(npool)
    upd_lo = npool - len(graph.updates)
    extremes = [
        np.concatenate([idx[upd_lo:], idx[:upd_lo]]),  # updates first
        idx.copy(),                                    # updates last
    ]
    tags = [None] + [rng.permutation(npool) for _ in range(perms)]
    tags += [e for e in extremes]
    cert["orders"] = len(tags)
    for t in tags:
        got = order_list(None if t is None else list(int(i) for i in t))
        if kind == "pagerank":
            if int(got.sum()) != total:
                report = report or AnalysisReport()
                f = report.add(
                    RULE, ERROR, "dg_update",
                    "dyngraph pagerank mass is NOT conserved across "
                    f"splice interleavings: {int(got.sum())} vs {total};"
                    " certification refused",
                    value_a=total, value_b=int(got.sum()),
                )
                cert["status"] = "refused (mass not conserved)"
                cert["findings"] = _finding_jsonable(f)
                if raise_on_error:
                    report.raise_errors()
                return cert
        elif not np.array_equal(ref, got):
            v = int(np.argwhere(ref != got)[0][0])
            report = report or AnalysisReport()
            f = report.add(
                RULE, ERROR, "dg_update",
                f"dyngraph kind {kind!r} incremental fixpoint is "
                f"order-DEPENDENT: vertex {v} diverges "
                f"({int(ref[v])} vs {int(got[v])}) from the "
                "from-scratch reference on the mutated graph; "
                "certification refused",
                vertex=v, value_a=int(ref[v]), value_b=int(got[v]),
            )
            cert["status"] = "refused (order-dependent)"
            cert["findings"] = _finding_jsonable(f)
            if raise_on_error:
                report.raise_errors()
            return cert
    cert["status"] = "certified"
    if not custom:
        _dyngraph_cache[key] = cert
    return cert


def _pr_survives(graph, v: int, m0: int, reps: int) -> bool:
    from ..device.frontier import _pr_split

    deg = int(graph.deg[v])
    qc = _pr_split(m0, deg)
    return m0 >= reps and qc > 0 and deg > 0


# -------------------------------------------------------------- bnb

_bnb_cache: Dict[Tuple, Dict[str, Any]] = {}


def certify_bnb_schedule(values, weights, cap: int, *,
                         buckets: int = 0,
                         perms: Optional[int] = None, seed: int = 0,
                         report: Optional[AnalysisReport] = None,
                         raise_on_error: bool = True) -> Dict[str, Any]:
    """Certify a branch-and-bound claim (device/bnb.py): the OPTIMUM a
    run proves is independent of the pop order. Runs the host worklist
    model (same bound test, same branch rule as the device body) under
    K permuted orders plus - when the claim is bucketed - the
    best-first order itself, and proves the final incumbent identical.
    Pruned/executed counts legitimately differ per schedule (that IS
    the priority speedup) and are deliberately not compared."""
    from ..device.bnb import Knapsack, bnb_bucket

    perms = _perms() if perms is None else int(perms)
    key = ("bnb", tuple(values), tuple(weights), int(cap), int(buckets),
           perms, seed)
    if key in _bnb_cache:
        return _bnb_cache[key]
    kp = Knapsack(values, weights, cap)
    cert: Dict[str, Any] = {
        "claim": "bnb", "kind": "bnb", "items": kp.n, "cap": kp.cap,
        "orders": perms + (1 if buckets else 0),
        **({"buckets": int(buckets)} if buckets else {}),
    }

    def run_order(perm_seed: int):
        rng = np.random.default_rng(seed * 1000 + max(perm_seed, 0))
        best, steps = 0, 0
        wl: List[Tuple[int, int, int, int]] = [(0, 0, 0, kp.total)]
        schedule: List[Tuple[int, ...]] = []
        while wl:
            steps += 1
            if steps > STEP_CAP:
                return None, schedule, steps
            if perm_seed == 0:
                i = 0
            elif perm_seed == -1:
                # The bucketed (best-first) pop: lowest bucket id =
                # highest bound, via the host spelling of the device
                # priority function.
                i = int(np.argmin([
                    min(bnb_bucket(kp, b, buckets), buckets - 1)
                    for _l, _v, _w, b in wl
                ]))
            else:
                i = int(rng.integers(len(wl)))
            level, value, weight, bound = wl.pop(i)
            schedule.append((level, value, weight, bound))
            if bound <= best:
                continue
            if level == kp.n:
                best = max(best, value)
                continue
            sfx = int(kp.suffix[level + 1])
            wl.append((level + 1, value, weight, value + sfx))
            v_i, w_i = int(kp.values[level]), int(kp.weights[level])
            if weight + w_i <= kp.cap:
                wl.append(
                    (level + 1, value + v_i, weight + w_i,
                     value + v_i + sfx)
                )
        return best, schedule, steps

    ref, sched0, steps0 = run_order(0)
    if ref is None:
        cert["status"] = f"unverified (search > {STEP_CAP} steps)"
        return cert
    cert["tasks"] = steps0
    cert["optimum"] = int(ref)
    for k in list(range(1, perms)) + ([-1] if buckets else []):
        got, schedk, _ = run_order(k)
        if got is None:
            cert["status"] = f"unverified (search > {STEP_CAP} steps)"
            return cert
        if got != ref:
            report = report or AnalysisReport()
            f = report.add(
                RULE, ERROR, "bnb_node",
                f"branch-and-bound incumbent is order-DEPENDENT: "
                f"optimum {ref} vs {got} between two pop orders; "
                "certification refused - the two divergent schedules "
                "ride the witness",
                value_a=int(ref), value_b=int(got),
                schedule_a=_schedule_witness(sched0),
                schedule_b=_schedule_witness(schedk),
            )
            cert["status"] = "refused (order-dependent)"
            cert["findings"] = _finding_jsonable(f)
            if raise_on_error:
                report.raise_errors()
            return cert
    cert["status"] = "certified"
    _bnb_cache[key] = cert
    return cert


# ------------------------------------------------------------ claims


def certify_claim(mk, *, raise_on_error: bool = True,
                  report: Optional[AnalysisReport] = None
                  ) -> Optional[Dict[str, Any]]:
    """Resolve and certify ``mk.si_claim`` (stamped by
    make_frontier_megakernel / run_forasync_device). Returns the
    certificate dict, or None when the builder made no claim. With
    ``raise_on_error`` a refused certification raises ``AnalysisError``
    carrying both divergent schedules."""
    claim = getattr(mk, "si_claim", None)
    if claim is None:
        return None
    if claim[0] == "frontier":
        # 3-tuple: (tag, kind, reps) - the unbucketed spelling. The
        # priority-bucketed builders (ISSUE 15) stamp the 5-tuple
        # (tag, kind, reps, buckets, delta) so the bucketed pop order
        # itself is one of the certified schedules.
        _tag, kind, reps = claim[:3]
        buckets = int(claim[3]) if len(claim) > 3 and claim[3] else 0
        delta = int(claim[4]) if len(claim) > 4 and claim[4] else 1
        return certify_frontier_schedule(
            kind, reps=int(reps or 64), buckets=buckets, delta=delta,
            report=report, raise_on_error=raise_on_error,
        )
    if claim[0] == "dyngraph":
        # (tag, kind, reps, buckets, updates) - the dynamic-graph
        # service claim (ISSUE 20). ``updates`` is None at build time
        # (the tile-claim discipline: certifying an unbound claim would
        # prove a stream the build never ran); run_dyngraph stamps the
        # registered stream before the run.
        _tag, kind, reps, buckets, updates = claim
        if updates is None:
            return {
                "claim": "dyngraph", "kind": kind,
                "status": "unbound (no update stream run yet: "
                          "run_dyngraph stamps it)",
            }
        return certify_dyngraph_schedule(
            kind, reps=int(reps or 64), buckets=int(buckets or 0),
            updates=updates, report=report,
            raise_on_error=raise_on_error,
        )
    if claim[0] == "bnb":
        _tag, values, weights, cap, buckets = claim
        return certify_bnb_schedule(
            values, weights, int(cap), buckets=int(buckets or 0),
            report=report, raise_on_error=raise_on_error,
        )
    if claim[0] == "tile":
        _tag, tk, bounds, tile = claim
        if bounds is None:
            return {
                "claim": "forasync-tiles", "kernel": tk.name,
                "status": "unbound (no tile space run yet: "
                          "run_forasync_device stamps it)",
            }
        return certify_tile_schedule(
            tk, bounds, tile, report=report,
            raise_on_error=raise_on_error,
        )
    raise ValueError(f"unknown schedule-independence claim {claim[0]!r}")
