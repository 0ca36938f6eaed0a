#!/usr/bin/env python
"""hclint: run the build-time program verifier + whole-program
concurrency model checker over the repo's builders.

The library half (``hclib_tpu.analysis``) runs automatically at
``Megakernel`` construction when ``verify=True`` / ``HCLIB_TPU_VERIFY``
(default-on under pytest) and RAISES on violations. This CLI is the
audit spelling for CI and humans: it constructs every curated in-repo
program builder (workloads, stress configurations, the kernels the
benches and tutorials build), runs the full analysis suite over each -
word-layout consistency, batch-slot race detection, prefetch-protocol
conformance, tile store-window disjointness over concrete tile spaces,
the reshard/migratability classification audit, and (v2, ISSUE 14) the
whole-program model checker: wait-graph deadlock detection over every
kind's spawn/wait/satisfy ops, bounded-interleaving exploration of the
inject-poll / steal-credit / quiesce protocols (every schedule of a
small seeded configuration, checked for termination, conservation, and
the quiesce freeze - wall-budgeted by ``HCLIB_TPU_MODEL_BUDGET_S`` and
depth-bounded by ``HCLIB_TPU_MODEL_DEPTH``), and schedule-independence
certification for the kernels that claim it (frontier BFS/SSSP/
PageRank, forasync tiles - K permuted pop orders to the fixpoint).
Every finding prints with its concrete witness (the colliding windows,
the wait cycle's kind chain, the interleaving prefix, the two divergent
schedules). Exit 1 when any unsuppressed error/warn finding exists
(info notes and spec-annotated suppressions don't gate).

Everything is host-only composition: kernels are CONSTRUCTED, never
built or run - no Pallas lowering, no Mosaic, a few seconds total.

Usage: ``python tools/hclint.py [--json] [--json-out FILE] [--verbose]
[--no-explore]``; ``--json-out`` writes the machine-readable findings
(rule, kernel, witness, severity per program) for the CI artifact so
regressions diff across PRs. CI runs this beside tools/lint.py, before
the test suite.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Tuple

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The CLI drives verification EXPLICITLY (collecting findings instead of
# raising at construction), so force the construction-time hook off for
# the builders below no matter what the environment says.
os.environ["HCLIB_TPU_VERIFY"] = "0"


def _programs() -> List[Tuple[str, "callable"]]:
    """(label, thunk) per curated builder; each thunk returns either a
    Megakernel or a finished AnalysisReport."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from hclib_tpu.analysis import (
        AnalysisReport, check_migratable, check_tile_windows,
        verify_megakernel,
    )
    from hclib_tpu.device.cholesky import make_cholesky_megakernel
    from hclib_tpu.device.forasync_tier import Slab, TileKernel, \
        make_forasync_megakernel
    from hclib_tpu.device.frontier import (
        Graph, bfs_kernel, make_frontier_megakernel, pagerank_kernel,
        search_kernel, sssp_kernel,
    )
    from hclib_tpu.device.smithwaterman import (
        make_sw_batched_megakernel, make_sw_megakernel,
        make_sw_wave_megakernel,
    )
    from hclib_tpu.device.workloads import (
        FIB, make_fib_megakernel, make_uts_megakernel,
        make_vfib_megakernel,
    )

    progs: List[Tuple[str, "callable"]] = []
    progs.append(("fib(scalar)", lambda: make_fib_megakernel(
        256, interpret=True)))
    progs.append(("fib(batch=4)", lambda: make_fib_megakernel(
        256, interpret=True, batch_width=4)))
    progs.append(("uts", lambda: make_uts_megakernel(interpret=True)))
    progs.append(("vfib", lambda: make_vfib_megakernel(interpret=True)))
    progs.append(("cholesky(nt=4)", lambda: make_cholesky_megakernel(
        4, interpret=True)))
    progs.append(("sw", lambda: make_sw_megakernel(4, 4, interpret=True)))
    progs.append(("sw-wave", lambda: make_sw_wave_megakernel(
        4, 4, interpret=True)))
    progs.append(("sw-batched", lambda: make_sw_batched_megakernel(
        4, 4, interpret=True, width=4)))

    rng = np.random.default_rng(7)
    n, m = 32, 96
    g = Graph(
        n, rng.integers(0, n, m), rng.integers(0, n, m),
        rng.integers(1, 9, m),
    )
    for kf in (bfs_kernel, sssp_kernel, pagerank_kernel, search_kernel):
        progs.append((
            f"frontier:{kf().name}",
            lambda kf=kf: make_frontier_megakernel(
                kf(), g, width=4, interpret=True
            ),
        ))

    # The ISSUE 15 priority-bucketed builders: delta-stepping SSSP and
    # bounded-frontier PageRank (bucket rings over the frontier lane;
    # the 5-tuple si_claim certifies the bucketed pop order), plus the
    # branch-and-bound search (best-first = the speedup; optimum
    # certified order-free).
    for kf in (sssp_kernel, pagerank_kernel):
        progs.append((
            f"priority:{kf().name}",
            lambda kf=kf: make_frontier_megakernel(
                kf(), g, width=4, interpret=True, priority_buckets=4,
            ),
        ))

    def bnb_builder():
        from hclib_tpu.device.bnb import make_bnb_megakernel, make_knapsack

        return make_bnb_megakernel(
            make_knapsack(10, seed=5), width=4, priority_buckets=4,
            interpret=True,
        )

    progs.append(("priority:bnb", bnb_builder))

    # The forasync tutorial's 2D Jacobi tile loop, with the whole-loop
    # store-window proof over its concrete tile space.
    N, TS = 32, 8

    def jacobi() -> AnalysisReport:
        from hclib_tpu.analysis import certify_tile_schedule

        specs = {
            "grid": jax.ShapeDtypeStruct((N, N), jnp.int32),
            "out": jax.ShapeDtypeStruct((N, N), jnp.int32),
        }
        tk = TileKernel(
            loads=[Slab(
                "win", "grid",
                lambda a: (pl.ds(a[1], TS), pl.ds(a[2], TS)), (TS, TS),
            )],
            stores=[Slab(
                "wout", "out",
                lambda a: (pl.ds(a[1], TS), pl.ds(a[2], TS)), (TS, TS),
            )],
            compute=lambda ins: {"wout": ins["win"] * 2 + 1},
            data_specs=specs,
        )
        mk = make_forasync_megakernel(tk, width=4, interpret=True)
        rep = verify_megakernel(mk, raise_on_error=False)
        check_tile_windows(tk, [N, N], [TS, TS], report=rep)
        # The schedule-independence certificate over the concrete tile
        # space (refusals would land in rep as findings).
        rep.certificates = {tk.name: certify_tile_schedule(
            tk, [N, N], [TS, TS], report=rep, raise_on_error=False,
        )}
        return rep

    progs.append(("forasync:jacobi2d", jacobi))

    # The same sweep advanced three time steps in one launch, tiles
    # awaiting their five neighbours of the step before: the build, the
    # store windows, read-before-overwrite between steps, and K orders
    # that honour the declared awaits and no more.
    def jacobi_steps() -> AnalysisReport:
        from hclib_tpu.analysis import certify_tile_schedule
        from hclib_tpu.device.workloads import jacobi_loop

        tk, bounds, tile = jacobi_loop(32, 512, 8, 128, steps=3)
        mk = make_forasync_megakernel(
            tk, width=4, interpret=True, space=(bounds, tile))
        rep = verify_megakernel(mk, raise_on_error=False)
        check_tile_windows(tk, bounds, tile, report=rep)
        rep.certificates = {tk.name: certify_tile_schedule(
            tk, bounds, tile, report=rep, raise_on_error=False,
        )}
        return rep

    progs.append(("forasync:jacobi-steps", jacobi_steps))

    # The mesh stress configuration's migratability claim (stress.
    # forest_steal: fib on the sharded exchange) - audited, with the
    # workload's own suppression annotation honored.
    def forest_claim() -> AnalysisReport:
        mk = make_fib_megakernel(256, interpret=True, batch_width=4)
        return check_migratable(
            mk, [FIB], "stress.forest_steal",
            suppress=mk.verify_suppress,
        )

    progs.append(("stress:forest_steal", forest_claim))

    # Tenant front-door roster (the PR 8/13 ingress configuration the
    # CI smokes run): its WRR poll explored over EVERY schedule via the
    # roster-seeded protocol model (TenantTable.protocol_model wraps
    # wrr_poll_reference - the same executable spec the fairness tests
    # pin), plus the inner megakernel's standard verification.
    def tenant_front_door() -> AnalysisReport:
        from hclib_tpu.analysis import check_protocols
        from hclib_tpu.device.tenants import TenantSpec, TenantTable

        tb = TenantTable(
            [TenantSpec("gold", weight=2), TenantSpec("std"),
             TenantSpec("best-effort")],
            16, clock=lambda: 0.0,
        )
        return check_protocols(configs=[
            ("tenants:wrr(2:1:1)", tb.protocol_model()),
        ])

    progs.append(("tenants:front_door", tenant_front_door))
    return progs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--json-out", metavar="FILE",
                    help="also write the machine-readable findings to "
                         "FILE (the CI artifact - diffable across PRs)")
    ap.add_argument("--no-explore", action="store_true",
                    help="skip the bounded-interleaving protocol "
                         "exploration (the model-checker half)")
    ap.add_argument("--verbose", action="store_true",
                    help="print clean programs and info findings too")
    args = ap.parse_args(argv)

    from hclib_tpu.analysis import (
        check_layout, check_protocols, classify_megakernel,
        verify_megakernel,
    )
    from hclib_tpu.analysis.findings import AnalysisReport

    out = {}
    bad = 0

    lay = check_layout(force=True)
    out["layout"] = {"findings": lay.to_jsonable(), "kind_classes": {}}
    bad += len(lay.actionable())

    def emit(label, rep, certs=None):
        nonlocal bad
        out[label] = {
            "findings": rep.to_jsonable(),
            "kind_classes": dict(rep.kind_classes),
            "certificates": dict(certs or {}),
        }
        if rep.kind_classes and not args.json and args.verbose:
            cls = ", ".join(
                f"{k}={v}" for k, v in sorted(rep.kind_classes.items())
            )
            print(f"{label}: {cls}")
        if certs and not args.json and args.verbose:
            for k, c in sorted(certs.items()):
                print(f"{label}: schedule-independence[{k}]: "
                      f"{c.get('status')}")
        bad += len(rep.actionable())
        for f in rep.findings:
            if args.json:
                continue
            if f.severity == "info" and not args.verbose:
                continue
            print(f"{label}: {f}")

    for label, thunk in _programs():
        try:
            obj = thunk()
        except Exception as e:  # noqa: BLE001 - report, keep auditing
            out[label] = {"findings": [{
                "rule": "builder-error", "severity": "error",
                "kernel": None, "message": f"{type(e).__name__}: {e}",
                "witness": {}, "suppressed": False,
            }], "kind_classes": {}, "certificates": {}}
            bad += 1
            continue
        certs = {}
        if isinstance(obj, AnalysisReport):
            rep = obj
            certs = dict(getattr(obj, "certificates", {}) or {})
        else:
            rep = verify_megakernel(
                obj, suppress=getattr(obj, "verify_suppress", ()),
                raise_on_error=False,
            )
            rep.kind_classes = classify_megakernel(obj)
            if getattr(obj, "si_claim", None) is not None:
                from hclib_tpu.analysis import certify_claim

                cert = certify_claim(
                    obj, raise_on_error=False, report=rep,
                )
                if cert is not None:
                    certs[cert.get("kind", cert.get("kernel", "?"))] = (
                        cert
                    )
        emit(label, rep, certs)

    # The bounded-interleaving model checker over the curated protocol
    # configurations (inject WRR + quiesce freeze + credit exchange):
    # every schedule of each small seeded config, wall-budgeted
    # (HCLIB_TPU_MODEL_BUDGET_S) and depth-bounded
    # (HCLIB_TPU_MODEL_DEPTH) - CI's hard budget is the step timeout.
    if not args.no_explore:
        prot = check_protocols()
        prot.kind_classes = {}
        emit("protocols", prot)

    doc = json.dumps(out, indent=2)
    if args.json:
        print(doc)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(doc + "\n")
    if bad:
        print(f"hclint: {bad} actionable finding(s)", file=sys.stderr)
        return 1
    if not args.json:
        n = len(out) - 1
        print(f"hclint: {n} program(s) + layout table clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
