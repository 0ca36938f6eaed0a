"""Driver of the Graph500 deployments: one operation is one breadth-first
search, ``GraphSearch.bfs(key)`` from numpy key to numpy parent array, over
ONE graph that lives on the chip, on one prebuilt ``Megakernel``.

Set-up asks the program for its search first, before any data is made: a
program without ``hclib_tpu.device.frontier.GraphSearch`` fails there,
within seconds. Then the edge list comes from the plain reference
(``reference/graph500.py``: it is the data, as weights are to a model),
the search keys by the specification's rule, and the program builds its
graph from the list (kernel 1: not in the window, the specification times
it apart) and its ``Megakernel``, once.

Each operation searches from the next key, in the specification's order,
wrapping after the last. The program resets its own state on the device
inside the call (the filter cleared, the values zero). Every record holds
the search's own books; the parent arrays of the newest search and of a
reservoir of earlier ones (``keep_results`` of the mix) are kept for the
check, which holds each to the reference in full: the five rules, levels
equal to the reference's own search at every vertex, and the program's
``reached`` and ``levels`` to the tree's. All integers, all limits 0.

A control (``fuel`` at the configuration's top level, where the
configuration of record does not have it) gives a search fewer tasks than
it needs.
"""

from __future__ import annotations

import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import graph500 as ref

BOOKS = ("edges", "reached", "levels", "expands", "frontier_max",
         "live_rows_max", "capacity", "batch_rounds", "batch_slots",
         "hbm_words_read", "hbm_words_written")


def setup(cfg, mix, seed, interpret):
    try:
        from hclib_tpu.device.frontier import Graph, GraphSearch
    except ImportError as e:
        raise RuntimeError(
            "this program has no GraphSearch: it cannot run this "
            f"deployment ({e})") from e
    if cfg["eblock"] != 128 or not cfg["undirected"]:
        raise RuntimeError("this driver runs undirected graphs in the "
                           "program's 128-entry blocks")
    n = 1 << cfg["scale"]
    u, v = ref.edge_list(seed, cfg["scale"], cfg["edgefactor"],
                         tuple(cfg["initiator"]))
    keys = ref.search_keys(seed, n, u, v, cfg["search_keys"])
    t0 = time.monotonic()
    graph = Graph.undirected(n, u, v)
    kw = {} if cfg.get("fuel") is None else {"fuel": cfg["fuel"]}
    search = GraphSearch(graph, width=cfg["width"],
                         capacity=cfg["capacity"], interpret=interpret, **kw)
    print(json.dumps({"kernel1": {
        "seconds": time.monotonic() - t0, "vertices": n, "tuples": len(u),
        "blocks": graph.nblocks, "max_degree": int(graph.deg.max())}}))
    return {
        "n": n, "u": u, "v": v, "keys": keys, "search": search,
        "calls": 0, "kept": [], "keep": mix["keep_results"],
        "rng": np.random.default_rng([seed, 1 << 28]),
    }


def operation(st):
    call = st["calls"]
    key = int(st["keys"][call % len(st["keys"])])
    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        parent, info = st["search"].bfs(key)
    t1 = time.monotonic()
    st["calls"] = call + 1
    # The newest result last; before it a reservoir of the earlier ones.
    kept, keep = st["kept"], st["keep"]
    if len(kept) > keep:
        j = int(st["rng"].integers(0, call))
        if j < keep:
            kept[j] = kept[-1]
        kept.pop()
    kept.append((call, key, parent))
    books, tiers = info["search"], info["tiers"]
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": 1, "call": call,
        "key": key, "root_is_its_own_parent": int(parent[key] == key),
        "blocks_in_component": st["search"].blocks_of(parent),
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "interpret", "platform")},
        **{k: books[k] for k in BOOKS},
        "batch_occupancy": tiers["batch_occupancy"],
    }


def check(st, records):
    n, u, v = st["n"], st["u"], st["v"]
    by_call = {r["call"]: r for r in records}
    t0 = time.monotonic()
    found = []
    for call, key, parent in st["kept"]:
        r = by_call.get(call)
        if r is None:  # the warm call, or the one before a trace
            continue
        held = ref.search_and_validate(n, u, v, key, parent)
        # What the metrics read of the reference, on the record itself.
        r["component_edges"] = held["component_edges"]
        r["component_entries"] = 2 * held["component_edges"]
        r["reached_by_reference"] = held["reached"]
        found.append({
            **{k: held[k] for k in ref.RULES + ("levels_differ",)},
            "reached_abs_err": abs(r["reached"] - held["reached"]),
            "levels_abs_err": abs(r["levels"] - held["levels"]),
        })
    print(json.dumps({"reference": {
        "seconds": time.monotonic() - t0, "searches_held": len(found)}}))

    def books(r):
        return {
            "pending": r["pending"],
            "overflowed": int(bool(r["overflow"])),
            "table_filled": int(r["live_rows_max"] >= r["capacity"]),
            "root_not_its_own_parent": 1 - r["root_is_its_own_parent"],
            "nothing_reached": int(r["reached"] < 1),
            "expands_abs_err": abs(r["expands"] - r["blocks_in_component"]),
        }

    per_call = [books(r) for r in records]
    bad = sum(any(e.values()) for e in per_call)
    if not found or any(any(f.values()) for f in found):
        bad = len(records)  # a wrong search judges no search sound
    compared = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared += [(k, max(f[k] for f in found), 0) for k in found[0]] \
        if found else [("searches_held_missing", 1, 0)]
    return bad, compared
