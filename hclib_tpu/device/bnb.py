"""Branch-and-bound search on the priority-bucket dispatch tier.

ISSUE 15's third workload - the one where priority IS the speedup. The
frontier traversals use buckets to do the *same* fixpoint with less
re-relaxation; branch-and-bound is different: the set of nodes a run
must EXPAND depends on how early a good incumbent is found, so
best-first retirement (highest optimistic bound first) prunes
subtrees an unordered run would fully explore. The proven optimum is
schedule-independent either way - any completed leaf only ever RAISES
the incumbent (a monotone max in one SMEM value slot), and a node is
pruned only when its bound cannot beat the incumbent, which can never
cut off the optimal leaf's prefix - so "bit-identical optimum, fewer
executed nodes" is the whole acceptance story (certified by
analysis/model.py over permuted pop orders including the best-first
one; the pruning COUNTS legitimately differ per schedule and are
reported, not certified).

The concrete problem is 0/1 knapsack over a seeded item set
(``make_knapsack``): small enough that the exact optimum has an
independent host witness (the classic DP, ``host_knapsack_opt``), rich
enough that bound-ordered exploration prunes hard. One descriptor kind:

    ``NODE(level, value, weight, bound)``

``level`` items are decided; ``value``/``weight`` are the committed
totals; ``bound = value + suffix_value[level]`` is the optimistic
completion (take everything remaining) computed AT SPAWN - so the
priority rides the descriptor's own arg word 3 (the ISSUE 15 bucket
discipline: residue re-buckets on resume/reshard because the bucket is
a pure function of descriptor words). A popped node re-checks its bound
against the CURRENT incumbent (it was spawned against an older one),
prunes or branches take/skip, and leaves fold into the incumbent.

Best-first priority: bucket 0 = highest bound, so
``bucket = ((total - bound) * B) // (total + 1)`` - a pure arg-word
function, the same shape as the frontier's ``dist // delta``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
from jax.experimental import pallas as pl

from .descriptor import TaskGraphBuilder
from .megakernel import BatchSpec, Megakernel, _batch_stub

__all__ = [
    "BB_NODE",
    "V_BEST",
    "V_PRUNED",
    "V_LEAVES",
    "make_knapsack",
    "host_knapsack_opt",
    "host_bnb",
    "bnb_bucket",
    "make_bnb_megakernel",
    "run_bnb",
]

# The one kernel-table entry (single-kind family, like the frontier).
BB_NODE = 0

# Value-slot layout: three counters, then the host-preset tables.
V_BEST = 0    # incumbent (monotone max; the PROVEN optimum at drain)
V_PRUNED = 1  # nodes cut by the bound test (schedule-dependent count)
V_LEAVES = 2  # complete assignments folded into the incumbent
BB_TAB = 8    # suffix-value sums [n+1], then values [n], then weights [n]


class Knapsack:
    """One seeded 0/1-knapsack instance: int item values/weights, a
    weight capacity, and the suffix-value table the bound uses."""

    def __init__(self, values, weights, cap: int) -> None:
        self.values = np.asarray(values, np.int64)
        self.weights = np.asarray(weights, np.int64)
        if self.values.shape != self.weights.shape or self.values.ndim != 1:
            raise ValueError("values/weights must be equal-length 1D")
        if len(self.values) and (
            self.values.min() < 0 or self.weights.min() <= 0
        ):
            raise ValueError("values must be >= 0 and weights > 0")
        self.n = int(len(self.values))
        self.cap = int(cap)
        # suffix[k] = total value of items k.. (suffix[n] = 0): the
        # optimistic take-everything completion bound.
        self.suffix = np.zeros(self.n + 1, np.int64)
        self.suffix[:-1] = np.cumsum(self.values[::-1])[::-1]
        self.total = int(self.suffix[0])

    @property
    def num_value_slots(self) -> int:
        return BB_TAB + (self.n + 1) + 2 * self.n

    def preset_values(self, num_values: int) -> np.ndarray:
        if num_values < self.num_value_slots:
            raise ValueError(
                f"knapsack wants num_values >= {self.num_value_slots}, "
                f"got {num_values}"
            )
        iv = np.zeros(num_values, np.int32)
        iv[BB_TAB : BB_TAB + self.n + 1] = self.suffix
        v0 = BB_TAB + self.n + 1
        iv[v0 : v0 + self.n] = self.values
        iv[v0 + self.n : v0 + 2 * self.n] = self.weights
        return iv


def make_knapsack(n: int, seed: int = 0,
                  cap_frac: float = 0.5) -> Knapsack:
    """Seeded instance: values 1..100, weights 1..50 (independent, so
    value density varies and greedy order is wrong often enough that
    the bound test has real work), capacity = cap_frac of the total
    weight. Pure function of the arguments - every arm rebuilds the
    identical instance."""
    if n < 1:
        raise ValueError(f"knapsack n must be >= 1, got {n}")
    rng = np.random.default_rng(seed)
    values = rng.integers(1, 101, n)
    weights = rng.integers(1, 51, n)
    return Knapsack(values, weights, int(cap_frac * weights.sum()))


def host_knapsack_opt(kp: Knapsack) -> int:
    """The exact optimum by the classic weight-indexed DP - an
    INDEPENDENT witness (no bounds, no search order) the device
    incumbent must equal bit-for-bit."""
    dp = np.zeros(kp.cap + 1, np.int64)
    for v, w in zip(kp.values, kp.weights):
        w = int(w)
        if w <= kp.cap:
            dp[w:] = np.maximum(dp[w:], dp[:-w or None] + v)
    return int(dp.max())


def bnb_bucket(kp: Knapsack, bound: int, buckets: int) -> int:
    """HOST spelling of the best-first priority (the device twin lives
    in make_bnb_megakernel - keep in lockstep; analysis/model.py
    certifies the best-first pop order through this one): bucket 0 =
    highest optimistic bound, spread linearly over [0, total]."""
    return ((kp.total - int(bound)) * int(buckets)) // (kp.total + 1)


def host_bnb(kp: Knapsack, best_first: bool = False) -> Dict[str, int]:
    """Host worklist model of the device search (same bound, same
    branch rule): returns {best, executed, pruned, leaves}. The
    ``best_first`` arm pops max-bound-first - the model of the bucketed
    device run; FIFO otherwise. Both return the identical ``best``
    (the schedule-independence claim); executed/pruned differ."""
    import heapq
    from collections import deque

    best, executed, pruned, leaves = 0, 0, 0, 0
    if best_first:
        wl = [(-kp.total, 0, 0, 0, kp.total)]
    else:
        wl = deque([(0, 0, 0, 0, kp.total)])
    while wl:
        if best_first:
            _, level, value, weight, bound = heapq.heappop(wl)
        else:
            _, level, value, weight, bound = wl.popleft()
        executed += 1
        if bound <= best:
            pruned += 1
            continue
        if level == kp.n:
            leaves += 1
            best = max(best, value)
            continue
        sfx = int(kp.suffix[level + 1])
        v_i, w_i = int(kp.values[level]), int(kp.weights[level])
        children = [(level + 1, value, weight, value + sfx)]
        if weight + w_i <= kp.cap:
            children.append(
                (level + 1, value + v_i, weight + w_i,
                 value + v_i + sfx)
            )
        for c in children:
            if best_first:
                heapq.heappush(wl, (-c[3],) + c)
            else:
                wl.append((0,) + c)
    return {
        "best": best, "executed": executed, "pruned": pruned,
        "leaves": leaves,
    }


# ----------------------------------------------------------- device tier


def _node_kernel(kp: Knapsack):
    """The per-node scalar body (both dispatch spellings run it: scalar
    via the switch table, batched per-slot via slot_ctx)."""
    import jax.numpy as jnp

    n = kp.n
    cap = kp.cap
    v0 = BB_TAB + n + 1

    def body(ctx) -> None:
        level = ctx.arg(0)
        value = ctx.arg(1)
        weight = ctx.arg(2)
        bound = ctx.arg(3)
        best = ctx.value(V_BEST)
        live = bound > best

        @pl.when(jnp.logical_not(live))
        def _():
            ctx.set_value(V_PRUNED, ctx.value(V_PRUNED) + 1)

        @pl.when(live & (level == jnp.int32(n)))
        def _():
            # Complete assignment: fold into the incumbent (monotone
            # max - the write every schedule agrees on at the fixpoint).
            ctx.set_value(V_BEST, jnp.maximum(best, value))
            ctx.set_value(V_LEAVES, ctx.value(V_LEAVES) + 1)

        @pl.when(live & (level < jnp.int32(n)))
        def _():
            sfx = ctx.value(BB_TAB + 1 + level)  # suffix[level + 1]
            v_i = ctx.value(v0 + level)
            w_i = ctx.value(v0 + n + level)
            # Skip child: always feasible; bound tightens by v_i.
            ctx.spawn(
                BB_NODE, [level + 1, value, weight, value + sfx],
                nargs=4,
            )

            @pl.when(weight + w_i <= jnp.int32(cap))
            def _():
                ctx.spawn(
                    BB_NODE,
                    [level + 1, value + v_i, weight + w_i,
                     value + v_i + sfx],
                    nargs=4,
                )

    return body


def make_bnb_megakernel(
    kp: Knapsack,
    *,
    width: int = 4,
    priority_buckets: Optional[int] = None,
    capacity: int = 768,  # fits a v5e's SMEM (Megakernel.check_smem)
    num_values: Optional[int] = None,
    interpret: Optional[bool] = None,
    trace=None,
    lane_max_age: Optional[int] = None,
) -> Megakernel:
    """Build the search megakernel. ``width=0`` is scalar dispatch;
    ``width>0`` batches NODE expansion per-slot (the spawn-heavy
    slot_ctx spelling, like fib); ``priority_buckets=B`` additionally
    arms best-first retirement - bucket 0 = highest bound, and the
    age-fire guard (default 4*width, the frontier discipline) keeps
    low-bound buckets from starving outright."""
    import jax.numpy as jnp

    if num_values is None:
        num_values = kp.num_value_slots + 8
    if priority_buckets is None:
        # Process-wide spelling (the builder needs the resolved value
        # for the priority fn and the age-default scale).
        from ..runtime.env import env_int

        priority_buckets = env_int("HCLIB_TPU_PRIORITY_BUCKETS", None)
    priority_buckets = int(priority_buckets or 0)
    body = _node_kernel(kp)
    if width:
        def batch_body(ctx) -> None:
            for s in range(ctx.width):
                @pl.when(ctx.live(s))
                def _(s=s):
                    body(ctx.slot_ctx(s))

        total = kp.total
        nbk = int(priority_buckets or 0)
        spec = BatchSpec(
            batch_body,
            width=width,
            # Device twin of bnb_bucket (kept in lockstep): highest
            # bound -> bucket 0; a pure function of arg word 3, so
            # residue re-buckets wherever it lands.
            priority=(
                (lambda arg: ((jnp.int32(total) - arg(3))
                              * jnp.int32(nbk)) // jnp.int32(total + 1))
                if nbk else None
            ),
        )
        kernels = [("bnb_node", _batch_stub)]
        route = {"bnb_node": spec}
        if lane_max_age is None:
            from ..runtime.env import env_set

            if env_set("HCLIB_TPU_LANE_MAX_AGE"):
                # The process-wide spelling wins (pass None through so
                # Megakernel resolves + validates it) - the frontier
                # builder's discipline.
                lane_max_age = None
            else:
                # Bucketed: drain-period-scale backstop (the frontier
                # discipline - see make_frontier_megakernel);
                # unbucketed: PR 10's latency tune.
                lane_max_age = 2 * capacity if nbk else 4 * width
    else:
        if priority_buckets:
            raise ValueError(
                "priority_buckets needs the batched arm (width > 0)"
            )
        kernels = [("bnb_node", body)]
        route = None
        lane_max_age = 0 if lane_max_age is None else lane_max_age
    mk = Megakernel(
        kernels=kernels,
        route=route,
        capacity=capacity,
        num_values=num_values,
        succ_capacity=8,
        interpret=interpret,
        trace=trace,
        lane_max_age=lane_max_age,
        priority_buckets=priority_buckets,
    )
    mk._bnb_instance = kp
    # Schedule-independence claim: the OPTIMUM is order-free (the
    # incumbent is a monotone max and the bound test never cuts the
    # optimal prefix); certify_claim proves it over permuted pop orders
    # including the best-first one (analysis/model.py).
    mk.si_claim = (
        "bnb", tuple(map(int, kp.values)), tuple(map(int, kp.weights)),
        kp.cap, int(priority_buckets or 0),
    )
    return mk


def run_bnb(
    kp: Knapsack,
    *,
    width: int = 4,
    priority_buckets: Optional[int] = None,
    capacity: int = 768,
    interpret: Optional[bool] = None,
    trace=None,
    fuel: Optional[int] = None,
    mk: Optional[Megakernel] = None,
) -> Tuple[int, Dict]:
    """Run the search to the proven optimum; returns ``(best, info)``
    with ``info['pruned']``/``info['leaves']`` beside the scheduler
    counters (``executed`` is the expanded-node count the priority arm
    shrinks)."""
    if mk is None:
        mk = make_bnb_megakernel(
            kp, width=width, priority_buckets=priority_buckets,
            capacity=capacity, interpret=interpret, trace=trace,
        )
    elif getattr(mk, "_bnb_instance", None) is not kp:
        raise ValueError(
            "prebuilt bnb megakernel is bound to a different knapsack "
            "instance (the tables/bounds are baked into the trace): "
            "build one per instance via make_bnb_megakernel"
        )
    b = TaskGraphBuilder()
    b.reserve_values(kp.num_value_slots)
    b.add(BB_NODE, args=[0, 0, 0, kp.total])
    iv = kp.preset_values(mk.num_values)
    iv_o, _, info = mk.run(
        b, ivalues=iv, fuel=1 << 22 if fuel is None else fuel
    )
    info["pruned"] = int(iv_o[V_PRUNED])
    info["leaves"] = int(iv_o[V_LEAVES])
    return int(iv_o[V_BEST]), info
