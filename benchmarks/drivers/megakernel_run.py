"""Driver of the deployments that are one whole library call on one
``Megakernel``: ``fib`` (a fresh builder through ``Megakernel.run``) and
``cholesky`` (``device_cholesky``, numpy in to numpy out). One small
adapter each; the call sequences are chip_smoke.py's, proven on the chip.

Interface (every driver's): ``setup(cfg, mix, seed, interpret)`` builds
inputs and kernels and returns the state; ``operation(state)`` makes one
whole call from the caller's side and returns its record; ``check(state,
records)`` compares what the timed calls produced with the plain reference
and returns ``(failed, compared)``.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import cholesky as ref_chol
from ..reference import fib as ref_fib


def _ran(info: dict) -> dict:
    return {k: info[k] for k in
            ("executed", "pending", "overflow", "interpret", "platform")}


class Fib:
    def __init__(self, cfg, mix, seed, interpret):
        from hclib_tpu.device.workloads import FIB, make_fib_megakernel

        self.n, self.fuel, self.kind = cfg["n"], cfg["fuel"], FIB
        self.mk = make_fib_megakernel(cfg["capacity"], interpret=interpret)

    def operation(self):
        from hclib_tpu.device.descriptor import TaskGraphBuilder

        t0 = time.monotonic()
        with TraceAnnotation("bench:call"):
            with TraceAnnotation("bench:build"):
                b = TaskGraphBuilder()
                b.add(self.kind, args=[self.n], out=0)
            iv, _, info = self.mk.run(b, fuel=self.fuel)
            value = int(iv[0])
        t1 = time.monotonic()
        return {"wall_s": t1 - t0, "attempted": 1,
                "work": info["executed"], "value": value, **_ran(info)}

    def check(self, records):
        want_v, want_t = ref_fib.fib(self.n), ref_fib.descriptors(self.n)
        bad = [r for r in records
               if r["value"] != want_v or r["executed"] != want_t
               or r["pending"] != 0 or r["overflow"]]
        compared = [
            ("fib_value_abs_err",
             max(abs(r["value"] - want_v) for r in records), 0),
            ("executed_abs_err",
             max(abs(r["executed"] - want_t) for r in records), 0),
            ("pending_max", max(r["pending"] for r in records), 0),
            ("overflowed", sum(bool(r["overflow"]) for r in records), 0),
        ]
        return len(bad), compared


class Cholesky:
    def __init__(self, cfg, mix, seed, interpret):
        from hclib_tpu.device.cholesky import make_cholesky_megakernel

        self.n, self.tile, self.interpret = cfg["n"], cfg["tile"], interpret
        self.nt = self.n // self.tile
        self.limit = cfg["guarantees"]["residual_limit"]
        self.mk = make_cholesky_megakernel(
            self.nt, interpret=interpret, tile=self.tile,
            fused_only=cfg["fused_only"],
        )
        self.a = ref_chol.make_spd(seed, self.n)
        # The factors the check will read: the newest, and a reservoir
        # sample of the earlier ones drawn from the seed (one factor at
        # n=8192 is 256 MiB, so not all of them are kept).
        self.keep = max(1, cfg["check"]["keep_results"])
        self.rng = np.random.default_rng(seed)
        self.newest = None
        self.sample: list = []
        self.calls = 0

    def _keep(self, L):
        if self.newest is not None and self.keep > 1:
            seen = self.newest[0]  # earlier calls already offered
            if len(self.sample) < self.keep - 1:
                self.sample.append(self.newest)
            else:
                j = int(self.rng.integers(0, seen + 1))
                if j < self.keep - 1:
                    self.sample[j] = self.newest
        self.newest = (self.calls, L)
        self.calls += 1

    def operation(self):
        from hclib_tpu.device.cholesky import device_cholesky

        t0 = time.monotonic()
        with TraceAnnotation("bench:call"):
            L, info = device_cholesky(
                self.a, interpret=self.interpret, mk=self.mk,
                tile=self.tile,
            )
        t1 = time.monotonic()
        self._keep(L)
        return {"wall_s": t1 - t0, "attempted": 1,
                "work": 1, "call": self.calls - 1, **_ran(info)}

    def check(self, records):
        nt = self.nt
        want_t = nt + (nt - 1) + nt * (nt - 1) // 2
        mine = {r["call"] for r in records}
        read = {i: ref_chol.readings(L, self.a)
                for i, L in self.sample + [self.newest] if i in mine}
        self.sample, self.newest = [], None
        bad = {r["call"] for r in records
               if r["executed"] != want_t or r["pending"] != 0
               or r["overflow"]}
        bad |= {i for i, x in read.items()
                if not (x["finite"] and x["residual"] < self.limit
                        and x["upper_max"] == 0 and x["diag_min"] > 0)}
        worst = max(
            (x["residual"] if x["finite"] else float("inf"))
            for x in read.values()
        )
        compared = [
            ("residual_max", worst, self.limit),
            ("upper_triangle_max",
             max(x["upper_max"] for x in read.values()), 0),
            ("diag_min", min(x["diag_min"] for x in read.values()), 0),
            ("executed_abs_err",
             max(abs(r["executed"] - want_t) for r in records), 0),
            ("factors_compared", len(read), 1),
        ]
        return len(bad), compared


ADAPTERS = {"fib": Fib, "cholesky": Cholesky}


def setup(cfg, mix, seed, interpret):
    return ADAPTERS[cfg["problem"]](cfg, mix, seed, interpret)


def operation(state):
    return state.operation()


def check(state, records):
    return state.check(records)
