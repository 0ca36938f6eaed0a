"""Driver of the forasync deployments: one operation is one whole
``hc.forasync(tile_kernel, bounds, tile=, mode=hc.RECURSIVE,
place="device", data=...)`` call on grids that LIVE on the chip, on one
prebuilt ``Megakernel``: from the root range descriptor to ``data_out`` and
``info`` in hand, as a solver that sweeps its grid once a time step waits
for it. The body is the program's own 5-point stencil
(``hclib_tpu.device.workloads.stencil_loop``); the call sequence is
``chip_smoke.phase_forasync``'s with ``jax.Array`` operands.

Set-up asks the entry point's own validation first, before anything is
allocated: a program whose ``hc.forasync`` refuses ``mode=RECURSIVE`` on
the device fails there, within seconds. Then it makes ``gin`` on the chip
from the seed (uniform in [0, 2^20), a band at a time), ``gout`` full of
-1 (no legitimate value: sums lie in [0, 5 x 2^20)), and builds the
``Megakernel`` once.

Between calls, outside the timed call, the output buffer is overwritten
on the chip with -1 again and waited for, so that a tile that was counted
and not stored shows. After each call two digests of ``gout`` are taken on
the chip by plain ``jnp`` (a wrapping int32 sum and a position-weighted
one). ``check`` holds every call's counters and digests to the plain
reference (``reference/forasync.py``, from the ``gin`` pulled off the
chip), and the newest ``gout`` to it element by element, band by band.
All integers, all limits 0. The reference is held to its own cell-by-cell
loop on a corner and its counts to the configuration's.

A control (``fuel`` at the configuration's top level, where the
configuration of record does not have it) stops the scheduler early.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import forasync as ref

BAND = 1024  # rows of gin made at once


def _engine(cfg):
    import hclib_tpu as hc

    modes = {"flat": hc.FLAT, "recursive": hc.RECURSIVE}
    if cfg["dtype"] != "int32" or not cfg["resident"]:
        raise RuntimeError("this driver runs int32 grids resident on the "
                           f"chip, the configuration states {cfg['dtype']} "
                           f"/ resident {cfg['resident']}")
    return modes[cfg["mode"]]


def _ask_the_entry_point(tk, bounds, tile, mode):
    """``hc.forasync``'s own argument checks, in its own order (mode,
    then tile, then ``blocking``), with nothing allocated: a program that
    can run the deployment gets as far as refusing ``blocking=False``."""
    import hclib_tpu as hc

    try:
        hc.forasync(tk, bounds, tile=tile, mode=mode, place="device",
                    blocking=False)
    except ValueError as e:
        if "synchronous" in str(e):
            return
        raise RuntimeError(
            "this program's hc.forasync refuses the deployment before any "
            f"work ({e}): it cannot run this deployment") from e
    raise RuntimeError("hc.forasync accepted blocking=False on the device")


def _make_gin(seed: int, H: int, W: int, shape):
    """``gin`` on the chip: zeros of the program's padded ``shape`` with
    the interior ``[1:H+1, 1:W+1]`` uniform in [0, 2^20) from the seed,
    a band of rows at a time into the one buffer."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
    def band(g, key, r, n):
        bits = jax.random.bits(jax.random.fold_in(key, r), (n, W), jnp.uint32)
        vals = (bits >> 12).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(g, vals, (r + 1, 1))

    key = jax.random.key(seed)
    g = jnp.zeros(shape, jnp.int32)
    for r in range(0, H, BAND):
        g = band(g, key, jnp.int32(r), min(BAND, H - r))
    return g


def _chip_functions():
    import jax
    import jax.numpy as jnp

    def as_u32(g):
        return jax.lax.bitcast_convert_type(g, jnp.uint32)

    @jax.jit
    def digests(g):
        i = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0)
        j = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1)
        w = i * jnp.uint32(ref.W_ROW) + j * jnp.uint32(ref.W_COL) + 1
        u = as_u32(g)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    plain = jax.jit(lambda g: jnp.sum(as_u32(g), dtype=jnp.uint32))
    # -1 everywhere, written into the donated buffer itself: an OR with
    # all ones reads g (full_like would not, and jit drops an argument
    # nothing reads, its donation with it: a second 4 GB grid).
    blank = jax.jit(lambda g: g | -1, donate_argnums=0)
    return digests, plain, blank


def _signed(x) -> int:
    return int(np.uint32(x).view(np.int32))


def setup(cfg, mix, seed, interpret):
    import jax.numpy as jnp

    from hclib_tpu.device.forasync_tier import make_forasync_megakernel
    from hclib_tpu.device.workloads import stencil_loop

    mode = _engine(cfg)
    H, W, (th, tw) = cfg["H"], cfg["W"], cfg["tile"]
    tk, bounds, tile = stencil_loop(H, W, th, tw)
    _ask_the_entry_point(tk, bounds, tile, mode)
    gin = _make_gin(seed, H, W, tk.data_specs["gin"].shape)
    gout = jnp.full(tk.data_specs["gout"].shape, -1, jnp.int32)
    digests, plain, blank = _chip_functions()
    mk = make_forasync_megakernel(
        tk, width=cfg["width"], prefetch=cfg["prefetch"],
        interpret=interpret, space=(bounds, tile))
    return {
        "cfg": cfg, "interpret": interpret, "tk": tk, "bounds": bounds,
        "tile": tile, "mode": mode, "mk": mk, "gin": gin, "gout": gout,
        "digests": digests, "plain": plain, "blank": blank,
        "gin_plain": _signed(plain(gin)),
        "fuel": cfg.get("fuel"),  # only a control has it
    }


def operation(st):
    import hclib_tpu as hc

    st["gout"] = st["blank"](st["gout"])
    st["gout"].block_until_ready()
    kw = {} if st["fuel"] is None else {"fuel": st["fuel"]}
    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        out, info = hc.forasync(
            st["tk"], st["bounds"], tile=st["tile"], mode=st["mode"],
            place="device", data={"gin": st["gin"], "gout": st["gout"]},
            width=st["cfg"]["width"], mk=st["mk"], **kw)
    t1 = time.monotonic()
    st["gout"] = out["gout"]
    plain, weighted = np.asarray(st["digests"](st["gout"]))
    tiers, loop = info["tiers"], info["forasync"]
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": 1,
        "digest_plain": _signed(plain), "digest_weighted": _signed(weighted),
        "gin_kept": int(out["gin"] is st["gin"]
                        and not st["gin"].is_deleted()),
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "interpret", "platform")},
        **{k: tiers[k] for k in ("batch_rounds", "batch_tasks",
                                 "batch_occupancy", "prefetch_hits",
                                 "scalar_tasks")},
        **{k: loop[k] for k in ("live_rows_max", "capacity")},
    }


def check(st, records):
    cfg = st["cfg"]
    H, W = cfg["H"], cfg["W"]
    t0 = time.monotonic()
    gin_plain = _signed(st["plain"](st["gin"]))
    padded = np.asarray(st["gin"])
    got = np.asarray(st["gout"])
    differing = [0 if got.shape == (H, W) else H * W]

    def compared(blocks):  # each band of the reference against got's
        for row0, block in blocks:
            if got.shape == (H, W):
                differing[0] += int(np.count_nonzero(
                    got[row0:row0 + len(block)] != block))
            yield row0, block

    plain, weighted = ref.digests(compared(ref.sweep(padded, H, W)), W)
    corner, counts, ref_err = ref.self_check(
        padded, H, W, cfg["tile"], cfg["guarantees"])
    print(json.dumps({"reference": {
        "seconds": time.monotonic() - t0, "digest_plain": plain,
        "digest_weighted": weighted, "corner": corner, **counts}}))

    def errs(r):
        return {
            "digest_plain_differs": int(r["digest_plain"] != plain),
            "digest_weighted_differs": int(r["digest_weighted"] != weighted),
            "executed_abs_err": abs(r["executed"] - counts["executed"]),
            "batch_tasks_abs_err": abs(r["batch_tasks"] - counts["tiles"]),
            "scalar_tasks_abs_err": abs(
                r["scalar_tasks"] - counts["splits"]),
            "pending": r["pending"],
            "overflowed": int(bool(r["overflow"])),
            "table_filled": int(r["live_rows_max"] >= r["capacity"]),
            "gin_not_kept": 1 - r["gin_kept"],
        }

    per_call = [errs(r) for r in records]
    after = {"gout_differing": differing[0],
             "gin_changed": int(gin_plain != st["gin_plain"])}
    bad = sum(any(e.values()) for e in per_call)
    if any(after.values()) or any(ref_err.values()):
        bad = len(records)  # a wrong grid or reference judges no call sound
    compared_ = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared_ += [(k, v, 0) for k, v in after.items()]
    compared_ += [(f"reference_{k}", v, 0) for k, v in ref_err.items()]
    return bad, compared_
