"""Driver of the jacobi deployment: one operation is one whole
``hc.forasync(tile_kernel, bounds, tile=, mode=hc.RECURSIVE,
place="device", data=...)`` call that advances a grid that LIVES on the
chip ``steps`` time steps in ONE launch, on one prebuilt ``Megakernel``:
from the root range descriptor to the grid after the last step and
``info`` in hand, as an explicit solver that keeps its grid on the
accelerator waits for it. The body and the layout are the program's own
(``hclib_tpu.device.workloads.jacobi_loop``: two planes in one buffer,
each the interior inside a zero halo, step s reading plane ``s & 1`` and
writing the other; a tile awaits its own tile and the four it shares an
edge with in the step before).

Set-up asks the program first, before anything is allocated: one that has
no such loop, or whose ``hc.forasync`` refuses it, fails there, within
seconds. Then it makes the grid on the chip from the seed (plane 0's
interior uniform in [0, 2^20), a band at a time; plane 1's interior -1;
both halos zero) and builds the ``Megakernel`` once.

Between calls, outside the timed call, plane 0's interior is made again
from the seed on the chip and plane 1's overwritten with -1, and waited
for: every call solves the same problem, and a tile that was counted and
not stored shows. After each call two digests of the last step's interior
are taken on the chip by plain ``jnp`` (``forasync-2d-hbm``'s two).
``check`` holds every call's counters and digests to the plain reference
(``reference/jacobi.py``, from the step-0 grid pulled off the chip), the
newest grid to it element by element, band by band, and both halos to
zero. All integers, all limits 0. The reference is held to its own
cell-by-cell loop on a corner and its counts to the configuration's.

A control (``fuel`` at the configuration's top level, where the
configuration of record does not have it) stops the scheduler early.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np
from jax.profiler import TraceAnnotation

from ..reference import jacobi as ref

BAND = 1024  # rows made, or pulled, at once


def _engine(cfg):
    import hclib_tpu as hc

    if (cfg["dtype"], cfg["resident"], cfg["mode"]) != (
            "int32", True, "recursive"):
        raise RuntimeError(
            "this driver runs int32 grids resident on the chip through "
            f"RECURSIVE, the configuration states {cfg['dtype']} / "
            f"resident {cfg['resident']} / {cfg['mode']}")
    want = [[0, 0], [-1, 0], [1, 0], [0, -1], [0, 1]]
    if sorted(cfg["awaits"]) != sorted(want):
        raise RuntimeError(
            "the reference counts a tile's own and its four edge "
            f"neighbours, the configuration awaits {cfg['awaits']}")
    return hc.RECURSIVE


def _the_programs_loop(cfg):
    """The program's own loop of ``steps`` steps, and ``hc.forasync``'s
    own argument checks on it with nothing allocated: a program that can
    run the deployment gets as far as refusing ``blocking=False``."""
    import hclib_tpu as hc

    try:
        from hclib_tpu.device.workloads import jacobi_loop
    except ImportError as e:
        raise RuntimeError(
            "this program has no loop of several time steps "
            f"({e}): it cannot run this deployment") from e
    H, W, (th, tw) = cfg["H"], cfg["W"], cfg["tile"]
    tk, bounds, tile = jacobi_loop(
        H, W, th, tw, steps=cfg["steps"],
        awaits=[tuple(o) for o in cfg["awaits"]])
    try:
        hc.forasync(tk, bounds, tile=tile, mode=hc.RECURSIVE,
                    place="device", blocking=False)
    except ValueError as e:
        if "synchronous" in str(e):
            return tk, bounds, tile
        raise RuntimeError(
            "this program's hc.forasync refuses the deployment before any "
            f"work ({e}): it cannot run this deployment") from e
    raise RuntimeError("hc.forasync accepted blocking=False on the device")


def _chip_functions(H: int, W: int, R: int, C: int):
    """The driver's own work on the chip, all plain ``jnp`` on the one
    donated buffer: a band of plane 0 from the seed, plane 1 blanked, the
    two digests of a plane's interior, what the halos hold, a band out."""
    import jax
    import jax.numpy as jnp

    def as_u32(g):
        return jax.lax.bitcast_convert_type(g, jnp.uint32)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
    def band(g, key, r, n):
        bits = jax.random.bits(jax.random.fold_in(key, r), (n, W), jnp.uint32)
        vals = (bits >> 12).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(g, vals[None], (0, r + R, C))

    @functools.partial(jax.jit, donate_argnums=0)
    def blank(g):
        return jax.lax.dynamic_update_slice(
            g, jnp.full((1, H, W), -1, jnp.int32), (1, R, C))

    @functools.partial(jax.jit, static_argnums=1)
    def digests(g, plane):
        u = as_u32(g[plane, R:R + H, C:C + W])
        i = jax.lax.broadcasted_iota(jnp.uint32, u.shape, 0)
        j = jax.lax.broadcasted_iota(jnp.uint32, u.shape, 1)
        w = i * jnp.uint32(ref.W_ROW) + j * jnp.uint32(ref.W_COL) + 1
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    @jax.jit
    def halo_nonzero(g):
        inner = g[:, R:R + H, C:C + W]
        return (jnp.count_nonzero(g) - jnp.count_nonzero(inner)).astype(
            jnp.int32)

    @functools.partial(jax.jit, static_argnums=(1, 3))
    def rows(g, plane, r, n):
        return jax.lax.dynamic_slice(g, (plane, r + R, C), (1, n, W))[0]

    return band, blank, digests, halo_nonzero, rows


def _signed(x) -> int:
    return int(np.uint32(x).view(np.int32))


def _remake(st):
    """Step 0's grid again, and -1 where the last step will not write
    last: the problem every call solves."""
    import jax.numpy as jnp

    H = st["cfg"]["H"]
    g = st["grid"]
    for r in range(0, H, BAND):
        g = st["band"](g, st["key"], jnp.int32(r), min(BAND, H - r))
    st["grid"] = st["blank"](g)


def _pull(st, plane: int) -> np.ndarray:
    """One plane's interior off the chip, a band at a time."""
    import jax.numpy as jnp

    H, W = st["cfg"]["H"], st["cfg"]["W"]
    out = np.empty((H, W), np.int32)
    for r in range(0, H, BAND):
        n = min(BAND, H - r)
        out[r:r + n] = np.asarray(
            st["rows"](st["grid"], plane, jnp.int32(r), n))
    return out


def setup(cfg, mix, seed, interpret):
    import jax
    import jax.numpy as jnp

    from hclib_tpu.device.forasync_tier import make_forasync_megakernel

    mode = _engine(cfg)
    tk, bounds, tile = _the_programs_loop(cfg)
    H, W = cfg["H"], cfg["W"]
    shape = tk.data_specs["grid"].shape
    R, C = (shape[1] - H) // 2, (shape[2] - W) // 2
    band, blank, digests, halo_nonzero, rows = _chip_functions(H, W, R, C)
    mk = make_forasync_megakernel(
        tk, width=cfg["width"], prefetch=cfg["prefetch"],
        interpret=interpret, space=(bounds, tile))
    return {
        "cfg": cfg, "interpret": interpret, "tk": tk, "bounds": bounds,
        "tile": tile, "mode": mode, "mk": mk,
        "grid": jnp.zeros(shape, jnp.int32),
        # the hardware generator: a gigabyte of values a call, remade
        "key": jax.random.key(seed, impl="rbg"),
        "band": band, "blank": blank, "digests": digests,
        "halo_nonzero": halo_nonzero, "rows": rows,
        "fuel": cfg.get("fuel"),  # only a control has it
    }


def operation(st):
    import hclib_tpu as hc

    _remake(st)
    st["grid"].block_until_ready()
    kw = {} if st["fuel"] is None else {"fuel": st["fuel"]}
    t0 = time.monotonic()
    with TraceAnnotation("bench:call"):
        out, info = hc.forasync(
            st["tk"], st["bounds"], tile=st["tile"], mode=st["mode"],
            place="device", data={"grid": st["grid"]},
            width=st["cfg"]["width"], mk=st["mk"], **kw)
    t1 = time.monotonic()
    st["grid"] = out["grid"]
    last = st["cfg"]["steps"] & 1  # the plane the last step wrote
    plain, weighted = np.asarray(st["digests"](st["grid"], last))
    tiers, loop = info["tiers"], info["forasync"]
    return {
        "wall_s": t1 - t0, "attempted": 1, "work": 1,
        "digest_plain": _signed(plain), "digest_weighted": _signed(weighted),
        **{k: info[k] for k in ("executed", "pending", "overflow",
                                "interpret", "platform")},
        **{k: tiers[k] for k in ("batch_rounds", "batch_tasks",
                                 "batch_occupancy", "prefetch_hits",
                                 "scalar_tasks", "direct")},
        **{k: loop[k] for k in ("live_rows_max", "capacity", "steps",
                                "released", "decrements", "mixed_rounds",
                                "step_skew_max")},
    }


def check(st, records):
    cfg = st["cfg"]
    H, W, steps = cfg["H"], cfg["W"], cfg["steps"]
    stated = cfg["guarantees"]
    t0 = time.monotonic()
    halo = int(st["halo_nonzero"](st["grid"]))
    got = _pull(st, steps & 1)  # the newest call's last step
    _remake(st)
    interior = _pull(st, 0)  # what every call's step 0 read
    t_pull = time.monotonic() - t0
    differing = [0]

    def compared(blocks):  # each band of the reference against got's
        for row0, block in blocks:
            differing[0] += int(np.count_nonzero(
                got[row0:row0 + len(block)] != block))
            yield row0, block

    plain, weighted = ref.digests(
        compared(ref.sweeps(interior, H, W, steps)), W)
    corner, counts, ref_err = ref.self_check(
        interior, H, W, cfg["tile"], steps,
        {k: stated[k] for k in ("tiles", "splits", "released",
                                "decrements", "executed")})
    print(json.dumps({"reference": {
        "seconds": time.monotonic() - t0, "pull_seconds": t_pull,
        "digest_plain": plain, "digest_weighted": weighted,
        "corner": corner, **counts}}))

    def errs(r):
        return {
            "digest_plain_differs": int(r["digest_plain"] != plain),
            "digest_weighted_differs": int(r["digest_weighted"] != weighted),
            "executed_abs_err": abs(r["executed"] - counts["executed"]),
            "batch_tasks_abs_err": abs(r["batch_tasks"] - counts["tiles"]),
            "scalar_tasks_abs_err": abs(
                r["scalar_tasks"] - counts["splits"]),
            "released_abs_err": abs(r["released"] - counts["released"]),
            "direct_abs_err": abs(r["direct"] - counts["released"]),
            "decrements_abs_err": abs(
                r["decrements"] - counts["decrements"]),
            "steps_abs_err": abs(r["steps"] - steps),
            "pending": r["pending"],
            "overflowed": int(bool(r["overflow"])),
            "table_filled": int(r["live_rows_max"] >= r["capacity"]),
            # no barrier: rounds that held tiles of several steps
            "mixed_rounds_short": max(
                0, stated["mixed_rounds_min"] - r["mixed_rounds"]),
        }

    per_call = [errs(r) for r in records]
    after = {"grid_differing": differing[0], "halo_nonzero": halo}
    bad = sum(any(e.values()) for e in per_call)
    if any(after.values()) or any(ref_err.values()):
        bad = len(records)  # a wrong grid or reference judges no call sound
    compared_ = [(k, max(e[k] for e in per_call), 0) for k in per_call[0]]
    compared_ += [(k, v, 0) for k, v in after.items()]
    compared_ += [(f"reference_{k}", v, 0) for k, v in ref_err.items()]
    return bad, compared_
