"""What a ``Megakernel.run`` exchanges with the chip (ISSUE 39): one int32
slab up (the scheduler's blocks and the small int32 host buffers), the
other buffers alone, one packed read down; ``info['staging']`` counts it.

One graph on four builds (scalar, batch tier, checkpoint quiesced and
resumed, traced) times four residencies of one data buffer ``x``: fib(10)
into value slot 0 beside ``NT`` tile tasks ``c[t] = a[t] + x[t]``, so the
values, every data output and the counters have closed forms and the same
answers whichever way ``x`` crossed. Interpreter, on the CPU.
"""

import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from hclib_tpu.device import inject, megakernel
from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import SLAB_RIDE_BYTES, VBLOCK, Megakernel
from hclib_tpu.device.workloads import (
    FIB, _fib_kernel, _sum_kernel, batch_of,
)

ADDX = 2
N = 10
F11 = 89  # fib(10) spawns 2 F(11) - 1 FIB and F(11) - 1 SUM descriptors
FIB_TASKS, SUM_TASKS = 2 * F11 - 1, F11 - 1
NT = 2
TILE = (8, 128)
BIG = SLAB_RIDE_BYTES // (4 * 8 * 128) + 1  # tiles of an x over the constant
SCHEDULER = ["tasks", "succ", "ready", "counts", "ivalues"]

BUILDS = ["scalar", "batch", "checkpoint", "traced"]
RESIDENCIES = ["host_small", "on_device", "host_large", "host_float"]


def _addx_kernel(ctx):
    t = ctx.arg(0)
    va, vx, sems = ctx.scratch["va"], ctx.scratch["vx"], ctx.scratch["sems"]
    ins = [
        pltpu.make_async_copy(ctx.data["a"].at[t], va, sems.at[0]),
        pltpu.make_async_copy(ctx.data["x"].at[t], vx, sems.at[1]),
    ]
    for cp in ins:
        cp.start()
    for cp in ins:
        cp.wait()
    va[:] = va[:] + vx[:].astype(jnp.int32)
    out = pltpu.make_async_copy(va, ctx.data["c"].at[t], sems.at[2])
    out.start()
    out.wait()


def _mk(build: str, residency: str, read_only=("a", "x")) -> Megakernel:
    xdt = jnp.float32 if residency == "host_float" else jnp.int32
    xtiles = BIG if residency == "host_large" else NT
    small = jax.ShapeDtypeStruct((NT,) + TILE, jnp.int32)
    capacity = 96
    return Megakernel(
        kernels=[("fib", _fib_kernel), ("sum", _sum_kernel),
                 ("addx", _addx_kernel)],
        route={"fib": batch_of(_fib_kernel, width=4)}
        if build == "batch" else None,
        data_specs={
            "a": small,
            "x": jax.ShapeDtypeStruct((xtiles,) + TILE, xdt),
            "c": small,
        },
        scratch_specs={
            "va": pltpu.VMEM(TILE, jnp.int32),
            "vx": pltpu.VMEM(TILE, xdt),
            "sems": pltpu.SemaphoreType.DMA((3,)),
        },
        capacity=capacity,
        num_values=VBLOCK * capacity + 16,
        succ_capacity=64,
        uses_row_values=True,
        checkpoint=build == "checkpoint",
        trace=64 if build == "traced" else None,
        interpret=True,
        read_only=read_only,  # the addx kernel writes c alone
    )


def _graph() -> TaskGraphBuilder:
    b = TaskGraphBuilder()
    b.add(FIB, args=[N], out=0)
    for t in range(NT):
        b.add(ADDX, args=[t])
    return b


def _data(mk: Megakernel, residency: str) -> dict:
    rng = np.random.default_rng(39)
    spec = mk.data_specs["x"]
    x = rng.integers(-99, 99, spec.shape).astype(spec.dtype)
    return {
        "a": rng.integers(-99, 99, (NT,) + TILE).astype(np.int32),
        "x": jnp.asarray(x) if residency == "on_device" else x,
        "c": np.zeros((NT,) + TILE, np.int32),
    }


def test_a_written_buffer_on_the_device_is_consumed_and_comes_back():
    """``run``'s ownership rule (ISSUE 40): a ``jax.Array`` the build did
    not declare ``read_only`` is donated, so the caller's array is deleted
    and its contents come back under its name; a numpy buffer is never
    touched, and a declared one is never consumed."""
    mk = _mk("scalar", "on_device", read_only=("a",))
    assert mk.read_only == ("a",)
    data = _data(mk, "on_device")
    x, c = data["x"], jnp.asarray(data["c"])
    x_np, a_np = np.array(x), data["a"].copy()  # copies: a view pins x
    _, out, info = mk.run(_graph(), data={**data, "c": c})
    assert x.is_deleted() and c.is_deleted()
    assert np.array_equal(np.asarray(out["x"]), x_np)
    assert np.array_equal(np.asarray(out["c"]), a_np + x_np[:NT])
    assert np.array_equal(data["a"], a_np)  # the host's, untouched
    assert info["staging"]["uploads"] == 1  # the slab alone
    with pytest.raises(ValueError, match="undeclared buffers"):
        _mk("scalar", "on_device", read_only=("a", "nope"))


def _staging(mk: Megakernel, data: dict) -> dict:
    """The count the rule implies for these buffers."""
    rides = [
        k for k, d in data.items()
        if not isinstance(d, jax.Array) and d.dtype == np.int32
        and d.nbytes < SLAB_RIDE_BYTES
    ]
    alone = [
        k for k, d in data.items()
        if k not in rides and not isinstance(d, jax.Array)
    ]
    blocks = SCHEDULER + (["qctl"] if mk.checkpoint else [])
    blocks += ["data:" + k for k in mk.data_specs if k in rides]
    return {
        "uploads": 1 + len(alone),
        "slab_words": sum(
            int(np.prod(s)) for s in mk._exec_layout(
                ["data:" + k for k in rides]).up.values()
        ),
        "slab_blocks": blocks,
        "downloads": 1,
    }


@pytest.mark.parametrize("residency", RESIDENCIES)
@pytest.mark.parametrize("build", BUILDS)
def test_same_answers_however_the_buffers_cross(build, residency):
    mk = _mk(build, residency)
    data = _data(mk, residency)
    want_c = np.asarray(data["a"]) + np.asarray(data["x"])[:NT].astype(
        np.int32
    )
    if build == "checkpoint":
        _, _, info_q = mk.run(_graph(), data=data, quiesce=40)
        assert info_q["quiesced"] and info_q["pending"] > 0
        assert info_q["quiesce"]["executed_at"] >= 40
        # the cut's own read, then the state's in one more
        assert info_q["staging"] == {**_staging(mk, data), "downloads": 2}
        state = info_q["state"]
        assert all(isinstance(d, np.ndarray) for d in state["data"].values())
        iv, out, info = mk.resume(state)
        assert info["quiesced"] is False
        # resume() stages what the state holds: numpy, whatever run() got
        assert info["staging"] == _staging(mk, state["data"])
    else:
        iv, out, info = mk.run(_graph(), data=data)
        assert info["staging"] == _staging(mk, data)
    assert mk.stats_dict()["staging"] == info["staging"]
    assert int(iv[0]) == 55
    assert info["executed"] == FIB_TASKS + SUM_TASKS + NT
    assert info["pending"] == 0 and not info["overflow"]
    assert info["interpret"] is True and info["platform"] == "cpu"
    assert np.array_equal(np.asarray(out["c"]), want_c)
    for k in ("a", "x"):
        assert np.array_equal(np.asarray(out[k]), np.asarray(data[k]))
        assert np.asarray(out[k]).dtype == mk.data_specs[k].dtype
    if residency == "on_device" and build != "checkpoint":
        # read where it lies, and still the caller's (ISSUE 40)
        assert out["x"] is data["x"] and not data["x"].is_deleted()
    if build == "batch":
        t = info["tiers"]
        assert (t["batch_tasks"], t["routed"]) == (FIB_TASKS, FIB_TASKS)
        assert t["direct"] == 0  # a LIFO lane: its spawns keep the ring
        assert t["scalar_tasks"] == SUM_TASKS + NT
    else:
        assert "tiers" not in info
    if build == "traced":
        (ring,) = info["trace"]["rings"]
        assert ring["written"] > ring["capacity"] == 64  # a ring that wrapped
    else:
        assert "trace" not in info


def test_a_buffer_from_the_host_then_from_the_chip_builds_two_programs():
    mk = _mk("scalar", "host_small")
    data = _data(mk, "host_small")
    iv_h, out_h, info_h = mk.run(_graph(), data=data)
    assert len(mk._jitted) == 1
    mk.run(_graph(), data=data)
    assert len(mk._jitted) == 1  # the same layout: the same program
    on_chip = {**data, "x": jnp.asarray(data["x"])}
    iv_d, out_d, info_d = mk.run(_graph(), data=on_chip)
    assert len(mk._jitted) == 2
    assert "data:x" in info_h["staging"]["slab_blocks"]
    assert "data:x" not in info_d["staging"]["slab_blocks"]
    assert info_h["staging"]["uploads"] == info_d["staging"]["uploads"] == 1
    assert (info_h["staging"]["slab_words"]
            - info_d["staging"]["slab_words"]) == data["x"].size
    assert np.array_equal(iv_h, iv_d)
    for k in data:
        assert np.array_equal(np.asarray(out_h[k]), np.asarray(out_d[k]))
    assert info_h["executed"] == info_d["executed"]


def test_a_block_of_another_shape_is_refused_by_name():
    mk = _mk("scalar", "host_small")
    data = _data(mk, "host_small")
    data["x"] = data["x"][:1]
    with pytest.raises(ValueError, match="data:x"):
        mk.run(_graph(), data=data)
    with pytest.raises(ValueError, match="ivalues"):
        mk.run(_graph(), data=_data(mk, "host_small"),
               ivalues=np.zeros(3, np.int32))


def test_one_split_and_no_packer():
    assert not hasattr(_mk("scalar", "host_small"), "_packer")
    assert inject._split is megakernel._split
    package = pathlib.Path(megakernel.__file__).parents[1]
    defs = [
        str(f.relative_to(package)) for f in sorted(package.rglob("*.py"))
        if "def _split(" in f.read_text()
    ]
    assert defs == ["device/megakernel.py"]


def test_megakernel_has_one_host_program():
    """``run`` / ``resume`` go through ``_build_exec``; the bare
    ``jax.jit(_build_raw)`` of the slope harness, its in-kernel ``reps``
    loop and the continuation call nothing used are gone (ISSUE 47)."""
    import inspect

    assert not hasattr(Megakernel, "_build")
    for fn in (Megakernel._build_raw, Megakernel._kernel):
        assert "reps" not in inspect.signature(fn).parameters, fn
    assert not hasattr(megakernel.KernelContext, "take_continuation")
    assert "auto_route" not in inspect.signature(Megakernel).parameters
