"""SparseLU inside the megakernel: four kinds of block tasks over a
block-sparse matrix that fills in, every task after the root made on the
device when its inputs are final (``block_release.BlockPlan``).

The DAG is the host model's (models/sparselu.py; BOTS ``sparselu`` with
KASTORS' task dependences): ``lu0`` of a diagonal block, ``fwd`` of the
blocks right of it, ``bdiv`` of the blocks below it, ``bmod`` of every
pair, one descriptor a task of the source. The kinds and their tiers:

- ``lu0`` (scalar tier; VPU + MXU): ``ops.tiles.lu_and_inv`` - LU without
  pivoting of the tile packed in place, and ``inv(L)`` and ``inv(U)``
  written PRE-SPLIT to bf16 hi/lo (``linv``), so that
- ``fwd`` / ``bdiv`` (one batch lane, one body, the operand order
  swapped): ``inv(L) A_kj`` or ``A_ik inv(U)``, one 3-pass product each, as
  Cholesky's TRSM is;
- ``bmod`` (its own batch lane, 95 % of the tasks): ``A_ij -= A_ik A_kj``,
  three tile loads a slot, both operands split in VMEM (a 128-tile's
  split is 16 vregs a plane; a split cache in HBM would double the
  factor's bytes to save it), one store. The first update of a fill block
  loads nothing: it MAKES the block.
- two range kinds (scalar tier) that deal a finished block's releases out
  as the lanes drain (``BlockPlan.scan``).

Both lanes run the scheduler's cross-round prefetch (``_batch_round``):
their tiles and load semaphores stand in two halves, and while a round
computes, stores and releases out of one, the loads of the batch queued
behind it land in the other, so a round opens on tiles that are there.
The scheduler announces a round how many queued descriptors to prefetch
and tells the next how many it finds prefetched; a lane with nothing
queued is announced nothing and loads on demand. ``info["sparselu"]``
counts the tasks found prefetched by lane (``bmod_prefetched``,
``panel_prefetched``), ``info["tiers"]["prefetch_hits"]`` their sum, and
``BlockPlan.simulate`` replays the handshake to the unit.

Storage is sparse: ``blocks[slot, m, m]``, a slot a block of the FINAL
pattern, the blocks present before the call first (so the caller's array
of present blocks is read where it lies, under the same slot numbers, and
is not consumed), then the fill blocks, which the call itself makes: no
call counts on what the buffer held before. Products are 3-pass bf16
hi/lo throughout, as cholesky.py: about 2^-16 of a product, between one
bf16 pass and float32 (the componentwise backward error reads 20 times a
float32 factorisation's; PERF.md section 2).
"""

from __future__ import annotations

import functools as _ft
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.sparselu import Symbolic, genmat_pattern, symbolic
from ..ops.tiles import (
    lu_and_inv,
    mm_nn,
    mm_nn_lsplit,
    mm_nn_rsplit,
    split_bf16 as _split,
)
from ..runtime.spans import span
from . import block_release as br
from .block_release import (
    B_FRESH, B_HAS, V_DECREMENTS, V_RELEASED, BlockPlan,
)
from .descriptor import TaskGraphBuilder
from .megakernel import BatchSpec, Megakernel, _batch_stub

__all__ = ["device_sparselu", "make_sparselu_megakernel"]

PANEL_WIDTH = 8    # fwd / bdiv tasks a batch round
UPDATE_WIDTH = 16  # bmod tasks a batch round


def _copies(pairs, sem, wait: bool) -> None:
    """Start (or retire) every ``(src, dst)`` copy on one semaphore; each
    start has exactly one matching wait under the same predicate."""
    for src, dst in pairs:
        cp = pltpu.make_async_copy(src, dst, sem)
        (cp.wait if wait else cp.start)()


def _made(word):
    """Whether block ``word`` has data anywhere yet (a fill block has none
    until its first update makes it)."""
    return (word & (B_HAS | B_FRESH)) != 0


def _in_output(word):
    return (word & B_HAS) != 0


def _block_load(ctx, word, dst, sem, wait: bool, n_present: int) -> None:
    """Block ``word``'s data into ``dst`` from where it lies: the caller's
    input while the block is fresh, the output once a task has written it,
    nowhere for a fill block no update has made yet."""
    slot = BlockPlan.slot(word)

    @pl.when((word & B_FRESH) != 0)
    def _():
        _copies([(ctx.data["a"].at[jnp.minimum(slot, n_present - 1)], dst)],
                sem, wait)

    @pl.when(_in_output(word))
    def _():
        _copies([(ctx.data["blocks"].at[slot], dst)], sem, wait)


def _batch_round(ctx, loads, compute, stores, release, rounds,
                 prefetched) -> None:
    """One batch round of either lane, in the scheduler's cross-round
    double-buffer protocol (``BatchSpec(prefetch=True)``; the order is
    ``forasync_tier.TileKernel.batch_body``'s): the live slots the last
    round did not prefetch start their loads into half ``ctx.buf``; the
    ``ctx.prefetch_count`` descriptors queued behind this batch start
    theirs into the other half, to land under this round's products,
    stores and releases; every live slot waits its loads (a prefetched
    slot the copies the last round started: the same triples under the
    same predicates); the products in half ``ctx.buf``, in place; then
    one store wave from that half with the releases under it - they
    write SMEM only, and nothing they make starts a DMA before a later
    round, which opens after the waits. What a round prefetches was
    queued before the round began, so its blocks were stored and waited
    in an earlier round and no task between here and its use writes
    them (``B_BUSY``; its operands are final)."""
    buf, load = ctx.buf, _ft.partial(loads, ctx)

    def each(pred, fn, *args) -> None:
        for b in range(ctx.width):
            pl.when(pred(b))(_ft.partial(fn, b, *args))

    # The compiler predicates a slot's region and every path runs it: a
    # wave no slot takes part in (the on-demand one of a round that was
    # prefetched whole, the prefetch of a lane with nothing queued) is
    # jumped as one region behind one branch.
    @pl.when(ctx.prefetched < ctx.count)
    def _():
        each(lambda b: ctx.live(b) & (b >= ctx.prefetched),
             load, ctx.arg, buf, False)

    @pl.when(ctx.prefetch_count > 0)
    def _():
        each(lambda b: b < ctx.prefetch_count,
             load, ctx.next_arg, 1 - buf, False)

    each(ctx.live, load, ctx.arg, buf, True)
    compute(buf)
    each(ctx.live, stores, buf, False)
    each(ctx.live, release)
    ctx.set_value(rounds, ctx.value(rounds) + 1)
    ctx.set_value(prefetched, ctx.value(prefetched) + ctx.prefetched)
    each(ctx.live, stores, buf, True)


def _batch_drain(ctx, loads) -> None:
    """Retire the prefetch in flight for ``ctx.prefetched`` descriptors
    whose round will not run (the scheduler's exit with entries unrun, on
    ``fuel``): wait the copies ``_batch_round`` started for them into half
    ``ctx.buf``."""
    for b in range(ctx.width):
        pl.when(b < ctx.prefetched)(
            _ft.partial(loads, ctx, b, ctx.arg, ctx.buf, True))


def _lu0_kernel(ctx, plan: BlockPlan, m: int, n_present: int) -> None:
    kk, word = ctx.arg(0), ctx.arg(1)
    va = ctx.scratch["dva"]
    inv = [ctx.scratch[f"dinv{i}"] for i in range(4)]
    sem = ctx.scratch["dsem"]
    for wait in (False, True):
        _block_load(ctx, word, va, sem.at[0], wait, n_present)
    lu, il, iu = lu_and_inv(va[:], m)
    va[:] = lu
    for i, half in enumerate(_split(il) + _split(iu)):
        inv[i][:] = half
    linv = ctx.data["linv"]
    for wait in (False, True):
        _copies(
            [(va, ctx.data["blocks"].at[BlockPlan.slot(word)])]
            + [(inv[i], linv.at[kk, i // 2, i % 2]) for i in range(4)],
            sem.at[0], wait)
    plan.after_diag(ctx, kk)


def _panel_loads(ctx, b, arg, half, wait: bool, n_present: int) -> None:
    """Start (or retire) slot ``b``'s loads into ``half``: the inverse's
    two halves and the block; ``arg(b, i)`` reads the descriptor."""
    ii, jj, word = (arg(b, i) for i in range(3))
    kk, sel = jnp.minimum(ii, jj), (ii > jj).astype(jnp.int32)
    linv, sem = ctx.data["linv"], ctx.scratch["plsem"].at[half, b]
    _copies([(linv.at[kk, sel, 0], ctx.scratch["pih"].at[half, b]),
             (linv.at[kk, sel, 1], ctx.scratch["pil"].at[half, b])],
            sem, wait)
    _block_load(ctx, word, ctx.scratch["px"].at[half, b], sem, wait,
                n_present)


def _panel_body(ctx, loads, plan: BlockPlan) -> None:
    """``fwd`` (``ii < jj``: ``inv(L_kk) A_kj``) and ``bdiv`` (``A_ik
    inv(U_kk)``) through one body: the inverse's two halves and the block
    a slot, one product, the block stored back."""
    px, pih, pil = (ctx.scratch[k] for k in ("px", "pih", "pil"))
    ssem, blocks = ctx.scratch["pssem"], ctx.data["blocks"]

    def args_of(b):
        return ctx.arg(b, 0), ctx.arg(b, 1), ctx.arg(b, 2)

    def compute(buf) -> None:
        for b in range(ctx.width):
            ii, jj, _ = args_of(b)

            @pl.when(ctx.live(b) & (ii < jj))
            def _(b=b):
                px[buf, b] = mm_nn_lsplit(pih[buf, b], pil[buf, b],
                                          px[buf, b])

            @pl.when(ctx.live(b) & (ii > jj))
            def _(b=b):
                px[buf, b] = mm_nn_rsplit(px[buf, b], pih[buf, b],
                                          pil[buf, b])

    def stores(b, buf, wait: bool) -> None:
        _copies([(px.at[buf, b],
                  blocks.at[BlockPlan.slot(args_of(b)[2])])],
                ssem.at[b], wait)

    def release(b) -> None:
        ii, jj, _ = args_of(b)
        plan.after_panel(ctx.slot_ctx(b), ii, jj)

    _batch_round(ctx, loads, compute, stores, release, br.V_PANEL_ROUNDS,
                 br.V_PANEL_PREFETCHED)


def _bmod_loads(ctx, b, arg, half, wait: bool, plan: BlockPlan,
                n_present: int) -> None:
    """Start (or retire) slot ``b``'s loads into ``half``: the two
    operands, final blocks of the output, and the block from where it
    lies; ``arg(b, i)`` reads the descriptor."""
    ii, jj, kk, word = (arg(b, i) for i in range(4))
    blocks, sem = ctx.data["blocks"], ctx.scratch["ulsem"].at[half, b]
    sa = BlockPlan.slot(ctx.value(plan.word_at(ii, kk)))
    sb = BlockPlan.slot(ctx.value(plan.word_at(kk, jj)))
    _copies([(blocks.at[sa], ctx.scratch["ua"].at[half, b]),
             (blocks.at[sb], ctx.scratch["ub"].at[half, b])], sem, wait)
    _block_load(ctx, word, ctx.scratch["uc"].at[half, b], sem, wait,
                n_present)


def _bmod_body(ctx, loads, plan: BlockPlan, m: int) -> None:
    """``A_ij -= A_ik A_kj`` for every live slot: the two operands and the
    block a slot, one 3-pass product, the block stored back."""
    ua, ub, uc = (ctx.scratch[k] for k in ("ua", "ub", "uc"))
    ssem, blocks = ctx.scratch["ussem"], ctx.data["blocks"]

    def args_of(b):
        return tuple(ctx.arg(b, i) for i in range(4))

    def compute(buf) -> None:
        for b in range(ctx.width):
            word = args_of(b)[3]

            @pl.when(ctx.live(b) & jnp.logical_not(_made(word)))
            def _(b=b):  # allocate_clean_block: the update makes the block
                uc[buf, b] = jnp.zeros((m, m), jnp.float32)

            @pl.when(ctx.live(b))
            def _(b=b):
                uc[buf, b] = uc[buf, b] - mm_nn(ua[buf, b], ub[buf, b])

    def stores(b, buf, wait: bool) -> None:
        _copies([(uc.at[buf, b],
                  blocks.at[BlockPlan.slot(args_of(b)[3])])],
                ssem.at[b], wait)

    def release(b) -> None:
        ii, jj, kk, _ = args_of(b)
        plan.after_update(ctx.slot_ctx(b), ii, jj, kk)

    _batch_round(ctx, loads, compute, stores, release, br.V_UPD_ROUNDS,
                 br.V_UPD_PREFETCHED)


def make_sparselu_megakernel(
    n: int,
    m: int = 128,
    pattern: Optional[np.ndarray] = None,
    interpret: Optional[bool] = None,
) -> Megakernel:
    """The build for an ``n`` x ``n`` block pattern (default: ``genmat``'s)
    of ``m`` x ``m`` blocks. The table is sized by the schedule replayed
    on the host (``BlockPlan.simulate``), with an eighth to spare; the
    symbolic factorisation and the release ride the build as ``mk.slu_sym``
    and ``mk.slu_plan``."""
    sym = symbolic(genmat_pattern(n) if pattern is None else pattern)
    plan = BlockPlan(sym.present, sym.final, sym.slot_of)
    replay = plan.simulate(PANEL_WIDTH, UPDATE_WIDTH)
    live = replay["live_rows_max"]
    capacity = max(64, live + max(8, live // 8))
    tile = (m, m)
    pw, uw = PANEL_WIDTH, UPDATE_WIDTH
    scratch = {
        "dva": pltpu.VMEM(tile, jnp.float32),
        **{f"dinv{i}": pltpu.VMEM(tile, jnp.bfloat16) for i in range(4)},
        "dsem": pltpu.SemaphoreType.DMA((1,)),
        # the lanes' tiles and load semaphores in two halves: a round
        # computes in one while the next round's loads fill the other
        "px": pltpu.VMEM((2, pw) + tile, jnp.float32),
        "pih": pltpu.VMEM((2, pw) + tile, jnp.bfloat16),
        "pil": pltpu.VMEM((2, pw) + tile, jnp.bfloat16),
        "plsem": pltpu.SemaphoreType.DMA((2, pw)),
        "pssem": pltpu.SemaphoreType.DMA((pw,)),
        "ua": pltpu.VMEM((2, uw) + tile, jnp.float32),
        "ub": pltpu.VMEM((2, uw) + tile, jnp.float32),
        "uc": pltpu.VMEM((2, uw) + tile, jnp.float32),
        "ulsem": pltpu.SemaphoreType.DMA((2, uw)),
        "ussem": pltpu.SemaphoreType.DMA((uw,)),
    }
    # a lane's loads, started by its rounds and retired by its drain
    panel_loads = _ft.partial(_panel_loads, n_present=sym.n_present)
    bmod_loads = _ft.partial(_bmod_loads, plan=plan,
                             n_present=sym.n_present)
    mk = Megakernel(
        kernels=[
            ("lu0", _ft.partial(_lu0_kernel, plan=plan, m=m,
                                n_present=sym.n_present)),
            ("panel", _batch_stub),
            ("bmod", _batch_stub),
            ("scan_panel", _ft.partial(plan.scan, kind=br.K_SCANP)),
            ("scan_bmod", _ft.partial(plan.scan, kind=br.K_SCANU)),
        ],
        route={
            # FIFO lanes a spawn pushes straight onto, each firing at two
            # batches over a hot ring: a range is dealt out as they drain,
            # and the batch queued behind a round is prefetched under it.
            "panel": BatchSpec(
                _ft.partial(_panel_body, loads=panel_loads, plan=plan),
                width=pw, prefetch=True, fire_at=2 * pw,
                drain=_ft.partial(_batch_drain, loads=panel_loads)),
            "bmod": BatchSpec(
                _ft.partial(_bmod_body, loads=bmod_loads, plan=plan, m=m),
                width=uw, prefetch=True, fire_at=2 * uw,
                drain=_ft.partial(_batch_drain, loads=bmod_loads)),
        },
        data_specs={
            "a": jax.ShapeDtypeStruct((sym.n_present,) + tile, jnp.float32),
            "blocks": jax.ShapeDtypeStruct((sym.slots,) + tile, jnp.float32),
            "linv": jax.ShapeDtypeStruct((n, 2, 2) + tile, jnp.bfloat16),
        },
        scratch_specs=scratch,
        capacity=capacity,
        num_values=plan.num_values,
        succ_capacity=8,
        interpret=interpret,
        read_only=["a"],
        vmem_limit_bytes=32 * 1024 * 1024,
    )
    assert [mk.fn_id[k] for k in ("lu0", "panel", "bmod", "scan_panel",
                                  "scan_bmod")] == [
        br.K_DIAG, br.K_PANEL, br.K_UPDATE, br.K_SCANP, br.K_SCANU]
    mk.slu_sym, mk.slu_plan, mk.slu_replay = sym, plan, replay
    return mk


def device_sparselu(blocks, mk: Megakernel, out=None
                    ) -> Tuple[jax.Array, dict]:
    """Factor the block-sparse matrix whose present blocks are ``blocks``
    (``[n_present, m, m]`` float32, row by row of the pattern) on the
    device, in ONE ``Megakernel.run`` of ``mk``, the pattern's build
    (``make_sparselu_megakernel``); returns ``(factor, info)``.

    ``blocks`` may be a ``jax.Array`` on the chip: it is read where it lies
    and is still the caller's afterwards (the build declares it
    ``read_only``), so the same matrix can be factored again. ``factor`` is
    a ``jax.Array`` ``[slots, m, m]`` on the chip, a slot a block of the
    final pattern (``info["sparselu"]["rows"]`` / ``["cols"]``; the present
    blocks first, as they lay), LU packed in the blocks (L unit lower, its
    ones implied). ``out`` is a buffer of that shape to write the factor
    into (consumed; whatever it held is not read: a fill block is made by
    the first update that writes it); by default one is allocated. Only
    the root descriptor, the release's presets and the counters cross the
    host link.

    ``info["sparselu"]``: executed by kind (``lu0`` / ``fwd`` / ``bdiv`` /
    ``bmod``), ``fill_blocks``, ``released`` (tasks the release made: all
    but the root), ``releases`` (tests of a block's readiness that made
    nothing), ``scans`` (range descriptors run), rounds and tasks by lane
    and how many of a lane's tasks found their tiles prefetched,
    ``live_rows_max`` against ``capacity``. Two spans split the call,
    ``slu.seed`` and ``slu.run`` (``runtime/spans.py:STAGES``)."""
    sym: Symbolic = mk.slu_sym
    plan: BlockPlan = mk.slu_plan
    m = mk.data_specs["a"].shape[-1]
    if tuple(blocks.shape) != (sym.n_present, m, m):
        raise ValueError(
            f"the build factors {sym.n_present} present blocks of {m} x {m}, "
            f"got {tuple(blocks.shape)}")
    with span("slu.seed"):
        b = TaskGraphBuilder()
        b.add(br.K_DIAG, args=[0, plan.root_word()])
        dev = jax.devices("cpu")[0] if mk.interpret else None
        with jax.default_device(dev):
            data = {
                "a": blocks if isinstance(blocks, jax.Array)
                else jnp.asarray(blocks, jnp.float32),
                "blocks": out if out is not None
                else jnp.zeros((sym.slots, m, m), jnp.float32),
                "linv": jnp.zeros((sym.n, 2, 2, m, m), jnp.bfloat16),
            }
    with span("slu.run"):
        vals, data, info = mk.run(b, data=data, ivalues=plan.presets())
    v = {k: int(vals[getattr(br, "V_" + k.upper())])
         for k in ("fill", "scans", "diag", "row", "col", "upd",
                   "panel_rounds", "upd_rounds", "panel_prefetched",
                   "upd_prefetched")}
    released, tests = int(vals[V_RELEASED]), int(vals[V_DECREMENTS])
    info["sparselu"] = {
        "lu0": v["diag"], "fwd": v["row"], "bdiv": v["col"], "bmod": v["upd"],
        "fill_blocks": v["fill"], "scans": v["scans"],
        "released": released, "releases": tests - released,
        "decrements": tests,
        "panel_rounds": v["panel_rounds"], "panel_tasks": v["row"] + v["col"],
        "bmod_rounds": v["upd_rounds"], "bmod_tasks": v["upd"],
        "panel_prefetched": v["panel_prefetched"],
        "bmod_prefetched": v["upd_prefetched"],
        "live_rows_max": info["allocated"], "capacity": mk.capacity,
        "rows": sym.rows, "cols": sym.cols,
    }
    return data["blocks"], info
