"""One-sided device PGAS inside the resident kernel: put / active messages /
wait-until on *data*, between devices, without leaving the kernel.

This closes the gap between the descriptor-only ICI steal machinery
(device/ici_steal.py moves 16-word task rows) and the reference's SHMEM
layer, which does one-sided put/get/AMO/wait-until on *user data* in a
symmetric heap (/root/reference/modules/openshmem/src/hclib_openshmem.cpp:
136-760; wait-sets :755-920) and pushes lambdas at arbitrary PEs
(/root/reference/modules/openshmem-am/src/hclib_openshmem-am.cpp:64-123).
SURVEY §2.4 maps both to "TPU remote DMA between chips" - this module is
that mapping:

- **symmetric buffers**: the megakernel's ``data_specs`` buffers exist on
  every device of the mesh with identical shapes - a symmetric heap. A
  *channel* is a static contract (buffer name, row count) under which
  one-sided writes travel.
- **put**: ``ctx.pgas.put(dev, chan, dst_row, src_row)`` remote-DMAs rows
  of the channel's buffer from this device into ``dev``'s same-named
  buffer (``pltpu.make_async_remote_copy``), signalling the channel's
  arrival semaphore on the target. SHMEM-style contract: concurrent puts
  to one target must write disjoint regions.
- **active message**: ``ctx.pgas.am(dev, fn, args)`` queues a task
  descriptor for *that specific device's* resident scheduler - unlike the
  steal schedule, which only moves work to its round partner. ``get`` is
  its composition, exactly as in the reference's AM-over-SHMEM design: am
  a handler at the owner; the handler puts the data back on a reply
  channel the caller's consumer task waits on.
- **wait-until**: ``ctx.pgas.wait_until(chan, need, row)`` parks task
  ``row`` until ``need`` messages have *landed* on ``chan`` - the
  scheduler loop polls arrival counts each round and readies parked rows
  (the reference's wait-set poll task, hclib_openshmem.cpp:755-894, as
  part of the resident scheduler itself).

**The counting protocol** (how one-sided completes without a receiver-side
call site): senders count messages per (target, channel); each round, the
counts ride the termination ring-allreduce, so every device learns exactly
how many messages were directed at it; it then *consumes* exactly that many
arrival-semaphore signals via matching ``wait_recv`` descriptors (blocking,
but for messages already launched - never speculative). Data reads happen
only after the matching semaphore count is consumed, so no torn/partial
payload is ever observed, with zero non-blocking semaphore reads (Mosaic's
interpret mode has none). Termination is message-counting (Mattern-style):
exit when globally pending == 0, outboxes empty, and messages sent ==
messages received - so an in-flight message always blocks exit and every
semaphore is drained to zero at kernel exit.

AM flow control needs no credit round-trips: device s owns inbox row
``inbox[s, :]`` on every target (AMW slots, cycled). A receiver drains
*everything* the round-k snapshot announced during round k; ring-allreduce
completion of round k+1 implies every device finished that drain, so a
sender that launches at most AMW//2 AMs per target per round can never
overwrite an unconsumed slot. Queued-but-uncapped AMs wait in a local
outbox (the reference's pending-op list at the NIC locale,
modules/common/hclib-module-common.h:10-115), drained by the round loop.

Stat payload is O(ndev^2 + ndev*nchan) words per hop - fine for a pod
slice's worth of devices; past that the matrix wants the same hierarchical
split the locality graph gives steal paths.
"""

from __future__ import annotations

import functools
import types
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from .descriptor import (
    DESC_WORDS,
    F_A0,
    F_CSR_N,
    F_CSR_OFF,
    F_DEP,
    F_FN,
    F_HOME,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    TaskGraphBuilder,
)
from .megakernel import (
    interpret_mode,
    C_OVERFLOW,
    C_PENDING,
    C_ROUNDS,
    C_TAIL,
    LS_WORDS,
    Megakernel,
    VBLOCK,
)
from .tracebuf import (
    NullTracer,
    TR_ABORT,
    TR_XFER,
    Tracer,
    trace_info,
)

__all__ = ["PGASMegakernel"]

# pstate[] slots
PS_RECV = 0   # messages received (drained) on this device, all kinds
PS_NWAIT = 1  # live wait-table entries


class PGASMegakernel:
    """Per-device resident scheduler + one-sided PGAS over a 1D mesh.

    ``channels`` maps channel name -> (data buffer name, rows per message);
    every put on a channel moves exactly that many leading-axis rows (the
    static-shape contract that lets receivers consume arrival semaphores
    with matching descriptors). ``chan_id`` gives the table index kernels
    use. ``am_window`` is the per-(source, target) inbox depth; at most
    ``am_window // 2`` AMs per target leave the outbox per round.
    """

    def __init__(
        self,
        mk: Megakernel,
        mesh: Mesh,
        channels: Optional[Dict[str, Tuple[str, int]]] = None,
        am_window: int = 8,
        outbox: int = 64,
        max_waits: int = 64,
    ) -> None:
        if len(mesh.axis_names) != 1:
            raise ValueError("PGASMegakernel wants a 1D mesh")
        if am_window < 2:
            raise ValueError("am_window must be >= 2")
        self.mk = mk
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.ndev = int(np.prod(mesh.devices.shape))
        self.channels: List[Tuple[str, int]] = []
        self.chan_id: Dict[str, int] = {}
        for cname, (bname, rows) in (channels or {}).items():
            if bname not in mk.data_specs:
                raise ValueError(f"channel {cname!r}: no data buffer {bname!r}")
            if rows < 1 or rows > mk.data_specs[bname].shape[0]:
                raise ValueError(f"channel {cname!r}: bad row count {rows}")
            self.chan_id[cname] = len(self.channels)
            self.channels.append((bname, int(rows)))
        self.nchan = max(1, len(self.channels))
        self.am_window = int(am_window)
        self.outbox = int(outbox)
        self.max_waits = int(max_waits)
        # Power-of-two meshes delegate to the unified resident kernel
        # (device/resident.py) in its PGAS-only configuration, which also
        # upgrades the counting protocol: per-source arrival semaphores
        # (closing this module's shared-semaphore cross-round aliasing
        # exposure) and O(ndev log ndev) stat routing instead of the ring
        # allreduce of an O(ndev^2) matrix. This class remains the
        # non-pof2 fallback (and the named legacy API).
        self._resident = None
        if self.ndev & (self.ndev - 1) == 0:
            from .resident import ResidentKernel

            self._resident = ResidentKernel(
                mk, mesh, steal=False, channels=dict(channels or {}),
                am_window=self.am_window, outbox=self.outbox,
                max_waits=self.max_waits,
            )
        # Stat-vector layout (ring-allreduced every round; all entries
        # sum). Slot 3 folds the per-device abort word so a host abort
        # exits the whole ring in lockstep one round later.
        self.ST_AM = 4  # [src * ndev + dst] AM send counts
        self.ST_DATA = 4 + self.ndev * self.ndev  # [dst * nchan + chan]
        self.S = self.ST_DATA + self.ndev * self.nchan
        self._jitted: Dict[Any, Any] = {}
        self._pc_stats: Optional[Dict[str, Any]] = None

    # -- the kernel --

    def _kernel(self, quantum: int, max_rounds: int, trace, *refs) -> None:
        # ``trace`` captured at _build time (pallas traces lazily; see
        # Megakernel._kernel).
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = 1 if mk.batch_specs else 0
        ntrace = 1 if trace is not None else 0
        n_in = 7 + ndata  # + waits_in + abort word (last)
        in_refs = refs[:n_in]
        out_refs = refs[n_in : n_in + 4 + ndata + nbatch + ntrace]
        rest = refs[n_in + 4 + ndata + nbatch + ntrace :]
        nscratch = len(mk.scratch_specs)
        scratch_refs = rest[:nscratch]
        stail = list(rest[nscratch:])
        (
            free, vfree,
            outq_tgt, outq_desc, ambuf, obctl, inbox, am_sent, am_recv, sent_round,
            data_sent, chan_recv, pstate, wait_tab,
            statsnd, statrcv, statacc, abuf,
            dsems, am_sem, chan_sems, csem, asem,
        ) = stail[:23]
        # Batched dispatch tier (ISSUE 7): lane scratch rides last; the
        # spill discipline empties it at every sched() exit, so the AM
        # drain and ring fold between rounds only ever see ring rows. The
        # length check keeps the positional bind loud: an edit to
        # _build's scratch list that forgets these indices must fail at
        # trace time, not scribble batch descriptors into a neighbor.
        assert len(stail) == 23 + 2 * nbatch, len(stail)
        lanes, lstate = (stail[23], stail[24]) if nbatch else (None, None)
        abort_in = in_refs[n_in - 1]
        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        waits_in = in_refs[5 + ndata]  # waits ride after the data inputs
        tasks, ready, counts, ivalues = out_refs[:4]
        data = dict(zip(mk.data_specs.keys(), out_refs[4 : 4 + ndata]))
        tstats = out_refs[4 + ndata] if nbatch else None
        tr = (
            Tracer(out_refs[4 + ndata + nbatch], trace.capacity)
            if ntrace
            else NullTracer()
        )
        scratch = dict(zip(mk.scratch_specs.keys(), scratch_refs))

        ndev = self.ndev
        nchan = self.nchan
        AMW = self.am_window
        OUTQ = self.outbox
        MAXW = self.max_waits
        ST_AM, ST_DATA, S = self.ST_AM, self.ST_DATA, self.S
        axis = self.axis

        me = jax.lax.axis_index(axis)
        right = (me + 1) % ndev
        left = (me + ndev - 1) % ndev

        # -- ops attached to every task's KernelContext (ctx.pgas.*) --

        def op_put(dev, chan: int, dst_row, src_row) -> None:
            """One-sided write of channel ``chan``'s row window from my
            buffer rows [src_row, +rows) into device ``dev``'s rows
            [dst_row, +rows). Local completion on return (send done);
            target-side arrival is what wait_until/count observe."""
            if not isinstance(chan, int):
                raise TypeError("chan must be a static channel id")
            bname, rows = self.channels[chan]
            buf = data[bname]
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[pl.ds(src_row, rows)],
                dst_ref=buf.at[pl.ds(dst_row, rows)],
                send_sem=dsems.at[2],
                recv_sem=chan_sems.at[chan],
                device_id=dev,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdma.wait_send()
            data_sent[dev, chan] = data_sent[dev, chan] + 1

        def op_am(dev, fn: int, args: Sequence = (), out=0) -> None:
            """Queue a task descriptor for device ``dev``'s scheduler (the
            reference's async_remote at a chosen PE). Non-blocking: the
            round loop launches it under the inbox-window cap; a full
            outbox sets the overflow flag (bounded, like every queue
            here)."""
            if len(args) > 6:
                raise ValueError(f"at most 6 args per AM, got {len(args)}")
            h = obctl[1]
            ok = h - obctl[0] < OUTQ
            slot = h % OUTQ

            @pl.when(ok)
            def _():
                outq_tgt[slot] = dev
                outq_desc[slot, F_FN] = jnp.int32(fn)
                outq_desc[slot, F_DEP] = 0
                outq_desc[slot, F_SUCC0] = jnp.int32(NO_TASK)
                outq_desc[slot, F_SUCC1] = jnp.int32(NO_TASK)
                outq_desc[slot, F_CSR_OFF] = 0
                outq_desc[slot, F_CSR_N] = 0
                for i in range(6):
                    outq_desc[slot, F_A0 + i] = (
                        jnp.int32(args[i]) if i < len(args) else 0
                    )
                outq_desc[slot, F_OUT] = jnp.int32(out)
                for w in range(F_OUT + 1, DESC_WORDS):
                    # F_HOME word: AM tasks are local to their target.
                    outq_desc[slot, w] = NO_TASK if w == F_HOME else 0
                obctl[1] = h + 1

            @pl.when(jnp.logical_not(ok))
            def _():
                counts[C_OVERFLOW] = 1

        def op_wait_until(chan, need, row) -> None:
            """Park descriptor ``row`` (spawned with an extra dep) until
            ``need`` messages have landed on ``chan``; the round loop
            readies it (the reference's wait-set enqueue,
            hclib_openshmem.cpp:895-920)."""
            n = pstate[PS_NWAIT]
            ok = n < MAXW
            nc = jnp.minimum(n, MAXW - 1)

            @pl.when(ok)
            def _():
                wait_tab[nc, 0] = chan
                wait_tab[nc, 1] = need
                wait_tab[nc, 2] = row
                pstate[PS_NWAIT] = n + 1

            @pl.when(jnp.logical_not(ok))
            def _():
                counts[C_OVERFLOW] = 1

        def op_count(chan: int):
            """Messages landed-and-consumed on ``chan`` at this device (the
            wait-until counter; monotone)."""
            return chan_recv[chan]

        def ctx_hook(ctx) -> None:
            ctx.pgas = types.SimpleNamespace(
                put=op_put, am=op_am, wait_until=op_wait_until,
                count=op_count, me=me, ndev=ndev,
                nchan=len(self.channels),
            )

        core = mk._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            tasks_in, ready_in, counts_in, ivalues_in, True, ctx_hook,
            lanes=lanes, lstate=lstate, tstats=tstats,
            tracer=tr if tr.enabled else None,
        )

        # -- round-loop phases --

        def stage_pgas() -> None:
            def z(i, _):
                am_sent[i] = 0
                am_recv[i] = 0
                for c in range(nchan):
                    data_sent[i, c] = 0
                return 0

            jax.lax.fori_loop(0, ndev, z, 0)
            for c in range(nchan):
                chan_recv[c] = 0
            pstate[PS_RECV] = 0
            pstate[PS_NWAIT] = waits_in[0, 0]
            obctl[0] = 0
            obctl[1] = 0

            def cw(i, _):
                for w in range(3):
                    wait_tab[i, w] = waits_in[1 + i, w]
                return 0

            jax.lax.fori_loop(0, waits_in[0, 0], cw, 0)

        def drain_outbox() -> None:
            """Launch queued AMs under the per-target window cap (FIFO:
            a capped head entry stalls the queue until next round, which
            preserves per-target order)."""

            def zz(i, _):
                sent_round[i] = 0
                return 0

            jax.lax.fori_loop(0, ndev, zz, 0)

            def cond(h):
                more = h < obctl[1]
                t = outq_tgt[h % OUTQ]
                return more & (sent_round[jnp.where(more, t, 0)] < AMW // 2)

            def body(h):
                slot_q = h % OUTQ
                t = outq_tgt[slot_q]
                slot = am_sent[t] % AMW
                # Stage into the 128-word-aligned comm row: Mosaic requires
                # SMEM DMA slices to be 128-word multiples in the minor
                # dim, so the wire unit is a padded row, not the bare
                # 16-word descriptor.
                for w in range(DESC_WORDS):
                    ambuf[w] = outq_desc[slot_q, w]
                rdma = pltpu.make_async_remote_copy(
                    src_ref=ambuf,
                    dst_ref=inbox.at[me, slot],
                    send_sem=dsems.at[3],
                    recv_sem=am_sem,
                    device_id=t,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                rdma.start()
                rdma.wait_send()
                am_sent[t] = am_sent[t] + 1
                sent_round[t] = sent_round[t] + 1
                return h + 1

            h0 = obctl[0]
            h = jax.lax.while_loop(cond, body, h0)
            obctl[0] = h

            @pl.when(h > h0)
            def _():
                # AM launches this round (wire traffic, all targets).
                tr.emit(TR_XFER, tr.now(), me, h - h0)

        def stat_allreduce(r):
            """Ring-allreduce of the S-word stat vector (pending, received,
            outbox backlog, AM send matrix, data send matrix). Same 1-deep
            credited channel as ici_steal's termination collective."""

            def zs(i, _):
                statsnd[i] = 0
                statacc[i] = 0
                return 0

            jax.lax.fori_loop(0, S, zs, 0)
            statsnd[0] = counts[C_PENDING]
            statsnd[1] = pstate[PS_RECV]
            statsnd[2] = obctl[1] - obctl[0]
            statsnd[3] = (abuf[0] != 0).astype(jnp.int32)

            def fill_am(t, _):
                statsnd[ST_AM + me * ndev + t] = am_sent[t]
                for c in range(nchan):
                    statsnd[ST_DATA + t * nchan + c] = data_sent[t, c]
                return 0

            jax.lax.fori_loop(0, ndev, fill_am, 0)

            def acc_local(i, _):
                statacc[i] = statsnd[i]
                return 0

            jax.lax.fori_loop(0, S, acc_local, 0)
            for k in range(ndev - 1):
                if k > 0:
                    pltpu.semaphore_wait(csem, 1)
                else:

                    @pl.when(r > 0)
                    def _():
                        pltpu.semaphore_wait(csem, 1)

                rdma = pltpu.make_async_remote_copy(
                    src_ref=statsnd,
                    dst_ref=statrcv,
                    send_sem=dsems.at[0],
                    recv_sem=dsems.at[1],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                rdma.start()
                rdma.wait()

                def fwd(i, _):
                    v = statrcv[i]
                    statsnd[i] = v
                    statacc[i] = statacc[i] + v
                    return 0

                jax.lax.fori_loop(0, S, fwd, 0)
                # statrcv consumed: free our left neighbor to overwrite it
                # with its next hop. Signal strictly AFTER the read above.
                pltpu.semaphore_signal(
                    csem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )

        def drain_receives() -> None:
            """Consume exactly the arrivals the snapshot announced for this
            device: per-channel data messages (matching-shape wait_recv on
            the channel semaphore), then per-source AM inbox slots in FIFO
            order. Reads happen only after the semaphore count is consumed,
            so payloads are never observed partially written."""
            for c, (bname, rows) in enumerate(self.channels):
                buf = data[bname]
                waiter = pltpu.make_async_remote_copy(
                    src_ref=buf.at[pl.ds(0, rows)],
                    dst_ref=buf.at[pl.ds(0, rows)],
                    send_sem=dsems.at[2],
                    recv_sem=chan_sems.at[c],
                    device_id=me,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                expected = statacc[ST_DATA + me * nchan + c]
                delta = expected - chan_recv[c]

                def one(i, _):
                    waiter.wait_recv()
                    return 0

                jax.lax.fori_loop(0, delta, one, 0)
                chan_recv[c] = expected
                pstate[PS_RECV] = pstate[PS_RECV] + delta

            am_waiter = pltpu.make_async_remote_copy(
                src_ref=inbox.at[0, 0],
                dst_ref=inbox.at[0, 0],
                send_sem=dsems.at[3],
                recv_sem=am_sem,
                device_id=me,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )

            # The AM arrival semaphore is SHARED across sources, so a
            # per-source wait can be satisfied by another source's bytes
            # while the wanted slot is still in flight (a real race, caught
            # by the interpreter's randomized scheduling). Wait for the
            # TOTAL announced arrivals first - the count only reaches
            # total * |row| bytes once every message has fully landed - and
            # only then read any inbox slot. (sent_round doubles as the
            # per-source delta scratch; drain_outbox re-zeroes it.)
            def calc(s, tot):
                d = statacc[ST_AM + s * ndev + me] - am_recv[s]
                sent_round[s] = d
                return tot + d

            total = jax.lax.fori_loop(0, ndev, calc, jnp.int32(0))

            def wait_one(i, _):
                am_waiter.wait_recv()
                return 0

            jax.lax.fori_loop(0, total, wait_one, 0)

            def install_src(s, _):
                base = am_recv[s]
                delta = sent_round[s]

                def install_one(i, _):
                    slot = (base + i) % AMW
                    core.install_descriptor(lambda w: inbox[s, slot, w])
                    return 0

                jax.lax.fori_loop(0, delta, install_one, 0)
                am_recv[s] = base + delta
                pstate[PS_RECV] = pstate[PS_RECV] + delta
                return 0

            jax.lax.fori_loop(0, ndev, install_src, 0)

        def scan_waits() -> None:
            """Ready parked rows whose channel counters reached their
            threshold; compact survivors in place (the wait-set poll,
            hclib_openshmem.cpp:755-894)."""
            n = pstate[PS_NWAIT]

            def one(i, kept):
                ch = wait_tab[i, 0]
                need = wait_tab[i, 1]
                row = wait_tab[i, 2]
                fire = chan_recv[ch] >= need

                @pl.when(fire)
                def _():
                    d = tasks[row, F_DEP] - 1
                    tasks[row, F_DEP] = d

                    @pl.when(d == 0)
                    def _():
                        core.push_ready(row)

                @pl.when(jnp.logical_not(fire))
                def _():
                    wait_tab[kept, 0] = ch
                    wait_tab[kept, 1] = need
                    wait_tab[kept, 2] = row

                return kept + jnp.where(fire, 0, 1)

            pstate[PS_NWAIT] = jax.lax.fori_loop(0, n, one, jnp.int32(0))

        # -- the round loop --

        core.stage()
        stage_pgas()

        def cond(carry):
            r, done = carry
            return jnp.logical_not(done) & (r < max_rounds)

        def body(carry):
            r, done = carry
            core.sched(quantum)
            # Host abort word: re-read from HBM inside the round loop and
            # folded below, so an abort stops the mesh within one round.
            cpa = pltpu.make_async_copy(abort_in, abuf, asem.at[0])
            cpa.start()
            cpa.wait()
            drain_outbox()
            stat_allreduce(r)
            tot_sent = jax.lax.fori_loop(
                ST_AM, S, lambda i, a: a + statacc[i], jnp.int32(0)
            )
            done = (
                (statacc[0] == 0)
                & (statacc[2] == 0)
                & (tot_sent == statacc[1])
            ) | (statacc[3] > 0)

            @pl.when(statacc[3] > 0)
            def _():
                tr.emit(TR_ABORT, tr.now(), r)
            # Unconditional: on the done round every delta is zero, and on
            # a max_rounds cutoff this leaves no arrival semaphore
            # unconsumed for announced messages.
            drain_receives()
            scan_waits()
            return r + 1, done

        r, done = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False))
        )
        counts[C_ROUNDS] = r
        # Ring-credit drain (mirror of ici_steal): the first stat hop of
        # the run never waited, so one credit is outstanding iff any ring
        # hop ran.
        if ndev > 1:

            @pl.when(r >= 1)
            def _():
                pltpu.semaphore_wait(csem, 1)

    # -- host entry --

    def _build(self, quantum: int, max_rounds: int):
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = 1 if mk.batch_specs else 0
        ndev, nchan = self.ndev, self.nchan
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        anyspace = functools.partial(pl.BlockSpec, memory_space=pl.ANY)
        ntrace = 1 if mk.trace is not None else 0
        in_specs = [smem()] * 5 + [anyspace()] * ndata + [smem()]
        in_specs += [anyspace()]  # abort word (HBM: re-read per round)
        out_specs = tuple(
            [smem()] * 4 + [anyspace()] * ndata
            + [smem()] * nbatch  # tstats (batch-routed builds)
            + [smem()] * ntrace
        )
        data_shapes = [
            jax.ShapeDtypeStruct(s.shape, s.dtype)
            for s in mk.data_specs.values()
        ]
        from .megakernel import TS_WORDS

        out_shape = tuple(
            [
                jax.ShapeDtypeStruct((mk.capacity, DESC_WORDS), jnp.int32),
                jax.ShapeDtypeStruct((mk.capacity,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((mk.num_values,), jnp.int32),
            ]
            + data_shapes
            + (
                [jax.ShapeDtypeStruct((TS_WORDS,), jnp.int32)]
                if nbatch else []
            )
            + ([mk.trace.out_shape()] if ntrace else [])
        )
        aliases = {0: 0, 2: 1, 3: 2, 4: 3}
        for i in range(ndata):
            aliases[5 + i] = 4 + i
        kern = pl.pallas_call(
            functools.partial(self._kernel, quantum, max_rounds, mk.trace),
            out_shape=out_shape,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=list(mk.scratch_specs.values())
            + [
                pltpu.SMEM((mk.capacity + 1,), jnp.int32),  # free
                pltpu.SMEM((mk.num_values // VBLOCK + 1,), jnp.int32),
                pltpu.SMEM((self.outbox,), jnp.int32),  # outq targets
                pltpu.SMEM((self.outbox, DESC_WORDS), jnp.int32),
                pltpu.SMEM((128,), jnp.int32),  # ambuf: padded wire row
                pltpu.SMEM((2,), jnp.int32),  # obctl head/tail
                pltpu.SMEM((ndev, self.am_window, 128), jnp.int32),
                pltpu.SMEM((ndev,), jnp.int32),  # am_sent
                pltpu.SMEM((ndev,), jnp.int32),  # am_recv
                pltpu.SMEM((ndev,), jnp.int32),  # sent_round
                pltpu.SMEM((ndev, nchan), jnp.int32),  # data_sent
                pltpu.SMEM((nchan,), jnp.int32),  # chan_recv
                pltpu.SMEM((8,), jnp.int32),  # pstate
                pltpu.SMEM((self.max_waits, 3), jnp.int32),
                pltpu.SMEM((self.S,), jnp.int32),  # statsnd
                pltpu.SMEM((self.S,), jnp.int32),  # statrcv
                pltpu.SMEM((self.S,), jnp.int32),  # statacc
                pltpu.SMEM((8,), jnp.int32),  # abuf (abort staging)
                pltpu.SemaphoreType.DMA((4,)),
                pltpu.SemaphoreType.DMA(()),  # am arrival
                pltpu.SemaphoreType.DMA((nchan,)),  # channel arrivals
                pltpu.SemaphoreType.REGULAR,  # ring credit
                pltpu.SemaphoreType.DMA((1,)),  # asem
            ]
            + (
                [
                    # Batched dispatch tier lane scratch (unpacked last;
                    # rows = kinds x priority buckets).
                    pltpu.SMEM(
                        (mk.lane_scratch_rows, mk.capacity), jnp.int32
                    ),
                    pltpu.SMEM((mk.lane_scratch_rows, LS_WORDS), jnp.int32),
                ]
                if mk.batch_specs
                else []
            ),
            input_output_aliases=aliases,
            interpret=interpret_mode() if mk.interpret else False,
        )

        def step(tasks, succ, ring, counts, iv, *data_and_waits):
            data_in = data_and_waits[:ndata]
            waits = data_and_waits[ndata]
            abort = data_and_waits[ndata + 1]
            outs = kern(
                tasks[0], succ[0], ring[0], counts[0], iv[0],
                *[d[0] for d in data_in], waits[0], abort[0],
            )
            tasks_o, ready_o, counts_o, iv_o = outs[:4]
            data_o = outs[4 : 4 + ndata]
            extra_o = outs[4 + ndata :]  # [tstats?, trace?]
            gcounts = jax.lax.psum(counts_o, self.axis)
            return (
                counts_o[None],
                iv_o[None],
                gcounts[None],
                *[d[None] for d in data_o],
                *[t[None] for t in extra_o],
            )

        nin = 7 + ndata
        f = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(self.axis),) * nin,
            out_specs=(P(self.axis),) * (3 + ndata + nbatch + ntrace),
            check_vma=False,
        )
        return jax.jit(f)

    def run(
        self,
        builders: Sequence[TaskGraphBuilder],
        data: Optional[Dict[str, np.ndarray]] = None,
        ivalues: Optional[np.ndarray] = None,
        waits: Optional[Sequence[Sequence[Tuple[int, int, int]]]] = None,
        quantum: int = 64,
        max_rounds: int = 1 << 14,
        abort=None,
    ):
        """Execute all partitions fully on-device.

        ``waits[d]`` lists host-declared wait-sets for device d as
        (chan_id, need, task_index) - the named task gains one extra
        dependency satisfied when ``need`` messages have landed on the
        channel. Returns (ivalues[ndev, V], data, info); ``data`` values
        carry a leading device axis (per-device symmetric-heap instances).
        ``abort``: host abort word (truthy or per-device flags) - the
        round loops observe it within one round and the mesh exits in
        lockstep with ``info['aborted']`` instead of draining.
        """
        from .sharded import execute_partitions

        if self._resident is not None:
            return self._resident.run(
                builders, data=data, ivalues=ivalues, waits=waits,
                quantum=quantum, max_rounds=max_rounds, abort=abort,
            )
        mk = self.mk
        ndev = self.ndev
        waits = list(waits or [])
        if len(waits) < ndev:
            waits = waits + [[] for _ in range(ndev - len(waits))]
        waits_arr = np.zeros((ndev, self.max_waits + 1, 3), np.int32)
        for d, wl in enumerate(waits):
            if len(wl) > self.max_waits:
                raise ValueError(f"device {d}: too many waits ({len(wl)})")
            waits_arr[d, 0, 0] = len(wl)
            for i, (ch, need, row) in enumerate(wl):
                if not (0 <= ch < len(self.channels)):
                    raise ValueError(f"bad channel id {ch}")
                if not (0 <= row < builders[d].num_tasks):
                    raise ValueError(
                        f"device {d}: wait names task {row}, but the "
                        f"partition has {builders[d].num_tasks} tasks"
                    )
                waits_arr[d, 1 + i] = (ch, need, row)

        def bump_waits(tasks, succ, ring, counts):
            """Each parked task owes one extra dependency (satisfied by the
            wait-table when its channel count reaches `need`), and must not
            start on the ready ring."""
            for d, wl in enumerate(waits):
                for (_, _, row) in wl:
                    tasks[d, row, F_DEP] += 1
                bumped = {row for (_, _, row) in wl}
                if not bumped:
                    continue
                old_n = counts[d][C_TAIL]
                keep = [r for r in ring[d][:old_n] if r not in bumped]
                ring[d][: len(keep)] = keep
                counts[d][C_TAIL] = len(keep)

        key = (quantum, max_rounds)
        first_build = key not in self._jitted
        if first_build:
            from ..runtime.progcache import mesh_key, shared_build

            variant = (
                "pgas", mesh_key(self.mesh), tuple(self.channels),
                self.am_window, self.outbox, self.max_waits,
            ) + key
            self._jitted[key], self._pc_stats = shared_build(
                mk, variant,
                lambda: self._build(quantum, max_rounds),
            )
        from .sharded import abort_words

        abort_arr = abort_words(abort, ndev)
        import time as _time

        t0_ns = _time.monotonic_ns()
        iv_o, data_o, info = execute_partitions(
            mk, self.mesh, ndev, self._jitted[key], builders, data, ivalues,
            with_rounds=True, mutate=bump_waits,
            extra_inputs=[waits_arr, abort_arr],
        )
        t1_ns = _time.monotonic_ns()
        if (
            first_build and self._pc_stats is not None
            and not self._pc_stats["hit"]
        ):
            # jax.jit is lazy: a cache MISS pays trace/lower/compile
            # inside this first entry (the Megakernel._execute
            # discipline), so fold the first wall into build_s before
            # it is reported.
            self._pc_stats["build_s"] += (t1_ns - t0_ns) / 1e9
        if self._pc_stats is not None:
            info["program_cache"] = dict(self._pc_stats)
        info["rounds"] = info.pop("steal_rounds")
        tail = info.pop("extra_outputs", None)
        if mk.trace is not None and tail:
            info["trace"] = trace_info(
                [tail[-1][d] for d in range(ndev)], t0_ns, t1_ns,
                mk.trace.capacity,
            )
        if mk.batch_specs and tail:
            # Per-device batched-tier counters (tstats rides before the
            # trace ring in the appended outputs).
            trows = tail[0]
            info["tiers"] = [
                mk.decode_tier_stats(trows[d]) for d in range(ndev)
            ]
        info["aborted"] = bool(abort_arr[:, 0].any()) and info["pending"] != 0
        if info["overflow"]:
            raise RuntimeError(
                "pgas kernel overflow: task table, value slots, outbox, or "
                "wait table exceeded - raise the limits or coarsen"
            )
        if info["pending"] != 0 and not info["aborted"]:
            from ..runtime.resilience import StallError

            raise StallError(
                f"pgas kernel stalled: {info['pending']} pending after "
                f"{info['executed']} executed ({info['rounds']} rounds) - "
                "a wait-until whose messages never arrive, or max_rounds "
                "too small",
                stats=info,
            )
        return iv_o, data_o, info
