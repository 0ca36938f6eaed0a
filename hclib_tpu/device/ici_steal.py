"""In-kernel ICI work stealing: the whole multi-device run is ONE resident
kernel per device - scheduling, migration, and termination never exit to XLA.

This is the fully-resident evolution of device/sharded.py's bulk-synchronous
steal loop (which re-enters the kernel every round and exchanges surplus with
host-jitted ``ppermute``): here each device's kernel runs rounds internally,

  1. drain the local ready ring for a bounded quantum
     (megakernel._make_core's scheduler - the same pop/dispatch/complete),
  2. for every XOR dimension k < log2(ndev): a paired stats exchange with
     the partner at distance 2^k folds (pending, backlog) partial sums -
     recursive-doubling termination in log2(ndev) hops - then a paired
     row exchange pairwise-equalizes backlog (send (mine - theirs)/2,
     window-capped) by remote-DMAing descriptor rows straight between SMEM
     task tables, importing before the next hop so received work diffuses
     further the same round,
  3. exit when the folded global pending hits zero.

(Non-power-of-two 1D meshes keep the older schedule: a ring allreduce for
termination plus one cycling partner per round.)

The reference analogue is the thief CASing a victim's deque slot from
another core (src/hclib-locality-graph.c:843-888, src/hclib-deque.c:75-106);
on TPU the "CAS" becomes paired remote DMAs with semaphore flow control:

- every (hop, sub-channel) inbox is 1-deep with a fixed writer: the
  receiver signals that writer's REGULAR *credit* semaphore after
  consuming, and the writer waits a credit before its next-round write -
  so an inbox is never overwritten before it is consumed, without any
  global barrier;
- recv DMA semaphores are per-hop: a device two hops ahead may deliver
  early, and a shared recv semaphore would hand its signal to a wait for a
  different hop's message (desynchronizing the pairing);
- all devices execute the identical hop schedule, so every semaphore wait
  has a matching signal by construction (lockstep SPMD, no dynamic
  handshakes to deadlock on).

Tested end-to-end on 8-device 1D and 4x2 2D simulated meshes via Mosaic's
TPU interpret mode (``pltpu.InterpretParams`` - simulates remote DMA +
semaphores on CPU) and compiled/run on real TPU hardware on a 1-device mesh
(self-loop exchange).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from .descriptor import (
    DESC_WORDS,
    F_CSR_N,
    F_DEP,
    F_FN,
    F_SUCC0,
    F_SUCC1,
    TaskGraphBuilder,
)
from .megakernel import (
    interpret_mode,
    C_HEAD,
    C_PENDING,
    C_ROUNDS,
    C_TAIL,
    Megakernel,
)
from .tracebuf import (
    HDR as _TR_HDR,
    NullTracer,
    TR_ABORT,
    TR_XFER,
    Tracer,
    trace_info,
)

__all__ = ["ICIStealMegakernel"]


class ICIStealMegakernel:
    """Runs one resident scheduler+steal kernel per device of a 1D/2D/3D
    mesh.

    ``mk`` supplies the kernel table/capacities (as for ShardedMegakernel);
    ``migratable_fns`` whitelists kernel ids whose successor-free tasks may
    migrate; ``window`` bounds rows per exchange; ``scan`` bounds how far
    past the ring head the exporter looks for eligible rows.

    Power-of-two device counts (the practical case: TPU slices come in
    pof2 per-axis shapes) use the **paired hypercube dimension-exchange**:
    every round runs ALL log2(ndev) XOR-partner hops, each hop pairwise-
    equalizing backlog (send (mine - theirs)/2, capped at ``window``) and
    folding (pending, backlog) partial sums into the same hop schedule -
    recursive-doubling termination in log2(ndev) hops with no separate
    ring collective, and a maximal skew spreads across the whole mesh in
    one or two rounds instead of one window per round. On a 2D mesh the
    XOR dimensions decompose into per-axis exchanges (low bits = minor
    axis), so every hop is a torus-neighbor-distance transfer. Non-pof2
    1D meshes keep the cycling single-partner schedule with the ring
    termination collective.
    """

    def __init__(
        self,
        mk: Megakernel,
        mesh: Mesh,
        migratable_fns: Iterable[int] = (),
        window: int = 8,
        scan: Optional[int] = None,
        fault_plan=None,
    ) -> None:
        if len(mesh.axis_names) not in (1, 2, 3):
            raise ValueError("ICIStealMegakernel wants a 1D/2D/3D mesh")
        self.mk = mk
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.axis = self.axes[0]  # psum axis for gcounts (legacy name)
        self.dims = tuple(int(d) for d in mesh.devices.shape)
        self.ndev = int(np.prod(self.dims))
        self._pof2 = self.ndev & (self.ndev - 1) == 0
        if len(self.axes) > 1 and not self._pof2:
            raise ValueError("2D/3D meshes need power-of-two device counts")
        self.migratable_fns = frozenset(int(f) for f in migratable_fns)
        self.window = int(window)
        self.scan = int(scan) if scan is not None else 2 * self.window
        self._jitted: Dict[Any, Any] = {}
        self._pc_stats: Optional[Dict[str, Any]] = None
        # Power-of-two meshes delegate to the unified resident kernel
        # (device/resident.py) in its steal-only, whole-row-migration
        # configuration - this class remains the non-pof2 fallback (and
        # the named legacy API). Seeded device fault injection
        # (DeviceFaultPlan) lives in the resident kernel's exchange
        # protocol; the non-pof2 ring supports the abort word only.
        self._resident = None
        if self._pof2:
            from .resident import ResidentKernel

            self._resident = ResidentKernel(
                mk, mesh, steal=True, migratable_fns=self.migratable_fns,
                homed=False, window=self.window, scan=self.scan,
                fault_plan=fault_plan,
            )
        elif fault_plan is not None and fault_plan.enabled():
            raise ValueError(
                "DeviceFaultPlan injection needs a power-of-two mesh (the "
                "resident kernel's credited hypercube exchange); the "
                "non-pof2 ring supports only the abort word"
            )

    # -- shared kernel helpers --

    def _flat_me(self):
        """Flattened device index. This class's own kernel bodies only ever
        run on non-pof2 1D meshes - every pof2 mesh (the only legal
        multi-axis shape) delegates run() to ResidentKernel, whose
        addressing handles 1D/2D/3D."""
        assert len(self.axes) == 1, "multi-axis meshes delegate to resident"
        return jax.lax.axis_index(self.axes[0])

    def _did(self, flat):
        """Remote-op device_id for a flattened index (1D: the logical id;
        see _flat_me for why multi-axis never reaches this)."""
        assert len(self.axes) == 1
        return flat

    @property
    def _did_type(self):
        # 1D-only like _flat_me/_did: multi-axis meshes never reach this
        # class's kernel bodies (pof2 delegates to ResidentKernel).
        assert len(self.axes) == 1
        return pltpu.DeviceIdType.LOGICAL

    def _make_xfer(self, core, tasks, ready, counts, free, candbuf, sendbuf):
        """Shared transfer closures for both kernel bodies: paired remote
        copy (device-id type per mesh rank), the export scan/compact pass,
        and descriptor-row import via the core's adoption path."""
        cap = self.mk.capacity
        W = self.window
        SCAN = self.scan
        wl = sorted(self.migratable_fns)
        did_type = self._did_type

        def remote_copy(src, dst, dev, s_send, s_recv):
            rdma = pltpu.make_async_remote_copy(
                src_ref=src, dst_ref=dst, send_sem=s_send, recv_sem=s_recv,
                device_id=dev, device_id_type=did_type,
            )
            rdma.start()
            rdma.wait()

        def export(quota):
            """Scan up to SCAN entries behind the ring head (the cold,
            steal-side end of the Chase-Lev split), move up to ``quota``
            eligible rows into sendbuf, compact the kept candidates back
            against the new head. Returns nsend."""
            head = counts[C_HEAD]
            backlog = counts[C_TAIL] - head
            S = jnp.minimum(backlog, SCAN)

            def copy_cand(j, _):
                candbuf[j] = ready[(head + j) % cap]
                return 0

            jax.lax.fori_loop(0, S, copy_cand, 0)

            def elig_of(cand):
                d_fn = tasks[cand, F_FN]
                ok = jnp.bool_(False)
                for f in wl:
                    ok = ok | (d_fn == f)
                return (
                    ok
                    & (tasks[cand, F_SUCC0] == -1)
                    & (tasks[cand, F_SUCC1] == -1)
                    & (tasks[cand, F_CSR_N] == 0)
                )

            def count_elig(j, n):
                return n + elig_of(candbuf[j]).astype(jnp.int32)

            nelig = jax.lax.fori_loop(0, S, count_elig, jnp.int32(0))
            nsend = jnp.minimum(quota, nelig)

            def classify(j, carry):
                se, kp = carry
                cand = candbuf[j]
                take = elig_of(cand) & (se < nsend)

                @pl.when(take)
                def _():
                    for w in range(DESC_WORDS):
                        sendbuf[se, w] = tasks[cand, w]
                    # The task now lives on the target: tombstone + free
                    # the row (spawn/import reuse it).
                    tasks[cand, F_DEP] = -1
                    nf = free[0] + 1
                    free[0] = nf
                    free[nf] = cand

                @pl.when(jnp.logical_not(take))
                def _():
                    ready[(head + nsend + kp) % cap] = cand

                return (
                    se + take.astype(jnp.int32),
                    kp + (1 - take.astype(jnp.int32)),
                )

            jax.lax.fori_loop(0, S, classify, (jnp.int32(0), jnp.int32(0)))
            counts[C_HEAD] = head + nsend
            counts[C_PENDING] = counts[C_PENDING] - nsend
            return nsend

        def import_rows(box):
            """Install received descriptors through the shared adoption
            path (core.install_descriptor: freed rows first, then the bump
            cursor; stolen rows came off a ready ring so their dep counter
            is 0 and they go straight back to ready)."""
            n = box[W, 0]

            def one(i, _):
                core.install_descriptor(lambda w: box[i, w])
                return 0

            jax.lax.fori_loop(0, n, one, 0)

        return remote_copy, export, import_rows

    # -- the kernel --

    def _kernel(self, quantum: int, max_rounds: int, trace, *refs) -> None:
        # ``trace`` captured at _build time (pallas traces lazily; see
        # Megakernel._kernel).
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = 1 if mk.batch_specs else 0
        ntrace = 1 if trace is not None else 0
        n_in = 6 + ndata  # + abort word (last input)
        in_refs = refs[:n_in]
        out_refs = refs[n_in : n_in + 4 + ndata + nbatch + ntrace]
        rest = refs[n_in + 4 + ndata + nbatch + ntrace :]
        nscratch = len(mk.scratch_specs)
        scratch_refs = rest[:nscratch]
        stail = list(rest[nscratch:])
        (
            free, vfree, candbuf, sendbuf, inbox, statsnd, statrcv,
            abuf, dsems, csems, asem,
        ) = stail[:11]
        # Batched dispatch tier (ISSUE 7): lane scratch rides last; the
        # spill discipline empties it at every sched() exit, so the steal
        # export scan between rounds only ever sees ring rows. The length
        # check keeps the positional bind loud: an edit to _build's
        # scratch list that forgets these indices must fail at trace
        # time, not scribble batch descriptors into a neighboring ref.
        assert len(stail) == 11 + 2 * nbatch, len(stail)
        lanes, lstate = (stail[11], stail[12]) if nbatch else (None, None)
        abort_in = in_refs[n_in - 1]
        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        tasks, ready, counts, ivalues = out_refs[:4]
        data = dict(zip(mk.data_specs.keys(), out_refs[4 : 4 + ndata]))
        tstats = out_refs[4 + ndata] if nbatch else None
        tr = (
            Tracer(out_refs[4 + ndata + nbatch], trace.capacity)
            if ntrace
            else NullTracer()
        )
        scratch = dict(zip(mk.scratch_specs.keys(), scratch_refs))
        # stage_all_values=True: imported tasks may read/accumulate value
        # slots the local partition never declared (an empty partition has
        # value_alloc 0 but still hosts migrated counter tasks).
        core = mk._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            tasks_in, ready_in, counts_in, ivalues_in, True,
            lanes=lanes, lstate=lstate, tstats=tstats,
            tracer=tr if tr.enabled else None,
        )

        ndev = self.ndev
        W = self.window
        axis = self.axis
        # Hop schedule: powers of two below ndev (hypercube diffusion); a
        # 1-device ring degenerates to hop 0 = self-exchange, which still
        # exercises the full remote-DMA path (quota is 0 vs oneself).
        nh = max(1, (ndev - 1).bit_length())

        me = jax.lax.axis_index(axis)
        right = (me + 1) % ndev
        left = (me + ndev - 1) % ndev
        remote_copy, export, import_rows = self._make_xfer(
            core, tasks, ready, counts, free, candbuf, sendbuf
        )

        def allreduce(r, local_abort):
            """Ring-allreduce of (pending, backlog, abort): every device
            learns the global totals in ndev-1 hops (the done-flag join,
            src/hclib-runtime.c:403-421, as an in-kernel collective). The
            abort word rides the same fold so a host abort exits the
            WHOLE ring in lockstep one round later - a divergent exit
            would strand neighbors in the paired exchanges."""
            cur_p = counts[C_PENDING]
            cur_b = counts[C_TAIL] - counts[C_HEAD]
            cur_a = local_abort.astype(jnp.int32)
            tot_p, tot_b, tot_a = cur_p, cur_b, cur_a
            for k in range(ndev - 1):
                statsnd[0] = cur_p
                statsnd[1] = cur_b
                statsnd[2] = cur_a
                if k > 0:
                    pltpu.semaphore_wait(csems.at[0], 1)
                else:

                    @pl.when(r > 0)
                    def _():
                        pltpu.semaphore_wait(csems.at[0], 1)

                remote_copy(
                    statsnd, statrcv, right, dsems.at[0], dsems.at[1]
                )
                cur_p = statrcv[0]
                cur_b = statrcv[1]
                cur_a = statrcv[2]
                # Consumed: free the writer (our left neighbor) to send its
                # next step into our statrcv.
                pltpu.semaphore_signal(
                    csems.at[0], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                tot_p = tot_p + cur_p
                tot_b = tot_b + cur_b
                tot_a = tot_a + cur_a
            return tot_p, tot_b, tot_a

        def exchange(r, tot_b):
            """One steal hop: send surplus rows to the device at distance
            d = 2^(r mod nh), receive from the mirror device."""
            d = (jnp.int32(1) << (r % nh)) % ndev
            target = (me + d) % ndev
            gavg = tot_b // ndev
            backlog = counts[C_TAIL] - counts[C_HEAD]
            quota = jnp.clip(backlog - gavg, 0, W)
            nsend = export(quota)
            sendbuf[W, 0] = nsend

            @pl.when(nsend > 0)
            def _():
                tr.emit(TR_XFER, tr.now(), target, nsend)
            # Credit: our *target's* inbox is free once it signalled us at
            # the end of its previous round (it signals its next-round
            # source, which is exactly us because the hop schedule is
            # global). Round 0 inboxes start free.
            @pl.when(r > 0)
            def _():
                pltpu.semaphore_wait(csems.at[1], 1)

            remote_copy(sendbuf, inbox, target, dsems.at[2], dsems.at[3])
            import_rows(inbox)
            # Our inbox is consumed: credit the device that targets it
            # next round (distance 2^((r+1) mod nh)).
            dn = (jnp.int32(1) << ((r + 1) % nh)) % ndev
            src_next = (me + ndev - dn) % ndev
            pltpu.semaphore_signal(
                csems.at[1], inc=1, device_id=src_next,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )

        core.stage()

        def cond(carry):
            r, done = carry
            return jnp.logical_not(done) & (r < max_rounds)

        def body(carry):
            r, done = carry
            core.sched(quantum)
            # Host abort word: re-read from HBM inside the round loop, so
            # an abort stops a running quantum stream within one round.
            cpa = pltpu.make_async_copy(abort_in, abuf, asem.at[0])
            cpa.start()
            cpa.wait()
            tot_p, tot_b, tot_a = allreduce(r, abuf[0] != 0)
            done = (tot_p == 0) | (tot_a > 0)

            @pl.when(tot_a > 0)
            def _():
                tr.emit(TR_ABORT, tr.now(), r)

            @pl.when(jnp.logical_not(done))
            def _():
                exchange(r, tot_b)

            return r + 1, done

        r, done = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False))
        )
        counts[C_ROUNDS] = r
        # Drain outstanding flow-control credits so semaphores are zero at
        # kernel exit: the first send of each channel never waited (round-0
        # priming), so each channel holds exactly one unconsumed credit
        # once it was used at all.
        e = jnp.where(done, r - 1, r)  # rounds that ran an exchange

        @pl.when(e >= 1)
        def _():
            pltpu.semaphore_wait(csems.at[1], 1)

        if ndev > 1:

            @pl.when(r >= 1)
            def _():
                pltpu.semaphore_wait(csems.at[0], 1)

    def _kernel_hc(self, quantum: int, max_rounds: int, trace,
                   *refs) -> None:
        """Paired hypercube dimension-exchange body (pof2 device counts).

        Each round: drain the local ring for a quantum, then for every XOR
        dimension k: (1) paired stats exchange folding (pending, backlog)
        partial sums - recursive-doubling termination - and carrying the
        partner's current backlog, (2) paired row exchange sending
        clip((mine - theirs)/2, 0, W) eligible rows, importing the mirror
        flow immediately so later hops diffuse just-received work further.
        Every (hop, sub-channel) has its own inbox buffer and credit
        semaphore: the writer for a given hop never changes, so a 1-deep
        credited channel per hop is race-free without any global barrier.
        """
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = 1 if mk.batch_specs else 0
        ntrace = 1 if trace is not None else 0
        # + abort word (last input; this body polls nothing but must
        # count it - _build passes 6 + ndata inputs to whichever body it
        # binds, and miscounting here shears every ref slice after the
        # inputs). NOTE: run() delegates every pof2 mesh to
        # ResidentKernel, so this body is unreachable today; it is kept
        # aligned with _build so a direct build fails loudly (the
        # scratch-tail length assert below) rather than silently.
        n_in = 6 + ndata
        in_refs = refs[:n_in]
        out_refs = refs[n_in : n_in + 4 + ndata + nbatch + ntrace]
        rest = refs[n_in + 4 + ndata + nbatch + ntrace :]
        nscratch = len(mk.scratch_specs)
        scratch_refs = rest[:nscratch]
        nh = self._nh
        tail = rest[nscratch:]
        free, vfree, candbuf, sendbuf, statsnd = tail[:5]
        statrcv = tail[5 : 5 + nh]
        inboxes = tail[5 + nh : 5 + 2 * nh]
        ssems, rsems, csems = tail[5 + 2 * nh : 5 + 2 * nh + 3]
        assert len(tail) == 5 + 2 * nh + 3 + 2 * nbatch, len(tail)
        lanes, lstate = (
            (tail[5 + 2 * nh + 3], tail[5 + 2 * nh + 4])
            if nbatch else (None, None)
        )
        tasks_in, succ, ready_in, counts_in, ivalues_in = in_refs[:5]
        tasks, ready, counts, ivalues = out_refs[:4]
        data = dict(zip(mk.data_specs.keys(), out_refs[4 : 4 + ndata]))
        tstats = out_refs[4 + ndata] if nbatch else None
        if ntrace:
            # This body is only reachable on pof2 meshes, which run()
            # routes to ResidentKernel (the traced path) - but keep the
            # appended output deterministic if built directly.
            for w in range(_TR_HDR):
                out_refs[4 + ndata + nbatch][w] = 0
        scratch = dict(zip(mk.scratch_specs.keys(), scratch_refs))
        core = mk._make_core(
            succ, tasks, ready, counts, ivalues, data, scratch, free, vfree,
            tasks_in, ready_in, counts_in, ivalues_in, True,
            lanes=lanes, lstate=lstate, tstats=tstats,
        )

        ndev = self.ndev
        cap = mk.capacity
        W = self.window
        SCAN = self.scan
        wl = sorted(self.migratable_fns)
        me = self._flat_me()
        did_type = self._did_type

        def remote_copy(src, dst, dev, s_send, s_recv):
            rdma = pltpu.make_async_remote_copy(
                src_ref=src, dst_ref=dst, send_sem=s_send, recv_sem=s_recv,
                device_id=dev, device_id_type=did_type,
            )
            rdma.start()
            rdma.wait()

        def export(quota):
            head = counts[C_HEAD]
            backlog = counts[C_TAIL] - head
            S = jnp.minimum(backlog, SCAN)

            def copy_cand(j, _):
                candbuf[j] = ready[(head + j) % cap]
                return 0

            jax.lax.fori_loop(0, S, copy_cand, 0)

            def elig_of(cand):
                d_fn = tasks[cand, F_FN]
                ok = jnp.bool_(False)
                for f in wl:
                    ok = ok | (d_fn == f)
                return (
                    ok
                    & (tasks[cand, F_SUCC0] == -1)
                    & (tasks[cand, F_SUCC1] == -1)
                    & (tasks[cand, F_CSR_N] == 0)
                )

            def count_elig(j, n):
                return n + elig_of(candbuf[j]).astype(jnp.int32)

            nelig = jax.lax.fori_loop(0, S, count_elig, jnp.int32(0))
            nsend = jnp.minimum(quota, nelig)

            def classify(j, carry):
                se, kp = carry
                cand = candbuf[j]
                take = elig_of(cand) & (se < nsend)

                @pl.when(take)
                def _():
                    for w in range(DESC_WORDS):
                        sendbuf[se, w] = tasks[cand, w]
                    tasks[cand, F_DEP] = -1
                    nf = free[0] + 1
                    free[0] = nf
                    free[nf] = cand

                @pl.when(jnp.logical_not(take))
                def _():
                    ready[(head + nsend + kp) % cap] = cand

                return (
                    se + take.astype(jnp.int32),
                    kp + (1 - take.astype(jnp.int32)),
                )

            jax.lax.fori_loop(0, S, classify, (jnp.int32(0), jnp.int32(0)))
            counts[C_HEAD] = head + nsend
            counts[C_PENDING] = counts[C_PENDING] - nsend
            return nsend

        def import_rows(box):
            n = box[W, 0]

            def one(i, _):
                core.install_descriptor(lambda w: box[i, w])
                return 0

            jax.lax.fori_loop(0, n, one, 0)

        core.stage()

        def cond(carry):
            r, done = carry
            return jnp.logical_not(done) & (r < max_rounds)

        def body(carry):
            r, done = carry
            core.sched(quantum)
            # Round-start snapshot: every task is either in some device's
            # pending count or was already executed - nothing is in flight
            # between rounds, so the folded sums are exact.
            tot_p = counts[C_PENDING]
            for k in range(nh):
                partner = (me ^ (1 << k)) % ndev  # ndev==1: self-loop
                pdev = self._did(partner)
                statsnd[0] = tot_p
                statsnd[1] = counts[C_TAIL] - counts[C_HEAD]

                @pl.when(r > 0)
                def _(k=k):
                    pltpu.semaphore_wait(csems.at[2 * k], 1)

                # Per-hop recv semaphores: a faster device two hops ahead
                # may deliver its hop-k' message while we still wait at
                # hop k - a shared recv sem would hand us its signal and
                # desynchronize the pairing (observed as a deadlock).
                remote_copy(
                    statsnd, statrcv[k], pdev, ssems.at[0], rsems.at[2 * k]
                )
                tot_p = tot_p + statrcv[k][0]
                peer_b = statrcv[k][1]
                pltpu.semaphore_signal(
                    csems.at[2 * k], inc=1, device_id=pdev,
                    device_id_type=did_type,
                )
                myb = counts[C_TAIL] - counts[C_HEAD]
                quota = jnp.clip((myb - peer_b + 1) // 2, 0, W)
                # Zero quota (balanced or deficit side - the steady state)
                # skips the whole export scan/compact pass.
                sendbuf[W, 0] = 0

                @pl.when(quota > 0)
                def _():
                    sendbuf[W, 0] = export(quota)

                @pl.when(r > 0)
                def _(k=k):
                    pltpu.semaphore_wait(csems.at[2 * k + 1], 1)

                remote_copy(
                    sendbuf, inboxes[k], pdev, ssems.at[1],
                    rsems.at[2 * k + 1],
                )
                import_rows(inboxes[k])
                pltpu.semaphore_signal(
                    csems.at[2 * k + 1], inc=1, device_id=pdev,
                    device_id_type=did_type,
                )
            return r + 1, tot_p == 0

        r, done = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False))
        )
        counts[C_ROUNDS] = r
        # Every executed round ran every hop and its first send never
        # waited, so each of the 2*nh credit channels holds exactly one
        # unconsumed credit once any round ran.
        for k in range(2 * self._nh):

            @pl.when(r >= 1)
            def _(k=k):
                pltpu.semaphore_wait(csems.at[k], 1)

    @property
    def _nh(self) -> int:
        return max(1, (self.ndev - 1).bit_length())

    # -- host entry --

    def _build(self, quantum: int, max_rounds: int):
        mk = self.mk
        ndata = len(mk.data_specs)
        nbatch = 1 if mk.batch_specs else 0
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        anyspace = functools.partial(pl.BlockSpec, memory_space=pl.ANY)
        ntrace = 1 if mk.trace is not None else 0
        # Trailing abort-word input (HBM: the kernel re-reads it per round).
        in_specs = [smem()] * 5 + [anyspace()] * ndata + [anyspace()]
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
        from .megakernel import VBLOCK

        W = self.window
        base_scratch = list(mk.scratch_specs.values()) + [
            pltpu.SMEM((mk.capacity + 1,), jnp.int32),  # free
            pltpu.SMEM((mk.num_values // VBLOCK + 1,), jnp.int32),
            pltpu.SMEM((self.scan,), jnp.int32),  # candbuf
            pltpu.SMEM((W + 1, DESC_WORDS), jnp.int32),  # sendbuf
        ]
        if self._pof2:
            nh = self._nh
            body = self._kernel_hc
            scratch = base_scratch + (
                [pltpu.SMEM((4,), jnp.int32)]  # statsnd
                + [pltpu.SMEM((4,), jnp.int32) for _ in range(nh)]
                + [
                    pltpu.SMEM((W + 1, DESC_WORDS), jnp.int32)
                    for _ in range(nh)
                ]  # per-hop inboxes (fixed writer each -> own channel)
                + [
                    pltpu.SemaphoreType.DMA((2,)),  # send sems (stat, rows)
                    pltpu.SemaphoreType.DMA((2 * nh,)),  # per-hop recv sems
                    pltpu.SemaphoreType.REGULAR((2 * nh,)),
                ]
            )
        else:
            body = self._kernel
            scratch = base_scratch + [
                pltpu.SMEM((W + 1, DESC_WORDS), jnp.int32),  # inbox
                pltpu.SMEM((4,), jnp.int32),  # statsnd (+ abort word)
                pltpu.SMEM((4,), jnp.int32),  # statrcv
                pltpu.SMEM((8,), jnp.int32),  # abuf (abort staging)
                pltpu.SemaphoreType.DMA((4,)),
                pltpu.SemaphoreType.REGULAR((2,)),
                pltpu.SemaphoreType.DMA((1,)),  # asem
            ]
        if mk.batch_specs:
            # Batched dispatch tier lane scratch (both bodies unpack it
            # last): re-entrant across sched() entries via the spill
            # discipline, so the steal exchange never sees a lane entry.
            nb = mk.lane_scratch_rows  # kinds x priority buckets
            from .megakernel import LS_WORDS

            scratch += [
                pltpu.SMEM((nb, mk.capacity), jnp.int32),  # lanes
                pltpu.SMEM((nb, LS_WORDS), jnp.int32),  # lstate
            ]
        kern = pl.pallas_call(
            functools.partial(body, quantum, max_rounds, mk.trace),
            out_shape=out_shape,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
            input_output_aliases=aliases,
            interpret=interpret_mode() if mk.interpret else False,
        )

        def step(tasks, succ, ring, counts, iv, *rest):
            data = rest[:ndata]
            abort = rest[ndata]
            outs = kern(
                tasks[0], succ[0], ring[0], counts[0], iv[0],
                *[d[0] for d in data], abort[0]
            )
            tasks_o, ready_o, counts_o, iv_o = outs[:4]
            data_o = outs[4 : 4 + ndata]
            extra_o = outs[4 + ndata :]  # [tstats?, trace?]
            gcounts = jax.lax.psum(counts_o, self.axes)
            return (
                counts_o[None],
                iv_o[None],
                gcounts[None],
                *[d[None] for d in data_o],
                *[t[None] for t in extra_o],
            )

        nin = 6 + ndata
        f = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(self.axes),) * nin,
            out_specs=(P(self.axes),) * (3 + ndata + nbatch + ntrace),
            check_vma=False,
        )
        return jax.jit(f)

    def run(
        self,
        builders: Sequence[TaskGraphBuilder],
        data: Optional[Dict[str, np.ndarray]] = None,
        ivalues: Optional[np.ndarray] = None,
        quantum: int = 64,
        max_rounds: int = 1 << 14,
        abort=None,
    ):
        """Execute all partitions fully on-device; returns
        (ivalues[ndev, V], data, info). ``abort``: host abort word (truthy
        or per-device flags) - the round loops observe it within one round
        and the mesh exits in lockstep with ``info['aborted']`` instead of
        running the workload out."""
        from .sharded import execute_partitions

        if self._resident is not None:
            iv_o, data_o, info = self._resident.run(
                builders, data=data, ivalues=ivalues, quantum=quantum,
                max_rounds=max_rounds, abort=abort,
            )
            info["steal_rounds"] = info.pop("rounds")
            return iv_o, data_o, info
        key = (quantum, max_rounds)
        first_build = key not in self._jitted
        if first_build:
            from ..runtime.progcache import mesh_key, shared_build

            variant = (
                "ici", mesh_key(self.mesh),
                tuple(sorted(self.migratable_fns)), self.window,
                self.scan,
            ) + key
            self._jitted[key], self._pc_stats = shared_build(
                self.mk, variant,
                lambda: self._build(quantum, max_rounds),
            )
        from .sharded import abort_words

        abort_arr = abort_words(abort, self.ndev)
        t0_ns = time.monotonic_ns()
        iv_o, data_o, info = execute_partitions(
            self.mk, self.mesh, self.ndev, self._jitted[key], builders,
            data, ivalues, with_rounds=True, extra_inputs=[abort_arr],
        )
        t1_ns = time.monotonic_ns()
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
        tail = info.pop("extra_outputs", None)
        if self.mk.trace is not None and tail:
            info["trace"] = trace_info(
                [tail[-1][d] for d in range(self.ndev)], t0_ns, t1_ns,
                self.mk.trace.capacity,
            )
        if self.mk.batch_specs and tail:
            # Per-device batched-tier counters (tstats rides before the
            # trace ring in the appended outputs).
            trows = tail[0]
            info["tiers"] = [
                self.mk.decode_tier_stats(trows[d])
                for d in range(self.ndev)
            ]
        info["aborted"] = bool(abort_arr[:, 0].any()) and info["pending"] != 0
        if info["overflow"]:
            raise RuntimeError("ici steal: task-table overflow")
        if info["pending"] != 0 and not info["aborted"]:
            from ..runtime.resilience import StallError

            raise StallError(
                f"ici steal stalled: {info['pending']} pending after "
                f"{info['executed']} executed ({info['steal_rounds']} rounds)",
                stats=info,
            )
        return iv_o, data_o, info
