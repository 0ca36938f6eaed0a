"""Cross-process ranks over the jax.distributed coordination service.

The reference's comm modules span real OS processes launched by mpirun
(modules/mpi/src/hclib_mpi.cpp:107-286 two-sided + collectives;
modules/openshmem symmetric heap put/get; modules/openshmem-am active
messages, hclib_openshmem-am.cpp:64-123). The in-process ``World``
(modules/world.py) gives rank semantics inside one controller; this module
is the *multi-controller* counterpart: every rank is a separate process
wired by ``jax.distributed.initialize``, and the transport is the JAX
coordination service (key-value store + named barriers) that the
multi-controller runtime already establishes over DCN.

Design mapping (reference -> here):

- MPI_Send/Recv            -> ordered KV messages (per (src, dst, tag)
                              sequence numbers; receiver deletes after take)
- MPI_Isend/Irecv          -> future-returning ops polled by the COMM-locale
                              pending-op poller (``ProcWorldModule``), the
                              reference's hclib_mpi.cpp:130-210 shape
- MPI_Allreduce/Barrier    -> recursive-doubling exchange through the KV
                              store (O(n log n) messages); coordination-
                              service named barriers
- SHMEM symmetric heap     -> same-named numpy arrays allocated collectively
                              in every process; put/get are *op records*
                              addressed to the owner
- SHMEM progress engine    -> a daemon progress thread per process polling
                              its op directory and applying puts / serving
                              gets / running AM handlers in arrival order -
                              the reference's NIC-locale poller
                              (modules/common/hclib-module-common.h:10-115)
                              as a thread instead of a pinned worker
- shmem_quiet / fence      -> a no-op op with a reply key: when the owner's
                              progress thread reaches it, every earlier op
                              from this rank has been applied (ops apply in
                              global sequence order)
- async_remote (AM)        -> op records naming a registered handler
                              (handlers must be registered in every process,
                              mirroring the reference's identical-binary
                              assumption)

Failure model: coordination-service RPCs are classified by gRPC status code
(the leading token of the error string - jaxlib exposes no code attribute).
NOT_FOUND means "key absent"; UNAVAILABLE/ABORTED/etc. are transient and the
progress engine retries them with backoff for up to ``timeout_s`` before
declaring the engine dead. A dying engine best-effort *poisons* the reply
key of every op still queued at this rank and publishes a tombstone, so
peers blocked on a reply fail fast with ``ProcWorldError`` instead of
running out their own timeouts (the reference simply aborts the job;
multi-controller JAX deserves a diagnosable failure).

The KV store is a control-plane transport: fine for task descriptors,
small tensors, and coordination; bulk tensors ride XLA collectives over a
global mesh (``allreduce`` dispatches to ``parallel/multihost.py`` above a
size threshold) - the same split the reference makes between AM packets and
bulk MPI datatypes.
"""

from __future__ import annotations

import io
import json
import struct
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

# A backend that cannot run multiprocess computations fails LOCALLY at
# dispatch, identically on every rank of a committed collective - the one
# failure class where a joint fallback is safe (see allreduce). Anything
# raised mid-collective stays fatal.
from ..parallel.multihost import (
    is_multiprocess_capability_error as _bulk_capability_error,
)
from ..runtime.module import Module

__all__ = ["ProcWorld", "ProcWorldError", "ProcWorldModule"]


def _pack(meta: dict, arr: Optional[np.ndarray]) -> bytes:
    """4-byte length + JSON metadata + optional .npy payload."""
    m = json.dumps(meta).encode()
    buf = io.BytesIO()
    if arr is not None:
        np.save(buf, arr, allow_pickle=False)
    return struct.pack("<I", len(m)) + m + buf.getvalue()


def _unpack(b: bytes) -> Tuple[dict, Optional[np.ndarray]]:
    (mlen,) = struct.unpack("<I", b[:4])
    meta = json.loads(b[4 : 4 + mlen].decode())
    rest = b[4 + mlen :]
    arr = np.load(io.BytesIO(rest), allow_pickle=False) if rest else None
    return meta, arr


class ProcWorldError(RuntimeError):
    """A peer's (or this rank's) progress engine died, or an op was
    poisoned during engine shutdown."""


# gRPC status names, as they lead JaxRuntimeError strings ("NOT_FOUND: ...").
_GRPC_STATUSES = {
    "OK", "CANCELLED", "UNKNOWN", "INVALID_ARGUMENT", "DEADLINE_EXCEEDED",
    "NOT_FOUND", "ALREADY_EXISTS", "PERMISSION_DENIED", "RESOURCE_EXHAUSTED",
    "FAILED_PRECONDITION", "ABORTED", "OUT_OF_RANGE", "UNIMPLEMENTED",
    "INTERNAL", "UNAVAILABLE", "DATA_LOSS", "UNAUTHENTICATED",
}
# Worth retrying: the service may be mid-(re)start, a stream may have been
# torn down, or the RPC raced a barrier epoch. Everything else is a
# programming error or a hard disconnect.
_TRANSIENT = {"UNAVAILABLE", "ABORTED", "CANCELLED", "UNKNOWN", "INTERNAL",
              "DEADLINE_EXCEEDED", "RESOURCE_EXHAUSTED"}


def _status(e: BaseException) -> str:
    """gRPC status code of a coordination-service error (by leading token,
    not substring - 'NOT_FOUND' can legitimately appear inside unrelated
    messages)."""
    head = str(e).split(":", 1)[0].strip()
    return head if head in _GRPC_STATUSES else "UNKNOWN"


class _ClientCompat:
    """Adapter for older ``DistributedRuntimeClient`` builds (jaxlib
    0.4.x) that lack ``key_value_try_get_bytes``: emulated with a
    non-blocking parent-directory listing (one RPC; a blocking-get
    emulation measured orders slower under progress-loop polling). Every
    other method proxies through unchanged. (The op queue itself needs no
    atomic increment on any build - per-source sequencing, see
    ``_post_op``.)

    Known limit: a directory listing transfers its VALUES, so probing a
    deep per-source op backlog re-downloads queued payloads - O(backlog)
    bytes per idle probe on these legacy builds. A hint-key protocol was
    tried and reverted: these clients' ``key_value_set`` is INSERT-only
    (ALREADY_EXISTS on overwrite), so no cheap mutable counter exists.
    The progress loop drains each source to its first miss, which keeps
    probes per APPLIED op at one; only sustained deep backlogs on 0.4.x
    pay the listing cost."""

    __slots__ = ("_c",)

    def __init__(self, c) -> None:
        self._c = c

    def __getattr__(self, name):
        return getattr(self._c, name)

    def key_value_try_get_bytes(self, key):
        parent = key.rsplit("/", 1)[0] + "/"
        for k, v in self._c.key_value_dir_get_bytes(parent):
            if k == key:
                return v
        raise RuntimeError(f"NOT_FOUND: {key} (dir-scan emulation)")


def _adapt_client(c):
    return c if hasattr(c, "key_value_try_get_bytes") else _ClientCompat(c)




class ProcWorld:
    """Rank-per-process communication world (requires an initialized
    jax.distributed runtime; see parallel/multihost.init_multihost).

    All collective entry points (``barrier``, ``allreduce``, ``alloc``)
    follow SPMD discipline: every process calls them in the same order.
    """

    #: payload bytes above which allreduce rides XLA collectives over the
    #: global device mesh instead of the KV control plane (see allreduce).
    BULK_THRESHOLD = 1 << 16

    def __init__(
        self,
        namespace: str = "hcpw",
        poll_interval_s: float = 0.002,
        timeout_s: float = 60.0,
        retry_s: Optional[float] = None,
        fault_plan=None,
        _client=None,
        _rank: Optional[int] = None,
        _size: Optional[int] = None,
    ) -> None:
        if _client is not None:
            # Test seam: a fake coordination client (threads as ranks) so
            # engine failure paths are unit-testable in one process - the
            # reference's comm modules have no such seam and are untestable
            # without a cluster (SURVEY §4 'do better').
            self._c = _client
            self.rank = int(_rank or 0)
            self.size = int(_size or 1)
            self._native_runtime = False
        else:
            import jax
            from jax._src import distributed

            if not jax.distributed.is_initialized():
                raise RuntimeError(
                    "ProcWorld needs jax.distributed initialized "
                    "(parallel.multihost.init_multihost)"
                )
            self._c = _adapt_client(distributed.global_state.client)
            self.rank = jax.process_index()
            self.size = jax.process_count()
            self._native_runtime = True
        self._ns = namespace
        self._timeout_ms = int(timeout_s * 1000)
        self._timeout_s = timeout_s
        self._retry_s = timeout_s if retry_s is None else retry_s
        self._poll_s = poll_interval_s
        # Guards the sequence/reply counters: AM handlers run on the
        # progress thread and receive this world, so send/get/fence may be
        # called concurrently with the application thread.
        self._seq_lock = threading.Lock()
        self._send_seq: Dict[Tuple[int, int], int] = {}
        self._recv_seq: Dict[Tuple[int, int], int] = {}
        self._barrier_n = 0
        self._ar_epoch = 0
        self._reply_n = 0
        self._heap: Dict[str, np.ndarray] = {}
        self._heap_lock = threading.Lock()
        self._handlers: Dict[str, Callable] = {}
        self._applied = 0  # total ops applied by the progress thread
        # Per-source op cursors: the op queue is sequenced per (src, dst)
        # stream (see _post_op), so the consumer tracks one dense cursor
        # per source and the producer needs no service-side increment.
        self._op_seq: Dict[int, int] = {}
        self._applied_src = [0] * self.size
        self._bulk_broken: Optional[str] = None  # see _bulk_usable
        # Chaos (runtime/resilience.FaultPlan): may kill this rank's
        # progress engine on cue, exercising tombstones + reply poisoning.
        self._fault_plan = fault_plan
        self._stop = threading.Event()
        self._dead: Optional[BaseException] = None
        self.last_allreduce_path: Optional[str] = None
        self._thread = threading.Thread(
            target=self._progress_loop, daemon=True,
            name=f"procworld-progress-{self.rank}",
        )
        self._thread.start()

    # ---- health ----

    @property
    def dead(self) -> Optional[BaseException]:
        """The error that killed this rank's progress engine, if any."""
        return self._dead

    def _check_alive(self) -> None:
        if self._dead is not None:
            raise ProcWorldError(
                f"rank {self.rank}: progress engine is dead"
            ) from self._dead

    def _tomb_key(self, rank: int) -> str:
        return f"{self._ns}/dead/{rank}"

    def _peer_dead(self, rank: int) -> Optional[str]:
        """Tombstone text if ``rank``'s progress engine died, else None
        (also None when the service is unreachable: the caller's own wait
        loop decides what a dead service means for it)."""
        try:
            b = self._c.key_value_try_get_bytes(self._tomb_key(rank))
        except Exception:
            return None
        return b.decode(errors="replace") if b is not None else None

    def _raise_if_peer_dead(self, rank: int, context: str = "") -> None:
        """The ONE tombstone protocol for every wait loop (recv/_await_key,
        barrier, allreduce, module futures): raise ProcWorldError when this
        rank's own engine died, or when ``rank`` published a tombstone -
        never leave a waiter to run out its full timeout against a peer
        that is already known dead."""
        self._check_alive()
        if rank == self.rank:
            return
        tomb = self._peer_dead(rank)
        if tomb is not None:
            raise ProcWorldError(
                f"rank {rank}'s progress engine died{context}: {tomb}"
            )

    # ---- reply-key plumbing ----

    def _new_reply_key(self) -> str:
        with self._seq_lock:
            self._reply_n += 1
            return f"{self._ns}/re/{self.rank}/{self._reply_n}"

    def _try_take(self, key: str):
        """Non-blocking take of any protocol key: (found, payload array);
        deletes the key on take. Transient service errors read as
        not-found (the caller's poll loop retries); a poisoned payload
        (deposited by a dying peer) raises ProcWorldError."""
        try:
            b = self._c.key_value_try_get_bytes(key)
        except Exception as e:
            st = _status(e)
            if st == "NOT_FOUND" or st in _TRANSIENT:
                return False, None
            raise
        if b is None:
            return False, None
        self._c.key_value_delete(key)
        meta, arr = _unpack(b)
        if "poisoned" in meta:
            raise ProcWorldError(
                f"op poisoned by dying peer: {meta['poisoned']}"
            )
        return True, arr

    # The module poller and the blocking waits share one take protocol.
    _try_reply = _try_take

    def _await_key(self, key: str, target: int) -> Optional[np.ndarray]:
        """Block for a protocol key, failing fast if the target rank's
        engine (or our own) published a tombstone instead of ever
        depositing it, or if a dying peer poisoned it."""
        deadline = time.monotonic() + self._timeout_s
        chunk_ms = min(2000, self._timeout_ms)
        while True:
            self._check_alive()
            try:
                # Try the key FIRST: a reply the peer deposited before
                # dying is valid (only unapplied ops get poisoned) and
                # must win over its tombstone. The tombstone is consulted
                # when a chunk comes back empty/transient, so a dead peer
                # still surfaces within one chunk.
                b = self._c.blocking_key_value_get_bytes(key, chunk_ms)
            except Exception as e:
                st = _status(e)
                if st not in _TRANSIENT:
                    raise
                try:
                    self._raise_if_peer_dead(
                        target, context=f"; op {key} will never complete"
                    )
                except ProcWorldError as pe:
                    raise pe from e
                if time.monotonic() >= deadline:
                    raise
                continue
            self._c.key_value_delete(key)
            meta, arr = _unpack(b)
            if "poisoned" in meta:
                raise ProcWorldError(
                    f"op poisoned by dying peer: {meta['poisoned']}"
                )
            return arr

    _await_reply = _await_key

    # ---- two-sided messaging (hclib_mpi.cpp:107-128) ----

    def _next_send_key(self, dst: int, tag: int) -> str:
        """Claim the next (dst, tag) sequence slot. Message order is
        defined by this claim (program order), not by deposit time - which
        lets isend defer the deposit to the COMM-locale poller."""
        with self._seq_lock:
            seq = self._send_seq.get((dst, tag), 0)
            self._send_seq[(dst, tag)] = seq + 1
        return f"{self._ns}/msg/{self.rank}/{dst}/{tag}/{seq}"

    def _deposit(self, key: str, arr: np.ndarray) -> None:
        self._c.key_value_set_bytes(key, _pack({}, arr))

    def send(self, dst: int, arr, tag: int = 0) -> None:
        """Ordered per (src, dst, tag); non-blocking (KV deposit)."""
        self._check_alive()
        self._deposit(self._next_send_key(dst, tag), np.asarray(arr))

    def _claim_recv(self, src: int, tag: int) -> Tuple[str, int]:
        with self._seq_lock:
            seq = self._recv_seq.get((src, tag), 0)
            self._recv_seq[(src, tag)] = seq + 1
        return f"{self._ns}/msg/{src}/{self.rank}/{tag}/{seq}", seq

    def _unclaim_recv(self, src: int, tag: int, seq: int) -> None:
        """Roll back a failed receive's sequence claim so a retry waits for
        the SAME message instead of permanently skewing the (src, tag)
        stream (only possible when no later claim happened meanwhile)."""
        with self._seq_lock:
            if self._recv_seq.get((src, tag)) == seq + 1:
                self._recv_seq[(src, tag)] = seq

    # Non-blocking in-order receive attempt shares the take protocol too.
    _try_take_msg = _try_take

    def recv(self, src: int, tag: int = 0) -> np.ndarray:
        """Blocks for the next in-order message from (src, tag); fails
        fast (ProcWorldError) if the sender's engine tombstones or the
        message was poisoned by a dying sender."""
        self._check_alive()
        key, seq = self._claim_recv(src, tag)
        try:
            return self._await_key(key, src)
        except ProcWorldError:
            raise  # poisoned (consumed) or peer dead: the claim stands
        except Exception:
            # Timeout/service error, message NOT consumed: roll back so a
            # retry waits for the SAME message instead of skewing the
            # (src, tag) stream by one forever.
            self._unclaim_recv(src, tag, seq)
            raise

    # ---- collectives (hclib_mpi.cpp:220-286) ----

    def barrier(self) -> None:
        self._check_alive()
        # Under _seq_lock: AM handlers may invoke world ops from the
        # progress thread, and a torn increment would desynchronize
        # barrier ids across ranks (a wedge, not an error).
        with self._seq_lock:
            self._barrier_n += 1
            bn = self._barrier_n
        try:
            self._c.wait_at_barrier(f"{self._ns}/b/{bn}", self._timeout_ms)
        except Exception as e:
            # A barrier has no single target: on failure, scan every peer
            # for a tombstone so the error NAMES the dead rank instead of
            # reading as an anonymous DEADLINE_EXCEEDED.
            for r in range(self.size):
                if r == self.rank:
                    continue
                try:
                    self._raise_if_peer_dead(r, context=f" (barrier {bn})")
                except ProcWorldError as pe:
                    raise pe from e
            raise

    _REDUCE_FNS = {
        "sum": lambda a, b: a + b,
        "max": np.maximum,
        "min": np.minimum,
        "prod": lambda a, b: a * b,
    }

    def allreduce(self, arr, op: str = "sum") -> np.ndarray:
        """Recursive-doubling allreduce through the KV store: log2(n)
        rounds of pairwise exchange, O(n log n) total messages (the round-2
        design read all n contributions on every rank - O(n^2) reads).

        Payloads larger than ``BULK_THRESHOLD`` bytes ride the global
        device mesh (XLA collectives over ICI/DCN, parallel/multihost.py)
        when one is active - the reference's split between control-plane
        AM packets and bulk MPI datatypes. The bulk-vs-KV choice is made
        *collectively* (a 1-byte KV vote each epoch): a rank whose local
        bulk probe fails must not silently fall back while its peers enter
        the device collective - that wedges the job and desynchronizes
        epochs forever."""
        self._check_alive()
        arr = np.asarray(arr)
        fn = self._REDUCE_FNS[op]
        with self._seq_lock:  # see barrier(): epoch ids must not tear
            self._ar_epoch += 1
            e = self._ar_epoch
        if self._native_runtime and arr.nbytes >= self.BULK_THRESHOLD:
            want = np.uint8(1 if self._bulk_usable(op) else 0)
            agreed = self._kv_allreduce(e, want, np.minimum,
                                        round_base=100)
            if int(agreed) == 1:
                # All ranks committed to the device collective; a failure
                # inside it is fatal (raise), never a silent solo fallback
                # - EXCEPT a deterministic local capability error: a
                # backend that cannot run multiprocess computations at all
                # (CPU pre-gloo jaxlib) rejects the dispatch on EVERY rank
                # before any cross-rank rendezvous, so a collective
                # fallback to the KV path is consistent, and later epochs
                # vote KV outright (_bulk_broken).
                from ..parallel.multihost import bulk_allreduce

                try:
                    out = bulk_allreduce(arr, op)
                except Exception as exc:
                    if not _bulk_capability_error(exc):
                        raise
                    self._bulk_broken = f"{type(exc).__name__}: {exc}"
                    self.last_allreduce_path = "kv-fallback"
                    return self._kv_allreduce(e, arr, fn, round_base=0)
                self.last_allreduce_path = "bulk"
                return out
        self.last_allreduce_path = "kv"
        return self._kv_allreduce(e, arr, fn, round_base=0)

    def _bulk_usable(self, op: str) -> bool:
        """Local probe: can this rank run the device-collective path?"""
        if op not in ("sum", "max", "min"):
            return False
        if self._bulk_broken is not None:
            return False  # backend proved incapable; degrade permanently
        try:
            import jax

            return jax.process_count() == self.size
        except Exception:
            return False

    def _kv_allreduce(self, e: int, arr, fn, round_base: int) -> np.ndarray:
        acc = arr
        # Non-power-of-two: fold extras into the power-of-two core first
        # (the classic recursive-doubling pre/post step).
        n = self.size
        pof2 = 1
        while pof2 * 2 <= n:
            pof2 *= 2
        rem = n - pof2
        me = self.rank
        in_core = True
        if me < 2 * rem:
            if me % 2 == 1:  # odd extras send to even partner, then idle
                self._ar_send(e, me - 1, round_base, acc)
                in_core = False
            else:
                acc = fn(acc, self._ar_recv(e, me + 1, round_base))
        if in_core:
            core = me // 2 if me < 2 * rem else me - rem
            mask, round_i = 1, round_base + 1
            while mask < pof2:
                peer_core = core ^ mask
                peer = peer_core * 2 if peer_core < rem else peer_core + rem
                self._ar_send(e, peer, round_i, acc)
                acc = fn(acc, self._ar_recv(e, peer, round_i))
                mask *= 2
                round_i += 1
            if me < 2 * rem:  # send final result back to the odd partner
                self._ar_send(e, me + 1, round_base + 99, acc)
        else:
            acc = self._ar_recv(e, me - 1, round_base + 99)
        return acc

    def _ar_send(self, epoch: int, dst: int, rnd: int, arr) -> None:
        key = f"{self._ns}/ar/{epoch}/{rnd}/{self.rank}/{dst}"
        self._c.key_value_set_bytes(key, _pack({}, np.asarray(arr)))

    def _ar_recv(self, epoch: int, src: int, rnd: int) -> np.ndarray:
        # Chunked wait with tombstone detection (_await_key): an allreduce
        # whose partner died surfaces as a prompt ProcWorldError naming the
        # dead rank, not a raw DEADLINE_EXCEEDED after the full timeout.
        key = f"{self._ns}/ar/{epoch}/{rnd}/{src}/{self.rank}"
        return self._await_key(key, src)

    # ---- symmetric heap + one-sided ops (modules/openshmem) ----

    def alloc(self, name: str, shape, dtype=np.int32) -> np.ndarray:
        """Collective: allocate the same-named array in every process (the
        symmetric-heap contract; SPMD call order required)."""
        with self._heap_lock:
            if name in self._heap:
                raise ValueError(f"heap array {name!r} exists")
            a = np.zeros(shape, dtype)
            self._heap[name] = a
        self.barrier()
        return a

    def heap(self, name: str) -> np.ndarray:
        return self._heap[name]

    def _post_op(self, dst: int, meta: dict, arr=None) -> None:
        self._check_alive()
        if dst == self.rank:
            self._apply(meta, arr)  # loopback: apply inline
            return
        # Per-source sequencing: each (src -> dst) op stream carries its
        # own dense local counter, so posting needs no atomic-increment
        # primitive (absent on older jaxlib clients). Per-source FIFO is
        # the guarantee that matters; the old global counter's
        # cross-source arbitration was race-decided anyway, and
        # fences/barriers provide real cross-rank ordering.
        with self._seq_lock:
            seq = self._op_seq.get(dst, 0)
            self._op_seq[dst] = seq + 1
        self._c.key_value_set_bytes(
            f"{self._ns}/op/{dst}/{self.rank}/{seq}", _pack(meta, arr)
        )

    def put(self, dst: int, name: str, arr, offset: int = 0) -> None:
        """One-sided write into rank ``dst``'s heap array (applied by its
        progress thread; order vs other ops from this rank preserved).
        Completion at the target is observable via fence()/barrier()."""
        self._post_op(
            dst, {"op": "put", "name": name, "off": int(offset)},
            np.asarray(arr),
        )

    def _post_get(self, src: int, name: str, offset: int,
                  size: Optional[int]) -> str:
        rk = self._new_reply_key()
        self._post_op(
            src,
            {"op": "get", "name": name, "off": int(offset),
             "size": -1 if size is None else int(size), "reply": rk},
        )
        return rk

    def get(self, src: int, name: str, offset: int = 0,
            size: Optional[int] = None) -> np.ndarray:
        """One-sided read of rank ``src``'s heap array (served by its
        progress thread; sequenced after this rank's earlier ops to src)."""
        if src == self.rank:
            with self._heap_lock:
                a = self._heap[name].reshape(-1)
                end = a.size if size is None else offset + size
                return a[offset:end].copy()
        return self._await_reply(self._post_get(src, name, offset, size), src)

    def _post_fence(self, dst: int) -> Optional[str]:
        if dst == self.rank:
            return None
        rk = self._new_reply_key()
        self._post_op(dst, {"op": "fence", "reply": rk})
        return rk

    def fence(self, dst: int) -> None:
        """Returns once every op this rank posted to ``dst`` has been
        applied (shmem_quiet for one target: a no-op op with a reply)."""
        rk = self._post_fence(dst)
        if rk is not None:
            self._await_reply(rk, dst)

    def quiet(self) -> None:
        """shmem_quiet: fence every target this rank has posted ops to."""
        for r in range(self.size):
            self.fence(r)

    # ---- active messages (hclib_openshmem-am.cpp:64-123) ----

    def register_handler(self, name: str, fn: Callable) -> None:
        """AM handlers are named (not function pointers): every process
        registers the same names - the portable form of the reference's
        identical-binary fn-pointer assumption."""
        self._handlers[name] = fn

    def am(self, dst: int, handler: str, arr=None, **kwargs) -> None:
        """Run the named handler on rank ``dst``'s progress thread with
        (world, payload_array, **kwargs)."""
        self._post_op(
            dst, {"op": "am", "h": handler, "kw": kwargs},
            None if arr is None else np.asarray(arr),
        )

    # ---- progress engine ----

    def _apply(self, meta: dict, arr) -> None:
        op = meta["op"]
        if op == "put":
            with self._heap_lock:
                a = self._heap[meta["name"]]
                flat = a.reshape(-1)
                v = arr.astype(a.dtype, copy=False).reshape(-1)
                flat[meta["off"] : meta["off"] + v.size] = v
        elif op == "get":
            with self._heap_lock:
                a = self._heap[meta["name"]].reshape(-1)
                off = meta["off"]
                end = a.size if meta["size"] < 0 else off + meta["size"]
                out = a[off:end].copy()
            self._c.key_value_set_bytes(meta["reply"], _pack({}, out))
        elif op == "fence":
            self._c.key_value_set_bytes(meta["reply"], _pack({}, None))
        elif op == "am":
            h = meta["h"]
            # A fast peer can post an AM before this rank reaches its
            # register_handler call (registration is local, not collective):
            # wait briefly for the name instead of dropping the op. Ordered
            # application makes this a short stall of the queue, not a skip.
            deadline = time.monotonic() + min(2.0, self._timeout_s)
            while (h not in self._handlers and not self._stop.is_set()
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            fn = self._handlers.get(h)
            if fn is None:
                raise ValueError(
                    f"AM handler {h!r} never registered; op dropped "
                    f"(register handlers before communicating)"
                )
            fn(self, arr, **meta.get("kw", {}))
        else:  # pragma: no cover
            raise ValueError(f"unknown op {op!r}")

    def _progress_loop(self) -> None:
        me = self.rank
        backoff = 0.005
        retry_deadline = None  # armed on the first consecutive transient
        fp = self._fault_plan
        while not self._stop.is_set():
            if fp is not None and fp.on_procworld_poll(me, self._applied):
                from ..runtime.resilience import InjectedFault

                self._die(InjectedFault(
                    f"chaos: rank {me} progress engine killed by FaultPlan"
                ))
                return
            progressed = False
            transient = False
            for src in range(self.size):
                # Drain this source to its first miss before moving on:
                # one probe per APPLIED op (a probe-per-source-per-op
                # sweep would multiply RPC cost by the world size).
                while not self._stop.is_set():
                    key = (
                        f"{self._ns}/op/{me}/{src}/"
                        f"{self._applied_src[src]}"
                    )
                    try:
                        b = self._c.key_value_try_get_bytes(key)
                    except Exception as e:
                        st = _status(e)
                        if st == "NOT_FOUND":
                            b = None
                        elif st in _TRANSIENT:
                            # The service may be mid-restart
                            # (multi-controller startup on some PJRT
                            # platforms churns the channel): back off and
                            # retry for up to retry_s before giving up.
                            now = time.monotonic()
                            if retry_deadline is None:
                                retry_deadline = now + self._retry_s
                            if now < retry_deadline:
                                self._stop.wait(backoff)
                                backoff = min(backoff * 2, 0.25)
                                transient = True
                                break
                            self._die(e)
                            return
                        else:
                            self._die(e)
                            return
                    retry_deadline = None
                    backoff = 0.005
                    if b is None:
                        break
                    meta, arr = _unpack(b)
                    self._c.key_value_delete(key)
                    self._applied_src[src] += 1
                    self._applied += 1
                    progressed = True
                    try:
                        self._apply(meta, arr)
                    except Exception:  # pragma: no cover - engine lives
                        import traceback

                        traceback.print_exc()
                if self._stop.is_set():
                    return
                if transient:
                    break
            if not progressed and not transient:
                time.sleep(self._poll_s)

    def _die(self, err: BaseException) -> None:
        """Fatal engine failure: publish a tombstone and poison the reply
        key of every op still queued here, so peers fail fast instead of
        running out their fence/get timeouts. All best-effort - the
        service itself may be the thing that died."""
        self._dead = err
        import traceback

        print(f"procworld rank {self.rank}: progress engine died "
              f"({_status(err)}):", flush=True)
        traceback.print_exception(type(err), err, err.__traceback__)
        try:
            self._c.key_value_set_bytes(
                self._tomb_key(self.rank),
                f"{_status(err)}: {err}".encode()[:512],
            )
        except Exception:
            pass
        poison = _pack({"poisoned": f"rank {self.rank}: {_status(err)}"},
                       None)
        for src in range(self.size):
            # Per-source queues are dense (set-only, posted in order), so
            # the first miss ends a source's scan; a producer racing its
            # next set loses only that op's poisoning - its caller still
            # fails fast on the tombstone.
            seq = self._applied_src[src]
            while True:
                try:
                    b = self._c.key_value_try_get_bytes(
                        f"{self._ns}/op/{self.rank}/{src}/{seq}"
                    )
                except Exception as e:
                    if _status(e) != "NOT_FOUND":
                        return  # service gone: nothing more we can do
                    b = None
                if b is None:
                    break
                seq += 1
                try:
                    meta, _ = _unpack(b)
                    if "reply" in meta:
                        self._c.key_value_set_bytes(meta["reply"], poison)
                except Exception:
                    return

    def close(self) -> None:
        """Stop the progress engine (pending remote ops stay queued in the
        coordination service; call quiet() first for a clean drain)."""
        self._stop.set()
        self._thread.join(timeout=5)


class ProcWorldModule(Module):
    """ProcWorld as a runtime module: ops are *tasks at the COMM locale
    returning futures*, completion-polled by the shared pending-op
    harness - the reference's comm-module integration pattern
    (modules/mpi/src/hclib_mpi.cpp:130-210 Isend/Irecv + MPI_Test polling;
    modules/common/hclib-module-common.h:10-115).

    ``isend``/``irecv``/``iget``/``ifence`` return hclib futures that
    ``async_await`` tasks can depend on; the poller runs at the COMM locale
    so any worker whose pop/steal path covers it services cross-process
    completion while the rest compute.
    """

    name = "procworld"

    def __init__(self, world: Optional[ProcWorld] = None, **world_kwargs):
        self._world = world
        self._owns_world = world is None
        self._world_kwargs = world_kwargs
        self.locale = None
        self.pending = None

    # -- Module lifecycle (runtime/module.py) --

    def pre_init(self, runtime) -> None:
        from .common import PendingList

        ici = runtime.graph.locales_of_type("ici")
        self.locale = ici[0] if ici else runtime.graph.central_locale()
        self.locale.mark_special("COMM")
        self.pending = PendingList(locale=self.locale)

    def post_init(self, runtime) -> None:
        if self._world is None:
            self._world = ProcWorld(**self._world_kwargs)

    def finalize(self, runtime) -> None:
        """Drain + close only a world this module created; an injected one
        stays open for its owner (the reference's module-finalize hooks
        likewise only tear down state the module initialized)."""
        if not self._owns_world or self._world is None:
            return
        if self._world.dead is None:
            try:
                self._world.quiet()
            except ProcWorldError:
                pass
        self._world.close()

    @property
    def world(self) -> ProcWorld:
        if self._world is None:
            raise RuntimeError("ProcWorldModule not post-initialized")
        return self._world

    # -- future-returning ops --

    def _pend(self, test):
        from ..runtime.promise import Promise
        from .common import PendingOp

        return self.pending.append(PendingOp(test, Promise()))

    def _guarded(self, test, target: int, on_fail=None):
        """Wrap a pending-op test with the same failure model the blocking
        API has: raise ProcWorldError (poisoning the future) on the op
        timeout, on a peer tombstone, or on local engine death - a module
        future must fail fast, not pend forever past a dead peer."""
        w = self.world
        deadline = time.monotonic() + w._timeout_s
        state = {"tomb_at": 0.0}

        def run(op):
            try:
                done, val = test(op)
            except ProcWorldError:
                raise  # op consumed/poisoned: rollback would double-take
            except Exception as e:
                if _status(e) in _TRANSIENT:
                    return False, None  # service blip: retry next sweep
                if on_fail is not None:
                    on_fail()
                raise
            if done:
                return True, val
            now = time.monotonic()
            err = None
            if now >= state["tomb_at"]:
                # Tombstone polls are KV RPCs: throttle to 2/s. Same
                # protocol as the blocking waits (_raise_if_peer_dead):
                # local engine death and peer tombstones both fail fast.
                state["tomb_at"] = now + 0.5
                try:
                    w._raise_if_peer_dead(
                        target, context="; op will never complete"
                    )
                except ProcWorldError as pe:
                    err = pe
            elif w.dead is not None:
                err = ProcWorldError(
                    f"rank {w.rank}: local progress engine died"
                )
            if err is None and now >= deadline:
                err = ProcWorldError(
                    f"op to rank {target} timed out after {w._timeout_s}s"
                )
            if err is not None:
                if on_fail is not None:
                    on_fail()
                raise err
            return False, None

        return run

    def isend(self, dst: int, arr, tag: int = 0):
        """Future completing when the message is committed to the KV store
        (local completion, like MPI_Isend's buffer-free guarantee). The
        sequence slot is claimed here (program order); the deposit itself
        runs on the COMM-locale poller, so the calling worker never blocks
        on the coordination-service RPC."""
        w = self.world
        w._check_alive()
        arr = np.asarray(arr)
        key = w._next_send_key(dst, tag)

        def test(op):
            # Transient failures are retried by _guarded, but the deposit
            # is not idempotent: if the first set committed server-side and
            # only the RPC response was lost, the retry sees
            # ALREADY_EXISTS. The slot is ours by construction (claimed
            # under _seq_lock above), so that means delivered - success.
            try:
                w._deposit(key, arr)
            except Exception as e:
                if _status(e) == "ALREADY_EXISTS":
                    return True, None
                raise
            return True, None

        def on_fail():
            # The sequence slot is claimed and later sends may hold higher
            # slots, so it can't be unclaimed - deposit a poison marker
            # instead, turning the peer's recv of this slot into a prompt
            # ProcWorldError rather than a stream wedged at seq k forever.
            try:
                w._c.key_value_set_bytes(
                    key, _pack({"poisoned": f"rank {w.rank} isend failed"},
                               None),
                )
            except Exception:
                pass

        return self._pend(self._guarded(test, dst, on_fail=on_fail))

    def irecv(self, src: int, tag: int = 0):
        """Future carrying the next in-order message from (src, tag); fails
        (poisoned future) on timeout or peer death, rolling back the
        sequence claim so a retry waits for the same message."""
        w = self.world
        key, seq = w._claim_recv(src, tag)

        def test(op):
            return w._try_take_msg(key)

        return self._pend(self._guarded(
            test, src, on_fail=lambda: w._unclaim_recv(src, tag, seq)
        ))

    def iput(self, dst: int, name: str, arr, offset: int = 0):
        """Future completing at local completion of the put. The op is
        posted eagerly (op-queue sequencing happens at post time, so a
        following ifence/fence is guaranteed to cover this put)."""
        w = self.world
        w.put(dst, name, arr, offset)

        def test(op):
            return True, None

        return self._pend(test)

    def iget(self, src: int, name: str, offset: int = 0,
             size: Optional[int] = None):
        """Future carrying the remote heap slice - the poller polls the
        reply key instead of blocking a worker on it."""
        w = self.world
        if src == w.rank:
            def test_local(op):
                return True, w.get(src, name, offset, size)

            return self._pend(test_local)
        rk = w._post_get(src, name, offset, size)

        def test(op):
            return w._try_reply(rk)

        return self._pend(self._guarded(test, src))

    def ifence(self, dst: int):
        """Future completing once every op this rank posted to ``dst`` has
        been applied."""
        w = self.world
        rk = w._post_fence(dst)
        if rk is None:
            def test_local(op):
                return True, None

            return self._pend(test_local)

        def test(op):
            return w._try_reply(rk)

        return self._pend(self._guarded(test, dst))

    def wait_all(self, *futures):
        """MPI_Waitall (hclib_mpi.cpp:143-149): wait each future."""
        return [f.wait() for f in futures]
