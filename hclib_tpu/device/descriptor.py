"""Task-descriptor ABI and host-side task-graph builder.

A task is a fixed row of 16 int32 words - the device replacement for the
reference's heap task struct + promise waiter lists (inc/hclib-task.h:32-44,
inc/hclib-promise.h:76-90). Dependencies are inverted relative to the
reference: instead of tasks registering on promises, each task carries a
*dependency counter* and every task lists its *successors*; completing a task
decrements each successor's counter and pushes those that reach zero onto the
ready ring. (The reference's one-at-a-time registration walk exists to avoid
locks on the waiter list; on-device, the scheduler loop is single-threaded
per core, so plain counters are the natural design.)

Word layout (all int32):

    0  F_FN       kernel-table index (what to run)
    1  F_DEP      remaining unsatisfied dependencies (runnable at 0)
    2  F_SUCC0    inline successor task index, or NO_TASK. Released
                  inline by every retiring row: a row whose ONLY link is
                  this one (a fork-join child, an injected request with one
                  completion future) is the cheap shape
    3  F_SUCC1    inline successor task index, or NO_TASK. A row that
                  holds one, or a CSR list, pays one branch more when it
                  retires (``retire()``'s slow region, counted in
                  ``info['walked']``); either slot may be filled alone
    4  F_CSR_OFF  offset into the successor-CSR array (extra successors)
    5  F_CSR_N    number of CSR successors (released after the two inline
                  ones, in list order)
    6..11 F_A0+i  six argument words (meaning defined by the kernel)
    12 F_OUT      output value slot (index into the int32 value buffer)
    13 F_HOME     home device (flat mesh index) of a migrated task, or -1.
                  A row with F_HOME >= 0 is a *traveling copy*: a proxy row
                  F_HROW still exists on device F_HOME holding the real
                  successor links, and completing this copy forwards its
                  out-slot value home via a remote-completion active message
                  (device/resident.py) - the TPU re-design of the reference
                  thief taking dependency-bearing tasks out of a victim's
                  deque (src/hclib-deque.c:75-106), where shared memory made
                  links location-transparent.
    14 F_HROW     proxy row index on device F_HOME (valid iff F_HOME >= 0)
    15 F_VMASK    bitmask of arg words carrying *dereferenced values* (a
                  migrated task's value-slot args are resolved at export and
                  rehydrated into local slots at install)

Static DAGs (Cholesky, Smith-Waterman) are built host-side with
``TaskGraphBuilder``; dynamic tasks (fib, UTS) are allocated on-device by
kernels via ``KernelContext.spawn``.

A *re-armed row* (``KernelContext.become``) is a fork-join task that turned
into its own continuation where it lies: its handler rewrote F_FN (the
continuation's kind) and F_DEP (the children it waits for, above zero) and
nothing else, so F_SUCC0/1, the CSR pair, F_OUT, the args it did not set
again, its row-owned value block and a traveling copy's F_HOME/F_HROW all
pass to the continuation without a word moved. From the end of that
dispatch it is an ordinary pending row: off the ring until its children
count F_DEP down, exported by a checkpoint cut and kept home by the steal
filters like any dependent row, retired (hook, successors, tombstone) when
the continuation completes.

Injection-ring row extension (multi-tenant ingress, device/tenants.py):
ring rows are padded to 256 words (``RING_ROW``, device/inject.py) so any
row offset DMA-aligns, and the pad words directly above the descriptor
ABI carry *transport metadata* the scheduler never copies
(``install_descriptor`` reads exactly ``DESC_WORDS`` words):

    16 TEN_ID      tenant lane index of an injected row (0 = default lane)
    17 TEN_EXPIRED nonzero = the row's admission deadline passed while it
                   sat on the ring; the in-kernel tenant poll drops it
                   (counted, a ``TenantExpired`` record) instead of
                   installing it
    18 TEN_DEADLINE_MS  the row's REMAINING admission-deadline budget in
                   milliseconds (0 = no deadline), stamped by the host at
                   checkpoint export and re-armed against the resuming
                   clock - deadlines survive a cut as remaining budget,
                   never as stale wall-clock instants. Host-only: the
                   device poll never reads it.
    19 TEN_TOKEN   submit token of a tracked request (0 = fire-and-forget
                   row, no completion published). Stamped at admission by
                   the tenant front door when egress is enabled
                   (device/egress.py): the egress-enabled inject poll
                   records it per installed row and the retirement-time
                   mailbox publish carries it back to the host, where it
                   keys the ``Future`` ledger (``FutureTable``).
    20 TEN_ADMIT_ROUND  admit-round stamp of the request, in the stream's
                   cumulative scheduler-round timebase (device/telemetry
                   .py): the host pump stamps the round gauge it last saw
                   echoed (``TenantTable.set_admit_round``), the
                   telemetry-enabled install path copies it into the
                   per-row stamp table, and retirement folds
                   ``retire - admit`` into the on-device latency
                   histogram. 0 = unstamped (telemetry off, or the
                   stream's first entry). A nonzero stamp is PRESERVED by
                   the pump on re-publication, so residue re-published
                   after a checkpoint cut keeps its original admission
                   round (the round gauge itself rides the echoed
                   telemetry block across the cut).

Because the words ride the row itself, tenant identity - a residue
row's remaining deadline budget, and its submit token - survives every
path a row can travel: checkpoint residue export, ``reshard``'s
round-robin re-deal, and resume re-publication (which is what lets
futures re-attach across a cut via their resume tokens).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

__all__ = [
    "DESC_WORDS",
    "NO_TASK",
    "F_FN",
    "F_DEP",
    "F_SUCC0",
    "F_SUCC1",
    "F_CSR_OFF",
    "F_CSR_N",
    "F_A0",
    "F_OUT",
    "F_HOME",
    "F_HROW",
    "F_VMASK",
    "RING_ROW",
    "TEN_ID",
    "TEN_EXPIRED",
    "TEN_DEADLINE_MS",
    "TEN_TOKEN",
    "TEN_ADMIT_ROUND",
    "TaskGraphBuilder",
    "ring_len",
    "ring_slot",
    "ring_window",
    "relay_ring",
]

DESC_WORDS = 16
NO_TASK = -1

F_FN = 0
F_DEP = 1
F_SUCC0 = 2
F_SUCC1 = 3
F_CSR_OFF = 4
F_CSR_N = 5
F_A0 = 6  # args occupy words 6..11
F_OUT = 12
F_HOME = 13
F_HROW = 14
F_VMASK = 15
NUM_ARGS = 6

# Injection-ring row width: descriptors padded to 1024 B so any row
# offset is a legal dynamic DMA offset (Mosaic wants coarse alignment).
# Canonical home of the constant device/inject.py and device/resident.py
# share (both re-export it for their callers).
RING_ROW = 256

# Ring-row transport metadata (words beyond DESC_WORDS; see module
# docstring). Valid only on RING_ROW-padded injection rows - task-table
# rows are DESC_WORDS wide and never carry them.
TEN_ID = 16
TEN_EXPIRED = 17
TEN_DEADLINE_MS = 18
TEN_TOKEN = 19
TEN_ADMIT_ROUND = 20


def ring_len(capacity: int) -> int:
    """Words of the ready ring (and of each batch lane's ring) of a table
    of ``capacity`` rows: the next power of two, so that a ring index is
    a mask (``ring_slot``) where ``% capacity`` is a divide on the scalar
    core (``sdivrem``, two pops and the sign fix-ups, five times a task:
    PR 41's listing). The TABLE keeps ``capacity`` rows, and a ring never
    holds more than ``capacity`` live entries (each is a table row), so
    no overflow rule reads this length."""
    return 1 << (int(capacity) - 1).bit_length()


def ring_slot(x, ring: int):
    """Where the all-time counter ``x`` (C_HEAD, C_TAIL, a lane's LS_*; a
    traced int32, a numpy array or a Python int) lies in a ring of
    ``ring`` words: ``x`` modulo ``ring``, as a mask. It is the FLOOR
    modulus for a negative ``x`` too (two's complement), which lane
    spills need: they walk C_HEAD below zero."""
    if ring <= 0 or ring & (ring - 1):
        raise ValueError(f"a ring is a power of two long, not {ring}")
    return x & (ring - 1)


def ring_window(ready, head: int, tail: int) -> np.ndarray:
    """The live entries ``[head, tail)`` of one ready ring on the host,
    the steal side (head) first: the one place that reads a ring's window
    out of a state dict. The ring's own length is its modulus, so it
    reads a ring of ``ring_len(capacity)`` words and one of ``capacity``
    (a snapshot written before PR 45) alike."""
    ready = np.asarray(ready)
    return ready[np.arange(int(head), int(tail)) % ready.shape[-1]]


def relay_ring(ready, counts, ring: int) -> np.ndarray:
    """``ready`` re-laid into rings of ``ring`` words: the live window
    ``[C_HEAD, C_TAIL)`` of each ring (one, or one a device on a leading
    axis, with ``counts`` stacked alike) lands where ``ring_slot`` will
    look for it, every other word is NO_TASK. A ring that is ``ring``
    long already is returned as it is; what arrives shorter is a snapshot
    written when the ring was ``capacity`` long."""
    ready = np.asarray(ready)
    if ready.shape[-1] == ring:
        return ready
    flat = ready.reshape(-1, ready.shape[-1])
    heads_tails = np.asarray(counts).reshape(len(flat), -1)[:, :2]
    out = np.full((len(flat), ring), NO_TASK, np.int32)
    for row, old, (head, tail) in zip(out, flat, heads_tails):
        live = ring_window(old, head, tail)
        if len(live) > ring:
            raise ValueError(
                f"a ready ring of {ring} words cannot hold the "
                f"{len(live)} live entries of the state's "
                f"[{int(head)}, {int(tail)})"
            )
        row[ring_slot(np.arange(int(head), int(tail)), ring)] = live
    return out.reshape(ready.shape[:-1] + (ring,))


class TaskGraphBuilder:
    """Builds the host-side arrays for a static task DAG.

    ``add(fn, args, deps=[...])`` returns the new task's index; ``deps`` are
    indices of tasks that must complete first (the builder fills dep counters
    and successor lists - inline first, CSR overflow after).
    """

    def __init__(self) -> None:
        self._rows: List[List[int]] = []
        self._succs: List[List[int]] = []  # successor indices per task
        self._reserved_values = 0

    def add(
        self,
        fn: int,
        args: Sequence[int] = (),
        deps: Sequence[int] = (),
        out: int = 0,
    ) -> int:
        if len(args) > NUM_ARGS:
            raise ValueError(f"at most {NUM_ARGS} args per task, got {len(args)}")
        idx = len(self._rows)
        row = [0] * DESC_WORDS
        row[F_FN] = int(fn)
        row[F_DEP] = len(deps)
        row[F_SUCC0] = NO_TASK
        row[F_SUCC1] = NO_TASK
        row[F_HOME] = NO_TASK  # local task (no migration home-link)
        for i, a in enumerate(args):
            row[F_A0 + i] = int(a)
        row[F_OUT] = int(out)
        self._rows.append(row)
        self._succs.append([])
        for d in deps:
            self._succs[d].append(idx)
        return idx

    @property
    def num_tasks(self) -> int:
        return len(self._rows)

    def reserve_values(self, n: int) -> None:
        """Declare slots [0, n) as host-owned: they are staged into the
        kernel (even if preset to zero) and the device allocator/row blocks
        start above them. Out slots already reserve themselves; use this for
        input-only or deliberately-zero slots."""
        self._reserved_values = max(self._reserved_values, int(n))

    def finalize(self, capacity: Optional[int] = None, succ_capacity: Optional[int] = None):
        """Returns (tasks, succ_csr, ready, counts0) numpy arrays sized to
        ``capacity`` tasks (extra rows are free slots for on-device spawns);
        the ready ring is ``ring_len(capacity)`` words.

        counts0 = [head, tail, alloc, pending, value_alloc, 0, 0, 0].
        """
        n = len(self._rows)
        capacity = capacity or max(64, n)
        if n > capacity:
            raise ValueError(f"{n} tasks exceed capacity {capacity}")
        tasks = np.zeros((capacity, DESC_WORDS), dtype=np.int32)
        csr: List[int] = []
        for idx, row in enumerate(self._rows):
            succ = self._succs[idx]
            r = list(row)
            if len(succ) > 0:
                r[F_SUCC0] = succ[0]
            if len(succ) > 1:
                r[F_SUCC1] = succ[1]
            extra = succ[2:]
            r[F_CSR_OFF] = len(csr)
            r[F_CSR_N] = len(extra)
            csr.extend(extra)
            tasks[idx] = r
        succ_capacity = succ_capacity or max(64, len(csr))
        if len(csr) > succ_capacity:
            raise ValueError("successor CSR overflow")
        succ_arr = np.full(succ_capacity, NO_TASK, dtype=np.int32)
        if csr:
            succ_arr[: len(csr)] = csr
        # Ready ring: initially-runnable tasks in index order.
        ready0 = [i for i, row in enumerate(self._rows) if row[F_DEP] == 0]
        ring = np.full(ring_len(capacity), NO_TASK, dtype=np.int32)
        ring[: len(ready0)] = ready0
        counts = np.zeros(8, dtype=np.int32)
        counts[0] = 0  # head
        counts[1] = len(ready0)  # tail
        counts[2] = n  # alloc cursor (next free descriptor row)
        counts[3] = n  # pending (tasks not yet executed)
        # Start on-device value allocation past every host-assigned out slot
        # (and any reserve_values declaration) so alloc_values/row blocks
        # never alias a host slot.
        counts[4] = max(
            1 + max((row[F_OUT] for row in self._rows), default=-1),
            self._reserved_values,
        )
        return tasks, succ_arr, ring, counts
