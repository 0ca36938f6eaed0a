"""Device execution: task descriptors + the persistent Pallas megakernel.

The reference's work-stealing loop (pthread workers polling Chase-Lev deques,
src/hclib-runtime.c:705-724) is re-imagined TPU-first: a single long-running
Pallas kernel per core whose scalar unit runs a resident scheduler loop over
an SMEM task table and ready ring, dispatching to a static kernel table
(``lax.switch`` - TPU has no function pointers) whose entries do scalar work
in SMEM or drive the MXU/VPU on HBM/VMEM tiles. Promise satisfaction is a
dep-counter decrement + ready-ring push instead of a waiter-list walk.
"""

from .descriptor import (
    DESC_WORDS,
    F_A0,
    F_CSR_N,
    F_CSR_OFF,
    F_DEP,
    F_FN,
    F_OUT,
    F_SUCC0,
    F_SUCC1,
    NO_TASK,
    TaskGraphBuilder,
)
from .forasync_tier import (
    Slab,
    TileKernel,
    make_forasync_megakernel,
    place_tiles,
    run_forasync_device,
    seed_tiles,
)
from .frontier import (
    Graph,
    GraphSearch,
    host_bfs,
    host_pagerank,
    host_sssp,
    make_frontier_megakernel,
    run_frontier,
)
from .megakernel import BatchContext, BatchSpec, KernelContext, Megakernel
from .resident import ResidentKernel
from .tenants import Admission, TenantSpec, TenantTable
from .tracebuf import TraceRing, decode_ring, trace_to_jsonable

__all__ = [
    "Admission",
    "Graph",
    "GraphSearch",
    "host_bfs",
    "host_pagerank",
    "host_sssp",
    "make_frontier_megakernel",
    "run_frontier",
    "Slab",
    "TileKernel",
    "make_forasync_megakernel",
    "place_tiles",
    "run_forasync_device",
    "seed_tiles",
    "TenantSpec",
    "TenantTable",
    "ResidentKernel",
    "TraceRing",
    "decode_ring",
    "trace_to_jsonable",
    "BatchContext",
    "BatchSpec",
    "DESC_WORDS",
    "NO_TASK",
    "TaskGraphBuilder",
    "KernelContext",
    "Megakernel",
    "F_FN",
    "F_DEP",
    "F_SUCC0",
    "F_SUCC1",
    "F_CSR_OFF",
    "F_CSR_N",
    "F_A0",
    "F_OUT",
]
