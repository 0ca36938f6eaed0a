"""Host-side runtime: the semantic core of hclib_tpu.

Pins the reference's finish/async/promise/forasync semantics on the host
before the TPU device path re-implements them on-chip (see ../device/).
"""

from . import progcache  # noqa: F401  registers the build ledger's listeners
from .deque import WSDeque
from .finish import Finish
from .forasync import FLAT, RECURSIVE, forasync, forasync_future, register_dist_func
from .locality import (
    Locale,
    LocalityGraph,
    MeshPlacement,
    generate_default_graph,
    load_locality_file,
    resolve_placement,
    steal_hop_order,
)
from .autoscaler import (
    Autoscaler,
    AutoscalerPolicy,
    Observation,
    ScaleEvent,
)
from .checkpoint import (
    BundleFault,
    BundleStore,
    CheckpointBundle,
    CheckpointError,
    checkpoint_on_preempt,
    default_store,
    restore_megakernel,
    restore_resident,
    restore_stream,
    snapshot_megakernel,
    snapshot_resident,
    snapshot_stream,
)
from .instrument import EventLog, load_dump, register_event_type
from .mem import allocate_at, async_copy, free_at, memset_at
from .metrics import MetricsRegistry
from .module import Module, register_module, unregister_all_modules
from .promise import Future, Promise, PromiseError
from .reducers import MaxReducer, OrReducer, Reducer, SumReducer
from .resilience import (
    CancelScope,
    CancelledError,
    DeviceFaultPlan,
    FaultPlan,
    InjectedFault,
    RetryPolicy,
    StallError,
)
from .scheduler import (
    Runtime,
    async_,
    async_future,
    current_finish,
    current_runtime,
    current_worker,
    end_finish,
    end_finish_nonblocking,
    finish,
    launch,
    num_workers,
    start_finish,
    run_on_main,
    yield_,
)
from .task import Task
from .timer import IDLE, OVH, SEARCH, WORK, StateTimer
