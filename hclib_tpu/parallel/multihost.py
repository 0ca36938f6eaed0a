"""Multi-host (DCN) support: jax.distributed lifecycle + global mesh.

The reference reaches other nodes through MPI/OpenSHMEM launchers
(modules/mpi, modules/openshmem: NIC locale + comm worker). The TPU-native
equivalent is JAX's multi-controller runtime: every host runs the same
program, ``jax.distributed.initialize`` wires the controllers over DCN, and
a global ``Mesh`` over ``jax.devices()`` (all hosts' devices) lets the same
``shard_map``/collective code that rides ICI within a slice span hosts -
XLA routes collective edges over ICI inside a slice and DCN between slices.

On a single host everything degrades gracefully: ``init_multihost`` is a
no-op (process 0 of 1), ``global_mesh`` is a mesh over local devices, so
the same program runs unmodified from laptop CPU to multi-host pod - which
is also how this module is tested without a cluster (the reference's
multi-node paths are untestable without one, SURVEY §4).

Typical use (same script on every host, launched by the cluster runtime):

    from hclib_tpu.parallel import multihost as mh
    mh.init_multihost()                  # no-op single-host
    mesh = mh.global_mesh("dp")          # all devices, every host
    ... shard_map / ShardedMegakernel over `mesh` ...
    mh.shutdown()
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from .mesh import make_mesh

__all__ = [
    "init_multihost",
    "shutdown",
    "process_index",
    "process_count",
    "is_multihost",
    "global_mesh",
    "local_devices",
    "sync_global",
    "bulk_allreduce",
]

_initialized = False
_owns_init = False



def is_multiprocess_capability_error(e: BaseException) -> bool:
    """True for errors a backend raises LOCALLY, at dispatch, because it
    cannot run multiprocess device computations at all (CPU pre-gloo
    jaxlib). Deterministic on every rank - the one failure class a
    committed collective may jointly degrade from. Matched by the two
    SPECIFIC messages of that class (the raw XLA dispatch error and this
    package's structured wrapper), never by a bare status prefix: an
    unrelated rank-local UNIMPLEMENTED must stay fatal, or one rank would
    solo-fallback while its peers sit in the device collective."""
    msg = str(e)
    return (
        "Multiprocess computations aren't implemented" in msg
        or "bulk device collectives are unavailable" in msg
    )

def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Wire this controller into the multi-host runtime.

    With explicit arguments, initializes directly. With none, initializes
    (letting JAX's cluster plugins fill in the details) only when a known
    multi-process launcher environment is detected — coordinator-address env
    vars, a multi-task srun/mpirun step, or a multi-worker Cloud TPU pod
    slice. Plain single-process runs skip initialization entirely.
    Idempotent, including when jax.distributed was already initialized by an
    outer launcher or sibling framework (adopted, not re-initialized; such an
    adopted runtime is left for its owner to shut down)."""
    global _initialized, _owns_init
    if _initialized:
        return
    import jax

    if jax.distributed.is_initialized():
        _initialized = True  # wired by someone else: adopt
        return
    explicit = any(
        a is not None for a in (coordinator_address, num_processes, process_id)
    )
    auto_env = _cluster_env_present()
    if explicit or auto_env:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        _owns_init = True


def _cluster_env_present() -> bool:
    import os

    env = os.environ
    if any(
        env.get(k)
        for k in (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "MEGASCALE_COORDINATOR_ADDRESS",
        )
    ):
        return True
    # Multi-task srun/mpirun steps (JAX ships cluster plugins for both).
    # Deliberately NOT SLURM_NTASKS: that leaks into plain `python` runs
    # inside an sbatch allocation, where auto-init would hang waiting for
    # peers; these step-scoped vars are only set by the actual launcher.
    for k in ("SLURM_STEP_NUM_TASKS", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"):
        try:
            if int(env.get(k, "1")) > 1:
                return True
        except ValueError:
            pass
    # Cloud TPU pod slice: worker hostnames list has more than one entry.
    hostnames = env.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1


def shutdown() -> None:
    """Tear down the distributed runtime if this module started it; an
    adopted external runtime is left for its owner."""
    global _initialized, _owns_init
    if _owns_init:
        import jax

        jax.distributed.shutdown()
        _owns_init = False
    _initialized = False


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_multihost() -> bool:
    return process_count() > 1


def local_devices():
    import jax

    return jax.local_devices()


def global_mesh(
    *axis_names: str,
    axis_shape: Optional[Sequence[int]] = None,
    devices=None,
):
    """Mesh over ALL hosts' devices (jax.devices() is global under the
    multi-controller runtime). 1 axis name -> 1D mesh over every device;
    more names need an explicit ``axis_shape``. ``devices`` overrides the
    device set (e.g. jax.devices("cpu") for virtual-mesh tests)."""
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    if axis_shape is None:
        if len(axis_names) != 1:
            raise ValueError("multi-axis mesh needs axis_shape")
        axis_shape = (len(devs),)
    if int(np.prod(axis_shape)) != len(devs):
        raise ValueError(
            f"axis_shape {tuple(axis_shape)} != {len(devs)} devices"
        )
    return make_mesh(tuple(axis_shape), axis_names, devs)


def sync_global(tag: int = 0) -> None:
    """Cross-host barrier (the reference's analogue is MPI_Barrier through
    the NIC locale, modules/mpi/src/hclib_mpi.cpp:220-286).

    Multi-host: delegates to ``multihost_utils.sync_global_devices`` — the
    coordination-service barrier that works with non-addressable devices.
    Single-host: a tiny psum over every local device, exercising the same
    collective path the sharded scheduler uses."""
    if is_multihost():
        from jax.experimental import multihost_utils

        try:
            multihost_utils.sync_global_devices(f"hclib_tpu_sync_{tag}")
        except Exception as e:
            if not is_multiprocess_capability_error(e):
                raise
            # The backend cannot run multiprocess device computations at
            # all (CPU pre-gloo jaxlib): every rank fails this dispatch
            # locally and identically, so all jointly degrade to the
            # coordination-service barrier - the same rendezvous with no
            # device computation in it.
            from jax._src import distributed

            distributed.global_state.client.wait_at_barrier(
                f"hclib_tpu_sync_{tag}", 120_000
            )
        return
    import jax

    devs = tuple(jax.devices())
    out = _local_barrier(devs)(np.full((len(devs),), tag, np.int32))
    np.asarray(out)  # materialize = every participant arrived


def bulk_allreduce(arr: np.ndarray, op: str = "sum") -> np.ndarray:
    """All-process reduction of a per-process host array over the global
    device runtime (the bulk-data path of ProcWorld.allreduce: arrays above
    the control-plane threshold ride XLA's cross-host collectives instead
    of the coordination-service KV store - the reference's AM-packet vs
    bulk-MPI-datatype split, modules/mpi/src/hclib_mpi.cpp:220-286).

    One representative device per process forms a 1-axis mesh; each process
    contributes its array as one shard of a global (nproc, ...) array, and
    a jitted reduce-to-replicated makes XLA emit an actual all-reduce over
    ICI/DCN - O(nbytes) per host, not O(nproc * nbytes) like an allgather
    + host reduce would be."""
    import jax

    arr = np.asarray(arr)
    nproc = jax.process_count()
    if nproc == 1:
        return arr.copy()
    reps = {}
    for d in jax.devices():
        if d.process_index not in reps or d.id < reps[d.process_index].id:
            reps[d.process_index] = d
    if len(reps) != nproc:
        raise RuntimeError(
            f"only {len(reps)}/{nproc} processes contribute devices"
        )
    devs = tuple(reps[p] for p in sorted(reps))
    jitted, sharding = _bulk_reducer(devs, op)
    local = jax.device_put(arr[None], reps[jax.process_index()])
    garr = jax.make_array_from_single_device_arrays(
        (nproc,) + arr.shape, sharding, [local]
    )
    try:
        out = jitted(garr)
    except Exception as e:
        if not is_multiprocess_capability_error(e):
            raise
        # Structured degradation signal: ProcWorld.allreduce recognizes
        # the UNIMPLEMENTED status and jointly falls back to its KV path;
        # direct callers get an error naming the missing capability
        # instead of a dispatch-internal message.
        raise RuntimeError(
            "UNIMPLEMENTED: bulk device collectives are unavailable on "
            f"this backend/jaxlib ({e})"
        ) from e
    return np.asarray(out.addressable_data(0))


@functools.lru_cache(maxsize=32)
def _bulk_reducer(devs, op: str):
    """Jitted reduce-to-replicated, cached per (device set, op) - a fresh
    jit wrapper per call would retrace and recompile every bulk allreduce
    (shape/dtype variations hit jit's own signature cache)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devs), ("p",))
    red = {
        "sum": lambda x: x.sum(0),
        "max": lambda x: x.max(0),
        "min": lambda x: x.min(0),
    }[op]
    jitted = jax.jit(red, out_shardings=NamedSharding(mesh, P()))
    return jitted, NamedSharding(mesh, P("p"))


@functools.lru_cache(maxsize=8)
def _local_barrier(devs):
    """Compiled psum barrier, cached per device set (a fresh jit per call
    would retrace the psum on every barrier)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = global_mesh("all", devices=devs)

    def f(v):
        return jax.lax.psum(v, "all")

    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=P("all"), out_specs=P(), check_vma=False
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
