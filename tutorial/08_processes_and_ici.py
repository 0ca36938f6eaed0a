"""Lesson 8: cross-process ranks and in-kernel ICI work stealing.

The distributed story at two levels:

1. **ProcWorld** - ranks as real OS processes wired by jax.distributed:
   two-sided send/recv, allreduce/barrier, a symmetric heap with
   one-sided put/get served by a per-process progress thread, and named
   active-message handlers - all over the coordination service the
   multi-controller runtime already establishes. (The reference needs
   mpirun + MPI/OpenSHMEM for this surface.) This lesson SPAWNS two real
   processes and runs a put/get/allreduce exchange between them.

2. **In-kernel ICI steal** - per-device resident schedulers that
   exchange surplus task descriptors by remote DMA between their SMEM
   task tables, with semaphore credits for flow control and a ring
   allreduce as the termination collective - the whole multi-device run
   is one kernel launch per device, no host round-trips. Here it runs on
   a 2-device simulated mesh (Mosaic TPU interpret mode emulates the
   remote DMAs + semaphores); identical code compiles for a real slice.
"""

import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

# -- 1. two real processes exchanging through ProcWorld ------------------

WORKER = textwrap.dedent("""
    import sys
    import numpy as np
    import jax
    pid, port = int(sys.argv[1]), sys.argv[2]
    jax.distributed.initialize(f"localhost:{port}", num_processes=2, process_id=pid)
    sys.path.insert(0, %r)
    from hclib_tpu.modules.procworld import ProcWorld
    w = ProcWorld(timeout_s=30.0)
    w.alloc("cell", (2,), np.int32)
    for r in range(2):  # one-sided write of my slot into EVERY rank's cell
        w.put(r, "cell", np.array([10 + pid]), offset=pid)
    w.fence(1 - pid)
    w.barrier()
    total = w.allreduce(np.int32(w.heap("cell").sum()))
    assert int(total) == 2 * (10 + 0 + 10 + 1), total
    w.quiet(); w.barrier(); w.close()
    jax.distributed.shutdown()
    print(f"rank {pid} OK", flush=True)
""") % (REPO,)

with socket.socket() as s:
    s.bind(("localhost", 0))
    port = str(s.getsockname()[1])
# The ranks are CPU-only coordination processes (JAX_PLATFORMS=cpu below):
# a chip belongs to one process at a time, so two ranks that both reached
# for it would fail or hang. The engine also tolerates transient service
# errors (see tests/test_procworld_unit.py), but a demo should not rely on
# retries.
env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
env.pop("XLA_FLAGS", None)
procs = [
    subprocess.Popen([sys.executable, "-c", WORKER, str(pid), port], env=env)
    for pid in range(2)
]
for p in procs:
    assert p.wait(timeout=120) == 0
print("procworld: 2 processes exchanged put/get + allreduce")

# -- 2. in-kernel ICI steal on a simulated 2-device mesh -----------------

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.resident import ResidentKernel
from hclib_tpu.parallel.mesh import cpu_mesh

BUMP = 0


def bump(ctx):
    ctx.set_value(0, ctx.value(0) + ctx.arg(0))


mesh = cpu_mesh(2, axis_name="queues")
mk = Megakernel(kernels=[("bump", bump)], capacity=128, num_values=4,
                succ_capacity=8, interpret=True)
# Steal-only: successor-free BUMP rows migrate whole (homed=False), no PGAS.
smk = ResidentKernel(mk, mesh, steal=True, migratable_fns=[BUMP],
                     homed=False, window=8)
builders = [TaskGraphBuilder() for _ in range(2)]
for i in range(16):
    builders[0].add(BUMP, args=[i + 1])  # all work lands on device 0
iv, _, info = smk.run(builders, quantum=4)
assert int(iv[:, 0].sum()) == 16 * 17 // 2
per_dev = info["per_device_counts"][:, 5]
assert per_dev[1] > 0, "device 1 stole nothing"
print(f"ici steal: skewed load executed as {per_dev.tolist()} across devices "
      f"in {info['rounds']} resident rounds")

print("lesson 8 OK")
