"""Lesson 6: throughput engines and multi-device scheduling.

Task-per-node scheduling has a per-task floor (~100 ns even on-device).
When the workload is regular enough, the TPU-first answer is to vectorize
the *algorithm* across VPU lanes instead: thousands of lanes each run an
independent traversal, balanced through a shared work queue - the
work-stealing idea recast as data-parallel claims. And for multi-device,
per-device megakernel queues exchange surplus tasks over the ICI ring
between bulk-synchronous rounds.

Uses a virtual 8-device CPU mesh (env set below); on real hardware the
same code runs over the chips of a slice.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

import jax


def vectorized_uts() -> None:
    """Exact UTS tree count, thousands of DFS lanes + shared root queue."""
    from hclib_tpu.device.uts_vec import NLANES, uts_vec
    from hclib_tpu.models.uts import T_TINY, count_seq

    r = uts_vec(T_TINY, target_roots=64, device=jax.devices("cpu")[0])
    want_nodes, want_leaves, want_depth = count_seq(T_TINY)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == (
        want_nodes, want_leaves, want_depth,
    )
    print(f"UTS T_TINY: {r['nodes']} nodes counted exactly by {NLANES} lanes")


def fused_smith_waterman() -> None:
    """Batched alignment scores from the fused Pallas row sweep."""
    from hclib_tpu.device.sw_pallas import sw_scores_pallas
    from hclib_tpu.models.smithwaterman import random_seq, sw_seq

    B = 4
    A = np.stack([random_seq(96, i) for i in range(B)])
    Bs = np.stack([random_seq(128, 100 + i) for i in range(B)])
    got = sw_scores_pallas(A, Bs, interpret=True)
    want = [int(sw_seq(A[i], Bs[i]).max()) for i in range(B)]
    assert list(got) == want
    print("Smith-Waterman scores", list(got), "match the sequential DP")


def sharded_megakernel() -> None:
    """Per-device task queues + bulk-synchronous stealing over the ring."""
    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.megakernel import Megakernel
    from hclib_tpu.device.sharded import ShardedMegakernel
    from hclib_tpu.parallel.mesh import cpu_mesh

    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    mesh = cpu_mesh(8, axis_name="queues")
    mk = Megakernel(kernels=[("bump", bump)], capacity=64, num_values=8,
                    succ_capacity=8, interpret=True)
    smk = ShardedMegakernel(mk, mesh, migratable_fns=[0])
    builders = [TaskGraphBuilder() for _ in range(8)]
    for _ in range(32):  # all work starts on device 0...
        builders[0].add(0, args=[1])
    iv, _, info = smk.run(builders, steal=True, quantum=4, window=8)
    assert info["pending"] == 0 and int(iv[:, 0].sum()) == 32
    spread = int((iv[:, 0] > 0).sum())
    print(f"sharded megakernel: 32 tasks stole across {spread} devices in "
          f"{info['steal_rounds']} rounds")


def main() -> None:
    vectorized_uts()
    fused_smith_waterman()
    sharded_megakernel()


if __name__ == "__main__":
    main()
