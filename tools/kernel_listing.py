#!/usr/bin/env python3
"""Dump the v5e compiler's final-bundle listing of a scheduler kernel.

No chip: the kernel is compiled for a DESCRIBED ``v5e:2x2`` (as
``tests/test_chip_compile.py`` does) with libtpu's LLO dump on, in a child
process of its own: libtpu reads its flags once, when it loads, and the
child aborts after the listing is written (on a report template this
install lacks), so its exit code says nothing. A scalar-tier task costs
the straight-line length of the code its path runs (``PERF.md`` section 6,
PR 41 / 45 / 46): read the listing before and after a change to the
scheduler, and count its paths with ``tools/listing_paths.py``.

    python tools/kernel_listing.py <outdir> [--tree DIR]
                                   [--kernel fib|forest|search|forasync|jacobi|wave|
                                             uts_bin|sparselu]
                                   [--capacity N] [--if-conversion]

``--tree`` is the checkout to compile (default: this one; give a copy of
the parent commit to compare). ``fib`` is ``fib30-scalar``'s kernel
(``make_fib_megakernel(768)`` through ``Megakernel._build_exec``);
``forest`` is ``forest-steal-4chip``'s resident mesh kernel (capacity 640,
four described chips), which inlines the same scheduler core; ``search``
is ``g500-bfs-search``'s build as ``tests/test_chip_compile.py:_search``
compiles it (Graph500's scale 22 by the shapes alone, width 8, capacity
128, fuel ``1 << 30``, the adjacency, vertex table and queue on the
device; ten seconds). In its listing the loop of
``SearchKernel._relax_block`` stands once a batch slot of each copy of the
batch body (16 times at width 8). It is the ``LB:`` whose first bundle
shifts the loop's counter by the group's log2 (``sshll.u32 ..., 4``: ``e0
= g * SR_TEST``; the trip count, ``cnt + 15`` shifted right by 4, is made
in the bundles in front of it). A group that finds nothing runs from there
to the first ``sbr.rel`` and its four delay slots, then from that branch's
target (the ``PF:`` that reloads the counter) to the back-branch and its
four: 77 + 9 bundles for 16 entries (PR 49; 55 + 7 for 4 before it).
The scheduler's loop is the widest one (``0xaf .. 0x21b2`` at PR 52, of
8,903 bundles; a 10-bundle loop that spills the lanes at the exit follows
it, which is why ``tools/listing_paths.py`` takes the widest and not the
last). It has too many paths to list: follow one with ``listing_paths.py
<listing> --take 0x<branch>,...``. Its head tests the starved phase and
jumps that phase's copy of the batch body (the first ``sbr.rel`` of the
loop, ``0xbe``), then the drain phase's (``0xe67``), then the pop
(``0x1c1a``): all three taken is the round that does nothing, 76 bundles.
The ROUTING path is the pop not jumped and the next branch (``0x1c27``,
over the scalar ``step``) taken: pop, ``F_FN``, the compare and the
predicated lane push, ``TS_ROUTED``, the age clock, 92 bundles a row; a
row the maker spawns no longer runs it (PR 50: ``spawn`` pushes the lane),
a row the host staged or ``retire()`` released does. Behind that branch
stands the maker (PR 52: a trip of its loop is a VERTEX). The ``LB:`` at
``0x1c52`` is ``_make_kernel``'s outer ``while_loop``; its first branch
(``0x1c55``) is taken by a trip whose group still holds a vertex and jumps
the refill (the level logic and the gather, some 1,200 bundles, once a
group of sixteen); the next on that path (``0x20f9``) jumps the inner
loop where the vertex has no block to make. ``--loop 0x1c52 --take
0x1c55,0x20f9``: 43 bundles a vertex. The inner ``LB:`` at ``0x20fe``, with
a back-branch of its own at ``0x211f``, is the spawn loop: ``--loop
0x20fe``: 38 bundles an EXPAND, ``spawn``'s own and two register updates;
``--loop 0x1c52 --take 0x1c55`` is a one-block vertex, 81. (The parent's
loop did one thing an iteration, ``spawn``, ``take`` and the level logic
predicated into every one: 98 bundles an EXPAND and 98 a vertex, ``--loop
0x1c4f --take 0x1c8b`` on its listing.) The
addresses move with every change to the kernel: find them again by the
order of the branches, not by their numbers. ``forasync`` is
``forasync-2d-hbm``'s build and ``jacobi`` ``jacobi-dep-hbm``'s (PR 51:
eight steps, the release of a finished tile under the store wave of each
copy of the batch body), both as ``tests/test_chip_compile.py`` compiles
them, grids on the device. ``wave`` is ``sw-wave-8192``'s build as
``tests/test_chip_compile.py:_sw_wave`` compiles it (``with_h=False``, the
table as the engine sizes it: 568 rows, and ``--capacity`` may name no
other). Its listing holds ONE copy of the batch body, and the sweep
(``smithwaterman.py:_sw_wave_batch_kernel``, phase 4) is the ``LB:`` whose
body holds the kernel's ``vrot.lane.b32``: from it to the first ``sbr.rel``
and its four delay slots is a ROW, 128 of them a round. A row costs what it
waits for, not its length: every ``vrot.lane`` / ``vadd.xlane`` / ``vperm``
is pushed to the cross-lane unit and popped a latency later, so count the
pushes in series, ``tools/listing_paths.py <listing> --loop 0x<that LB>
--chain vrot.lane`` (PR 54). The parent of PR 54 (4,885 bundles, the loop
at ``0xc9a``): 155 bundles a row, 16 ``vrot.lane`` (the diagonal's roll and
a seven-stage radix-2 scan, two vregs each), 12 ``vadd.xlane.f32`` (three
columns taken by a masked int32 lane sum, four each) and 4 ``vperm``, 32
pushes; EIGHT ``vrot`` in series, TEN pushes in series with the column in
front of them and the right column's ``vperm`` behind. PR 54 (4,899
bundles, the loop at ``0xcac``): 146 bundles a row, 34 ``vrot.lane`` (a
three-stage radix-8 scan of 7, 7 and 1 rolls whose last stage makes the
next row's diagonal too, two more rolls; two vregs each), no
``vadd.xlane`` (a column is one lane gather a vreg, a ``vperm`` with the
row's number as its pattern, taken a row ahead), 6 ``vperm`` (two more
stand in the loop's tail under the exit's predicate: the last row's right
column), 40 pushes; THREE ``vrot`` in series and nothing else in a row's
chain. On the chip a push in series costs 76 ns and a push more 2-4 ns
(``PERF.md`` section 6, PR 54): the parent's row took 0.81 us for its 155
bundles, this one takes 0.31.
The child's output goes to ``<outdir>/compile.log``; with
``--if-conversion`` the compiler's if-conversion pass logs into it which
``pl.when`` / ``lax.cond`` regions it predicated and which it kept as
branches (``grep if_conversion <outdir>/compile.log``), and the regions
are summed up after the listing's path, which is always printed: a region
"kept" is a branch (the listing's ``sbr.rel ... region = N`` is numbered one
above the log's), a region "predicated" is code every path runs.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys
from typing import Dict, List

_HERE = os.path.dirname(os.path.abspath(__file__))

def find_listing(outdir: str, kernel: str) -> str:
    """The final-bundle listing of ``kernel`` under ``outdir`` (the
    schedule analysis beside it has the same suffix and is not it)."""
    name = KERNELS[kernel][0]
    found = [
        f for f in glob.glob(os.path.join(outdir, "*-final_bundles.txt"))
        if name in os.path.basename(f)
        and "schedule-analysis" not in os.path.basename(f)
    ]
    if not found:
        raise FileNotFoundError(
            f"no *{name}*-final_bundles.txt under {outdir}: "
            f"the child did not reach the compiler (read {outdir}/compile.log)"
        )
    return max(found, key=os.path.getsize)


def if_conversion_summary(log_path: str) -> Dict[str, List[int]]:
    """``{"kept": [...], "predicated": [...]}``: the regions the
    if-conversion pass considered, by its last word on each (a region it
    rejects stays a branch; one it un-predicates becomes straight-line
    code that every path pays for)."""
    last: Dict[int, str] = {}
    pat = re.compile(
        r"if_conversion\.cc:\d+\] (Rejecting|Un-predicating region:) "
        r"\$region(\d+)"
    )
    with open(log_path, errors="replace") as f:
        for line in f:
            m = pat.search(line)
            if m:
                last[int(m.group(2))] = (
                    "kept" if m.group(1) == "Rejecting" else "predicated"
                )
    return {
        k: sorted(r for r, v in last.items() if v == k)
        for k in ("kept", "predicated")
    }


def _compile_mk(mk, fuel: int, on_device=()) -> None:
    """Compile ``Megakernel.run``'s program for one described v5e chip,
    laid out as ``run`` lays it out (``tests/test_chip_compile.py:
    _compile_mk``): the small int32 buffers ride the slab, those named in
    ``on_device`` are handed in as ``jax.Array``s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from hclib_tpu.device.megakernel import SLAB_RIDE_BYTES

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    sh = SingleDeviceSharding(topo.devices[0])
    lay = mk._exec_layout(
        [
            "data:" + k for k, s in mk.data_specs.items()
            if s.dtype == jnp.int32
            and 4 * np.prod(s.shape) < SLAB_RIDE_BYTES
            and k not in on_device
        ],
        ["data:" + k for k in on_device],
    )
    words = sum(int(np.prod(s)) for s in lay.up.values())
    args = [jax.ShapeDtypeStruct((words,), jnp.int32, sharding=sh)]
    args += [
        jax.ShapeDtypeStruct(
            mk.data_specs[n[5:]].shape, mk.data_specs[n[5:]].dtype,
            sharding=sh,
        )
        for n in lay.alone
    ]
    mk._build_exec(fuel, False, lay).lower(*args).compile()


def _compile_fib(capacity: int) -> None:
    from hclib_tpu.device.workloads import make_fib_megakernel

    _compile_mk(make_fib_megakernel(capacity, interpret=False), 1 << 22)


def _compile_forest(capacity: int) -> None:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from hclib_tpu.device.descriptor import TaskGraphBuilder
    from hclib_tpu.device.megakernel import VBLOCK
    from hclib_tpu.device.resident import ResidentKernel
    from hclib_tpu.device.sharded import abort_words, partition_builders
    from hclib_tpu.device.workloads import FIB, make_fib_megakernel

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    ndev, roots = 4, 160
    mesh = Mesh(np.array(topo.devices).reshape(ndev), ("q",))
    mk = make_fib_megakernel(
        capacity=capacity, interpret=False,
        num_values=VBLOCK * capacity + max(64, roots),
    )
    rk = ResidentKernel(
        mk, mesh, migratable_fns=[FIB], homed=False, window=16
    )
    tasks, succ, ring, counts = partition_builders(
        mk, ndev, [TaskGraphBuilder() for _ in range(ndev)]
    )
    args = [
        tasks, succ, ring, counts,
        np.zeros((ndev, mk.num_values), np.int32),
        np.zeros((ndev, rk.max_waits + 1, 3), np.int32),
        abort_words(None, ndev),
    ]
    sh = NamedSharding(mesh, PartitionSpec("q"))
    shapes = [
        jax.ShapeDtypeStruct(tuple(a.shape), a.dtype, sharding=sh)
        for a in args
    ]
    rk._build(256, 1 << 14, None).lower(*shapes).compile()


def _compile_search(capacity: int) -> None:
    import types

    from hclib_tpu.device.frontier import (
        make_frontier_megakernel, search_kernel,
    )

    g = types.SimpleNamespace(n=1 << 22, nblocks=3_200_000)
    _compile_mk(
        make_frontier_megakernel(
            search_kernel(), g, width=8, capacity=capacity, interpret=False,
        ),
        1 << 30, on_device=("indices", "vtab", "queue"),
    )


def _compile_loop(loop: str, capacity: int, on_device, **kw) -> None:
    """A RECURSIVE forasync build of ``workloads.<loop>`` over the cells'
    32768 x 32768 grid in (256, 1024) tiles at width 8."""
    from hclib_tpu.device import workloads
    from hclib_tpu.device.forasync_tier import make_forasync_megakernel

    tk, bounds, tile = getattr(workloads, loop)(
        32768, 32768, 256, 1024, **kw)
    _compile_mk(
        make_forasync_megakernel(tk, width=8, capacity=capacity,
                                 interpret=False, space=(bounds, tile)),
        1 << 22, on_device=on_device,
    )


def _compile_wave(capacity: int) -> None:
    """``sw-wave-8192``'s build (``tests/test_chip_compile.py:_sw_wave``);
    the engine sizes the table itself (568 descriptors)."""
    from hclib_tpu.device.smithwaterman import T, make_sw_wave_megakernel

    nt = 8192 // T
    mk = make_sw_wave_megakernel(nt, nt, interpret=False, with_h=False)
    if capacity != mk.capacity:
        raise SystemExit(f"the wave engine sizes its own table: "
                         f"{mk.capacity} rows, not --capacity {capacity}")
    _compile_mk(mk, 1 << 22)


def _compile_forasync(capacity: int) -> None:
    _compile_loop("stencil_loop", capacity, ("gin", "gout"))


def _compile_jacobi(capacity: int) -> None:
    _compile_loop("jacobi_loop", capacity, ("grid",), steps=8)


# kernel -> (its name in the trace and in the dump's file names (PERF.md
# section 3: the jit round a Megakernel's pallas_call is named
# tpu_custom_call, the mesh kernel resident_mesh), its compile, the
# cell's table rows)
def _compile_uts_bin(stack_size: int) -> None:
    """``uts-t3l``'s kernel (PR 56) as ``tests/test_chip_compile.py:_uts_t3l``
    compiles it: the binomial traversal on 64 x 128 lanes; ``--capacity`` is
    the ring's height. Its listing holds one loop: the balance round,
    then ``BIN_EVERY`` steps unrolled (one SHA-1 and the ring's selects
    each). PR 56 read 17.9 k bundles a trip with the steps in a loop of
    their own and 14.7 k unrolled, at a ring of 4 and 4 steps a round; at
    the cell's ring of 2 and 2 steps a round ``listing_paths.py`` counts
    7,874 bundles on the trip that neither fetches nor spills a slab, and
    8,782 since PR 57 (frames split as they change hands: a third rank
    product, 35 in-row gathers a round for 21)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from hclib_tpu.device import uts_pallas as up
    from hclib_tpu.device import uts_vec as uv

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    lanes = (64, 128)
    args = (
        jax.ShapeDtypeStruct((1, uv.FRAME_WORDS) + lanes, jnp.int32,
                             sharding=sh),
        jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sh),
    )
    up._uts_bin_pallas.lower(
        *args, stack_size=stack_size, lanes=lanes,
        every=uv.BIN_EVERY, pool_slabs=uv.BIN_POOL_SLABS, interpret=False,
    ).compile()


def _compile_sparselu(capacity: int) -> None:
    """``sparselu-dep-128``'s build (PR 58) as ``tests/test_chip_compile.py:
    _sparselu`` compiles it: 128 x 128 blocks of 128 x 128, the present
    blocks and the factor on the device; the build sizes its own table from
    the replayed schedule (``--capacity`` may name no other). Its listing
    holds the scalar ``lu0`` and the two range kinds behind the switch, and
    two copies (starved phase off: one, the drain phase's) of each batch
    body, the panel's at width 8 and the update's at width 16, each with
    its releases under the store wave."""
    from hclib_tpu.device.sparselu import make_sparselu_megakernel

    mk = make_sparselu_megakernel(128, 128, interpret=False)
    if capacity != mk.capacity:
        raise SystemExit(f"the sparselu build sizes its own table: "
                         f"{mk.capacity} rows, not --capacity {capacity}")
    _compile_mk(mk, 1 << 22, on_device=("a", "blocks", "linv"))


KERNELS = {
    "uts_bin": ("uts_dfs_bin", _compile_uts_bin, 2),
    "fib": ("tpu_custom_call", _compile_fib, 768),
    "forest": ("resident_mesh", _compile_forest, 640),
    "search": ("tpu_custom_call", _compile_search, 128),
    "forasync": ("tpu_custom_call", _compile_forasync, 64),
    "jacobi": ("tpu_custom_call", _compile_jacobi, 99),
    "wave": ("tpu_custom_call", _compile_wave, 568),
    "sparselu": ("tpu_custom_call", _compile_sparselu, 129),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("outdir")
    ap.add_argument("--tree", default=os.path.dirname(_HERE))
    ap.add_argument("--kernel", choices=sorted(KERNELS), default="fib")
    ap.add_argument("--capacity", type=int, default=None,
                    help="table rows (default: the cell's, 768 / 640 / 128 / 64 / 99 "
                    "/ 568)")
    ap.add_argument("--if-conversion", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    _, compile_kernel, cell_capacity = KERNELS[a.kernel]
    capacity = a.capacity or cell_capacity
    if a.child:
        sys.path.insert(0, a.tree)
        compile_kernel(capacity)
        return 0
    outdir = os.path.abspath(a.outdir)
    os.makedirs(outdir, exist_ok=True)
    env = dict(os.environ)
    env["LIBTPU_INIT_ARGS"] = (
        f"--xla_jf_dump_to={outdir} --xla_jf_dump_llo_text=true"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    if a.if_conversion:
        env.update(TPU_STDERR_LOG_LEVEL="0", TPU_MIN_LOG_LEVEL="0",
                   TPU_VMODULE="if_conversion=5")
    cmd = [sys.executable, os.path.abspath(__file__), outdir, "--child",
           "--tree", os.path.abspath(a.tree), "--kernel", a.kernel,
           "--capacity", str(capacity)]
    log_path = os.path.join(outdir, "compile.log")
    with open(log_path, "w") as log:
        subprocess.run(cmd, env=env, stdout=log, stderr=log, check=False)
    print(find_listing(outdir, a.kernel))
    if a.if_conversion:
        for k, regions in if_conversion_summary(log_path).items():
            print(f"{k} {len(regions)}: {' '.join(map(str, regions))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
