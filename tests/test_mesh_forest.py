"""The deployment ``fib-forest-mesh`` (benchmark cell
``forest-steal-4chip``) at a tiny size: a fib forest spawned on device 0 of
an interpreter mesh and stolen by the rest through ``ResidentKernel(
homed=False)``, held to the benchmark's plain reference; the steal
exchange's two counters (``info["steal"]``); and the four ``bench:mesh.*``
spans of ``execute_partitions``. The kernel's trace name is asked of the
real compiler in tests/test_chip_compile.py.

One ``Megakernel`` for the module; the interpreter mesh costs about 50 ms a
task and 4 s a call, so the forest is 4 roots of fib(4), 52 descriptors."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import fib_forest as ref  # noqa: E402
from hclib_tpu.device import resident  # noqa: E402
from hclib_tpu.device.descriptor import TaskGraphBuilder  # noqa: E402
from hclib_tpu.device.megakernel import C_EXECUTED, VBLOCK  # noqa: E402
from hclib_tpu.device.workloads import FIB, make_fib_megakernel  # noqa: E402
from hclib_tpu.parallel.mesh import cpu_mesh  # noqa: E402
from hclib_tpu.runtime import spans  # noqa: E402

ROOTS, N, CAPACITY = 4, 4, 64
SPANS = ["bench:mesh.partition", "bench:mesh.upload", "bench:mesh.run",
         "bench:mesh.readback"]


def forest(ndev):
    """The cell's builders: every root on device 0, the out slots reserved
    on every device (a stolen root writes its slot on the thief)."""
    builders = [TaskGraphBuilder() for _ in range(ndev)]
    for r in range(ROOTS):
        builders[0].add(FIB, args=[N], out=r)
    for b in builders:
        b.reserve_values(ROOTS)
    return builders


@pytest.fixture(scope="module")
def mk():
    return make_fib_megakernel(
        CAPACITY, interpret=True,
        num_values=VBLOCK * CAPACITY + max(64, ROOTS),
    )


def run_on(mk, ndev):
    rk = resident.ResidentKernel(
        mk, cpu_mesh(ndev, axis_name="q"), migratable_fns=[FIB],
        homed=False, window=4,
    )
    iv, _, info = rk.run(forest(ndev), quantum=8)
    return np.asarray(iv), info


@pytest.fixture(scope="module")
def stolen(mk):
    """One call on two devices, with the spans of the host half recorded
    in the order they were opened."""
    opened = []

    class Span:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "TraceAnnotation", Span)
        iv, info = run_on(mk, 2)
    return iv, info, opened


def test_forest_on_two_devices_equals_the_plain_reference(stolen):
    iv, info, _ = stolen
    want = ref.closed_form(ROOTS, N)
    assert want == {"value": 12, "descriptors": 52}
    assert int(iv[:, :ROOTS].sum(dtype=np.int64)) == want["value"]
    assert info["executed"] == want["descriptors"]
    per_dev = [int(c[C_EXECUTED]) for c in info["per_device_counts"]]
    assert sum(per_dev) == info["executed"] and min(per_dev) > 0, per_dev
    assert info["pending"] == 0 and not info["overflow"]
    assert info["input_devices"] == 2


def test_steal_counters_conserve_and_count_the_stolen_rows(stolen):
    _, info, _ = stolen
    steal = info["steal"]
    assert set(steal) == {"exported", "imported"}
    assert len(steal["exported"]) == len(steal["imported"]) == 2
    assert sum(steal["exported"]) == sum(steal["imported"]) > 0
    assert steal["exported"][0] > 0 and steal["imported"][1] > 0
    # link-free roots are all that moves (homed=False): never more rows
    # than roots in one direction
    assert steal["imported"][1] <= ROOTS
    assert steal == {
        "exported": [f["steal_exported"] for f in info["fault_stats"]],
        "imported": [f["steal_imported"] for f in info["fault_stats"]],
    }


def test_a_mesh_of_one_device_steals_nothing(mk):
    iv, info = run_on(mk, 1)
    want = ref.closed_form(ROOTS, N)
    assert int(iv[:, :ROOTS].sum(dtype=np.int64)) == want["value"]
    assert info["executed"] == want["descriptors"]
    assert info["steal"] == {"exported": [0], "imported": [0]}


def test_the_host_half_opens_its_four_spans_in_order(stolen):
    """The call is the mesh program's first: the build ledger's bracket
    stands around the four, and marks where a compile ended."""
    opened = stolen[2]
    assert opened[0] == "bench:prog.first_call"
    assert "bench:prog.compiled" in opened
    assert [n for n in opened if not n.startswith("bench:prog.")] == SPANS
    row = stolen[1]["program_cache"]
    assert not row["hit"] and row["trace_s"] > 0 and row["compile_s"] > 0
    assert row["trace_s"] + row["lower_s"] + row["compile_s"] <= (
        row["wall_s"]) == row["build_s"]


def test_fault_stats_row_decodes_the_two_counters():
    row = np.zeros(resident.FS_WORDS, np.int32)
    row[resident.FS_EXPORTED], row[resident.FS_IMPORTED] = 7, 5
    got = resident.decode_fault_stats(row)
    assert (got["steal_exported"], got["steal_imported"]) == (7, 5)
    assert resident.FS_TEN_EXPIRED < resident.FS_EXPORTED
    assert resident.FS_EXPORTED < resident.FS_IMPORTED < resident.FS_WORDS


def test_the_reference_closed_form_equals_its_direct_count():
    for n in (0, 1, 2, N, 12, 18):
        assert ref.closed_form(1, n) == ref.direct_count(n), n
    assert ref.closed_form(160, 18) == {"value": 413440,
                                        "descriptors": 2006560}
