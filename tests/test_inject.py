"""Host -> resident-kernel task injection (device/inject.py).

Reference counterpart: materializing work on a running runtime from outside
(/root/reference/modules/openshmem-am/src/hclib_openshmem-am.cpp:64-123)."""

import threading
import time

import jax
import pytest
from conftest import bump_mk

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.workloads import FIB, make_fib_megakernel

BUMP = 0


def fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def tree_tasks(n):
    if n < 2:
        return 1
    return 1 + tree_tasks(n - 1) + tree_tasks(n - 2)


def test_ring_rows_discovered_by_in_kernel_poll():
    """Injected rows are NEVER staged with the graph - they can only enter
    through the in-kernel ring poll; exact totals prove that path."""
    sm = StreamingMegakernel(bump_mk(), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[1000])
    for i in range(20):
        sm.inject(BUMP, args=[i + 1])
    sm.close()
    iv, info = sm.run_stream(b)
    assert info["executed"] == 21
    assert info["injected"] == 20
    assert int(iv[0]) == 1000 + 20 * 21 // 2


def test_concurrent_feeder_thread():
    """A host thread appends fib seeds while the stream runs; every seed's
    value lands in its out slot and the task totals are exact."""
    mk = make_fib_megakernel(capacity=768, interpret=True)
    sm = StreamingMegakernel(mk, ring_capacity=32)
    b = TaskGraphBuilder()
    b.add(FIB, args=[10], out=0)
    b.reserve_values(10)
    ns = [5, 7, 8, 9, 11, 6, 4, 12]

    def feeder():
        for i, n in enumerate(ns):
            sm.inject(FIB, args=[n], out=1 + i)
            time.sleep(0.02)
        sm.close()

    t = threading.Thread(target=feeder)
    t.start()
    iv, info = sm.run_stream(b, quantum=64)
    t.join()
    assert int(iv[0]) == fib(10)
    for i, n in enumerate(ns):
        assert int(iv[1 + i]) == fib(n), (i, n)
    assert info["injected"] == len(ns)
    # Scalar-tier fib counts FIB nodes plus SUM joins: t + (t-1)//2.
    scalar_tasks = lambda n: tree_tasks(n) + (tree_tasks(n) - 1) // 2
    assert info["executed"] == sum(scalar_tasks(n) for n in [10] + ns)


def test_inject_after_close_raises():
    sm = StreamingMegakernel(bump_mk(), ring_capacity=8)
    sm.close()
    with pytest.raises(RuntimeError):
        sm.inject(BUMP, args=[1])


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs TPU")
def test_streaming_on_tpu():
    """The ring poll + install path through real Mosaic lowering."""
    sm = StreamingMegakernel(bump_mk(interpret=False), ring_capacity=64)
    b = TaskGraphBuilder()
    b.add(BUMP, args=[7])
    for i in range(10):
        sm.inject(BUMP, args=[i + 1])
    sm.close()
    iv, info = sm.run_stream(b)
    assert info["executed"] == 11
    assert int(iv[0]) == 7 + 55
