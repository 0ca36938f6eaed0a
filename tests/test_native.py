"""C++ native runtime tests (built on demand via make; skipped without g++)."""

import shutil

import numpy as np
import pytest

# Match the Makefile's default compiler (CXX ?= g++, overridable via env).
import os

_cxx = os.environ.get("CXX", "g++")
pytestmark = pytest.mark.skipif(
    shutil.which(_cxx) is None, reason=f"no C++ compiler ({_cxx})"
)


@pytest.fixture(scope="module")
def rt():
    from hclib_tpu.native import NativeRuntime

    with NativeRuntime(2) as r:
        yield r


def test_native_fib(rt):
    assert rt.fib(20) == 6765
    assert rt.fib(1) == 1
    assert rt.fib(0) == 0


def test_native_uts_t3(rt):
    # T_TINY: FIXED shape, depth 5, b0=4, seed 42 (pinned in models/uts.py)
    assert rt.uts(3, 5, 4.0, 42) == (1279, 1018, 5)


def test_native_uts_matches_python_spec(rt):
    from hclib_tpu.models import uts

    params = uts.UTSParams(shape=uts.FIXED, gen_mx=4, b0=3.0, root_seed=7)
    seq = uts.count_seq(params)
    assert rt.uts(3, 4, 3.0, 7) == seq


def test_native_arrayadd(rt):
    n = 10_000
    a = np.arange(n, dtype=np.float64)
    b = 2.0 * np.arange(n, dtype=np.float64)
    c = np.zeros(n)
    rt.arrayadd(a, b, c, tile=512)
    assert np.array_equal(c, a + b)


def test_native_stats(rt):
    before = rt.executed
    rt.fib(15)
    assert rt.executed > before


def test_native_fib_ddt(rt):
    # Promise-based fib (reference workload test/misc/fib-ddt): every join
    # is an async_await on two child promises.
    assert rt.fib_ddt(18) == 2584
    assert rt.fib_ddt(2) == 1


def _sw_python_reference(nx, ny, ts, seed):
    """Replicates the native splitmix64 sequence generation + DP scoring."""
    mask = (1 << 64) - 1

    def gen(state, count):
        out = []
        s = state
        for _ in range(count):
            s = (s + 0x9E3779B97F4A7C15) & mask
            z = s
            z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
            z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
            out.append((z ^ (z >> 31)) & 3)
        return out, s

    s0 = (seed * 2654435761 + 1) & mask
    a, s1 = gen(s0, nx * ts)
    b, _ = gen(s1, ny * ts)
    n, m = len(a), len(b)
    prev = [0] * (m + 1)
    best = 0
    for i in range(1, n + 1):
        cur = [0] * (m + 1)
        for j in range(1, m + 1):
            sc = 1 if a[i - 1] == b[j - 1] else -1
            v = max(prev[j - 1] + sc, prev[j] - 1, cur[j - 1] - 1, 0)
            cur[j] = v
            if v > best:
                best = v
        prev = cur
    return best


def test_native_smithwaterman_matches_reference_dp(rt):
    got = rt.smithwaterman(2, 2, 24, seed=5)
    assert got == _sw_python_reference(2, 2, 24, 5)


def test_native_smithwaterman_deterministic(rt):
    a = rt.smithwaterman(4, 4, 32, seed=9)
    b = rt.smithwaterman(4, 4, 32, seed=9)
    assert a == b and a > 0


def test_native_python_tasks_finish(rt):
    import threading

    hits = []
    lock = threading.Lock()
    with rt.finish() as f:
        for i in range(50):
            rt.async_(lambda i=i: (lock.acquire(), hits.append(i), lock.release()),
                      finish=f)
    assert sorted(hits) == list(range(50))


def test_native_promise_dependencies(rt):
    order = []
    p1 = rt.promise()
    p2 = rt.promise()
    with rt.finish() as f:
        rt.async_(lambda: order.append("dep"), finish=f, deps=(p1, p2))
        rt.async_(lambda: (order.append("a"), p1.put(7)), finish=f)
        rt.async_(lambda: (order.append("b"), p2.put(9)), finish=f)
    assert order[-1] == "dep" and set(order) == {"a", "b", "dep"}
    assert p1.wait() == 7 and p2.get() == 9
    p1.free()
    p2.free()


def test_native_end_finish_nonblocking(rt):
    import time

    done = []
    f = rt.finish()
    rt.async_(lambda: (time.sleep(0.01), done.append(1)), finish=f)
    p = f.end_nonblocking()
    assert p.wait() == 0  # promise satisfied once the scope drains
    assert done == [1]


def test_native_forasync(rt):
    n = 1000
    out = [0] * n
    rt.forasync1d(lambda i: out.__setitem__(i, i * 2), n, tile=64)
    assert out == [2 * i for i in range(n)]
    grid = [[0] * 8 for _ in range(8)]
    rt.forasync2d(lambda i, j: grid[i].__setitem__(j, i + j), 8, 8, 2, 2)
    assert grid == [[i + j for j in range(8)] for i in range(8)]


def test_native_forasync_recursive(rt):
    n = 513
    out = [0] * n
    rt.forasync1d(lambda i: out.__setitem__(i, i + 1), n, tile=32, recursive=True)
    assert out == [i + 1 for i in range(n)]


def test_native_locality_graph():
    from hclib_tpu.native import NativeRuntime
    from hclib_tpu.runtime.locality import generate_default_graph

    g = generate_default_graph(2)
    with NativeRuntime(graph=g) as rt:
        assert rt.nlocales == len(g.locales)
        assert rt.fib(15) == 610
        # Spawn at a non-default locale; a worker whose steal path covers it
        # must pick it up.
        hits = []
        with rt.finish() as f:
            rt.async_(lambda: hits.append(1), finish=f, locale=2)
        assert hits == [1]
        sm = rt.steal_matrix()
        assert len(sm) == 2 and len(sm[0]) == 2
        assert "executed=" in rt.format_stats()


def test_native_yield(rt):
    ran = []
    with rt.finish() as f:
        rt.async_(lambda: ran.append(1), finish=f)
        # Give the spawned task a chance to be picked up by the main thread.
        rt.yield_()
    assert ran == [1]


def test_affinity_pins_workers(monkeypatch):
    """HCLIB_TPU_AFFINITY=strided pins worker w to CPU w % ncpu
    (reference: HCLIB_AFFINITY, src/hclib-runtime.c:731-900)."""
    import os

    from hclib_tpu.native import NativeRuntime

    monkeypatch.setenv("HCLIB_TPU_AFFINITY", "strided")
    allowed = sorted(os.sched_getaffinity(0))  # respects cgroup/taskset
    with NativeRuntime(nworkers=2) as r:
        assert r.pinned_cpus() == [allowed[w % len(allowed)] for w in range(2)]
        assert r.fib(15) == 610  # still schedules correctly while pinned
    # Teardown restored the caller's mask: later runtimes must be unpinned.
    assert sorted(os.sched_getaffinity(0)) == allowed


def test_no_affinity_by_default(monkeypatch):
    from hclib_tpu.native import NativeRuntime

    monkeypatch.delenv("HCLIB_TPU_AFFINITY", raising=False)
    monkeypatch.delenv("HCLIB_AFFINITY", raising=False)
    with NativeRuntime(nworkers=2) as r:
        assert r.pinned_cpus() == [-1, -1]


def test_unknown_affinity_mode_ignored(monkeypatch):
    """Only strided|chunked activate pinning; anything else is rejected
    (a stray HCLIB_AFFINITY=none must not hard-pin the host thread)."""
    from hclib_tpu.native import NativeRuntime

    monkeypatch.setenv("HCLIB_TPU_AFFINITY", "none")
    with NativeRuntime(nworkers=2) as r:
        assert r.pinned_cpus() == [-1, -1]


def test_multicore_speedup():
    """Where cores exist, more workers must actually help - the measured
    CPU-baseline story depends on it (gated: the TPU bench host has 1
    core; CI runners have >= 2)."""
    import os
    import time

    import pytest

    from hclib_tpu.native import NativeRuntime

    ncpu = os.cpu_count() or 1
    if ncpu < 2:
        pytest.skip("single-core host")

    def wall(workers):
        with NativeRuntime(nworkers=workers) as r:
            r.fib(24)  # warm the pools
            t0 = time.perf_counter()
            r.fib(27)
            return time.perf_counter() - t0

    t1 = min(wall(1) for _ in range(2))
    tn = min(wall(min(ncpu, 4)) for _ in range(2))
    assert tn < t1 / 1.15, (t1, tn)


def test_typed_cpp_promise_future():
    """promise_t<int>/future_t<double> (reference inc/hclib_promise.h:41-124):
    a typed int promise chained through async_await into a typed double
    future; the demo returns 1000*42 + 2."""
    from hclib_tpu.native import NativeRuntime

    with NativeRuntime(nworkers=2) as r:
        assert r._lib.hcn_typed_promise_demo(r._handle) == 42002


def test_lint_clean():
    """The static-check gate (tools/lint.py - the reference's astyle +
    cppcheck station): the whole tree must pass, so style violations fail
    a plain pytest run locally, not just CI."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "lint.py")],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, f"lint violations:\n{r.stdout}"
