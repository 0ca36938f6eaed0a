"""Host runtime core tests, mirroring the reference's test/c and test/cpp
feature suites (async0/1, finish0/1/2, future0-3, asyncAwait, yield,
nested_finish, future_wait_in_finish; see SURVEY.md section 4)."""

import threading

import pytest

import hclib_tpu as hc


def test_async_runs_before_finish_exits():
    hit = []

    def main():
        with hc.finish():
            hc.async_(lambda: hit.append(1))
            hc.async_(lambda: hit.append(2))
        assert sorted(hit) == [1, 2]

    hc.launch(main, nworkers=2)


def test_launch_returns_value():
    assert hc.launch(lambda: 42, nworkers=1) == 42


def test_launch_propagates_exceptions():
    def main():
        raise ValueError("boom")

    with pytest.raises(ValueError):
        hc.launch(main, nworkers=2)


def test_nested_finish():
    order = []

    def main():
        with hc.finish():
            def outer():
                with hc.finish():
                    hc.async_(lambda: order.append("inner"))
                order.append("after-inner")

            hc.async_(outer)
        order.append("after-outer")

    hc.launch(main, nworkers=2)
    assert order == ["inner", "after-inner", "after-outer"]


def test_many_asyncs_single_worker():
    n = 2000
    counter = []

    def main():
        with hc.finish():
            for i in range(n):
                hc.async_(counter.append, i)

    hc.launch(main, nworkers=1)
    assert len(counter) == n


def test_many_asyncs_multi_worker():
    n = 2000
    lock = threading.Lock()
    box = [0]

    def bump():
        with lock:
            box[0] += 1

    def main():
        with hc.finish():
            for _ in range(n):
                hc.async_(bump)

    hc.launch(main, nworkers=4)
    assert box[0] == n


def test_promise_put_get():
    def main():
        p = hc.Promise()
        f = p.future
        assert not f.satisfied()
        p.put(99)
        assert f.satisfied()
        assert f.get() == 99
        assert f.wait() == 99

    hc.launch(main, nworkers=1)


def test_promise_double_put_raises():
    def main():
        p = hc.Promise()
        p.put(1)
        with pytest.raises(hc.PromiseError):
            p.put(2)

    hc.launch(main, nworkers=1)


def test_future_wait_blocks_until_put():
    def main():
        p = hc.Promise()
        with hc.finish():
            hc.async_(lambda: p.put("val"))
            assert p.future.wait() == "val"

    hc.launch(main, nworkers=2)


def test_future_wait_single_worker():
    """A blocked context must release its worker so the producer task runs
    (the reference's fiber-swap; here, identity hand-off)."""

    def main():
        p = hc.Promise()
        with hc.finish():
            hc.async_(lambda: p.put(7))
            assert p.future.wait() == 7

    hc.launch(main, nworkers=1)


def test_async_await_dependency_order():
    log = []

    def main():
        a = hc.Promise()
        b = hc.Promise()
        with hc.finish():
            hc.async_(lambda: log.append("dep-task"), await_=[a.future, b.future])
            hc.async_(lambda: (log.append("put-a"), a.put(None)))
            hc.async_(lambda: (log.append("put-b"), b.put(None)))
        assert log[-1] == "dep-task"
        assert set(log[:2]) == {"put-a", "put-b"}

    hc.launch(main, nworkers=2)


def test_async_await_many_deps():
    """More than 4 dependencies (past the reference's inline cap)."""
    n = 16

    def main():
        ps = [hc.Promise() for _ in range(n)]
        done = []
        with hc.finish():
            hc.async_(lambda: done.append(True), await_=[p.future for p in ps])
            for p in ps:
                hc.async_(p.put, None)
        assert done == [True]

    hc.launch(main, nworkers=3)


def test_async_future_returns_value():
    def main():
        f = hc.async_future(lambda: 10)
        g = hc.async_future(lambda x: x.get() + 5, f, await_=[f])
        assert g.wait() == 15

    hc.launch(main, nworkers=2)


def test_ddf_chain():
    """Chain of 100 data-driven tasks."""

    def main():
        prev = hc.async_future(lambda: 0)
        for _ in range(100):
            prev = hc.async_future(lambda p=prev: p.get() + 1, await_=[prev])
        assert prev.wait() == 100

    hc.launch(main, nworkers=2)


def test_end_finish_nonblocking():
    def main():
        hit = []
        fin = hc.start_finish()
        hc.async_(lambda: hit.append(1))
        fut = hc.end_finish_nonblocking(fin)
        fut.wait()
        assert hit == [1]

    hc.launch(main, nworkers=2)


def test_yield_runs_other_task():
    def main():
        hit = []
        with hc.finish():
            hc.async_(lambda: hit.append(1))
            hc.yield_()

    hc.launch(main, nworkers=1)


def test_future_wait_in_finish():
    """Reference: test/cpp/future_wait_in_finish.cpp."""

    def main():
        p = hc.Promise()
        out = []
        with hc.finish():
            def waiter():
                out.append(p.future.wait())

            hc.async_(waiter)
            hc.async_(lambda: p.put(3))
        assert out == [3]

    hc.launch(main, nworkers=2)


def test_async_at_locale():
    def main():
        rt = hc.current_runtime()
        central = rt.graph.central_locale()
        seen = []
        with hc.finish():
            hc.async_(lambda: seen.append(hc.current_worker()), at=central)
        assert len(seen) == 1

    hc.launch(main, nworkers=2)


def test_current_worker_and_num_workers():
    def main():
        assert hc.num_workers() == 3
        assert 0 <= hc.current_worker() < 3

    hc.launch(main, nworkers=3)


def test_remote_task_exception_propagates():
    """An exception in a task executed by a pool worker (not inline in the
    awaiting context) must surface at launch(), not vanish."""
    import time

    def main():
        with hc.finish():
            for _ in range(50):
                hc.async_(lambda: None)
            hc.async_(lambda: 1 / 0)
            time.sleep(0.05)  # give another worker time to steal it

    with pytest.raises(ZeroDivisionError):
        hc.launch(main, nworkers=4)


def test_failed_producer_poisons_dependents():
    """A failing async_future must not strand dependents: they run, see the
    poisoned promise on get(), and the error surfaces at launch()."""

    def main():
        f = hc.async_future(lambda: 1 / 0)
        hc.async_(lambda: f.get(), await_=[f])

    with pytest.raises((ZeroDivisionError, hc.PromiseError)):
        hc.launch(main, nworkers=2)


def test_failed_producer_future_wait():
    def main():
        f = hc.async_future(lambda: 1 / 0)
        with pytest.raises(hc.PromiseError):
            f.wait()

    with pytest.raises(ZeroDivisionError):
        hc.launch(main, nworkers=2)


def test_recursive_spawn_tree():
    """Binary task tree, depth 10 -> 2^10 leaves."""
    lock = threading.Lock()
    box = [0]

    def node(d):
        if d == 0:
            with lock:
                box[0] += 1
            return
        hc.async_(node, d - 1)
        hc.async_(node, d - 1)

    def main():
        with hc.finish():
            node(10)

    hc.launch(main, nworkers=4)
    assert box[0] == 1024


def test_run_on_main_executes_on_launch_thread():
    """hclib_run_on_main_ctx parity (src/hclib-runtime.c:1340-1358):
    workers hand main-thread-affine functions to the launch thread and
    block for the result; from the main thread it runs inline; errors
    re-raise in the caller."""
    import threading

    main_ident = threading.get_ident()
    seen = []

    def body():
        # inline from the main thread
        assert hc.run_on_main(threading.get_ident) == main_ident

        def from_worker():
            seen.append(hc.run_on_main(threading.get_ident))
            seen.append(hc.run_on_main(lambda a, b: a + b, 20, 22))

        with hc.finish():
            hc.async_(from_worker)

        def boom():
            def raiser():
                raise ValueError("main-ctx boom")

            try:
                hc.run_on_main(raiser)
            except ValueError as e:
                seen.append(str(e))

        with hc.finish():
            hc.async_(boom)

    hc.launch(body, nworkers=2)
    assert seen[0] == main_ident
    assert seen[1] == 42
    assert seen[2] == "main-ctx boom"


def test_run_on_main_wakes_do_not_poison_finish_parks():
    """ADVICE r5 medium regression: run_on_main wakes a main thread parked
    in help_finish through a CALLER-OWNED event registered on the finish
    (Promise._register_ctx shape), never a shared cached scope event. A
    string of wakes mid-scope must (a) each reach the main thread, (b)
    leave no set/abandoned event registered on the still-open finish, and
    (c) not degrade the pool into a busy spin (park -> instant wake)."""
    import time as _time

    main_ident = threading.get_ident()
    got = []
    waiters_seen = []

    def body():
        rt = hc.current_runtime()
        release = threading.Event()

        def blocker():
            release.wait(10.0)  # holds the root scope open

        def pesterer():
            for _ in range(5):
                got.append(rt.run_on_main(threading.get_ident))
                _time.sleep(0.02)
            fin = rt.root_finish
            with fin._lock:
                evs = list(fin._zero_events)
            waiters_seen.append([ev.is_set() for ev in evs])
            release.set()

        hc.async_(blocker)
        hc.async_(pesterer)

    rt_holder = {}

    def wrapped():
        rt_holder["rt"] = hc.current_runtime()
        return body()

    hc.launch(wrapped, nworkers=2)
    assert got == [main_ident] * 5
    # Nothing set stayed registered on the open scope (a set shared event
    # was the old busy-spin poison); at most the main park + a worker.
    (flags,) = waiters_seen
    assert len(flags) <= 2 and not any(flags)
    # Busy-spin detector: five wakes cost ~a dozen parks, not thousands.
    parks = sum(st.parks for st in rt_holder["rt"].worker_stats)
    assert parks < 100, parks


def test_run_on_main_wakes_leave_no_stale_promise_waiters():
    """ADVICE r5 low regression: a spurious run_on_main wake on the
    wait_on park path unregisters its event from Promise._ctx_waiters
    before re-parking, so repeated wakes against a long-lived promise
    never accumulate dead waiter events."""
    import time as _time

    from hclib_tpu.runtime.promise import Promise

    sizes = []

    def body():
        rt = hc.current_runtime()
        prom = Promise()

        def pesterer():
            for _ in range(6):
                rt.run_on_main(lambda: None)
                _time.sleep(0.02)
                with prom._lock:
                    sizes.append(len(prom._ctx_waiters))
            prom.put(7)

        with hc.finish():
            hc.async_(pesterer)
            prom.future.wait()  # parked main thread, pestered awake
        assert prom.get() == 7

    hc.launch(body, nworkers=2)
    # At most the main thread's one live registration at any sample point
    # (0 while it is between unregister and re-register).
    assert max(sizes) <= 1, sizes


def test_run_on_main_from_escaping_task_at_finalize():
    """An escaping task still blocked in run_on_main when the root finish
    drains is serviced by the finalize join loop (the reference's
    src/hclib-runtime.c:1420-1423)."""
    import threading
    import time as _time

    main_ident = threading.get_ident()
    got = []

    def body():
        started = threading.Event()

        def late():
            started.set()
            _time.sleep(0.15)  # root finish drains before this fires
            got.append(hc.current_runtime().run_on_main(threading.get_ident))

        hc.current_runtime().spawn(late, escaping=True)
        started.wait(5.0)  # a worker is executing it when the root drains

    hc.launch(body, nworkers=2)
    assert got == [main_ident]


def test_every_registered_env_name_has_a_reader():
    """An option must not outlive its reader: every row of
    ``runtime/env.py:REGISTRY`` is named, by its canonical or a legacy
    spelling, somewhere in the program outside the registry's own rows
    (``HCLIB_TPU_BIG_TESTS`` is the tests' own switch and is read there)."""
    import pathlib
    import re

    from hclib_tpu.runtime import env
    from tools import lint

    root = pathlib.Path(__file__).resolve().parents[1]
    texts = {
        f: pathlib.Path(f).read_text(errors="replace")
        for f in lint._files([
            str(root / p) for p in
            ("hclib_tpu", "tools", "chip_smoke.py", "__graft_entry__.py")
        ])
    }
    own = texts[str(root / lint.ENV_MODULE)]
    start = own.index("REGISTRY = {")
    texts[str(root / lint.ENV_MODULE)] = (
        own[:start] + own[own.index("\n}\n", start):]
    )
    program = "\n".join(texts.values())
    tests = "\n".join(
        f.read_text() for f in (root / "tests").glob("test_*.py")
        if f != pathlib.Path(__file__).resolve()
    )
    unread = [
        var.name for var in env.REGISTRY.values()
        if not any(
            re.search(rf"\b{s}\b",
                      tests if var.name == "HCLIB_TPU_BIG_TESTS" else program)
            for s in (var.name,) + var.legacy
        )
    ]
    assert not unread, unread
