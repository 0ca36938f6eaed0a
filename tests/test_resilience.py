"""Resilience subsystem tests (ISSUE 1): cancellation propagation,
deadline StallError, retry/backoff/quarantine, watchdog escalation,
chaos-plan determinism, and the seeded worker-kill + peer-crash
acceptance run. Every blocking scenario runs under its own deadline -
no test here can hang past it (the feature bounding its own tests)."""

import logging
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import CHILD_SECONDS

import hclib_tpu as hc
from hclib_tpu.models import fib, uts
from hclib_tpu.runtime.resilience import _hash01


# ---------------------------------------------------------------- cancel

def test_cancel_scope_skips_queued_tasks():
    """Cancelling a scope drops its queued tasks (they drain without
    running) and end_finish raises CancelledError."""
    ran = []

    def body():
        with pytest.raises(hc.CancelledError):
            with hc.finish() as fin:
                fin.scope.cancel("test cancel")
                # Spawns into the cancelled scope refuse; pre-queued tasks
                # are exercised below with tasks queued BEFORE the cancel.
                hc.async_(ran.append, -1)
        fin = hc.start_finish()
        hc.async_(lambda: time.sleep(0.05))
        for i in range(200):
            hc.async_(ran.append, i)
        time.sleep(0.01)
        fin.scope.cancel("drop the backlog")
        with pytest.raises(hc.CancelledError):
            hc.end_finish(fin)
        # Drain the cancelled backlog inline: skipped bodies count as
        # cancelled_tasks, and the finish quiesces without running them.
        while hc.yield_():
            pass

    rt = hc.Runtime(nworkers=2)
    rt.run(body, deadline_s=30)
    assert -1 not in ran
    assert len(ran) < 200  # the bulk was dropped, not executed
    assert rt.cancelled_tasks > 0
    assert rt.stats_dict()["resilience"]["cancelled_tasks"] > 0


def test_cancel_is_inherited_by_child_scopes():
    """A child finish of a cancelled parent is cancelled by inheritance."""

    def body():
        with pytest.raises(hc.CancelledError):
            with hc.finish() as outer:
                outer.scope.cancel("outer down")
                with hc.finish() as inner:
                    assert inner.scope.cancelled()  # by inheritance
                    hc.async_(lambda: None)  # must refuse
                pytest.fail("child scope accepted work under cancel")

    hc.launch(body, nworkers=2, deadline_s=30)


def test_cancel_wakes_blocked_waiter():
    """A context blocked in Promise.wait inside a cancelled scope wakes
    and raises promptly (event-driven, not a timeout)."""
    woke = []

    def body():
        p = hc.Promise()
        with pytest.raises(hc.CancelledError):
            with hc.finish() as fin:
                def waiter():
                    try:
                        p.future.wait()
                    except hc.CancelledError:
                        woke.append(time.monotonic())
                        raise

                hc.async_(waiter)
                time.sleep(0.1)  # let the waiter park
                t0 = time.monotonic()
                fin.scope.cancel("wake up")
                woke.append(t0)

    hc.launch(body, nworkers=2, deadline_s=30)
    assert len(woke) == 2
    t0, t_wake = min(woke), max(woke)
    assert t_wake - t0 < 5.0  # woken by the cancel, not any timeout


def test_spawn_into_cancelled_scope_raises():
    def body():
        with pytest.raises(hc.CancelledError):
            with hc.finish() as fin:
                fin.scope.cancel()
                hc.async_(lambda: None)

    hc.launch(body, nworkers=2, deadline_s=30)


# -------------------------------------------------------------- deadline

def test_deadline_raises_structured_stall_error():
    """A wedged launch surfaces as StallError (with a stats snapshot) in
    bounded time instead of hanging forever."""
    t0 = time.monotonic()
    with pytest.raises(hc.StallError) as ei:
        hc.launch(
            lambda: hc.Promise().future.wait(), nworkers=2, deadline_s=0.3
        )
    assert time.monotonic() - t0 < 10.0
    assert "deadline" in str(ei.value)
    assert ei.value.stats.get("nworkers") == 2  # snapshot attached


def test_promise_wait_timeout_is_recoverable():
    """Future.wait(timeout=) raises StallError but the runtime (and the
    promise) survive: a later put + wait succeeds."""

    def body():
        p = hc.Promise()
        with pytest.raises(hc.StallError):
            p.future.wait(timeout=0.2)
        p.put("late")
        return p.future.wait()

    assert hc.launch(body, nworkers=2, deadline_s=30) == "late"


def test_finish_timeout_cancels_and_raises():
    """finish(timeout=) bounds the join. The waiter must be adopted by a
    pool worker first (help-first would otherwise inline it onto the
    joining context, whose untimed inner wait parks past the finish
    timeout - the documented help_finish caveat)."""

    def body():
        hang = hc.Promise()
        with pytest.raises(hc.StallError):
            with hc.finish(timeout=0.4):
                hc.async_(lambda: hang.future.wait())
                time.sleep(0.15)  # a pool worker adopts + parks the waiter
        hang.put(None)  # unblock the cancelled waiter

    hc.launch(body, nworkers=2, deadline_s=30)


# ----------------------------------------------------------------- retry

def test_retry_heals_flaky_task():
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ValueError("flake")
        return 42

    pol = hc.RetryPolicy(max_attempts=5, backoff_s=0, jitter=0)
    rt = hc.Runtime(nworkers=2)
    out = rt.run(lambda: hc.async_future(flaky, retry=pol).wait(),
                 deadline_s=30)
    assert out == 42
    assert calls[0] == 3
    assert rt.stats_dict()["resilience"]["retries"] == 2


def test_retry_deferred_backoff_keeps_finish_open():
    """A nonzero backoff defers the re-run through a timer; the finish
    must stay open (no early quiesce, no double check_out) until the
    retried attempt really completes."""
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 4:
            raise ValueError("flake")

    done = []
    pol = hc.RetryPolicy(max_attempts=8, backoff_s=0.005, multiplier=1.0,
                         jitter=0)

    def body():
        with hc.finish():
            hc.async_(flaky, retry=pol)
        done.append(calls[0])  # the finish joined AFTER the last attempt

    hc.launch(body, nworkers=2, deadline_s=30)
    assert done == [4]


def test_retry_exhausted_propagates_by_default():
    pol = hc.RetryPolicy(max_attempts=3, backoff_s=0, jitter=0)

    def body():
        with hc.finish():
            hc.async_(lambda: 1 / 0, retry=pol)

    with pytest.raises(ZeroDivisionError):
        hc.launch(body, nworkers=2, deadline_s=30)


def test_retry_quarantine_contains_poison_task():
    """quarantine=True: the run completes, the failure is recorded in
    stats_dict()['resilience'] with fn/attempts/error."""

    def poison():
        raise ValueError("always fails")

    pol = hc.RetryPolicy(max_attempts=2, backoff_s=0, jitter=0,
                         quarantine=True)
    rt = hc.Runtime(nworkers=2)

    def body():
        with hc.finish():
            hc.async_(poison, retry=pol)
            hc.async_(lambda: None)
        return "survived"

    assert rt.run(body, deadline_s=30) == "survived"
    res = rt.stats_dict()["resilience"]
    assert res["quarantined"] == 1
    q = res["quarantine"][0]
    assert q["fn"] == "poison" and q["attempts"] == 2
    assert "always fails" in q["error"]


def test_retry_policy_backoff_and_jitter_deterministic():
    pol = hc.RetryPolicy(max_attempts=5, backoff_s=0.1, multiplier=2.0,
                         jitter=0)
    assert pol.delay_s(1) == pytest.approx(0.1)
    assert pol.delay_s(3) == pytest.approx(0.4)
    a = hc.RetryPolicy(backoff_s=0.1, jitter=0.5, seed=3)
    b = hc.RetryPolicy(backoff_s=0.1, jitter=0.5, seed=3)
    assert [a.delay_s(1) for _ in range(4)] == [b.delay_s(1) for _ in range(4)]
    # Cancellation/stall signals never retry.
    assert not pol.should_retry(0, hc.CancelledError("x"))
    assert not pol.should_retry(0, hc.StallError("x"))
    assert pol.should_retry(0, ValueError("x"))


# -------------------------------------------------------------- watchdog

def test_watchdog_escalates_to_stall_error(caplog):
    """The escalation ladder's last rung cancels the root scope: a wedged
    launch fails with StallError after ~3 intervals instead of hanging."""
    t0 = time.monotonic()
    with caplog.at_level(logging.WARNING, logger="hclib_tpu.resilience"):
        with pytest.raises(hc.StallError) as ei:
            hc.launch(lambda: hc.Promise().future.wait(),
                      nworkers=1, watchdog_s=0.15)
    assert time.monotonic() - t0 < 30.0
    assert "watchdog" in str(ei.value)
    msgs = [r.getMessage() for r in caplog.records]
    assert any("watchdog" in m for m in msgs)  # rung 1: report via logging
    assert any("runtime stats" in m for m in msgs)  # rung 2: stats dump


def test_watchdog_shuts_down_promptly():
    """Event-based watchdog sleep: a 60s interval must not delay runtime
    teardown (the old time.sleep loop would park the thread for the full
    interval)."""
    rt = hc.Runtime(nworkers=2, watchdog_s=60.0)
    rt.run(lambda: None)
    rt._watchdog_thread.join(timeout=2.0)
    assert not rt._watchdog_thread.is_alive()


# ----------------------------------------------------------------- chaos

def test_fault_plan_hash_is_pure():
    assert _hash01(1, "task", 0) == _hash01(1, "task", 0)
    assert _hash01(1, "task", 0) != _hash01(2, "task", 0)
    assert 0.0 <= _hash01(5, "steal", 9) < 1.0


def test_chaos_same_seed_same_failure_trace():
    """The decision table is a pure function of the seed: two runs of the
    same workload with the same seed fire the same faults; a different
    seed fires a different set."""

    def run(seed):
        plan = hc.FaultPlan(seed=seed, task_failure_rate=0.25)
        v = hc.launch(
            fib.fib_finish, 10, 2, nworkers=2, fault_plan=plan,
            default_retry=hc.RetryPolicy(max_attempts=99, backoff_s=0,
                                         jitter=0),
            deadline_s=60,
        )
        assert v == 55
        return plan.trace_key()

    t1, t2, t3 = run(7), run(7), run(8)
    assert len(t1) > 0
    assert t1 == t2
    assert t1 != t3


def test_chaos_retry_with_backoff_under_load():
    """Injected faults + deferred (timer-based) retries across workers:
    the exact case that exposed the double-check_out and identity-leak
    wedges - must produce the right answer in bounded time."""
    plan = hc.FaultPlan(seed=11, task_failure_rate=0.15,
                        max_task_failures=50)
    out = fib.run(
        12, "finish", nworkers=2, fault_plan=plan,
        default_retry=hc.RetryPolicy(max_attempts=8, backoff_s=0.0005,
                                     jitter=0),
        deadline_s=60.0,
    )
    assert out["value"] == 144


def test_seeded_chaos_worker_kill_and_peer_crash():
    """Acceptance: ONE seeded FaultPlan kills a worker mid-UTS AND
    crashes a procworld peer; the traversal stays exact (worker identity
    re-binds) and the blocked peer op fails with a structured
    ProcWorldError - all in bounded time."""
    from test_procworld_unit import FakeClient
    from hclib_tpu.modules.procworld import ProcWorld, ProcWorldError

    plan = hc.FaultPlan(seed=5, kill_worker=1, kill_worker_after=1,
                        steal_delay_rate=0.1, steal_delay_s=0.001,
                        peer_crash_rank=1, peer_crash_after=0)
    kv = FakeClient(world_size=2)
    w0 = ProcWorld(_client=kv, _rank=0, _size=2, timeout_s=20.0)
    w1 = ProcWorld(_client=kv, _rank=1, _size=2, timeout_s=20.0,
                   fault_plan=plan)
    try:
        with w1._heap_lock:
            w1._heap["x"] = np.zeros(2, np.int32)
        expect = uts.count_seq(uts.T_TINY)[0]
        t0 = time.monotonic()
        # On a loaded 1-vCPU host the whole (50-100 ms) traversal can
        # finish before the doomed worker's OS thread is ever scheduled,
        # so the kill is raced against the run: every attempt must be
        # exact, and the kill must land within a few attempts.
        deaths = 0
        for _ in range(5):
            rt = hc.Runtime(nworkers=4, fault_plan=plan)

            def main():
                n = hc.SumReducer()

                def visit(state, depth):
                    n.add(1)
                    for i in range(uts.num_children(uts.T_TINY, state, depth)):
                        hc.async_(visit, uts.spawn_state(state, i),
                                  depth + 1)

                with hc.finish():
                    hc.async_(visit, uts.root_state(uts.T_TINY.root_seed), 0)
                return n.gather()

            assert rt.run(main, deadline_s=120) == expect
            deaths += rt.worker_deaths
            if deaths:
                break
        with pytest.raises(ProcWorldError):
            w0.get(1, "x")
        assert time.monotonic() - t0 < 60.0
        assert deaths == 1
        key = plan.trace_key()
        assert ("kill_worker", 1) in key and ("peer_crash", 1) in key
    finally:
        w0.close()
        w1.close()


def test_procworld_barrier_names_dead_peer():
    """Unified tombstone protocol: a barrier against a tombstoned peer
    raises ProcWorldError naming the dead rank, not an anonymous
    DEADLINE_EXCEEDED."""
    from test_procworld_unit import FakeClient
    from hclib_tpu.modules.procworld import ProcWorld, ProcWorldError

    kv = FakeClient(world_size=2)
    w0 = ProcWorld(_client=kv, _rank=0, _size=2, timeout_s=2.0)
    try:
        kv.key_value_set_bytes("hcpw/dead/1", b"INTERNAL: dead peer")
        with pytest.raises(ProcWorldError, match="rank 1"):
            w0.barrier()
    finally:
        w0.close()


# ---------------------------------------------------------------- device

def test_streaming_megakernel_context_manager_closes_on_error():
    """__exit__ guarantees close() when the producer body raises, so an
    aborted producer never leaves the injection ring open (host-side
    logic only: no kernel involved)."""
    from hclib_tpu.device.inject import StreamingMegakernel

    sk = StreamingMegakernel(SimpleNamespace(), ring_capacity=8)
    with pytest.raises(RuntimeError, match="producer blew up"):
        with sk:
            sk.inject(fn=0)
            raise RuntimeError("producer blew up")
    assert sk._closed
    with pytest.raises(RuntimeError, match="stream closed"):
        sk.inject(fn=0)


def test_streaming_megakernel_abort_flag():
    from hclib_tpu.device.inject import StreamingMegakernel

    sk = StreamingMegakernel(SimpleNamespace(), ring_capacity=8)
    sk.abort("host gave up")
    with pytest.raises(RuntimeError, match="host gave up"):
        sk.inject(fn=0)


@pytest.mark.parametrize("kind", ["plain", "tenants", "egress", "telemetry"])
def test_stream_abort_mid_burst_is_one_final_entry(kind):
    """abort() after the stream crossed a few entry boundaries: the
    resident state stays on the chip, ONE more entry carries the abort
    word, what the mailbox held resolves, everything else poisons, and
    the latency block is filled - whatever the build."""
    import threading

    from conftest import front_door, seed_builder, send
    from hclib_tpu.runtime.resilience import CancelledError

    sm, table = front_door(kind, max_in_flight=4)
    futs = send(sm, table, 24)
    seen = []

    def aborter():
        while sm.stats_dict()["stream"]["entries"] < 3:
            time.sleep(0.0005)
        seen.append(sm.stats_dict()["stream"]["entries"])
        sm.abort("client gone")

    t = threading.Thread(target=aborter)
    t.start()
    try:
        with pytest.raises(CancelledError, match="client gone"):
            sm.run_stream(
                seed_builder(), quantum=4, max_rounds=2, deadline_s=120.0,
            )
    finally:
        t.join()
    st = sm.stats_dict()
    assert st["aborts"] == 1 and st["abort_reason"] == "client gone"
    assert st["abort_latency_s"] is not None
    assert st["abort_drain_executed"] >= 0
    link = st["stream"]
    assert link["entries"] > seen[0] >= 3
    assert link["uploads"] <= 2 * link["entries"] + 1
    assert link["downloads"] <= link["entries"]
    with pytest.raises(RuntimeError, match="client gone|closed"):
        sm.inject(0, args=[1])
    if kind in ("egress", "telemetry"):
        assert {f.state for f in futs} <= {"RESULT", "POISONED"}
        assert table.futures.conservation()["ok"]


# ------------------------------------------------------------ chaos soak

def _run_soak(extra):
    import os

    return subprocess.run(
        [sys.executable, "tools/chaos_soak.py", "--timeout-s", "240"]
        + extra,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=CHILD_SECONDS,
    )


def test_chaos_soak_smoke():
    """tools/chaos_soak.py smoke sweep: every scenario on one seed, with
    the tool's own hang enforcement; nonzero exit = regression."""
    p = _run_soak(["--seeds", "1"])
    assert p.returncode == 0, f"soak failed:\n{p.stdout}\n{p.stderr}"
    assert '"failures": 0' in p.stdout


@pytest.mark.slow
def test_chaos_soak_full():
    """Standalone soak: more seeds at soak scale (slow tier)."""
    p = _run_soak(["--seeds", "4", "--scale", "soak"])
    assert p.returncode == 0, f"soak failed:\n{p.stdout}\n{p.stderr}"
    assert '"failures": 0' in p.stdout
