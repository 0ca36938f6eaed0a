"""Process-wide content-keyed program cache (ISSUE 18).

The acceptance spine: cache-on vs cache-off lowered text byte-identical
for the curated builders (fib, frontier SSSP, forasync tile, a
tenant+egress stream, a checkpoint-enabled build); a content-identical
second instance's first run is a HIT sharing the first instance's
executable with bit-identical results; every key component - the hclint
layout table, the kernel roster, kernel bodies, each device-word knob,
the mesh shape, the runner variant - provably misses when changed; cap
semantics (malformed or non-positive raises, cap=1 evicts and the
rebuild is bit-identical); fail-open on unfingerprintable input.
"""

import inspect
import threading
import time

import numpy as np
import pytest
from conftest import front_door, send

import jax
import jax.numpy as jnp

from hclib_tpu.device.descriptor import TaskGraphBuilder
from hclib_tpu.device.frontier import _KINDS, Graph, make_frontier_megakernel
from hclib_tpu.device.forasync_tier import make_forasync_megakernel
from hclib_tpu.device.inject import StreamingMegakernel
from hclib_tpu.device.megakernel import Megakernel
from hclib_tpu.device.tenants import TenantSpec, TenantTable
from hclib_tpu.device.egress import EgressSpec
from hclib_tpu.device.workloads import (
    FIB,
    make_fib_megakernel,
    make_uts_megakernel,
    rmat_edges,
    stencil_loop,
)
from hclib_tpu.runtime import progcache, spans
from hclib_tpu.runtime.progcache import (
    Uncacheable,
    cache_cap,
    cache_stats,
    enabled,
    fingerprint,
    layout_fingerprint,
    megakernel_fingerprint,
    mesh_key,
    probe,
    shared_build,
)


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Counter/entry isolation: the registry is process-wide state."""
    progcache.reset()
    yield
    progcache.reset()


def _lowered_text(mk, fuel=1 << 12):
    """The program the megakernel would run, as bytes: stage an empty
    graph for shapes only (lowered text depends on specs, not data)."""
    tasks, succ, ring, counts = TaskGraphBuilder().finalize(
        capacity=mk.capacity, succ_capacity=mk.succ_capacity
    )
    args = [tasks, succ, ring, counts, np.zeros(mk.num_values, np.int32)]
    for s in mk.data_specs.values():
        args.append(np.zeros(s.shape, s.dtype))
    if mk.checkpoint:
        args.append(Megakernel.quiesce_words(None))
    structs = [
        jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)
        for x in args
    ]
    return mk._build_raw(fuel).lower(*structs).as_text()


def _bump_mk(**kw):
    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    kw.setdefault("capacity", 128)
    kw.setdefault("num_values", 4)
    return Megakernel(
        kernels=[("bump", bump)], succ_capacity=8, interpret=True, **kw,
    )


# ------------------------------------------------ fingerprint basics


def test_fingerprint_is_content_not_identity():
    def mk_fn(k):
        def f(ctx):
            ctx.set_value(0, k)

        return f

    # Two distinct function OBJECTS with identical content agree...
    assert fingerprint(mk_fn(3)) == fingerprint(mk_fn(3))
    # ...and a closure-cell (or constant) change is content.
    assert fingerprint(mk_fn(3)) != fingerprint(mk_fn(4))
    a = np.arange(8, dtype=np.int32)
    assert fingerprint(a) == fingerprint(a.copy())
    b = a.copy()
    b[3] = 99
    assert fingerprint(a) != fingerprint(b)


def test_fingerprint_cycle_and_depth_fail_open():
    cyc = []
    cyc.append(cyc)
    fingerprint(cyc)  # cycle guard terminates, no raise
    deep = ()
    for _ in range(64):
        deep = (deep,)
    with pytest.raises(Uncacheable):
        fingerprint(deep)


# --------------------------- key sensitivity, one test per component


def test_key_sensitive_to_layout_table(monkeypatch):
    """ANY device-word layout drift invalidates every key (a stale
    program against a new ABI must be impossible)."""
    from hclib_tpu.analysis import layout as L

    mk = _bump_mk()
    before = megakernel_fingerprint(mk)
    lf = layout_fingerprint()
    patched = dict(L.LAYOUT)
    patched["__progcache_test_word__"] = ("smem", 0, 1)
    monkeypatch.setattr(L, "LAYOUT", patched)
    assert layout_fingerprint() != lf
    assert megakernel_fingerprint(mk) != before


def test_key_sensitive_to_kernel_roster():
    def bump(ctx):
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    one = Megakernel(
        kernels=[("bump", bump)], capacity=128, num_values=4,
        succ_capacity=8, interpret=True,
    )
    two = Megakernel(
        kernels=[("bump", bump), ("bump2", bump)], capacity=128,
        num_values=4, succ_capacity=8, interpret=True,
    )
    assert megakernel_fingerprint(one) != megakernel_fingerprint(two)


def test_key_sensitive_to_kernel_body():
    def mk_with(body):
        return Megakernel(
            kernels=[("k", body)], capacity=128, num_values=4,
            succ_capacity=8, interpret=True,
        )

    def body_a(ctx):
        ctx.set_value(0, ctx.arg(0) + 1)

    def body_b(ctx):
        ctx.set_value(0, ctx.arg(0) + 2)

    assert (
        megakernel_fingerprint(mk_with(body_a))
        != megakernel_fingerprint(mk_with(body_b))
    )


@pytest.mark.parametrize(
    "kw",
    [
        {"checkpoint": True},
        {"quiesce_stride": 4},
        {"trace": 4096},
        {"capacity": 256},
        {"num_values": 8},
    ],
)
def test_key_sensitive_to_each_device_word_knob(kw):
    """One knob flipped from the baseline = a different program key."""
    base = _bump_mk()
    other = _bump_mk(**kw)
    assert megakernel_fingerprint(base) != megakernel_fingerprint(other)


@pytest.mark.parametrize("attr,value", [
    ("lane_max_age", 7),
    ("priority_buckets", 4),
])
def test_key_sensitive_to_dispatch_tier_knobs(attr, value):
    """lane_max_age / priority_buckets ride the key directly (the
    fingerprint reads the resolved attributes, so the env spellings
    are covered by the same read)."""
    base = make_fib_megakernel(interpret=True, batch_width=2)
    other = make_fib_megakernel(interpret=True, batch_width=2)
    assert megakernel_fingerprint(base) == megakernel_fingerprint(other)
    setattr(other, attr, getattr(other, attr) + value)
    assert megakernel_fingerprint(base) != megakernel_fingerprint(other)


def test_key_sensitive_to_batch_routing():
    scalar = make_fib_megakernel(interpret=True)
    routed = make_fib_megakernel(interpret=True, batch_width=2)
    assert (
        megakernel_fingerprint(scalar) != megakernel_fingerprint(routed)
    )


def test_key_sensitive_to_mesh_and_variant():
    from hclib_tpu.parallel.mesh import cpu_mesh

    m2, m4 = cpu_mesh(2), cpu_mesh(4)
    assert mesh_key(m2) != mesh_key(m4)
    assert mesh_key(m2) == mesh_key(cpu_mesh(2))
    # The runner variant (hop order, quantum, windows...) is half the
    # key: same megakernel, different variant = different program.
    mk = _bump_mk()

    def build():
        return object()

    a, sa = shared_build(mk, ("resident", mesh_key(m2), 64), build)
    b, sb = shared_build(mk, ("resident", mesh_key(m2), 32), build)
    assert not sa["hit"] and not sb["hit"] and a is not b
    c, sc = shared_build(mk, ("resident", mesh_key(m2), 64), build)
    assert sc["hit"] and c is a


def test_key_sensitive_to_tenants_and_egress():
    """Compiled-surface stream facts key the variant: tenant count,
    region rows, egress depth (WRR weights ride tctl and must not)."""
    mk = _bump_mk()
    variants = [
        ("stream", 32, None, None, 8, 1 << 12),
        ("stream", 32, (1, 32), None, 8, 1 << 12),
        ("stream", 32, (2, 16), None, 8, 1 << 12),
        ("stream", 32, (1, 32), 64, 8, 1 << 12),
    ]
    digests = {fingerprint(v) for v in variants}
    assert len(digests) == len(variants)


# ------------------------------- byte identity: the curated builders


CURATED = {
    "fib": lambda: make_fib_megakernel(interpret=True),
    "fib-checkpoint": lambda: make_fib_megakernel(
        interpret=True, checkpoint=True
    ),
    "uts-checkpoint": lambda: make_uts_megakernel(
        max_depth=6, interpret=True, checkpoint=True
    ),
}


def _frontier_mk():
    n, src, dst, w = rmat_edges(4, efactor=4, seed=7)
    return make_frontier_megakernel(
        _KINDS["sssp"](), Graph(n, src, dst, w), width=4, interpret=True
    )


def _forasync_mk():
    tk, _, _ = stencil_loop(16, 512)
    return make_forasync_megakernel(tk, width=4, interpret=True)


CURATED["frontier-sssp"] = _frontier_mk
CURATED["forasync-tile"] = _forasync_mk


@pytest.mark.parametrize("name", sorted(CURATED))
def test_cache_on_off_lowered_text_byte_identical(name, monkeypatch):
    """The cache changes WHEN a program is built, never WHAT: with the
    cache forced off, a fresh content-identical instance lowers to the
    exact bytes the cache-on instance lowers to."""
    factory = CURATED[name]
    monkeypatch.delenv("HCLIB_TPU_PROGRAM_CACHE", raising=False)
    assert enabled()
    on_text = _lowered_text(factory())
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", "0")
    assert not enabled()
    off_text = _lowered_text(factory())
    assert on_text == off_text
    # Content-identical instances agree byte-for-byte (key-equal
    # implies program-equal for the builder), so sharing is sound.
    monkeypatch.delenv("HCLIB_TPU_PROGRAM_CACHE", raising=False)
    assert _lowered_text(factory()) == on_text
    # The build ledger listens and changes nothing (ISSUE 53): it saw
    # the three lowerings, outside any bracket, and the text is what a
    # process that never asks for it lowers to.
    rows = [r for r in progcache.build_ledger() if r["lower_s"] > 0]
    assert rows and all(r["runner"] is None for r in rows)
    assert sum(r["traces"] for r in progcache.build_ledger()) >= 3
    progcache.reset()
    assert progcache.build_ledger() == []
    assert _lowered_text(factory()) == on_text


def test_second_identical_fib_instance_hits_and_matches():
    b1, b2 = TaskGraphBuilder(), TaskGraphBuilder()
    b1.add(FIB, args=[8], out=0)
    b2.add(FIB, args=[8], out=0)
    iv1, _, i1 = make_fib_megakernel(interpret=True).run(b1)
    assert i1["program_cache"]["hit"] is False
    assert i1["program_cache"]["build_s"] > 0.0
    iv2, _, i2 = make_fib_megakernel(interpret=True).run(b2)
    assert i2["program_cache"]["hit"] is True
    assert i2["program_cache"]["build_s"] == 0.0
    assert iv1.tobytes() == iv2.tobytes()
    s = cache_stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["entries"] == 1


def test_stream_cold_start_hits_and_matches(monkeypatch):
    """Serving cold start: a second identical tenant+egress stream's
    first entry reuses the first stream's executable, bit-identically;
    the cache-off arm produces the same bytes with counters untouched."""
    def serve(tag):
        table = TenantTable(
            [TenantSpec("gold")], 32, clock=lambda: 100.0,
            egress=EgressSpec(depth=64),
        )
        sm = StreamingMegakernel(
            _bump_mk(), ring_capacity=32, tenants=table
        )
        subs = [sm.submit("gold", 0, args=[i + 1]) for i in range(4)]
        sm.close()
        b = TaskGraphBuilder()
        b.add(0, args=[1000])
        iv, info = sm.run_stream(b)
        for sub in subs:
            sub.future.result(timeout=5.0)
        return iv.tobytes(), info

    cold_bytes, cold_info = serve("cold")
    assert cold_info["program_cache"]["hit"] is False
    warm_bytes, warm_info = serve("warm")
    assert warm_info["program_cache"]["hit"] is True
    assert warm_bytes == cold_bytes
    before = cache_stats()
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", "0")
    off_bytes, off_info = serve("off")
    assert off_bytes == cold_bytes
    assert off_info["program_cache"]["hit"] is False
    assert cache_stats() == before


# ------------------------------------------------ knobs + cap + LRU


def test_enabled_spelling(monkeypatch):
    monkeypatch.delenv("HCLIB_TPU_PROGRAM_CACHE", raising=False)
    assert enabled()
    for off in ("", "0"):
        monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", off)
        assert not enabled()
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE", "1")
    assert enabled()


def test_cap_validation(monkeypatch):
    monkeypatch.delenv("HCLIB_TPU_PROGRAM_CACHE_CAP", raising=False)
    assert cache_cap() == 256
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE_CAP", "banana")
    with pytest.raises(ValueError):
        cache_cap()
    for bad in ("0", "-3"):
        monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE_CAP", bad)
        with pytest.raises(ValueError, match="PROGRAM_CACHE_CAP"):
            cache_cap()


def test_cap_one_evicts_and_rebuild_is_bit_identical(monkeypatch):
    """cap=1: program B evicts A; rebuilding A misses (the eviction
    counted) and the rebuilt executable produces A's exact bytes."""
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE_CAP", "1")

    def run_fib(n):
        b = TaskGraphBuilder()
        b.add(FIB, args=[n], out=0)
        iv, _, info = make_fib_megakernel(interpret=True).run(b)
        return iv.tobytes(), info["program_cache"]

    def run_bump():
        b = TaskGraphBuilder()
        b.add(0, args=[7])
        iv, _, info = _bump_mk().run(b)
        return iv.tobytes(), info["program_cache"]

    first, pc1 = run_fib(8)
    assert not pc1["hit"]
    _, pcb = run_bump()          # different program: evicts fib at cap=1
    assert not pcb["hit"]
    assert cache_stats()["evictions"] >= 1
    assert cache_stats()["entries"] == 1
    again, pc2 = run_fib(8)
    assert not pc2["hit"]        # evicted = a real rebuild
    assert again == first        # ...and bit-identical


def test_lru_order_refreshes_on_hit(monkeypatch):
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE_CAP", "2")
    mk = _bump_mk()
    a, _ = shared_build(mk, ("v", 1), object)
    shared_build(mk, ("v", 2), object)
    a2, sa2 = shared_build(mk, ("v", 1), object)   # refresh A
    assert sa2["hit"] and a2 is a
    shared_build(mk, ("v", 3), object)             # evicts B, not A
    a3, sa3 = shared_build(mk, ("v", 1), object)
    assert sa3["hit"] and a3 is a


def test_eviction_is_cost_weighted():
    """An expensive build survives a burst of cheap ones that would
    have rolled it off a plain LRU tail; uniform costs stay exact LRU."""
    from hclib_tpu.runtime.progcache import ProgramCache

    cap = 8
    pc = ProgramCache()
    pc.put(("exp",), "EXP", cap, build_s=40.0)
    for i in range(cap - 1):
        pc.put(("cheap", i), i, cap, build_s=0.01)
    assert len(pc) == cap and pc.evictions == 0
    pc.put(("cheap", cap - 1), cap - 1, cap, build_s=0.01)  # overflow
    assert pc.evictions == 1
    assert pc.contains(("exp",))          # LRU-oldest, but costly: kept
    assert not pc.contains(("cheap", 0))  # cheapest in the LRU window
    assert pc.get(("exp",)) == "EXP"

    pc2 = ProgramCache()
    for i in range(cap + 1):
        pc2.put(("u", i), i, cap, build_s=0.5)
    assert not pc2.contains(("u", 0)) and pc2.contains(("u", 1))
    assert pc2.evictions == 1


def test_probe_reads_without_counting():
    mk = _bump_mk()
    assert probe(mk, ("v",)) is False
    fn, _ = shared_build(mk, ("v",), object)
    before = cache_stats()
    assert probe(mk, ("v",)) is True
    assert cache_stats() == before


def test_unfingerprintable_variant_fails_open():
    """Irreducible input = a private build: no counters move, nothing
    enters the table, and the build still happens."""
    deep = ()
    for _ in range(64):
        deep = (deep,)
    mk = _bump_mk()
    before = cache_stats()
    fn, stats = shared_build(mk, deep, object)
    assert fn is not None and stats["hit"] is False
    assert cache_stats() == before


def test_metrics_exports_program_cache_gauges():
    from hclib_tpu.runtime.metrics import MetricsRegistry

    b = TaskGraphBuilder()
    b.add(FIB, args=[6], out=0)
    _, _, info = make_fib_megakernel(interpret=True).run(b)
    reg = MetricsRegistry()
    reg.add_run_info("fib", info)
    m = reg.snapshot()["metrics"]
    assert m["program_cache.misses"] == 1.0
    assert m["program_cache.entries"] == 1.0
    assert m["program_cache.hits"] == 0.0
    assert m["program_cache.evictions"] == 0.0
    assert "fib.program_cache.build_s" in m
    assert "fib.program_cache.cache_lookup_s" in m


def test_first_call_is_the_call_from_one_large_frame():
    """``first_call(fn, *args)`` is ``fn(*args)``: the value comes back,
    an exception passes through, and the frame it calls from is larger
    than the 16 KiB chunks CPython carves frames from, so the trace
    behind it never straddles a chunk's end (ISSUE 47)."""
    assert progcache.first_call(lambda a, b: (b, a), 1, 2) == (2, 1)
    with pytest.raises(KeyError, match="gone"):
        progcache.first_call({}.__getitem__, "gone")
    depth = progcache.first_call(lambda: len(inspect.stack()))
    assert depth == len(inspect.stack()) + 3  # first_call, roomy, the lambda
    assert progcache._roomy_frame().__code__.co_nlocals * 8 > 4 * 16384


# ------------------------------------------- the build ledger (ISSUE 53)


@pytest.fixture()
def opened(monkeypatch):
    """The profiler spans the program opens, in order (as
    tests/test_host_spans.py records them)."""
    names = []

    class Span:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Span)
    return names


def _fib(mk=None, n=8):
    b = TaskGraphBuilder()
    b.add(FIB, args=[n], out=0)
    return (mk or make_fib_megakernel(interpret=True)).run(b)


def _bracketed():
    return [r for r in progcache.build_ledger() if r["runner"] is not None]


def _built(row):
    return row["trace_s"] + row["lower_s"] + row["compile_s"]


def test_first_run_leaves_one_bracketed_row_and_a_steady_call_nothing(
        opened):
    mk = make_fib_megakernel(interpret=True)
    _, _, info = _fib(mk)
    (row,) = _bracketed()
    assert (row["name"], row["runner"]) == ("tpu_custom_call", "megakernel")
    assert row["hit"] is False and len(row["key"]) == 65
    assert min(row["trace_s"], row["lower_s"], row["compile_s"]) > 0
    assert _built(row) <= row["wall_s"] == row["build_s"]
    assert row["first_run_s"] == pytest.approx(row["wall_s"] - _built(row))
    assert row["traces"] == 1 and row["first"] < row["last"]
    # a run reports the row, and stats_dict carries it
    assert info["program_cache"] == {k: row[k] for k in info["program_cache"]}
    assert set(info["program_cache"]) == {
        "hit", "key", "cache_lookup_s", "build_s", "wall_s", "first_run_s",
        "trace_s", "lower_s", "compile_s", "cache_retrieval_s",
        "persistent_hit", "traces"}
    assert mk.stats_dict()["program_cache"] == info["program_cache"]
    assert opened.count("bench:prog.first_call") == 1
    assert "bench:prog.compiled" in opened
    # a second run, and a second instance's first (an in-process hit):
    # no row, neither span, and JAX told the listeners nothing
    heard = progcache._LEDGER.heard
    ledger = progcache.build_ledger()
    del opened[:]
    _fib(mk)
    _, _, again = _fib()
    assert again["program_cache"]["hit"] is True
    assert again["program_cache"]["wall_s"] == 0.0 == _built(
        again["program_cache"])
    assert progcache._LEDGER.heard == heard > 0
    assert not [n for n in opened if n.startswith("bench:prog.")]
    assert progcache.build_ledger() == ledger


def test_a_jit_that_calls_a_jit_is_counted_once_and_found_by_name():
    @jax.jit
    def ledger_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def ledger_outer(x):
        return ledger_inner(jnp.cos(x)) + 1

    t0 = time.time()
    ledger_outer(np.ones(4, np.float32)).block_until_ready()
    wall = time.time() - t0
    rows = {r["name"]: r for r in progcache.build_ledger()}
    # sin, multiply, ledger_inner, cos and add were traced inside
    # ledger_outer's trace: one row, one trace, under the outer's name
    # (the trace calls it ledger_outer, the lowering jit(ledger_outer))
    assert set(rows) == {"ledger_outer"}
    row = rows["ledger_outer"]
    assert row["runner"] is None and row["traces"] == 1
    assert min(row["trace_s"], row["lower_s"], row["compile_s"]) > 0
    assert _built(row) <= wall
    assert t0 <= row["first"] < row["last"] <= t0 + wall
    heard = progcache._LEDGER.heard
    ledger_outer(np.ones(4, np.float32)).block_until_ready()
    assert progcache._LEDGER.heard == heard
    assert progcache.build_totals() == {
        "trace_s": row["trace_s"], "lower_s": row["lower_s"],
        "compile_s": row["compile_s"],
        "cache_retrieval_s": row["cache_retrieval_s"], "traces": 1,
        "programs": 1}
    progcache.reset()
    assert progcache.build_ledger() == []
    assert progcache.build_totals()["programs"] == 0


def test_a_stream_leaves_a_row_an_entry_program(opened):
    """The plain entry program at the first entry; the delta program
    once a publish wants it, not before."""
    sm, _ = front_door("plain")
    send(sm, None, 6)
    sm.close()
    b = TaskGraphBuilder()
    b.add(0, args=[1000])
    _, info = sm.run_stream(b)
    assert info["stream"]["ring_deltas"] == 0
    (plain,) = _bracketed()
    assert (plain["name"], plain["runner"]) == ("tpu_custom_call", "stream")
    assert min(plain["trace_s"], plain["lower_s"], plain["compile_s"]) > 0
    assert _built(plain) <= plain["wall_s"]
    assert info["program_cache"] == sm.stats_dict()["program_cache"] == {
        k: plain[k] for k in info["program_cache"]}
    assert opened.count("bench:prog.first_call") == 1
    # the bracket stands around the first entry's launch and its wait
    at = opened.index("bench:prog.first_call")
    assert opened[at + 1] == "bench:stream.launch"

    progcache.reset()
    del opened[:]
    sm, table = front_door("tenants", max_in_flight=4)
    send(sm, table, 24)
    sm.close()
    _, info = sm.run_stream(b, quantum=4, max_rounds=2)
    assert info["stream"]["ring_deltas"] >= 2
    first, delta = _bracketed()
    assert first["runner"] == delta["runner"] == "stream"
    assert first["key"] != delta["key"] and first["last"] <= delta["first"]
    assert delta["trace_s"] > 0 and _built(delta) <= delta["wall_s"]
    # info reports the plain program's row; each was bracketed once
    assert info["program_cache"]["key"] == first["key"]
    assert opened.count("bench:prog.first_call") == 2
    assert opened.count("bench:stream.launch") == info["stream"]["entries"]


def test_two_threads_builds_do_not_take_each_others_spans():
    """One thread's bracket is open while another traces and compiles
    a jit of its own: the row holds the first thread's spans only, and
    the other's jit is found by its name."""
    def ledger_mine(x):
        return x * 3 + 1

    def ledger_theirs(x):
        return x * 5 - 1

    fn, stats = shared_build(
        _bump_mk(), ("ledger-thread",), lambda: jax.jit(ledger_mine))
    assert stats["hit"] is False and stats["wall_s"] == 0.0
    x = np.ones(4, np.float32)
    theirs = threading.Thread(
        target=lambda: jax.jit(ledger_theirs)(x).block_until_ready())
    with progcache.building("test", fn, stats):
        theirs.start()
        theirs.join(timeout=120)
        assert not theirs.is_alive()
        fn(x).block_until_ready()
    rows = {r["name"]: r for r in progcache.build_ledger()}
    assert set(rows) == {"ledger_mine", "ledger_theirs"}
    mine, other = rows["ledger_mine"], rows["ledger_theirs"]
    assert mine["runner"] == "test" and other["runner"] is None
    assert mine["traces"] == other["traces"] == 1
    assert min(other["trace_s"], other["lower_s"], other["compile_s"]) > 0
    # the other thread's spans lie inside the bracket's wall and are
    # not in its row: built and first_run_s stay this thread's
    assert mine["first"] <= other["first"] < other["last"] <= mine["last"]
    assert _built(mine) + _built(other) <= mine["wall_s"]
    assert stats == {k: mine[k] for k in stats}
    # a hit, or no stats at all, opens nothing
    hit_fn, hit = shared_build(
        _bump_mk(), ("ledger-thread",), lambda: jax.jit(ledger_mine))
    assert hit["hit"] and hit_fn is fn
    for nothing in (hit, None):
        with progcache.building("test", fn, nothing):
            pass
    assert len(progcache.build_ledger()) == 2


def test_eviction_weighs_the_first_calls_wall(monkeypatch):
    """Two real programs, one slow to trace, oldest in a full cache:
    the victim is the one whose first call was the shorter, not the
    one whose ``jax.jit(...)`` constructor happened to return sooner."""
    monkeypatch.setenv("HCLIB_TPU_PROGRAM_CACHE_CAP", "7")

    def run(body):
        mk = Megakernel(kernels=[("bump", body)], capacity=128,
                        num_values=4, succ_capacity=8, interpret=True)
        b = TaskGraphBuilder()
        b.add(0, args=[7])
        iv, _, info = mk.run(b)
        assert int(iv[0]) == 7
        return mk, info["program_cache"]

    def slow(ctx):  # the body runs while the kernel is traced
        time.sleep(2.0)
        ctx.set_value(0, ctx.value(0) + ctx.arg(0))

    def cheap(ctx):
        ctx.set_value(0, ctx.arg(0) + ctx.value(0))

    (mk_slow, pc_slow), (mk_cheap, pc_cheap) = run(slow), run(cheap)
    assert pc_slow["trace_s"] >= 2.0 > pc_cheap["trace_s"]
    assert pc_slow["wall_s"] > pc_cheap["wall_s"] > 0
    variant = ("megakernel-exec", 1 << 22, False, (), ())
    assert probe(mk_slow, variant) and probe(mk_cheap, variant)
    for i in range(5):  # never called: they weigh nothing, and are newer
        shared_build(mk_slow, ("filler", i), object)
    assert cache_stats() == {"hits": 0, "misses": 7, "evictions": 0,
                             "entries": 7}
    shared_build(mk_slow, ("filler", 5), object)  # overflow
    assert cache_stats()["evictions"] == 1
    # the window is the two least recently used: the slow build, oldest
    # of all, stays; the cheap one goes
    assert probe(mk_slow, variant) and not probe(mk_cheap, variant)


def test_metrics_exports_the_ledgers_sums():
    from hclib_tpu.runtime.metrics import MetricsRegistry

    _, _, info = _fib()
    reg = MetricsRegistry()
    reg.add_run_info("fib", info)
    m = reg.snapshot()["metrics"]
    totals = progcache.build_totals()
    for k in ("trace_s", "lower_s", "compile_s", "cache_retrieval_s",
              "traces", "programs"):
        assert m[f"program_cache.{k}"] == float(totals[k])
    for k in ("trace_s", "lower_s", "compile_s", "first_run_s", "wall_s"):
        assert m[f"fib.program_cache.{k}"] == info["program_cache"][k] > 0
