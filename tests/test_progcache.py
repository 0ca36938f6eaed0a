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

import numpy as np
import pytest

import jax

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
from hclib_tpu.runtime import progcache
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
