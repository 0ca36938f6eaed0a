"""Process-wide content-keyed program cache (ISSUE 18).

Every ``Megakernel`` (and every runner embedding one - sharded steal,
resident mesh, ICI/PGAS fallbacks, the streaming front door) pays the
full JAX trace -> lower -> compile pipeline on its first run, even when
a byte-identical program was built moments ago by another instance: the
dominant cost on warm machines, the tier-1 wall-clock tax, and the whole
price of a serving cold start or an autoscaler resize. The persistent
``JAX_COMPILATION_CACHE_DIR`` does not help: it dedupes identical XLA
*compilations*, after the trace/lower work that dominates warm builds
has already been paid.

This module is the layer above: a process-wide registry of JITTED
EXECUTABLES keyed on a content fingerprint of everything that shapes the
compiled program:

- the kernel table, positionally (the comparison ``CheckpointBundle``
  already uses), plus each kernel's CODE fingerprint (bytecode, consts,
  closure cell values - arrays hash by content) so same-named but
  different-bodied kernels can never collide;
- routed ``BatchSpec``s (width/prefetch/body/drain/priority fns);
- device-word knobs: checkpoint, quiesce_stride, lane_max_age,
  priority_buckets, trace capacity, tenants/egress shape;
- capacities and buffer specs (capacity, num_values, succ_capacity,
  data_specs, scratch_specs, vmem_limit_bytes, uses_row_values,
  tracks_home, interpret);
- the runner's own static config (mesh shape + device ids + hop order,
  steal windows, injection ring shape) via the ``variant`` argument;
- the hclint layout-table fingerprint (``analysis/layout.py``), so any
  device-word layout drift invalidates every key.

A hit returns the very jitted callable a cache-off build would have
produced for the same content - ``jax.jit`` tracing is lazy and cached
per-callable, so the second instance's first call rides JAX's own
fast path with zero trace/lower work. Lowered text is byte-identical
by construction (asserted in ``tests/test_progcache.py`` and the
``program-cache`` perf guard).

Fail-open discipline: a value the fingerprinter cannot reduce to
content (an exotic closure cell, a cycle deeper than the bound) makes
that build UNCACHEABLE - it builds privately, never poisons the table.
Address-bearing ``repr``s are safe by uniqueness: they can only ever
miss, never falsely hit.

Knobs (``runtime/env.py`` registry): ``HCLIB_TPU_PROGRAM_CACHE``
(default on; ``0`` forces off - byte-identity makes on safe under
pytest and in serving alike) and ``HCLIB_TPU_PROGRAM_CACHE_CAP``
(bounded entry count; malformed or non-positive text raises).
Eviction is cost-weighted LRU: on overflow the victim is the entry
with the smallest measured ``build_s`` among the quarter of entries
least recently used, so expensive mesh builds outlive bursts of cheap
scalar ones without letting any entry pin the cache forever. The cost
is the wall of the program's first call (``building`` writes it): the
trace, the lowering and the compile it would cost to lose.

The build ledger (ISSUE 53) is the same module's second half: what
JAX's own monitoring says each jit of the process cost to obtain, by
program (``build_ledger``; the class ``BuildLedger`` says how), and
the two profiler spans that put a build on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import threading
import time
import types
from collections import OrderedDict
from itertools import islice
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax import monitoring

# spelled as every module that opens a span spells it:
# tests/test_host_spans.py holds the source to this one line
from ..runtime.spans import span
from .env import env_int, env_raw

__all__ = [
    "enabled",
    "cache_cap",
    "fingerprint",
    "layout_fingerprint",
    "megakernel_fingerprint",
    "mesh_key",
    "shared_build",
    "first_call",
    "building",
    "build_ledger",
    "build_totals",
    "probe",
    "cache_stats",
    "reset",
]

_DEFAULT_CAP = 256
_MAX_DEPTH = 32


class Uncacheable(Exception):
    """A build input the fingerprinter refuses to reduce to content
    (cycle past the depth bound, an object that raises under
    inspection). The build proceeds uncached - never a wrong hit."""


class _FP:
    """Streaming content hash. Every ``add`` reduces one object to
    bytes fed into blake2b; containers and closures recurse with a
    depth bound and an id-keyed cycle guard."""

    def __init__(self) -> None:
        self._h = hashlib.blake2b(digest_size=16)
        self._seen: Dict[int, int] = {}
        self._pins: list = []  # keep ids alive while memoized

    def _feed(self, *parts) -> None:
        for p in parts:
            b = p if isinstance(p, bytes) else str(p).encode()
            self._h.update(b)
            self._h.update(b"\x1f")

    def digest(self) -> str:
        return self._h.hexdigest()

    # -- recursive reduction --

    def add(self, obj: Any, depth: int = 0) -> None:
        if depth > _MAX_DEPTH:
            raise Uncacheable("fingerprint depth bound exceeded")
        if obj is None or isinstance(obj, (bool, int, float, complex)):
            self._feed("s", type(obj).__name__, repr(obj))
            return
        if isinstance(obj, (str, bytes)):
            self._feed("t", type(obj).__name__, obj)
            return
        oid = id(obj)
        if oid in self._seen:
            self._feed("cycle", self._seen[oid])
            return
        self._seen[oid] = len(self._seen)
        self._pins.append(obj)
        import numpy as np

        if isinstance(obj, np.dtype):
            self._feed("dtype", obj.str)
            return
        if isinstance(obj, np.generic):
            self._feed("npscalar", obj.dtype.str, repr(obj.item()))
            return
        if isinstance(obj, np.ndarray) or type(obj).__name__ == "ArrayImpl":
            a = np.asarray(obj)
            self._feed("nd", a.shape, a.dtype.str)
            self._h.update(np.ascontiguousarray(a).tobytes())
            return
        if isinstance(obj, (tuple, list)):
            self._feed("seq", type(obj).__name__, len(obj))
            for x in obj:
                self.add(x, depth + 1)
            return
        if isinstance(obj, dict):
            self._feed("map", len(obj))
            for k in sorted(obj, key=repr):
                self.add(k, depth + 1)
                self.add(obj[k], depth + 1)
            return
        if isinstance(obj, (set, frozenset)):
            self._feed("set", len(obj))
            for x in sorted(obj, key=repr):
                self.add(x, depth + 1)
            return
        import functools

        if isinstance(obj, functools.partial):
            self._feed("partial")
            self.add(obj.func, depth + 1)
            self.add(obj.args, depth + 1)
            self.add(obj.keywords, depth + 1)
            return
        if isinstance(obj, types.MethodType):
            self._feed("method")
            self.add(obj.__func__, depth + 1)
            self.add(obj.__self__, depth + 1)
            return
        if isinstance(obj, types.FunctionType):
            self._add_fn(obj, depth)
            return
        if isinstance(obj, types.BuiltinFunctionType):
            self._feed("builtin", getattr(obj, "__module__", ""),
                       getattr(obj, "__qualname__", obj.__name__))
            return
        if isinstance(obj, types.CodeType):
            self._add_code(obj, depth)
            return
        if isinstance(obj, type):
            self._feed("class", obj.__module__, obj.__qualname__)
            return
        # ShapeDtypeStruct and kin: shape + dtype IS the content.
        shape = getattr(obj, "shape", None)
        dtype = getattr(obj, "dtype", None)
        if shape is not None and dtype is not None:
            self._feed("sds", type(obj).__name__, tuple(shape), str(dtype))
            return
        # Generic object: type identity + attribute dict. Objects
        # without inspectable state fall through to repr below -
        # address-bearing reprs are SAFE BY UNIQUENESS (permanent
        # miss, never a false hit).
        t = type(obj)
        state = getattr(obj, "__dict__", None)
        if state is None and hasattr(t, "__slots__"):
            state = {
                s: getattr(obj, s)
                for s in t.__slots__ if hasattr(obj, s)
            }
        if isinstance(state, dict):
            self._feed("obj", t.__module__, t.__qualname__)
            self.add(state, depth + 1)
            return
        self._feed("repr", t.__module__, t.__qualname__, repr(obj))

    def _add_fn(self, fn, depth: int) -> None:
        self._feed("fn", getattr(fn, "__module__", ""),
                   getattr(fn, "__qualname__", ""))
        self._add_code(fn.__code__, depth)
        self.add(fn.__defaults__, depth + 1)
        kwd = fn.__kwdefaults__
        if kwd:
            self.add(dict(kwd), depth + 1)
        if fn.__closure__:
            self._feed("closure", len(fn.__closure__))
            for cell in fn.__closure__:
                try:
                    v = cell.cell_contents
                except ValueError:
                    self._feed("emptycell")
                    continue
                self.add(v, depth + 1)

    def _add_code(self, code, depth: int) -> None:
        self._feed("code", code.co_name, code.co_argcount,
                   code.co_flags & 0x0F)
        self._h.update(code.co_code)
        self._feed(*code.co_names)
        for c in code.co_consts:
            if isinstance(c, types.CodeType):
                self._add_code(c, depth + 1)
            else:
                self.add(c, depth + 1)


def fingerprint(*objs: Any) -> str:
    """Content digest of arbitrary host objects (the test/verification
    entry point; raises :class:`Uncacheable` on irreducible input)."""
    fp = _FP()
    for o in objs:
        fp.add(o)
    return fp.digest()


def layout_fingerprint() -> str:
    """Digest of the hclint device-word layout table
    (``analysis/layout.py``: LAYOUT + the checkpoint state-key rosters).
    Part of every program key, so ANY layout drift - a new word, a
    moved offset, a renamed checkpoint member - invalidates the whole
    cache rather than risking a stale program against a new ABI.
    Recomputed per call (the table is small) so tests can prove the
    sensitivity by patching the table."""
    from ..analysis import layout as L

    fp = _FP()
    fp._feed("layout", len(L.LAYOUT))
    for name in sorted(L.LAYOUT):
        fp._feed(name)
        fp.add(L.LAYOUT[name])
    fp._feed(*L._CKPT_STATE_KEYS)
    fp._feed(*L._CKPT_OPT_KEYS)
    return fp.digest()


def mesh_key(mesh) -> Tuple:
    """The mesh facts a compiled program is pinned to: axis names,
    per-axis extents, and the flat device-id order (a reshuffled mesh
    must not reuse another's executable)."""
    return (
        tuple(mesh.axis_names),
        tuple(int(d) for d in mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def megakernel_fingerprint(mk) -> str:
    """Content digest of one ``Megakernel``'s program-shaping state:
    the kernel table (positional names + body fingerprints), routed
    BatchSpecs, buffer specs and capacities, and every device-word
    knob, prefixed with :func:`layout_fingerprint`. Raises
    :class:`Uncacheable` when some component resists content
    reduction (the caller then builds uncached)."""
    fp = _FP()
    fp._feed("hclib-progcache-v1", layout_fingerprint())
    fp._feed("kernels", len(mk.kernel_names))
    for name, fn in zip(mk.kernel_names, mk.kernel_fns):
        fp._feed(name)
        fp.add(fn)
    fp._feed("batch", len(mk.batch_specs))
    for fid, spec in mk.batch_specs:
        fp._feed(fid)
        fp.add(spec)
    fp.add(mk.data_specs)
    fp.add(mk.scratch_specs)
    fp.add((
        mk.capacity, mk.num_values, mk.succ_capacity,
        bool(mk.interpret), bool(mk.uses_row_values),
        mk.vmem_limit_bytes, bool(mk.tracks_home),
        bool(mk.checkpoint), getattr(mk, "quiesce_stride", 1),
        mk.lane_max_age, mk.priority_buckets, mk.read_only,
    ))
    tr = mk.trace
    fp.add(None if tr is None
           else (getattr(tr, "capacity", None), getattr(tr, "words", None)))
    return fp.digest()


# ------------------------------------------------------------ registry

def enabled() -> bool:
    """``HCLIB_TPU_PROGRAM_CACHE``: unset -> on (byte-identity makes
    the cache safe by default, under pytest and in serving alike);
    ``''``/``'0'`` -> off; anything else -> on."""
    v = env_raw("HCLIB_TPU_PROGRAM_CACHE")
    if v is None:
        return True
    return v not in ("", "0")


def cache_cap() -> int:
    """``HCLIB_TPU_PROGRAM_CACHE_CAP``: LRU entry bound (default
    256). Malformed text raises via the env registry; non-positive
    values raise here - a cap of 0 would silently disable caching
    under an innocent-looking spelling."""
    cap = env_int("HCLIB_TPU_PROGRAM_CACHE_CAP", _DEFAULT_CAP)
    if cap < 1:
        raise ValueError(
            f"HCLIB_TPU_PROGRAM_CACHE_CAP={cap} must be >= 1 (set "
            "HCLIB_TPU_PROGRAM_CACHE=0 to turn the cache off)"
        )
    return cap


class ProgramCache:
    """Bounded-LRU registry of jitted executables, with COST-WEIGHTED
    eviction: each entry remembers its measured ``build_s``, and on
    overflow the victim is the CHEAPEST-to-rebuild entry among the
    ``len // 4`` least-recently-used (ties: least recently used, so
    uniform costs - and any cache small enough that the window is one
    entry - degrade to exact LRU). A 40 s resident-mesh build thus
    survives a burst of 50 ms scalar builds that would have rolled it
    off the tail, while a hot expensive entry still cannot pin the
    cache forever (it ages into the window like everything else).
    Thread-safe; builds run outside the lock (a racing identical build
    is wasted work, not a correctness problem - first insert wins so
    every holder shares one callable)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # key -> (fn, build_s); OrderedDict order IS the recency order.
        self._entries: "OrderedDict[Tuple[str, str], Tuple[Any, float]]" \
            = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def contains(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def get(self, key):
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return ent[0]
            return None

    def put(self, key, fn, cap: int, build_s: float = 0.0):
        with self._lock:
            self.misses += 1
            kept = self._entries.setdefault(key, (fn, float(build_s)))
            self._entries.move_to_end(key)
            while len(self._entries) > cap:
                k = max(1, len(self._entries) // 4)
                window = list(islice(self._entries.items(), k))
                # min() is stable: equal costs evict the oldest.
                victim = min(window, key=lambda kv: kv[1][1])[0]
                del self._entries[victim]
                self.evictions += 1
            return kept[0]

    def set_cost(self, key, build_s: float) -> None:
        """The entry's eviction weight, once its first call has shown
        what the build cost; recency is left as it is."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries[key] = (ent[0], float(build_s))

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "entries": len(self._entries),
            }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0


_CACHE = ProgramCache()


def cache_stats() -> Dict[str, int]:
    """Process-wide counters: ``hits`` / ``misses`` / ``evictions`` /
    ``entries`` (the ``program_cache.*`` gauges MetricsRegistry
    exports)."""
    return _CACHE.stats()


def reset() -> None:
    """Drop every entry, zero the counters and empty the build ledger
    (test isolation)."""
    _CACHE.reset()
    _LEDGER.reset()


def _key(mk, variant) -> Optional[Tuple[str, str]]:
    try:
        return (megakernel_fingerprint(mk), fingerprint(variant))
    except Uncacheable:
        return None
    except Exception:
        # Fingerprinting must NEVER sink a build: an inspection that
        # raises (an exotic closure, a half-built object) means
        # uncacheable, not broken.
        return None


def probe(mk, variant) -> bool:
    """True when the program for (mk content, runner variant) is warm
    in the registry - the zero-rebuild read the autoscaler's
    ``ScaleEvent.cache_hit`` records. Does not touch LRU order or the
    hit counters."""
    if not enabled():
        return False
    key = _key(mk, variant)
    return key is not None and _CACHE.contains(key)


def _obtained(hit: bool, key, lookup_s: float) -> Dict[str, Any]:
    """How a runner got its program: the dict ``shared_build`` returns
    and ``building`` fills at the program's first call."""
    return {
        "hit": hit,
        "key": None if key is None else ":".join(key),
        "cache_lookup_s": lookup_s,
        "build_s": 0.0, "wall_s": 0.0, "first_run_s": 0.0,
        **_folded(()),
    }


def shared_build(mk, variant, build: Callable[[], Any]):
    """The one integration point every runner threads its jit through:

    ``fn, stats = shared_build(mk, variant, lambda: jax.jit(...))``

    ``variant`` is any content-reducible object naming the runner's own
    static build parameters (fuel/quantum/windows/mesh/hop order...);
    the megakernel fingerprint plus the variant digest is the cache
    key. Returns the shared callable and a stats dict: ``hit``, ``key``
    (the two digests, ``None`` uncached), ``cache_lookup_s``
    (fingerprint + registry probe) and the build's seconds, all 0.0
    here: ``jax.jit(...)`` is lazy, so what the build cost shows at the
    program's first call, and ``building`` around that call writes it
    in. Cache off / uncacheable input degrade to a plain build with
    ``hit=False``."""
    t0 = time.perf_counter()
    key = None
    if enabled():
        key = _key(mk, variant)
        if key is not None:
            fn = _CACHE.get(key)
            if fn is not None:
                return fn, _obtained(True, key, time.perf_counter() - t0)
    lookup_s = time.perf_counter() - t0
    fn = build()
    if key is not None:
        fn = _CACHE.put(key, fn, cache_cap())
    return fn, _obtained(False, key, lookup_s)


# -------------------------------------------------------- build ledger

_SPAN_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _folded(spans) -> Dict[str, Any]:
    """The seconds of ``(kind, name, start, end, cache_retrieval_s,
    persistent_hit)`` spans by kind, and how many of them were traces."""
    by = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
    for kind, _, start, end, _, _ in spans:
        by[kind] += end - start
    compiles = [sp for sp in spans if sp[0] == "compile"]
    return {
        "trace_s": by["trace"], "lower_s": by["lower"],
        "compile_s": by["compile"],
        "cache_retrieval_s": sum(sp[4] for sp in compiles),
        # every executable of the row came out of the persistent cache
        "persistent_hit": bool(compiles) and all(sp[5] for sp in compiles),
        "traces": sum(sp[0] == "trace" for sp in spans),
    }


class BuildLedger:
    """What each program of the process cost to obtain, as JAX stamps it.

    JAX records a time span (``jax.monitoring``, on ``time.time()``) around
    every trace of a jit (``jaxpr_trace_duration``, named ``f``), every
    lowering (``jaxpr_to_mlir_module_duration``, named ``jit(f)``) and
    every backend compile (``backend_compile_duration``, named
    ``jit(f)``; with the persistent cache warm that span is the load,
    and ``cache_hits`` / ``cache_retrieval_time_sec`` fire inside it on
    the same thread). A pallas kernel's body is traced inside its jit's
    trace and lowered to Mosaic inside its jit's lowering. None of these
    fires on a cached dispatch, so the three listeners cost a steady
    call nothing.

    Spans nest: a jit called while another is traced fires its own
    trace span inside the outer one, and a lowering rule that calls
    ``jnp`` fires trace spans inside the lowering. A thread's spans are
    context managers, so the inner one always ends first; when a span
    ends, the spans of its thread that started after it did are the
    ones it encloses, and they are dropped there and then (a kernel's
    trace fires thousands of them). What is kept is each thread's
    outermost spans, of whatever kind: their seconds add up to no more
    than the wall they lie in.

    ``bracket`` takes the spans that end on its thread while it is open
    for its own row; every other span is found by its ``fun_name``."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # thread -> its outermost spans, in the order they ended
        self._spans: Dict[int, List[tuple]] = {}
        # thread -> [persistent_hit, cache_retrieval_s] of the backend
        # compile open on it
        self._loading: Dict[int, list] = {}
        self._rows: List[Dict[str, Any]] = []
        self.heard = 0  # calls of the three listeners

    def listen(self) -> None:
        """Register the three listeners: once a process (JAX has no way
        to take one back)."""
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_span(self, event, start, end, fun_name="", **_) -> None:
        kind = _SPAN_KINDS.get(event)
        tid = threading.get_ident()
        with self._lock:
            self.heard += 1
            if kind is None:
                return
            mine = self._spans.setdefault(tid, [])
            while mine and mine[-1][2] >= start:
                mine.pop()
            hit, retrieval_s = (
                self._loading.pop(tid, (False, 0.0))
                if kind == "compile" else (False, 0.0))
            name = str(fun_name)
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            mine.append((kind, name, start, end, retrieval_s, hit))
        if kind == "compile":
            # An instant in a profile: an executable was obtained now.
            with span("prog.compiled"):
                pass

    def _load(self, slot: int, value) -> None:
        self._loading.setdefault(
            threading.get_ident(), [False, 0.0])[slot] = value

    def _on_event(self, event, **_) -> None:
        with self._lock:
            self.heard += 1
            if event == _CACHE_HIT:
                self._load(0, True)

    def _on_duration(self, event, secs, **_) -> None:
        with self._lock:
            self.heard += 1
            if event == _CACHE_RETRIEVAL:
                self._load(1, secs)

    @contextlib.contextmanager
    def bracket(self, runner: str, name: str, stats: Dict[str, Any]):
        """Around a program's first call: the spans that end on this
        thread meanwhile are the program's, and ``stats`` (what
        ``shared_build`` returned) becomes its row."""
        tid = threading.get_ident()
        with self._lock:
            first = len(self._spans.get(tid, ()))
        t0 = time.time()
        with span("prog.first_call"):
            yield
        t1 = time.time()
        with self._lock:
            mine = self._spans.get(tid, [])
            inside = mine[first:]
            del mine[first:]
            stats.update(_folded(inside))
            built = (
                stats["trace_s"] + stats["lower_s"] + stats["compile_s"])
            # the first execution, its upload and its read
            stats.update(wall_s=t1 - t0, build_s=t1 - t0,
                         first_run_s=t1 - t0 - built)
            self._rows.append({"name": name, "runner": runner,
                               "first": t0, "last": t1, **stats})

    def rows(self) -> List[Dict[str, Any]]:
        """The bracketed programs in the order they were built, then one
        row a ``fun_name`` for every jit no bracket took."""
        with self._lock:
            out = [dict(r) for r in self._rows]
            loose: Dict[str, list] = {}
            for mine in self._spans.values():
                for sp in mine:
                    loose.setdefault(sp[1], []).append(sp)
        for name, spans in loose.items():
            out.append({
                "name": name, "runner": None,
                "first": min(sp[2] for sp in spans),
                "last": max(sp[3] for sp in spans),
                **_folded(spans),
            })
        return out

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._loading.clear()
            del self._rows[:]
            self.heard = 0


_LEDGER = BuildLedger()
_LEDGER.listen()


def build_ledger() -> List[Dict[str, Any]]:
    """The process's builds so far, one row a program: ``name`` (the
    jit's ``fun_name``; ``jit(f)`` and ``f`` are one name), ``trace_s``
    / ``lower_s`` / ``compile_s`` (each thread's outermost spans only,
    so nothing nested is counted twice), ``cache_retrieval_s`` and
    ``persistent_hit`` (the compile was a load from JAX's persistent
    cache), ``traces`` (outermost trace spans: a jit traced again for a
    new shape or a new layout reads one more), ``first`` / ``last``
    (``time.time()`` stamps). A program a runner built through
    ``building`` has a row of its own with ``runner``, ``key``, ``hit``,
    ``cache_lookup_s``, ``wall_s`` (``build_s`` is its older name) and
    ``first_run_s``; every other row has ``runner`` None."""
    return _LEDGER.rows()


def build_totals() -> Dict[str, float]:
    """The ledger summed over its rows (MetricsRegistry's
    ``program_cache.*`` gauges beside ``cache_stats``)."""
    rows = build_ledger()
    out = {
        k: sum(r[k] for r in rows)
        for k in ("trace_s", "lower_s", "compile_s", "cache_retrieval_s",
                  "traces")
    }
    out["programs"] = len(rows)
    return out


_NOT_BUILDING = contextlib.nullcontext()


def building(runner: str, fn, stats: Optional[Dict[str, Any]]):
    """The context manager a runner puts around a call of its program:
    ``stats`` is what ``shared_build`` returned where this call is the
    program's FIRST, and ``None`` on every later one. ``None``, or an
    in-process hit (another instance already called the callable),
    opens nothing. A first call opens the profiler span
    ``prog.first_call``, takes the trace, lowering and compile spans
    that end inside it for the program's ledger row, records the call's
    wall (``wall_s``, and ``first_run_s``: the wall less those spans)
    into ``stats``, and makes that wall the cache entry's eviction
    weight."""
    if stats is None or stats["hit"]:
        return _NOT_BUILDING
    return _first_call_of(runner, getattr(fn, "__name__", runner), stats)


@contextlib.contextmanager
def _first_call_of(runner: str, name: str, stats: Dict[str, Any]):
    with _LEDGER.bracket(runner, name, stats):
        yield
    if stats["key"] is not None:
        _CACHE.set_cost(tuple(stats["key"].split(":")), stats["wall_s"])


@functools.cache
def _roomy_frame() -> Callable:
    # Locals after the return are never assigned; they only size the
    # frame: 1 << 15 of them make it 256 KiB and its chunk 512 KiB.
    source = "def roomy(fn, args):\n    out = fn(*args)\n    return out\n    "
    source += " = ".join(f"_{i}" for i in range(1 << 15)) + " = None\n"
    scope: Dict[str, Any] = {}
    exec(compile(source, "<progcache.first_call>", "exec"), scope)
    return scope["roomy"]


def first_call(fn: Callable, *args):
    """``fn(*args)`` for a jitted program's FIRST call, the one that
    traces its kernel, made from inside one large frame.

    CPython carves interpreter frames out of 16 KiB chunks: a call that
    runs past a chunk's end maps a new chunk, and the return of that
    call unmaps it. A trace is ten thousand short calls at one depth,
    and where that depth straddles a chunk's end each of them maps,
    faults in and unmaps four pages: one trace of the Cholesky kernel
    took 315,000 page faults against 10,000, and on the chip's host,
    where a fresh page costs 12 us (``PERF.md`` section 6, PR 29 and
    PR 47), 33 s against 8. Which side a trace falls on depends on how
    many frames its caller happens to stand on, so an edit that moves a
    call moves set-up by tens of seconds. A frame too large for a chunk
    gets a chunk of its own, with room behind it for the whole trace."""
    return _roomy_frame()(fn, args)
