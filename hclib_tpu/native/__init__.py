"""ctypes bindings for the native host runtime (built on demand).

The C++ core (src/) is the fast host-side work-stealing engine: Chase-Lev
deques with C++11 atomics, pthread workers, help-first finish joins, and
native implementations of the benchmark workloads (fib, UTS with an in-house
FIPS-180-1 SHA-1, arrayadd). It provides the compiled CPU baseline the
device megakernel is measured against, and the host-side queue engine for
feeding device work.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Tuple

def _csr(paths):
    """Flatten per-worker locale paths to CSR (offsets, data) int arrays."""
    off = [0]
    data = []
    for p in paths:
        data.extend(int(x) for x in p)
        off.append(len(data))
    return (ctypes.c_int * len(off))(*off), (ctypes.c_int * max(1, len(data)))(*data)


_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libhclib_native.so")
# What the library on disk was built FROM: the digest of _source_digest()
# at build time. The .so is untracked and travels with copies of the
# working directory, so file times say nothing about whether it matches
# the sources beside it.
_STAMP_PATH = _LIB_PATH + ".built-from"
_lib = None


class NativeBuildError(RuntimeError):
    pass


# Callback signatures crossing the ctypes boundary (tasks and loop bodies).
TASK_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
LOOP1_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_long)
LOOP2_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_long, ctypes.c_long)


def _source_digest() -> str:
    """sha256 over the Makefile and every file of src/, names included."""
    h = hashlib.sha256()
    src = os.path.join(_DIR, "src")
    paths = [os.path.join(_DIR, "Makefile")] + [
        os.path.join(src, f) for f in sorted(os.listdir(src))
    ]
    for path in paths:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(digest: str) -> None:
    try:
        # -B: make decides by file times too; the digest already decided.
        subprocess.run(
            ["make", "-s", "-B"], cwd=_DIR, check=True, capture_output=True,
            text=True,
        )
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        raise NativeBuildError(f"native runtime build failed: {detail}") from e
    with open(_STAMP_PATH, "w") as f:
        f.write(digest)


def load() -> ctypes.CDLL:
    """Build (if needed) and load the native library."""
    global _lib
    if _lib is not None:
        return _lib
    # Stale unless the library was built from exactly these sources:
    # decided by content (src/* and the Makefile), never by file times.
    digest = _source_digest()
    built_from = None
    if os.path.exists(_LIB_PATH) and os.path.exists(_STAMP_PATH):
        with open(_STAMP_PATH) as f:
            built_from = f.read().strip()
    if built_from != digest:
        _build(digest)
    lib = ctypes.CDLL(_LIB_PATH)
    lib.hcn_create.restype = ctypes.c_void_p
    lib.hcn_create.argtypes = [ctypes.c_int]
    lib.hcn_destroy.argtypes = [ctypes.c_void_p]
    lib.hcn_nworkers.restype = ctypes.c_int
    lib.hcn_nworkers.argtypes = [ctypes.c_void_p]
    lib.hcn_pinned_cpu.restype = ctypes.c_int
    lib.hcn_pinned_cpu.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.hcn_typed_promise_demo.restype = ctypes.c_longlong
    lib.hcn_typed_promise_demo.argtypes = [ctypes.c_void_p]
    lib.hcn_executed.restype = ctypes.c_ulonglong
    lib.hcn_executed.argtypes = [ctypes.c_void_p]
    lib.hcn_steals.restype = ctypes.c_ulonglong
    lib.hcn_steals.argtypes = [ctypes.c_void_p]
    lib.hcn_fib.restype = ctypes.c_longlong
    lib.hcn_fib.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.hcn_fib_ddt.restype = ctypes.c_longlong
    lib.hcn_fib_ddt.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.hcn_smithwaterman.restype = ctypes.c_int
    lib.hcn_smithwaterman.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    pint = ctypes.POINTER(ctypes.c_int)
    lib.hcn_create_graph.restype = ctypes.c_void_p
    lib.hcn_create_graph.argtypes = [ctypes.c_int, ctypes.c_int, pint, pint, pint, pint]
    lib.hcn_nlocales.restype = ctypes.c_int
    lib.hcn_nlocales.argtypes = [ctypes.c_void_p]
    lib.hcn_backlog.restype = ctypes.c_long
    lib.hcn_backlog.argtypes = [ctypes.c_void_p]
    lib.hcn_steal_matrix.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_ulonglong),
    ]
    lib.hcn_format_stats.restype = ctypes.c_int
    lib.hcn_format_stats.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.hcn_finish_new.restype = ctypes.c_void_p
    lib.hcn_finish_new.argtypes = [ctypes.c_void_p]
    lib.hcn_finish_end.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.hcn_finish_end_nonblocking.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.hcn_finish_free.argtypes = [ctypes.c_void_p]
    lib.hcn_async.argtypes = [
        ctypes.c_void_p, TASK_FN, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
    ]
    lib.hcn_yield.restype = ctypes.c_int
    lib.hcn_yield.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.hcn_promise_new.restype = ctypes.c_void_p
    lib.hcn_promise_new.argtypes = []
    lib.hcn_promise_free.argtypes = [ctypes.c_void_p]
    lib.hcn_promise_put.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.hcn_promise_get.restype = ctypes.c_void_p
    lib.hcn_promise_get.argtypes = [ctypes.c_void_p]
    lib.hcn_promise_satisfied.restype = ctypes.c_int
    lib.hcn_promise_satisfied.argtypes = [ctypes.c_void_p]
    lib.hcn_promise_wait.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.hcn_forasync1d.argtypes = [
        ctypes.c_void_p, LOOP1_FN, ctypes.c_void_p,
        ctypes.c_long, ctypes.c_long, ctypes.c_int,
    ]
    lib.hcn_forasync2d.argtypes = [
        ctypes.c_void_p, LOOP2_FN, ctypes.c_void_p,
        ctypes.c_long, ctypes.c_long, ctypes.c_long, ctypes.c_long,
    ]
    lib.hcn_uts.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_double,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_ulonglong),
        ctypes.POINTER(ctypes.c_ulonglong),
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.hcn_arrayadd.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_long,
        ctypes.c_long,
    ]
    _lib = lib
    return lib


class NativePromise:
    """Handle to a native single-assignment promise. Values are machine
    words (ints); the Python layer uses it for completion signalling and
    small payloads."""

    def __init__(self, rt: "NativeRuntime") -> None:
        self._rt = rt
        self._p = rt._lib.hcn_promise_new()

    def put(self, value: int = 0) -> None:
        self._rt._lib.hcn_promise_put(self._rt._handle, self._p, ctypes.c_void_p(value))

    def get(self) -> int:
        return int(self._rt._lib.hcn_promise_get(self._p) or 0)

    @property
    def satisfied(self) -> bool:
        return bool(self._rt._lib.hcn_promise_satisfied(self._p))

    def wait(self) -> int:
        self._rt._lib.hcn_promise_wait(self._rt._handle, self._p)
        return self.get()

    def free(self) -> None:
        if self._p is not None:
            self._rt._lib.hcn_promise_free(self._p)
            self._p = None


class NativeFinish:
    """Finish scope over the native runtime (blocking on exit)."""

    def __init__(self, rt: "NativeRuntime") -> None:
        self._rt = rt
        self._f = rt._lib.hcn_finish_new(rt._handle)

    def __enter__(self) -> "NativeFinish":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end()

    def end(self) -> None:
        if self._f is not None:
            self._rt._lib.hcn_finish_end(self._rt._handle, self._f)
            self._rt._lib.hcn_finish_free(self._f)
            self._f = None

    def end_nonblocking(self) -> NativePromise:
        """Detach: returned promise is satisfied when the scope drains
        (hclib_end_finish_nonblocking, src/hclib-runtime.c:1279-1313)."""
        p = NativePromise(self._rt)
        self._rt._lib.hcn_finish_end_nonblocking(self._rt._handle, self._f, p._p)
        self._f = None  # detached; the runtime frees the scope on drain
        return p


class NativeRuntime:
    """RAII wrapper over the native scheduler."""

    def __init__(self, nworkers: Optional[int] = None, graph=None) -> None:
        self._lib = load()
        self._live: dict = {}  # id -> ctypes callback, kept alive until executed
        if graph is not None:
            nworkers = graph.nworkers
            pop_off, pop_data = _csr([graph.pop_paths[w] for w in range(nworkers)])
            st_off, st_data = _csr([graph.steal_paths[w] for w in range(nworkers)])
            self._rt = self._lib.hcn_create_graph(
                nworkers, len(graph.locales), pop_off, pop_data, st_off, st_data
            )
        else:
            if nworkers is None:
                nworkers = os.cpu_count() or 1
            self._rt = self._lib.hcn_create(nworkers)
        self.nworkers = nworkers

    def close(self) -> None:
        if self._rt is not None:
            self._lib.hcn_destroy(self._rt)
            self._rt = None

    def __enter__(self) -> "NativeRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def _handle(self):
        if self._rt is None:
            raise RuntimeError("NativeRuntime used after close()")
        return self._rt

    @property
    def executed(self) -> int:
        return int(self._lib.hcn_executed(self._handle))

    @property
    def steals(self) -> int:
        return int(self._lib.hcn_steals(self._handle))

    def pinned_cpus(self) -> list:
        """Per-worker pinned CPU ids (-1 = unpinned). Pinning is opt-in
        via HCLIB_TPU_AFFINITY / HCLIB_AFFINITY = "strided" | "chunked"
        at runtime creation (reference: HCLIB_AFFINITY hwloc cpusets,
        src/hclib-runtime.c:731-900)."""
        h = self._handle
        return [
            int(self._lib.hcn_pinned_cpu(h, w)) for w in range(self.nworkers)
        ]

    # -- tasking API ------------------------------------------------------

    def promise(self) -> NativePromise:
        return NativePromise(self)

    def finish(self) -> NativeFinish:
        return NativeFinish(self)

    def async_(
        self,
        fn,
        finish: Optional[NativeFinish] = None,
        locale: int = 0,
        deps=(),
        non_blocking: bool = False,
    ) -> None:
        """Spawn a Python callable as a native task (worker threads call
        back through ctypes, which re-acquires the GIL per task).

        ``non_blocking`` is advisory parity metadata (reference async_nb):
        this engine's work-shift model may inline any ready task, so the
        flag does not change scheduling. Submissions from threads other
        than runtime workers are routed through an injection queue; blocking
        calls from such threads require nworkers >= 2 to make progress."""

        cb_box = []

        def tramp(_env):
            try:
                fn()
            finally:
                self._live.pop(id(cb_box[0]), None)

        cb = TASK_FN(tramp)
        cb_box.append(cb)
        self._live[id(cb)] = cb
        dep_arr = (
            (ctypes.c_void_p * len(deps))(*[p._p for p in deps]) if deps else None
        )
        self._lib.hcn_async(
            self._handle,
            cb,
            None,
            finish._f if finish is not None else None,
            locale,
            dep_arr,
            len(deps),
            int(non_blocking),
        )

    def yield_(self, locale: int = -1) -> bool:
        return bool(self._lib.hcn_yield(self._handle, locale))

    def forasync1d(self, fn, n: int, tile: int = 0, recursive: bool = False) -> None:
        cb = LOOP1_FN(lambda _env, i: fn(i))
        self._lib.hcn_forasync1d(
            self._handle, cb, None, n, tile, 1 if recursive else 0
        )

    def forasync2d(self, fn, n0: int, n1: int, tile0: int = 0, tile1: int = 0) -> None:
        cb = LOOP2_FN(lambda _env, i, j: fn(i, j))
        self._lib.hcn_forasync2d(self._handle, cb, None, n0, n1, tile0, tile1)

    # -- introspection ----------------------------------------------------

    @property
    def nlocales(self) -> int:
        return int(self._lib.hcn_nlocales(self._handle))

    @property
    def backlog(self) -> int:
        return int(self._lib.hcn_backlog(self._handle))

    def steal_matrix(self):
        n = self.nworkers
        buf = (ctypes.c_ulonglong * (n * n))()
        self._lib.hcn_steal_matrix(self._handle, buf)
        return [[int(buf[w * n + v]) for v in range(n)] for w in range(n)]

    def format_stats(self) -> str:
        n = self._lib.hcn_format_stats(self._handle, None, 0)
        buf = ctypes.create_string_buffer(n + 1)
        self._lib.hcn_format_stats(self._handle, buf, n + 1)
        return buf.value.decode()

    # -- native workloads -------------------------------------------------

    def fib(self, n: int) -> int:
        return int(self._lib.hcn_fib(self._handle, n))

    def fib_ddt(self, n: int) -> int:
        return int(self._lib.hcn_fib_ddt(self._handle, n))

    def smithwaterman(self, nx: int, ny: int, ts: int, seed: int = 1) -> int:
        return int(self._lib.hcn_smithwaterman(self._handle, nx, ny, ts, seed))

    def uts(self, shape: int, gen_mx: int, b0: float, seed: int) -> Tuple[int, int, int]:
        nodes = ctypes.c_ulonglong()
        leaves = ctypes.c_ulonglong()
        depth = ctypes.c_int()
        self._lib.hcn_uts(
            self._handle, shape, gen_mx, b0, seed,
            ctypes.byref(nodes), ctypes.byref(leaves), ctypes.byref(depth),
        )
        return int(nodes.value), int(leaves.value), int(depth.value)

    def arrayadd(self, a, b, c, tile: int = 4096) -> None:
        import numpy as np

        for name, arr in (("a", a), ("b", b), ("c", c)):
            if not isinstance(arr, np.ndarray) or arr.dtype != np.float64:
                raise TypeError(f"{name} must be a float64 ndarray")
            if not arr.flags["C_CONTIGUOUS"]:
                raise ValueError(f"{name} must be C-contiguous")
        n = len(a)
        if len(b) != n or len(c) != n:
            raise ValueError("a, b, c must have equal length")
        if tile <= 0:
            raise ValueError("tile must be positive")
        pd = ctypes.POINTER(ctypes.c_double)
        self._lib.hcn_arrayadd(
            self._handle,
            a.ctypes.data_as(pd),
            b.ctypes.data_as(pd),
            c.ctypes.data_as(pd),
            n,
            tile,
        )
