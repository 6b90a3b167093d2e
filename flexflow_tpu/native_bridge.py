"""ctypes bindings to the native runtime library.

Plays the role of the reference's cffi layer over its flat C API
(reference: python/flexflow/core/flexflow_cffi.py binding
include/flexflow/flexflow_c.h). The native library
(``native/`` → libflexflow_tpu_native.so) provides:

* :func:`sim_taskgraph` — event-driven task-graph replay (the hot loop of
  the strategy search's simulator);
* :func:`toposort` / :func:`dominators` / :func:`transitive_reduction` —
  graph algorithms backing the search;
* :class:`NativeLoader` — threaded shuffle/gather/prefetch batch assembly.

Every entry point has a pure-Python caller-side fallback (the callers check
:func:`available`), so the framework works without a C++ toolchain; with
one, the library is auto-built on first import. :func:`status` says which
of the two a process got and, for the Python twins, why.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(_REPO, "flexflow_tpu", "native",
                         "libflexflow_tpu_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
# why the Python twins were selected (None while the library is in use)
_why_python: Optional[str] = None


def _stale() -> bool:
    """True when the .so is missing or older than any native source."""
    if not os.path.exists(_LIB_PATH):
        return True
    lib_mtime = os.path.getmtime(_LIB_PATH)
    native = os.path.join(_REPO, "native")
    for sub in ("src", "include"):
        d = os.path.join(native, sub)
        if not os.path.isdir(d):
            continue
        for fn in os.listdir(d):
            if os.path.getmtime(os.path.join(d, fn)) > lib_mtime:
                return True
    return False


def _build(force: bool = False) -> bool:
    """``make`` the library under the build lock; records why it could
    not in ``_why_python``. ``force`` rebuilds whatever the mtimes say."""
    global _why_python
    makefile_dir = os.path.join(_REPO, "native")
    if not os.path.isdir(makefile_dir):
        _why_python = f"no native sources at {makefile_dir}"
        return False
    try:
        # serialize concurrent builders (pytest-xdist, multi-process
        # launches): without the lock a sibling can dlopen a half-linked .so
        import fcntl

        lock_path = os.path.join(makefile_dir, ".build.lock")
        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                if not force and not _stale():
                    return True  # a peer finished the build while we waited
                subprocess.run(
                    ["make", "-C", makefile_dir, "-s"] + (["-B"] if force else []),
                    check=True, capture_output=True, timeout=120)
            finally:
                fcntl.flock(lock, fcntl.LOCK_UN)
        return os.path.exists(_LIB_PATH)
    except subprocess.CalledProcessError as e:
        _why_python = ("make failed: "
                       + (e.stderr or b"").decode(errors="replace")[-300:])
    except (OSError, subprocess.TimeoutExpired) as e:
        _why_python = f"make did not run: {type(e).__name__}: {e}"
    return False


def rebuild() -> bool:
    """Rebuild the library from ``native/src`` unconditionally. For a tree
    whose file times cannot be trusted (a copy scrambles them, and
    :func:`_stale` compares mtimes). Call before anything loads the
    library; returns whether a library now exists."""
    if _lib is not None:
        raise RuntimeError("native library already loaded; rebuild() must "
                           "run before its first use")
    return _build(force=True)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _why_python
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("FLEXFLOW_TPU_NATIVE", "auto") == "off":
            _why_python = "FLEXFLOW_TPU_NATIVE=off"
            return None
        # rebuild only when a native source is newer than the .so
        # (stale-symbol safety without forking make in every process)
        if _stale() and not _build() and not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            _why_python = f"dlopen failed: {e}"
            return None
        _why_python = None
        i32p = ctypes.POINTER(ctypes.c_int32)
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.fftpu_version.restype = ctypes.c_int
        lib.fftpu_sim_taskgraph.restype = ctypes.c_double
        lib.fftpu_sim_taskgraph.argtypes = [
            ctypes.c_int32, f64p, i32p, ctypes.c_int32, i32p, i32p, f64p]
        lib.fftpu_toposort.restype = ctypes.c_int
        lib.fftpu_toposort.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
        lib.fftpu_dominators.restype = ctypes.c_int
        lib.fftpu_dominators.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, ctypes.c_int32, i32p]
        lib.fftpu_transitive_reduction.restype = ctypes.c_int32
        lib.fftpu_transitive_reduction.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p,
            ctypes.POINTER(ctypes.c_uint8)]
        if hasattr(lib, "fftpu_route_transfers"):  # absent in a stale .so
            lib.fftpu_route_transfers.restype = ctypes.c_double
            lib.fftpu_route_transfers.argtypes = [
                ctypes.c_int32, i32p, ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int32, i32p, i32p, f64p,
                ctypes.c_double, ctypes.c_double, f64p, i32p]
        lib.fftpu_loader_create.restype = ctypes.c_void_p
        lib.fftpu_loader_create.argtypes = [
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32]
        lib.fftpu_loader_destroy.argtypes = [ctypes.c_void_p]
        lib.fftpu_loader_num_batches.restype = ctypes.c_int64
        lib.fftpu_loader_num_batches.argtypes = [ctypes.c_void_p]
        lib.fftpu_loader_reset.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.fftpu_loader_reset_with_perm.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        lib.fftpu_loader_next.restype = ctypes.c_int64
        lib.fftpu_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)]
        if hasattr(lib, "fftpu_batcher_create"):  # absent in a stale .so
            i64p = ctypes.POINTER(ctypes.c_int64)
            lib.fftpu_batcher_create.restype = ctypes.c_void_p
            lib.fftpu_batcher_create.argtypes = [ctypes.c_int32, ctypes.c_int64]
            lib.fftpu_batcher_destroy.argtypes = [ctypes.c_void_p]
            lib.fftpu_batcher_submit.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.fftpu_batcher_close.argtypes = [ctypes.c_void_p]
            lib.fftpu_batcher_pending.restype = ctypes.c_int64
            lib.fftpu_batcher_pending.argtypes = [ctypes.c_void_p]
            lib.fftpu_batcher_next.restype = ctypes.c_int64
            lib.fftpu_batcher_next.argtypes = [ctypes.c_void_p, i64p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> str:
    """``"native library"`` or ``"python twins (<why>)"`` — which
    implementation this process's callers get."""
    if available():
        return "native library"
    return f"python twins ({_why_python})"


def _i32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int32)


def _as_i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def sim_taskgraph(durations: Sequence[float], devices: Sequence[int],
                  edges: Sequence[Tuple[int, int]],
                  want_starts: bool = False):
    """Returns makespan (and per-task start times when requested)."""
    lib = _load()
    assert lib is not None
    dur = np.ascontiguousarray(durations, dtype=np.float64)
    dev = _i32(devices)
    n = len(dur)
    es = _i32([e[0] for e in edges])
    ed = _i32([e[1] for e in edges])
    starts = np.zeros(n, np.float64) if want_starts else None
    res = lib.fftpu_sim_taskgraph(
        n, dur.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i32p(dev), len(edges), _as_i32p(es), _as_i32p(ed),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
        if starts is not None else None)
    if res < 0:
        raise ValueError("task graph has a cycle or invalid edges")
    return (res, starts) if want_starts else res


def route_transfers(dims: Sequence[int], wrap: Sequence[bool],
                    src: Sequence[int], dst: Sequence[int],
                    bytes_: Sequence[float], link_bandwidth: float,
                    hop_latency: float) -> Tuple[float, float, int]:
    """Torus routing + contention (native). Returns
    (completion_seconds, max_link_bytes, max_hops).

    reference: the routing/congestion estimation of NetworkedMachineModel
    (simulator.h:421-606, network.cc)."""
    lib = _load()
    assert lib is not None
    if not (len(src) == len(dst) == len(bytes_)):
        raise ValueError(
            f"src/dst/bytes length mismatch: {len(src)}/{len(dst)}/{len(bytes_)}")
    if len(dims) != len(wrap):
        raise ValueError("dims/wrap length mismatch")
    d = _i32(dims)
    w = np.ascontiguousarray([1 if x else 0 for x in wrap], dtype=np.uint8)
    s = _i32(src)
    t = _i32(dst)
    b = np.ascontiguousarray(bytes_, dtype=np.float64)
    max_link = ctypes.c_double(0.0)
    max_hops = ctypes.c_int32(0)
    res = lib.fftpu_route_transfers(
        len(d), _as_i32p(d), w.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(s), _as_i32p(s), _as_i32p(t),
        b.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        float(link_bandwidth), float(hop_latency),
        ctypes.byref(max_link), ctypes.byref(max_hops))
    if res < 0:
        raise ValueError("invalid torus routing input")
    return float(res), float(max_link.value), int(max_hops.value)


def toposort(n: int, edges: Sequence[Tuple[int, int]]) -> List[int]:
    lib = _load()
    assert lib is not None
    es = _i32([e[0] for e in edges])
    ed = _i32([e[1] for e in edges])
    out = np.zeros(n, np.int32)
    if lib.fftpu_toposort(n, len(edges), _as_i32p(es), _as_i32p(ed),
                          _as_i32p(out)) != 0:
        raise ValueError("graph has a cycle")
    return out.tolist()


def dominators(n: int, edges: Sequence[Tuple[int, int]], root: int) -> List[int]:
    """Immediate dominator per node (root maps to itself, unreachable → -1)."""
    lib = _load()
    assert lib is not None
    es = _i32([e[0] for e in edges])
    ed = _i32([e[1] for e in edges])
    out = np.zeros(n, np.int32)
    if lib.fftpu_dominators(n, len(edges), _as_i32p(es), _as_i32p(ed), root,
                            _as_i32p(out)) != 0:
        raise ValueError("invalid dominator input")
    return out.tolist()


def transitive_reduction(n: int, edges: Sequence[Tuple[int, int]]
                         ) -> List[Tuple[int, int]]:
    lib = _load()
    assert lib is not None
    es = _i32([e[0] for e in edges])
    ed = _i32([e[1] for e in edges])
    kept = np.zeros(len(edges), np.uint8)
    r = lib.fftpu_transitive_reduction(
        n, len(edges), _as_i32p(es), _as_i32p(ed),
        kept.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if r < 0:
        raise ValueError("graph has a cycle")
    return [e for e, k in zip(edges, kept) if k]


class NativeLoader:
    """Threaded shuffle/gather/prefetch over host numpy datasets
    (reference: SingleDataLoader, src/dataloader/dataloader.cc).

    Shuffle permutations come from ``np.random.default_rng(seed)`` on the
    Python side (pushed via ``fftpu_loader_reset_with_perm``), so a run is
    bit-identical whether or not the native library is in use.

    Single-consumer thread-safe: ``runtime/dataloader.py``'s Prefetcher
    drives ``next_batch`` from its worker thread (the C++ side already
    assembles one batch ahead on its own thread; the Python queue stacks
    the ahead-of-compute device_put on top).
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 shuffle: bool = False, seed: int = 0):
        lib = _load()
        assert lib is not None
        self._lib = lib
        # keep C-contiguous copies alive for the loader's lifetime
        self._arrays = [np.ascontiguousarray(a) for a in arrays]
        n = self._arrays[0].shape[0]
        assert all(a.shape[0] == n for a in self._arrays)
        self.batch_size = batch_size
        self.num_samples = n
        self.shuffle = shuffle
        self._rng = np.random.default_rng(seed)
        self._row_bytes = [a.nbytes // n for a in self._arrays]
        datas = (ctypes.c_void_p * len(self._arrays))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self._arrays])
        rb = (ctypes.c_int64 * len(self._arrays))(*self._row_bytes)
        self._h = lib.fftpu_loader_create(
            n, batch_size, len(self._arrays), datas, rb, 0, seed, 1)
        if not self._h:
            raise RuntimeError("fftpu_loader_create failed")
        # note: no shuffle until the first reset(reshuffle=True) — matching
        # the numpy fallback path so the two are batch-for-batch identical

    def _push_perm(self) -> None:
        perm = np.ascontiguousarray(
            self._rng.permutation(self.num_samples), dtype=np.int64)
        self._lib.fftpu_loader_reset_with_perm(
            self._h, perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))

    @property
    def num_batches(self) -> int:
        return int(self._lib.fftpu_loader_num_batches(self._h))

    @property
    def batch_nbytes(self) -> int:
        """Host bytes one batch gathers across all tensors (throughput
        accounting — mirrors SingleDataLoader.batch_nbytes)."""
        return sum(self._row_bytes) * self.batch_size

    def reset(self, reshuffle: bool = True) -> None:
        if self.shuffle and reshuffle:
            self._push_perm()
        else:
            self._lib.fftpu_loader_reset(self._h, 0)

    def next_batch(self) -> Optional[List[np.ndarray]]:
        # fresh buffers each call: the C side memcpys straight into them and
        # they are handed to the caller without another host copy
        outs_np = [
            np.empty((self.batch_size,) + a.shape[1:], a.dtype)
            for a in self._arrays
        ]
        outs = (ctypes.c_void_p * len(outs_np))(
            *[o.ctypes.data_as(ctypes.c_void_p).value for o in outs_np])
        b = self._lib.fftpu_loader_next(self._h, outs)
        if b < 0:
            return None
        return outs_np

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.fftpu_loader_destroy(self._h)
            # single-consumer contract (class docstring): the Prefetcher
            # worker has joined before the loader is closed/collected
            self._h = None  # concurrency: race-ok (single-consumer contract, worker joined before close)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeBatcher:
    """Dynamic micro-batch scheduler (native; reference: the Triton
    backend's request batching, triton/src/backend.cc). Requests are int64
    ids; ``next_batch`` blocks until ``max_batch`` ids are pending or the
    oldest has waited ``timeout_s``."""

    def __init__(self, max_batch: int, timeout_s: float):
        lib = _load()
        if lib is None or not hasattr(lib, "fftpu_batcher_create"):
            raise RuntimeError("native batcher unavailable")
        self._lib = lib
        self.max_batch = int(max_batch)
        # guards _h and _closed for the NON-blocking entry points, giving
        # the wrapper _PyBatcher's exact lifecycle semantics: submit fails
        # fast once closed (an id accepted under this lock is pushed
        # before close() can flip the flag, so the native drain-then-exit
        # always covers it), pending()/destroy() can never hand the C API
        # a NULL or freed handle, and double destroy() is a no-op.
        # next_batch stays OUTSIDE this lock — it blocks in native code
        # (the C batcher has its own mutex) and is covered by the engine's
        # destroy-after-join contract instead.
        self._hmu = threading.Lock()
        self._closed = False
        self._h = lib.fftpu_batcher_create(self.max_batch,
                                           int(timeout_s * 1e6))
        if not self._h:
            raise RuntimeError("fftpu_batcher_create failed")

    def submit(self, request_id: int) -> None:
        with self._hmu:
            if self._closed or not self._h:
                # a request appended after close() would never be drained
                # (the workers exit once the queue empties) — fail fast so
                # the engine can re-submit to the re-armed batcher
                raise RuntimeError("batcher is closed")
            self._lib.fftpu_batcher_submit(self._h, int(request_id))

    def pending(self) -> int:
        with self._hmu:
            if not self._h:
                return 0
            return int(self._lib.fftpu_batcher_pending(self._h))

    def next_batch(self) -> Optional[List[int]]:
        """Blocks; returns ids, or None once closed and drained.

        Reentrant: each call writes into its OWN buffer — instance groups
        run one consumer thread per instance against a shared batcher, and
        a shared output buffer would let one consumer's result overwrite
        another's between the native call and the Python read."""
        h = self._h  # concurrency: race-ok (destroy-after-join: stop() frees the handle only after this consumer thread joined)
        if not h:
            return None
        ids = (ctypes.c_int64 * self.max_batch)()
        n = self._lib.fftpu_batcher_next(h, ids)
        if n < 0:
            return None
        return list(ids[:n])

    def close(self) -> None:
        with self._hmu:
            self._closed = True
            if self._h:
                self._lib.fftpu_batcher_close(self._h)

    def destroy(self) -> None:
        # atomic check-and-clear: concurrent stop() calls both reaching
        # destroy() must not double-free the native handle
        with self._hmu:
            h, self._h = self._h, None
            self._closed = True
            if h:
                self._lib.fftpu_batcher_destroy(h)

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass
