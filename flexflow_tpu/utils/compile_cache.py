"""JAX's persistent compilation cache: where it lives and what it did.

One place decides the cache directory, reached by every path that builds
an :class:`~flexflow_tpu.runtime.model.FFModel` (``fit`` and serving
alike) before that model's first ``jit``:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself at import;
  nothing is set in code, so whoever launches the process (a chip
  harness, CI) places the cache.
* unset — ``<checkout>/.jax_cache``, a fixed path: one derived from a
  temp name, a pid or the time would never hit. Every program is kept,
  not only those that took a second to compile (JAX's default): a model
  here compiles hundreds of small ones — one ``jit`` per weight
  initializer, the eager metric folds — and together they are a large
  share of set-up.

The same module counts what the compiler did, from ``jax.monitoring``'s
events, into the process metrics registry (``jax.compiles``,
``jax.compile_s``, ``jax.cache_hits``, ``jax.cache_misses``): the fit
loop's per-epoch record and ``chip_smoke.py`` read them to show that a
steady-state window compiled nothing and that a second run hit the
cache. JAX decides whether to use the cache at its first compile, so
call :func:`configure_compile_cache` before any ``jax.numpy`` work.

The seconds are split by the compiler's stage, each the sum of one
event's durations as JAX reports them: ``jax.trace_s`` (a function
traced to a jaxpr), ``jax.mlir_s`` (a jaxpr lowered to an MLIR module),
``jax.compile_s`` (XLA compile requests) and, inside that one,
``jax.cache_read_s`` (persistent-cache reads that hit: ``compile_s``
holds ``cache_read_s``). The sums are INCLUSIVE: a ``jit`` traced inside
another's trace is in both events, and eager calls made while a
function is traced or lowered lie inside its duration too, so the
stages do not add up to a wall time and ``trace_s`` can pass the wall
time around it. Beside them stand the program's own set-up phases,
each a pair of clock reads at its call site (``setup.model_compile_s``
and, nested in it, ``setup.lower_s``, ``setup.init_params_s``,
``setup.audit_s``; ``setup.instance_build_s`` and in it
``setup.calibration_s``). All are always on, as ``jax.compile_s`` is.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

import jax

from ..obs.metrics import metrics_registry

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# jax.monitoring event names (jax/_src/dispatch.py, jax/_src/compiler.py)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# duration event -> the registry counter that sums its seconds
_DURATION_SUMS = {
    _BACKEND_COMPILE: "jax.compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.mlir_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_read_s",
}
_JAX_KEYS = ("compiles", "compile_s", "cache_hits", "cache_misses",
             "trace_s", "mlir_s", "cache_read_s")
_SETUP_KEYS = ("model_compile_s", "lower_s", "init_params_s", "audit_s",
               "instance_build_s", "calibration_s")

_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **_kw) -> None:
    name = _DURATION_SUMS.get(event)
    if name is not None:
        reg = metrics_registry()
        reg.counter(name).inc(duration)
        if event == _BACKEND_COMPILE:
            reg.counter("jax.compiles").inc()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        metrics_registry().counter("jax.cache_hits").inc()
    elif event == _CACHE_MISS:
        metrics_registry().counter("jax.cache_misses").inc()


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache (see the module docstring)
    and start counting compiles. Idempotent. Returns the directory this
    call chose, or None when ``JAX_COMPILATION_CACHE_DIR`` placed it."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


def compile_stats() -> Dict[str, float]:
    """Cumulative compile counters of this process: ``compiles`` (XLA
    compile requests, persistent-cache hits included), ``compile_s``
    (seconds inside them), ``cache_hits`` / ``cache_misses`` (persistent
    cache; both stay 0 while no cache is in use), the compiler's other
    stages ``trace_s``, ``mlir_s``, ``cache_read_s`` (inclusive sums: the
    module docstring) and the set-up phases ``model_compile_s``,
    ``lower_s``, ``init_params_s``, ``audit_s``, ``instance_build_s``,
    ``calibration_s`` (0 where the phase did not run)."""
    reg = metrics_registry()
    stats = {k: reg.counter(f"jax.{k}").value for k in _JAX_KEYS}
    stats.update((k, reg.counter(f"setup.{k}").value) for k in _SETUP_KEYS)
    return stats
