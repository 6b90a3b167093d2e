"""The machine a run is on: which devices JAX found, their peak memory,
where the compilation cache lives, and the profiler's window.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from typing import Dict, Optional


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices of a measurement run. There is no fallback: any other
    platform, or too few chips, raises."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or jax.default_backend() != "tpu":
        raise NoAccelerator(
            f"jax found platform {devices[0].platform!r} "
            f"({len(devices)} device(s)), not a TPU")
    if len(devices) != chips:
        # the program spreads a model over every device it finds, so a
        # cell runs only on a machine that holds exactly its chips
        raise NoAccelerator(
            f"the cell asks for {chips} chip(s), jax found {len(devices)}")
    return devices


def describe(devices) -> Dict:
    """``device`` of the result line, as JAX reports it. The peak is the
    fullest chip's ``peak_bytes_in_use + peak_bytes_reserved``: on a TPU
    a running program's temporaries are counted under the second, beside
    the live arrays of the first, and the two share the chip's memory
    (PERF.md section 5, PR 21's readings)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def place_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
    (the program's own default, ``utils/compile_cache.py``; a fixed path
    inside the checkout). Every program is kept, however quickly it
    compiled: a model here asks for hundreds of small ones. Also starts
    the program's compile counters (``jax.compiles`` and the rest)."""
    import jax

    from flexflow_tpu.utils.compile_cache import configure_compile_cache

    chosen = configure_compile_cache()
    if chosen is None:  # placed from outside; the program then sets nothing
        chosen = os.environ["JAX_COMPILATION_CACHE_DIR"]
    elif os.path.dirname(os.path.abspath(chosen)) != os.path.abspath(root):
        raise RuntimeError(f"the program put its compile cache at {chosen}, "
                           f"outside the checkout {root}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: one cell's programs come to some hundreds of MB (the
    # step program alone is 75 MB, twice), and a cache capped below that
    # drops a run's first programs before the next run asks for them
    jax.config.update("jax_compilation_cache_max_size", -1)
    return chosen


class Profiler:
    """At most one profiler window per run, ``--trace 1`` only. Spans are
    ``jax.profiler.TraceAnnotation`` so that they land on the device
    trace's clock; with tracing off a span costs one ``if``."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.out_dir = out_dir
        self.running = False
        self.done = False
        self.started_at: Optional[float] = None
        self.window_s: Optional[float] = None

    def start(self) -> None:
        if not self.enabled or self.running or self.done:
            return
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        # no Python call tracing: it slows the host that drives the device
        # and the trace holds what is needed without it (device operations,
        # and the TraceAnnotation spans of the host tracer)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.running = True
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self.window_s = time.perf_counter() - self.started_at
        jax.profiler.stop_trace()
        self.running = False
        self.done = True

    def span(self, name: str):
        if not self.running:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)
