"""Pieces every cell's driver shares: spans, the compile counter, the
device's memory peak, and the profiler around the traced window."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time

import jax

# host spans the benchmark's own files put around its calls into each
# layer; the trace reduction attributes the device's idle gaps to them
span = jax.profiler.TraceAnnotation
TRACE_MAX_S = 10.0  # a traced run traces a short window of its own

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration")
_CACHE_EVENTS = ("/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses")


class CompileCounter:
    """Counts traces and backend compilations while ``active`` (the
    window), and totals what tracing, compiling and reading the
    persistent cache cost over the whole run (``totals``: where set-up
    goes)."""

    def __init__(self):
        self.n = 0
        self.active = False
        self.totals = dict.fromkeys(_COMPILE_EVENTS + _CACHE_EVENTS, 0.0)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.totals[event] += duration
            self.n += self.active

    def _event(self, event, **_):
        if event in _CACHE_EVENTS:
            self.totals[event] += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False

    def summary(self) -> dict:
        return {k.rsplit("/", 1)[-1]: v for k, v in self.totals.items()}


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device, since start (0
    where the backend keeps no statistics: the CPU of the tests)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


@contextlib.contextmanager
def profiled(trace_dir):
    """Run the body under the profiler when ``trace_dir`` is set."""
    if trace_dir is None:
        yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def now() -> float:
    return time.perf_counter()
