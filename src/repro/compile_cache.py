"""JAX's persistent compilation cache, for the entry points.

`enable()` is called by the programs a user runs (`chip_smoke.py`,
`repro.launch.spmv_bench`, `benchmarks.run`), never by library imports or
tests. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
nothing here overrides it; otherwise the cache lives at a fixed path in
the checkout, `.cache/jax_compile`. The path is part of the cache key, so
it never depends on a temp dir, a pid or the time.

Cache hits and misses are counted as `compile_cache.hits` /
`compile_cache.misses` in the obs registry. While an obs sink is
installed, JAX's own timings of each program become obs spans named by
stage, with the program's `fun_name`: `runtime.trace` (Python to jaxpr),
`runtime.lower` (jaxpr to MLIR) and `runtime.compile` (the backend's
compile). `listen()` registers both listeners without touching the cache.

A program whose devices are not the process's first ones must never be
loaded from the cache: on a four-chip TPU v5e host (jax 0.9.0), a
two-device program on devices [2, 3] that came from the cache halts the
chip ("program continuator has halted unexpectedly"), even a bare
ppermute, while the same program compiled afresh runs, and so does one on
devices [0, 1] from the cache. Such programs are compiled inside
`not_persisted()`, so they are never written and so never read back.
"""
from __future__ import annotations

import os
import pathlib
import time

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".cache" / \
    "jax_compile"

_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache.hits",
           "/jax/compilation_cache/cache_misses": "compile_cache.misses"}
_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "runtime.trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "runtime.lower",
          "/jax/core/compile/backend_compile_duration": "runtime.compile"}
_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        from . import obs

        obs.counter(name).inc()


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    """A stage JAX timed on the wall clock, as an obs span on the obs
    clock: it ended now and lasted end - start."""
    name = _SPANS.get(event)
    if name is None:
        return
    from . import obs

    if obs.enabled():
        t1 = time.perf_counter_ns()
        obs.record(name, t1 - int((end - start) * 1e9), t1,
                   fun_name=str(kw.get("fun_name", "")))


def listen() -> None:
    """Count cache hits and misses, and span JAX's trace, lower and
    compile stages while tracing is on. Idempotent."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _listening = True


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: a Pallas kernel compiles in less than the
    # default one-second floor, and each chip run starts cold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    listen()
    return path


def not_persisted():
    """Context: what this thread compiles inside it is not written to the
    persistent cache (the write floor on compile time is set to
    infinity; JAX keeps that setting per thread)."""
    from jax._src import config as jax_config

    return jax_config.persistent_cache_min_compile_time_secs(float("inf"))
