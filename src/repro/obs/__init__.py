"""repro.obs — zero-dependency observability: spans, metrics, exporters.

Spans (phase-attributed wall time, thread-aware nesting):

    from repro import obs
    with obs.span("plan.probe", engine="sell", k=8):
        ...

Disabled (no sink installed) a span is a shared no-op — safe in hot
paths. Enable for a scope with::

    with obs.tracing() as buf:
        run_campaign(...)
    obs.write_trace("trace.json", buf.flush())   # load in Perfetto

Metrics (process-wide registry; one pane of glass over every cache and
the serving counters)::

    obs.counter("plan_store.hits").inc()
    obs.snapshot()   # {'counters': ..., 'gauges': ...}
"""
from .spans import (Span, TraceBuffer, enabled, install_sink,  # noqa: F401
                    record, remove_sink, span, timed, tracing)
from .metrics import (REGISTRY, Counter, Gauge,  # noqa: F401
                      counter, gauge, reset, snapshot)
from .export import (to_chrome_trace, validate_chrome_trace,  # noqa: F401
                     write_chrome_trace, write_jsonl, write_trace)

__all__ = [
    "span", "timed", "record", "tracing", "enabled", "install_sink",
    "remove_sink", "Span", "TraceBuffer",
    "counter", "gauge", "snapshot", "reset",
    "REGISTRY", "Counter", "Gauge",
    "to_chrome_trace", "write_chrome_trace", "write_jsonl",
    "write_trace", "validate_chrome_trace",
]
