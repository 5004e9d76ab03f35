"""Nested, thread-aware spans with a near-zero disabled path.

``span(name, **attrs)`` returns a context manager. With no sink
installed (the default) it returns one shared singleton whose
``__enter__``/``__exit__`` do nothing — a single module-global flag
test, no lock, no allocation — so instrumentation can stay on in hot
paths (per-request dispatch, operator ``__call__``).

With a sink installed (``install_sink`` / the ``tracing()``
contextmanager) spans record wall time via ``perf_counter_ns``, nest
through a thread-local stack (each thread owns its own span tree) and
are exception-safe:

* a span exited by an unwinding exception still records, with an
  ``error`` attribute naming the exception type;
* a child span that was entered but never exited (e.g. a probe that
  raised between ``__enter__`` and manual bookkeeping) is force-closed
  when its enclosing span exits, tagged ``unclosed``.

Timestamps are microseconds on the ``perf_counter_ns`` clock — an
arbitrary but monotonic origin, which is all the Chrome-trace/Perfetto
format needs. ``TraceBuffer.flush()`` returns events in a deterministic
order (ts, tid, id) regardless of which thread emitted first.

While a sink is installed, a span also opens a
``jax.profiler.TraceAnnotation`` of its name (with the stat ``obs_span``,
its id) when JAX is already imported, so a profiler trace shows the
program's spans on the profiler's clock, beside the device's operations.

``timed(name, **attrs)`` is a span that reads the clock whether or not a
sink is installed: a caller that keeps its own duration (``Plan.reorder_ms``)
reads ``.ms`` off it, so the record and the span time one interval.
``record(name, t0_ns, t1_ns, **attrs)`` emits a span that has already
ended, for durations reported after the fact (JAX's compile events).
"""
from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager

_PID = os.getpid()
_sinks: list = []          # sink objects with an .add(event: dict) method
_enabled = False           # fast-path flag, kept in sync with _sinks
_ids = itertools.count(1)  # CPython-atomic span id source
_tls = threading.local()


class _NullSpan:
    """Shared no-op span returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


class Span:
    """A live span (tracing enabled, or `timed()`). Use as a context
    manager."""

    __slots__ = ("name", "attrs", "id", "parent", "tid", "thread",
                 "t0", "t1", "_open", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.id = next(_ids)
        self.parent = None
        self.tid = 0
        self.thread = ""
        self.t0 = self.t1 = 0
        self._open = False
        self._ann = None

    def set(self, **attrs):
        """Attach attributes after entry (e.g. a result computed inside)."""
        self.attrs.update(attrs)
        return self

    @property
    def ms(self) -> float:
        """Milliseconds between entry and exit (0.0 before exit)."""
        return (self.t1 - self.t0) / 1e6

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread = t.name
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._open = True
        if _enabled:
            self._ann = _annotation(self.name, self.id)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, etype, evalue, tb):
        t1 = self.t1 = time.perf_counter_ns()
        stack = getattr(_tls, "stack", None) or []
        # Force-close any descendants left open by a raise between their
        # __enter__ and __exit__ (they sit above us on the stack).
        while stack and stack[-1] is not self:
            dangling = stack.pop()
            dangling._open = False
            dangling.t1 = t1
            _close_annotation(dangling)
            _emit(dangling, t1, unclosed=True)
        if stack and stack[-1] is self:
            stack.pop()
        self._open = False
        _close_annotation(self)
        _emit(self, t1, error=etype.__name__ if etype else None)
        return False


def _annotation(name: str, span_id: int):
    """An open profiler annotation of the span, where JAX is loaded (obs
    never imports it)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name, obs_span=span_id)
    ann.__enter__()
    return ann


def _close_annotation(span: Span) -> None:
    if span._ann is not None:
        span._ann.__exit__(None, None, None)
        span._ann = None


def _emit(span: Span, t1_ns: int, error=None, unclosed=False) -> None:
    attrs = span.attrs
    if error:
        attrs = dict(attrs, error=error)
    if unclosed:
        attrs = dict(attrs, unclosed=True)
    ev = {
        "name": span.name,
        "ts": span.t0 / 1e3,          # µs, perf_counter origin
        "dur": (t1_ns - span.t0) / 1e3,
        "pid": _PID,
        "tid": span.tid,
        "thread": span.thread,
        "id": span.id,
        "parent": span.parent,
        "args": attrs,
    }
    for sink in list(_sinks):
        sink.add(ev)


def span(name: str, **attrs):
    """Open a span. Near-free when no sink is installed."""
    if not _enabled:
        return _NULL
    return Span(name, attrs)


def timed(name: str, **attrs) -> Span:
    """A span that reads the clock even with no sink installed; `.ms`
    after exit. For callers that keep the duration themselves."""
    return Span(name, attrs)


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Emit a span that ran from t0_ns to t1_ns (perf_counter_ns clock) on
    this thread, under its innermost open span. Nothing while disabled."""
    if not _enabled:
        return
    sp = Span(name, attrs)
    t = threading.current_thread()
    sp.tid, sp.thread = t.ident or 0, t.name
    stack = getattr(_tls, "stack", None)
    sp.parent = stack[-1].id if stack else None
    sp.t0 = t0_ns
    _emit(sp, t1_ns)


def enabled() -> bool:
    return _enabled


class TraceBuffer:
    """The default sink: collects events; flush() orders deterministically."""

    def __init__(self):
        self._events: list = []
        self._lock = threading.Lock()

    def add(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def flush(self) -> list:
        """Events sorted by (ts, tid, id) — stable across thread races."""
        with self._lock:
            evs = list(self._events)
        return sorted(evs, key=lambda e: (e["ts"], e["tid"], e["id"]))


def install_sink(sink) -> None:
    global _enabled
    if sink not in _sinks:
        _sinks.append(sink)
    _enabled = True


def remove_sink(sink) -> None:
    global _enabled
    try:
        _sinks.remove(sink)
    except ValueError:
        pass
    _enabled = bool(_sinks)


@contextmanager
def tracing(buffer: TraceBuffer = None):
    """Enable tracing for a scope; yields the TraceBuffer."""
    buf = buffer if buffer is not None else TraceBuffer()
    install_sink(buf)
    try:
        yield buf
    finally:
        remove_sink(buf)
