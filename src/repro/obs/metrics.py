"""Process-wide metrics registry: counters and gauges.

One registry per process (module-level ``REGISTRY``); metrics are
get-or-created by ``(name, labels)`` so every layer that counts the same
thing increments the same object. ``snapshot()`` is the single pane of
glass the scattered per-subsystem counters used to be:

    plan_store.hits / .misses / .writes     core/spmv/plan.py
    opcache.hits / .misses                  core/spmv/opcache.py
    reorder_cache.hits / .misses            core/reorder/api.py
    result_store.hits / .misses / .writes   experiments/store.py
    service.*{service=...}                  serving/spmv_service.py

Metric objects have their own small lock, but callers holding a coarser
lock (e.g. the service condition variable) keep their existing snapshot
atomicity: all service counters are only mutated under ``_cv``, so a
``stats()`` read under ``_cv`` still sees a consistent cut.
"""
from __future__ import annotations

import threading


def _key(name: str, labels: dict) -> tuple:
    return (name, tuple(sorted(labels.items())))


def _fmt(key: tuple) -> str:
    name, labels = key
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels)
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonic-by-convention numeric counter (set() exists for views)."""

    __slots__ = ("_v", "_lock")

    def __init__(self):
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._v += n

    def set(self, v):
        with self._lock:
            self._v = v

    @property
    def value(self):
        return self._v


class Gauge:
    """Point-in-time value, with a max-tracking helper."""

    __slots__ = ("_v", "_lock")

    def __init__(self):
        self._v = 0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self._v = v

    def max(self, v):
        with self._lock:
            if v > self._v:
                self._v = v

    @property
    def value(self):
        return self._v


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict = {}
        self._gauges: dict = {}

    def _get(self, table: dict, cls, name: str, labels: dict):
        key = _key(name, labels)
        m = table.get(key)
        if m is None:
            with self._lock:
                m = table.setdefault(key, cls())
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(self._counters, Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(self._gauges, Gauge, name, labels)

    def total(self, name: str) -> float:
        """Sum of one counter name across all label sets."""
        return sum(c.value for (n, _), c in list(self._counters.items())
                   if n == name)

    def snapshot(self) -> dict:
        """All metrics as plain data: {'counters': {...}, ...}."""
        return {
            "counters": {_fmt(k): c.value
                         for k, c in sorted(self._counters.items())},
            "gauges": {_fmt(k): g.value
                       for k, g in sorted(self._gauges.items())},
        }

    def reset(self) -> None:
        """Drop every metric (tests only — live handles are invalidated)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()


REGISTRY = Registry()


def counter(name: str, **labels) -> Counter:
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def reset() -> None:
    REGISTRY.reset()
