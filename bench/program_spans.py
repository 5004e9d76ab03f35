"""Seconds under the program's obs spans, for the set-up readers.

A span event (repro.obs) has `ts` and `dur` in microseconds on the
process's perf_counter clock. Spans of one name may nest (a jitted
function traced inside another's trace), so the seconds under a set of
names are the union of their intervals, not the sum of their durations.
"""
from __future__ import annotations


def union_s(spans: list, names) -> float:
    """Seconds covered by the spans named in `names` (0.0 for none)."""
    ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in spans
                 if e["name"] in names)
    total, end = 0.0, None
    for s, e in ivs:
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e6


def planned(spans: list) -> bool:
    """Whether set-up ran the planner (its root span `plan`)."""
    return any(e["name"] == "plan" for e in spans)
