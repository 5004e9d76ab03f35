"""The set-up readers `reorder_s`, `tune_s` and `lower_s` on hand-made
readings: the union of their spans' intervals, 0.0 where the planner
reordered nothing, and nothing where the program records no such span."""
import os

import pytest

from bench import harness, program_spans

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ev(name, ts, dur):
    return {"name": name, "ts": float(ts), "dur": float(dur), "args": {}}


def reading(spans):
    return harness.Reading(spans=spans, compile_setup_s=0.0, units=1,
                           least_bytes_per_unit=1, peaks=None, trace=None)


def read(metric, spans):
    return harness.Bench(REPO).reader(metric).read(reading(spans))


# µs: plan 0..10 s; two schemes reordered and permuted, three tuned;
# JAX traced two programs, one nested in the other, and lowered one
SPANS = [ev("plan", 0, 10e6),
         ev("plan.reorder", 1e6, 2e6), ev("plan.permute", 3e6, 0.5e6),
         ev("plan.reorder", 4e6, 2e6), ev("plan.permute", 6e6, 0.5e6),
         ev("plan.tune", 0.2e6, 0.5e6), ev("plan.tune", 3.5e6, 0.5e6),
         ev("plan.tune", 6.5e6, 1e6),
         ev("plan.build", 10e6, 3e6),
         ev("runtime.trace", 14e6, 2e6), ev("runtime.trace", 14.5e6, 1e6),
         ev("runtime.lower", 16e6, 1e6), ev("runtime.compile", 17e6, 5e6)]


def test_union_counts_nested_spans_once():
    assert program_spans.union_s(
        [ev("a", 0, 10), ev("a", 2, 3), ev("b", 8, 4), ev("c", 0, 100)],
        ("a", "b")) == pytest.approx(12e-6)
    assert program_spans.union_s([], ("a",)) == 0.0


def test_reorder_s_is_reorder_and_permute():
    assert read("reorder_s", SPANS) == pytest.approx(5.0)


def test_reorder_s_is_zero_when_the_plan_reordered_nothing():
    baseline = [e for e in SPANS
                if e["name"] not in ("plan.reorder", "plan.permute")]
    assert read("reorder_s", baseline) == 0.0


def test_tune_s_is_the_tune_spans():
    assert read("tune_s", SPANS) == pytest.approx(2.0)
    assert read("tune_s", [ev("plan", 0, 1)]) == 0.0


def test_lower_s_is_the_union_of_trace_and_lower():
    assert read("lower_s", SPANS) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", ["reorder_s", "tune_s", "lower_s"])
def test_nothing_to_read_reads_none(metric):
    """Set-up without a planner, or a program that records no runtime
    spans (the parent of the change that added them)."""
    assert read(metric, []) is None
    no_runtime = [e for e in SPANS if not e["name"].startswith("runtime.")]
    if metric == "lower_s":
        assert read(metric, no_runtime) is None
    else:
        assert read(metric, no_runtime) is not None
