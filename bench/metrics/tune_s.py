"""Seconds of set-up in the planner's engine choice: its `plan.tune`
spans, one per scheme it scored. None where set-up ran no planner."""
from bench import program_spans


def read(reading):
    if not program_spans.planned(reading.spans):
        return None
    return program_spans.union_s(reading.spans, ("plan.tune",))
