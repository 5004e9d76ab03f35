"""Seconds of set-up in reordering: the planner's `plan.reorder` spans
(each scheme's permutation) and `plan.permute` spans (the matrix put in
that order). 0.0 when the plan reordered nothing, as `baseline` does;
None where set-up ran no planner."""
from bench import program_spans


def read(reading):
    if not program_spans.planned(reading.spans):
        return None
    return program_spans.union_s(reading.spans,
                                 ("plan.reorder", "plan.permute"))
