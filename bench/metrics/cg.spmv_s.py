"""Device seconds under the program's `spmv` scope (the operator's
permutation gathers and its engine) per CG set, from the traced window
(trace_scopes). None where the trace has no scopes or no op carries
`spmv`."""


def read(reading):
    return reading.scope_per_unit_s("spmv")
