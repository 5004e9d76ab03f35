"""Device seconds under the program's `spmv.permute` scope (the
operator's permutation gathers around the engine) per CG set, from the
traced window (trace_scopes). 0.0 where the plan's identity permutation
runs no gather; None where the trace has no scopes or no op carries
`spmv`."""


def read(reading):
    if reading.scope_per_unit_s("spmv") is None:
        return None
    return reading.scope_per_unit_s("spmv.permute") or 0.0
