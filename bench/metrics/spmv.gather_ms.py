"""Device milliseconds under the program's `spmv.gather` scope (the
engine's gather of x) per application of the operator, from the traced
window (trace_scopes). None where the trace has no scopes or no op
carries `spmv.gather`."""


def read(reading):
    s = reading.scope_per_unit_s("spmv.gather")
    return None if s is None else 1e3 * s
