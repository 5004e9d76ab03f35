"""Seconds of set-up in which JAX traced a Python function to a jaxpr or
lowered a jaxpr to MLIR: the runtime's `runtime.trace` and `runtime.lower`
spans, whichever program they belong to. None where the program records
no such span."""
from bench import program_spans

NAMES = ("runtime.trace", "runtime.lower")


def read(reading):
    if not any(e["name"] in NAMES for e in reading.spans):
        return None
    return program_spans.union_s(reading.spans, NAMES)
