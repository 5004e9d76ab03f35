"""Pallas TPU kernels for the block and sliced sparse formats.

Each kernel package holds kernel.py (the pallas_call), ops.py (the
device operator around it) and ref.py (the jnp oracle). An operator's
`use_kernel` picks how it runs: "pallas" compiles the kernel for the
TPU, "interpret" runs the kernel body on any backend (the tests), and
"ref" runs the jnp oracle.
"""
from __future__ import annotations

import logging

from .. import obs

USE_KERNEL_MODES = ("auto", "pallas", "interpret", "ref")

_log = logging.getLogger(__name__)
_warned: set = set()


def resolve_use_kernel(use_kernel: str, engine: str) -> str:
    """The execution mode for `use_kernel`: "auto" is "pallas" on a TPU
    backend and "ref" elsewhere. A Pallas engine that runs its jnp oracle
    for want of a TPU says so: the `kernel.ref_fallback` counter moves and
    a warning is logged once per engine."""
    if use_kernel not in USE_KERNEL_MODES:
        raise ValueError(f"use_kernel must be one of {USE_KERNEL_MODES}, "
                         f"got {use_kernel!r}")
    if use_kernel != "auto":
        return use_kernel
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    obs.counter("kernel.ref_fallback", engine=engine).inc()
    if engine not in _warned:
        _warned.add(engine)
        _log.warning("backend %r is not a TPU: engine %r runs its jnp "
                     "reference, not its Pallas kernel", backend, engine)
    return "ref"
