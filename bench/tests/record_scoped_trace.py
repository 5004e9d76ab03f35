#!/usr/bin/env python3
"""Record a small profiler trace whose device ops carry the program's
scopes, for tests/test_trace_scopes.py:

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>

On one TPU chip, inside a `bench.window` annotation with the program's
obs spans on: three eager SELL SpMVs on a small power-law matrix (the
`spmv.gather` scope inside the jitted `sell_spmm`), then one 5-iteration
CG on a shuffled 8^3 stencil under an RCM plan with the ELL engine, so
the operator's permutation gathers run (`spmv`, `spmv.permute`,
`spmv.engine`, `spmv.gather`), its wait inside the span `record.cg`.
Every program is compiled and run once before the trace starts; the
trace leaves out Python calls and HLO modules, to stay small.
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    harness.configure_env()
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.api import SpmvProblem, plan
    from repro.core.measure.cg import cg_solve
    from repro.matrices import generators

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 3
    graph = generators.power_law(4096, seed=3)
    sell = plan(SpmvProblem(graph), reorder="baseline", engine="sell",
                cache=False).build(cache=False)
    mesh = generators.shuffle(generators.stencil_3d(8), seed=1)
    ell = plan(SpmvProblem(mesh), reorder="rcm", engine="ell",
               cache=False).build(cache=False)
    if ell.perm is None:
        raise RuntimeError("the CG plan must not be the identity")
    x = jnp.ones(graph.shape[1], jnp.float32)
    b = jnp.ones(mesh.shape[1], jnp.float32)

    def solve():
        return cg_solve(ell, b, max_iter=5, tol=0.0).x

    jax.block_until_ready((sell(x), solve()))
    ann = jax.profiler.TraceAnnotation
    tmp = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # a small file: no Python calls,
    options.enable_hlo_proto = False     # no HLO modules
    jax.profiler.start_trace(tmp, profiler_options=options)
    with obs.tracing(), ann("bench.window"):
        for _ in range(3):
            with ann("bench.dispatch"):
                y = sell(x)
            with ann("bench.wait"):
                y.block_until_ready()
        with obs.span("record.cg"):
            with ann("bench.dispatch"):
                xs = solve()
            with ann("bench.wait"):
                xs.block_until_ready()
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))
    shutil.copy(found[-1], out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
