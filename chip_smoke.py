#!/usr/bin/env python3
"""Bring-up smoke run of the SpMV system on a TPU.

Drives the main path through the entry points a user calls —
`plan() -> Plan.build() -> Operator`, `cg.solve_problem` and
`SpmvService` — on an HPCG-style 3-D operator at HPCG's default local
grid of 104^3: `stencil_3d(104)`, 1,124,864 rows and 7,809,152 nnz, f32,
shuffled with seed 1 so that `baseline` and `rcm` differ. It is a
7-point stencil, not HPCG's 27-point one. As in HPCG, each diagonal entry
is the row's sum of |off-diagonal| (plus DIAG_MARGIN), so the
off-diagonal part carries a share of every y_i that the checks can see,
and CG does real iterations.

    python chip_smoke.py              # one chip: plans, engines, CG, service
    python chip_smoke.py --chips 4    # four chips: sharded layouts, router

Every result is checked against a host float64 (scipy) reference; any
failure ends the run with a traceback and a non-zero exit. Without a TPU
the run stops before any work and prints no result. Earlier lines give
each phase's wall and compile seconds (bring-up times, not benchmark
numbers); the last line of standard output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run plans into a fresh store under `.cache/chip_smoke` and keeps
JAX's compile cache where `repro.compile_cache` puts it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
STORE = os.path.join(ROOT, ".cache", "chip_smoke")
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402
import scipy.sparse as sps  # noqa: E402

from repro import compile_cache, obs  # noqa: E402

GRID = 104            # HPCG's default local grid
SHUFFLE_SEED = 1
DIAG_MARGIN = 1.0     # diagonal = row sum of |off-diagonal| + this
SPMV_RTOL = 1e-5      # max |y - y_ref| / max |y_ref|, f32 against f64
CG_RTOL = 1e-6        # CG stops at ||r|| <= CG_RTOL * ||b|| (f32 residual)
CG_CHECK = 1e-5       # host f64 ||b - A x|| / ||b|| the solution must meet
K = 8                 # SpMM width and service batch


class _CompileClock:
    """Seconds JAX spends in backend compilation (monitoring events)."""

    def __init__(self):
        self.secs = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


CLOCK = _CompileClock()


@contextlib.contextmanager
def phase(name: str):
    """Print one line per phase: pass/fail, wall and compile seconds, and
    whatever the phase records."""
    rec: dict = {}
    c0, t0 = CLOCK.secs, time.perf_counter()
    try:
        yield rec
    except BaseException:
        print(f"[{name}] FAIL wall_s={time.perf_counter() - t0:.3f}",
              flush=True)
        raise
    extra = " ".join(f"{k}={v}" for k, v in rec.items())
    print(f"[{name}] pass wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={CLOCK.secs - c0:.3f} {extra}", flush=True)


def check(label: str, got, want: np.ndarray, rtol: float = SPMV_RTOL) -> float:
    """Relative max error of `got` against the f64 reference; raises past
    `rtol` or on a wrong shape or a non-finite value."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"{label}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{label}: non-finite values")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    if err > rtol:
        raise AssertionError(f"{label}: relative error {err:.3e} > {rtol:g}")
    return err


def _timed(fn, *args):
    """(result, seconds) of fn(*args), waited on."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _exercise(op, x, xk, ref, refk, rec: dict) -> None:
    """One SpMV and one k-wide SpMM, each twice (the first call compiles),
    checked against the reference."""
    y, rec["spmv_first_s"] = _timed(op, x)
    y, rec["spmv_s"] = _timed(op, x)
    rec["spmv_err"] = f"{check('spmv', y, ref):.2e}"
    y, rec["spmm_first_s"] = _timed(op.matmul, xk)
    y, rec["spmm_s"] = _timed(op.matmul, xk)
    rec["spmm_err"] = f"{check('spmm', y, refk):.2e}"
    for k in ("spmv_first_s", "spmv_s", "spmm_first_s", "spmm_s"):
        rec[k] = f"{rec[k]:.4f}"


def hpcg_matrix(grid: int = GRID):
    """stencil_3d(grid) in grid order, f32, with an HPCG-like diagonal."""
    from repro.matrices import generators

    mat = generators.stencil_3d(grid)
    rows = np.repeat(np.arange(mat.m), np.diff(mat.rowptr))
    diag = rows == mat.cols
    off = np.bincount(rows[~diag], np.abs(mat.vals[~diag]), minlength=mat.m)
    vals = mat.vals.astype(np.float32)
    vals[diag] = off[rows[diag]] + DIAG_MARGIN
    return dataclasses.replace(mat, vals=vals)


def make_matrix(grid: int = GRID):
    """The smoke operator: hpcg_matrix(grid), shuffled."""
    from repro.matrices import generators

    return generators.shuffle(hpcg_matrix(grid), seed=SHUFFLE_SEED)


def host_ref(mat) -> sps.csr_matrix:
    return sps.csr_matrix((mat.vals.astype(np.float64), mat.cols, mat.rowptr),
                          shape=mat.shape)


def pallas_engines() -> set:
    from repro.api import ENGINE_REGISTRY

    return {n for n, s in ENGINE_REGISTRY.items() if s.device == "tpu"}


def check_spans(events: list, expect: str) -> int:
    """Every kernel span of a Pallas engine ran in mode `expect`; returns
    how many there were."""
    pallas = pallas_engines()
    seen = 0
    for ev in events:
        args = ev["args"]
        if ev["name"] in ("kernel.spmv", "kernel.spmm") \
                and args.get("engine") in pallas:
            seen += 1
            if args.get("use_kernel") != expect:
                raise AssertionError(
                    f"{args.get('engine')} ran use_kernel="
                    f"{args.get('use_kernel')!r}, expected {expect!r}")
    if not seen:
        raise AssertionError("no Pallas engine ran")
    return seen


def run_one_chip(mat, use_kernel: str = "auto", seed: int = 0) -> None:
    """Plans, every registered engine, CG and the service on one device."""
    import jax.numpy as jnp

    from repro.api import ENGINE_REGISTRY, SpmvProblem, plan
    from repro.core.measure import cg
    from repro.core.spmv import opcache, tune
    from repro.serving.spmv_service import SpmvService

    rng = np.random.default_rng(seed)
    S = host_ref(mat)
    n = mat.shape[1]
    x = rng.standard_normal(n).astype(np.float32)
    xk = rng.standard_normal((n, K)).astype(np.float32)
    ref, refk = S @ x.astype(np.float64), S @ xk.astype(np.float64)
    xd, xkd = jnp.asarray(x), jnp.asarray(xk)
    hints = {"use_kernel": use_kernel}

    for scheme in ("baseline", "rcm"):
        with phase(f"plan {scheme} auto") as rec:
            pl = plan(SpmvProblem(mat, hints=hints), reorder=scheme,
                      engine="auto", probe=False)
            op = pl.build()
            rec.update(choice=pl.label(), reorder_ms=f"{pl.reorder_ms:.0f}",
                       tune_ms=f"{pl.tune_ms:.0f}",
                       build_ms=f"{op.build_info['build_ms']:.0f}",
                       device_bytes=opcache.operator_nbytes(op))
            _exercise(op, xd, xkd, ref, refk, rec)
        del op
        gc.collect()

    feat = tune.matrix_features(mat)
    for name, spec in ENGINE_REGISTRY.items():
        if spec.candidates_fn is not None \
                and not spec.candidates_fn(mat, feat):
            print(f"[engine {name}] skipped: not a candidate at "
                  f"{mat.shape[0]} rows ({spec.description})", flush=True)
            continue
        # DIA stores a row of m values per distinct col - row: the grid's
        # own order has 7, RCM's thousands would not fit the device
        scheme = "baseline" if name == "dia" else "rcm"
        with phase(f"engine {name} {scheme}") as rec:
            # built outside the plan store: its arrays run to GBs
            pl = plan(SpmvProblem(mat, hints=hints), reorder=scheme,
                      engine=name, probe=False, cache=False)
            op = pl.build(cache=False)
            rec.update(format=pl.tune.label(),
                       use_kernel=getattr(op.inner, "use_kernel", "xla"),
                       build_ms=f"{op.build_info['build_ms']:.0f}",
                       device_bytes=opcache.operator_nbytes(op))
            _exercise(op, xd, xkd, ref, refk, rec)
        del op
        gc.collect()

    with phase("cg rcm") as rec:
        b = rng.standard_normal(n).astype(np.float32)
        bnorm = float(np.linalg.norm(b.astype(np.float64)))
        res, op = cg.solve_problem(
            SpmvProblem(mat, hints=hints), jnp.asarray(b), reorder="rcm",
            tol=CG_RTOL * bnorm, max_iter=500)
        xs = np.asarray(res.x, np.float64)
        rel = float(np.linalg.norm(b - S @ xs) / bnorm)
        if not rel <= CG_CHECK:
            raise AssertionError(f"cg: host relative residual {rel:.3e} > "
                                 f"{CG_CHECK:g}")
        rec.update(choice=op.plan.label(), iters=int(res.iters),
                   rel_residual=f"{rel:.2e}", target=f"{CG_RTOL:g}")
        del op
        gc.collect()

    with phase("service rcm") as rec:
        xs = rng.standard_normal((4 * K, n)).astype(np.float32)
        with SpmvService(max_batch=K, reorder="rcm",
                         use_kernel=use_kernel) as svc:
            svc.register("hpcg", mat)
            futs = [svc.submit("hpcg", xi) for xi in xs]
            ys = [f.result(timeout=1200) for f in futs]
            stats = svc.stats()
        err = max(check(f"service request {i}", y, S @ xi.astype(np.float64))
                  for i, (y, xi) in enumerate(zip(ys, xs)))
        rec.update(requests=len(ys), max_err=f"{err:.2e}",
                   batches=stats.get("batches"))


def run_four_chips(mat, seed: int = 0) -> None:
    """ShardedOperator layouts on a 4-device mesh and the router over two
    2-device meshes, against the one-chip operator and the reference."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.api import SpmvProblem, Topology, plan
    from repro.core.spmv import opcache
    from repro.core.spmv.distributed import _INDEX_MAPS
    from repro.router import MeshSpec, RoutedSpmvService

    def mesh_arrays(op) -> tuple:
        """The sharded operator's device arrays: panels and index maps."""
        return op._device_arrays(jnp.float32) + tuple(
            getattr(op, name) for name in _INDEX_MAPS)

    rng = np.random.default_rng(seed)
    S = host_ref(mat)
    n = mat.shape[1]
    x = rng.standard_normal(n).astype(np.float32)
    xk = rng.standard_normal((n, K)).astype(np.float32)
    ref, refk = S @ x.astype(np.float64), S @ xk.astype(np.float64)
    xd, xkd = jnp.asarray(x), jnp.asarray(xk)

    with phase("one chip rcm auto") as rec:
        one = plan(SpmvProblem(mat), reorder="rcm", engine="auto").build()
        y1 = np.asarray(one(xd), np.float64)
        rec.update(choice=one.plan.label(),
                   err=f"{check('one chip', y1, ref):.2e}")

    cases = [("1d_rows", "baseline", "all_gather"),
             ("1d_rows", "rcm", "halo"),
             ("2d_panels", "rcm", "psum")]
    for layout, scheme, expected in cases:
        with phase(f"sharded {layout} {scheme}") as rec:
            pl = plan(SpmvProblem(mat), reorder=scheme, engine="auto",
                      topology=Topology(devices=4, layout=layout))
            op = pl.build()
            if op.simulated is not False:
                raise AssertionError("sharded operator is simulated")
            devs = set(op.devices)
            for a in mesh_arrays(op):
                if not isinstance(a.sharding, NamedSharding) \
                        or a.sharding.device_set != devs:
                    raise AssertionError(f"panel array on {a.sharding}")
            rec.update(choice=pl.label(), schedule=op.layout.schedule,
                       expected=expected, halo=op.layout.halo,
                       mesh=op.topology.mesh_shape,
                       device_bytes=opcache.operator_nbytes(op))
            _exercise(op, xd, xkd, ref, refk, rec)
            rec["vs_one_chip"] = f"{check('vs one chip', op(xd), y1):.2e}"
        del op
        gc.collect()

    with phase("router 2x2-device meshes") as rec:
        meshes = [MeshSpec("m0", Topology(devices=2)),
                  MeshSpec("m1", Topology(devices=2))]
        xs = rng.standard_normal((K, n)).astype(np.float32)
        with RoutedSpmvService(meshes, max_batch=K,
                               reorder="rcm") as router:
            router.register("a", mat, mesh="m0")
            router.register("b", mat, mesh="m1")
            futs = {key: [router.submit(key, xi) for xi in xs]
                    for key in ("a", "b")}
            placed = {}
            for key, fs in futs.items():
                for i, (f, xi) in enumerate(zip(fs, xs)):
                    check(f"router {key} request {i}", f.result(timeout=1200),
                          S @ xi.astype(np.float64))
                op = router.operator(key)
                devs = set(op.devices)
                if op.simulated or len(devs) != 2:
                    raise AssertionError(f"key {key} not on a 2-device mesh")
                for a in mesh_arrays(op):
                    if set(a.devices()) != devs:
                        raise AssertionError(f"key {key}: arrays off its mesh")
                placed[key] = sorted(d.id for d in devs)
        if set(placed["a"]) & set(placed["b"]):
            raise AssertionError(f"meshes share devices: {placed}")
        rec.update(devices=placed, requests=2 * len(xs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded layouts and the router only")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)

    shutil.rmtree(STORE, ignore_errors=True)
    os.environ["REPRO_PLAN_CACHE"] = os.path.join(STORE, "plans")
    os.environ["REPRO_REORDER_CACHE"] = os.path.join(STORE, "reorder")
    os.environ["REPRO_OPERATOR_CACHE"] = "off"
    cache_dir = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(CLOCK)
    print(f"[compile cache] dir={cache_dir}", flush=True)

    with phase("matrix") as rec:
        mat = make_matrix()
        rec.update(source=f"stencil_3d({GRID}) 7-point (HPCG's grid, not "
                          f"its 27-point stencil), diagonal = row sum of "
                          f"|off-diagonal| + {DIAG_MARGIN:g}, shuffle seed "
                          f"{SHUFFLE_SEED}", rows=mat.shape[0], nnz=mat.nnz,
                   dtype=mat.vals.dtype.name)

    with obs.tracing() as buf:
        if args.chips == 4:
            run_four_chips(mat)
        else:
            run_one_chip(mat)
    if args.chips == 1:
        with phase("spans") as rec:
            rec["pallas_kernel_spans"] = check_spans(buf.flush(), "pallas")
    counters = obs.snapshot()["counters"]
    print(f"[compile cache] hits={counters.get('compile_cache.hits', 0)} "
          f"misses={counters.get('compile_cache.misses', 0)} "
          f"compile_s={CLOCK.secs:.3f}", flush=True)
    shutil.rmtree(STORE, ignore_errors=True)
    # count: the chips this run used; visible: all the process sees
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips, "visible": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
