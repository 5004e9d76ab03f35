"""Conjugate Gradient — the paper's "real application" yardstick (Listing 3).

Four forms:
  * cg_solve      — fully jit-compiled (lax.while_loop) production solver
                    used by examples/cg_solver.py and the distributed runtime.
  * block_cg_solve— k right-hand sides at once; one SpMM (operator.matmul)
                    per iteration instead of k SpMVs — the solver workload
                    the batched engine layer opens.
  * solve_problem — pipeline-facade consumer: plan + build + solve entirely
                    in the ORIGINAL index space (the permutation-carrying
                    operator absorbs the reordering; callers never permute
                    b or un-permute x by hand).
  * cg_measured   — open-coded iteration that times the SpMV separately from
                    the vector updates, exactly like the paper's
                    instrumented Listing 3 (per-iteration SpMV wall-clock).

The corpus generators make matrices strictly diagonally dominant
(diagonal = m), hence SPD, so CG converges.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _dot(a, b):
    # full f32: the TPU's default precision would round to bf16
    return jnp.vdot(a, b, precision=jax.lax.Precision.HIGHEST)


class CGResult(NamedTuple):
    x: jax.Array
    iters: jax.Array
    residual: jax.Array


@functools.partial(jax.jit, static_argnames=("max_iter",))
def cg_solve(matvec: Callable, b: jax.Array, max_iter: int = 100,
             tol: float = 1e-8) -> CGResult:
    """Standard CG, jit-compiled end-to-end (lax.while_loop).

    `matvec` is a pytree argument: a plan's `Operator` or an engine. The
    arrays of a pytree engine (`dia`) are arguments of the compiled loop;
    an engine registered static is compiled into it as a constant. Wrap
    a plain function in `jax.tree_util.Partial`."""
    x0 = jnp.zeros_like(b)
    r0 = b - matvec(x0)
    p0 = r0
    rs0 = _dot(r0, r0)

    def cond(state):
        _, _, _, rs, k = state
        return jnp.logical_and(k < max_iter, rs > tol * tol)

    def body(state):
        x, r, p, rs, k = state
        ap = matvec(p)
        alpha = rs / _dot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = _dot(r, r)
        p = r + (rs_new / rs) * p
        return (x, r, p, rs_new, k + 1)

    x, r, p, rs, k = jax.lax.while_loop(cond, body, (x0, r0, p0, rs0, 0))
    return CGResult(x=x, iters=k, residual=jnp.sqrt(rs))


@functools.partial(jax.jit, static_argnames=("matmul", "max_iter"))
def block_cg_solve(matmul: Callable, b: jax.Array, max_iter: int = 100,
                   tol: float = 1e-8) -> CGResult:
    """Batched CG over k right-hand sides: solve A X = B, B of shape [n, k].

    The k recurrences are mathematically independent (per-column α/β —
    'diagonal' block CG), but each iteration issues ONE SpMM `A @ P[n, k]`
    instead of k SpMVs: the solver-side consumer of the batched engine
    layer, streaming the matrix once per iteration for all systems.
    Converged columns freeze (α = β = 0), so the loop runs until the
    slowest column meets tol or max_iter.
    """
    x0 = jnp.zeros_like(b)
    r0 = b - matmul(x0)
    p0 = r0
    rs0 = jnp.sum(r0 * r0, axis=0)                 # [k] per-column ||r||^2

    def cond(state):
        _, _, _, rs, k = state
        return jnp.logical_and(k < max_iter, jnp.any(rs > tol * tol))

    def body(state):
        x, r, p, rs, k = state
        ap = matmul(p)                             # one SpMM for all k RHS
        pap = jnp.sum(p * ap, axis=0)
        live = rs > tol * tol
        alpha = jnp.where(live, rs / jnp.where(pap == 0, 1.0, pap), 0.0)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * ap
        rs_new = jnp.sum(r * r, axis=0)
        beta = jnp.where(live, rs_new / jnp.where(rs == 0, 1.0, rs), 0.0)
        p = jnp.where(live[None, :], r + beta[None, :] * p, p)
        return (x, r, p, jnp.where(live, rs_new, rs), k + 1)

    x, r, p, rs, k = jax.lax.while_loop(cond, body, (x0, r0, p0, rs0, 0))
    return CGResult(x=x, iters=k, residual=jnp.sqrt(rs))


def solve_problem(problem, b: jax.Array, reorder: str = "auto",
                  engine: str = "auto", max_iter: int = 100,
                  tol: float = 1e-8, probe: bool = False,
                  cache: bool = True, topology=None, partition="auto"):
    """Plan, build, and CG-solve A x = b through the pipeline facade.

    `problem` is an SpmvProblem or a bare CSRMatrix. b of shape [n] runs
    cg_solve; [n, k] runs block_cg_solve (one SpMM per iteration). Both b
    and the returned solution live in the ORIGINAL index space — the
    reordering the planner picks (e.g. reorder="auto" choosing rcm for
    locality) happens inside the permutation-carrying operator, so there
    is no hand-carried permutation between caller and solver.

    topology/partition (core/spmv/topology.py) run the same solve on a
    sharded plan: every per-iteration SpMV is the ShardedOperator's
    collective step, b and x still in the original index space.

    Returns (CGResult, Operator); the operator's `.plan` records what the
    pipeline decided (scheme, engine, partition, costs).
    """
    from ...api import SpmvProblem, plan as make_plan

    k = int(b.shape[1]) if getattr(b, "ndim", 1) == 2 else 1
    if not isinstance(problem, SpmvProblem):
        problem = SpmvProblem(problem, k=k)
    pl = make_plan(problem, reorder=reorder, engine=engine, probe=probe,
                   cache=cache, topology=topology, partition=partition)
    op = pl.build(cache=cache)
    if k > 1:
        res = block_cg_solve(op.matmul, b, max_iter=max_iter, tol=tol)
    else:
        res = cg_solve(op, b, max_iter=max_iter, tol=tol)
    return res, op


def cg_measured(matvec: Callable, b: jax.Array, iters: int = 20,
                warmup: int = 2) -> np.ndarray:
    """Instrumented CG (paper Listing 3): per-iteration SpMV ms.

    The vector updates (dot, axpy) run between timed SpMVs and perturb the
    cache state exactly as in the real application — this is the behaviour
    IOS approximates and YAX misses.
    """

    @jax.jit
    def vec_update(x, r, p, ap, rs_old):
        pap = _dot(p, ap)
        alpha = rs_old / pap
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = _dot(r, r)
        beta = rs_new / rs_old
        p = r + beta * p
        return x, r, p, rs_new

    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = _dot(r, r)
    times = []
    for i in range(iters + warmup):
        t0 = time.perf_counter()
        ap = matvec(p)
        ap.block_until_ready()
        dt = (time.perf_counter() - t0) * 1e3
        if i >= warmup:
            times.append(dt)
        x, r, p, rs = vec_update(x, r, p, ap, rs)
    return np.asarray(times)
