"""The program's layer names, where they must land.

* the device scopes (`spmv`, `spmv.permute`, `spmv.engine`,
  `spmv.gather`) reach the compiled HLO's `op_name` metadata, both inside
  a jitted caller (a non-identity operator under `jit`) and inside the
  jitted `sell_spmm` of an eager SELL call;
* they change metadata only: the compiled HLO, metadata aside, and its op
  histogram are the same without them;
* the planner's `plan.reorder` / `plan.permute` / `plan.tune` spans time
  the same intervals as the plan record's `reorder_ms` / `tune_ms`.
"""
import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import SpmvProblem, Topology, plan
from repro.core.measure.cg import cg_solve
from repro.kernels.sell_spmv.kernel import sell_spmm
from repro.launch import hlo
from repro.matrices import generators as G

OPS = ("fusion", "dot", "custom-call", "while", "dynamic-update-slice",
       "gather", "scatter", "reduce", "multiply", "add", "select",
       "broadcast", "concatenate", "transpose", "copy")


@pytest.fixture(autouse=True)
def no_stores(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", "off")
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", "off")


def mesh_operator(engine: str):
    mat = G.shuffle(G.stencil_3d(5), seed=1)
    op = plan(SpmvProblem(mat), reorder="rcm", engine=engine,
              cache=False).build(cache=False)
    assert op.perm is not None
    return mat, op


def op_names(text: str) -> collections.Counter:
    return collections.Counter(re.findall(r'op_name="([^"]*)"', text))


def scoped(names, *segments) -> bool:
    """Some op's path holds every segment, in order."""
    return any("/" + "/".join(segments) + "/" in "/" + n for n in names)


@pytest.mark.parametrize("engine", ["csr", "ell", "dia"])
@pytest.mark.parametrize("call", ["call", "matmul"])
def test_operator_scopes_reach_hlo_op_names(engine, call):
    mat, op = mesh_operator(engine)
    n = mat.shape[1]
    if call == "call":
        fn, x = (lambda v: op(v)), jnp.ones(n)
    else:
        fn, x = (lambda v: op.matmul(v)), jnp.ones((n, 2))
    names = op_names(jax.jit(fn).lower(x).compile().as_text())
    assert scoped(names, "spmv", "spmv.permute")
    assert scoped(names, "spmv", "spmv.engine")
    # DIA reads shifted windows of x: no gather to name
    assert any("spmv/spmv.engine/" in k and "/spmv.gather/" in k
               for k in names) == (engine != "dia")


def test_cg_solve_carries_the_operator_scopes():
    mat, op = mesh_operator("ell")
    names = op_names(cg_solve.lower(op, jnp.ones(mat.shape[1]), max_iter=3,
                                    tol=0.0).compile().as_text())
    inside = [k for k in names if k.startswith("jit(cg_solve)/while/body/")]
    assert scoped(inside, "spmv", "spmv.permute")
    assert any("spmv/spmv.engine/" in k and "/spmv.gather/" in k
               for k in inside)


def sell_lowered():
    mat = G.power_law(300, seed=2)
    op = plan(SpmvProblem(mat, hints={"use_kernel": "interpret"}),
              reorder="baseline", engine="sell", cache=False).build(
                  cache=False).inner
    x = jnp.ones((mat.shape[1], 1))
    return sell_spmm.lower(op.chunk_vals, op.chunk_cols, op.chunk_slice, x,
                           op.num_slices, segments=op.segments, kb=1,
                           interpret=True)


def test_sell_gather_scope_inside_jitted_kernel_wrapper():
    names = op_names(sell_lowered().compile().as_text())
    assert scoped(names, "jit(sell_spmm)", "spmv.gather")


_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def strip_metadata(text: str) -> str:
    """The HLO text without op metadata, the module's name line and the
    source-location tables (line numbers of the tracing code)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not line.startswith(("HloModule",) + _DEBUG_TABLES)
        and not re.match(r'^\d+ [{"]', line))


def compiled_texts() -> list:
    mat, op = mesh_operator("ell")
    b = jnp.ones(mat.shape[1])
    return [cg_solve.lower(op, b, max_iter=3, tol=0.0).compile().as_text(),
            jax.jit(op.matmul).lower(jnp.ones((mat.shape[1], 2)))
            .compile().as_text(),
            sell_lowered().compile().as_text()]


def test_scopes_leave_the_compiled_hlo_unchanged(monkeypatch):
    jax.clear_caches()
    with_scopes = compiled_texts()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    without = compiled_texts()
    jax.clear_caches()
    for a, b in zip(with_scopes, without):
        assert "spmv" in "".join(op_names(a)) and \
            "spmv" not in "".join(op_names(b))
        assert hlo.op_histogram(a, OPS) == hlo.op_histogram(b, OPS)
        assert hlo.op_histogram(a) == hlo.op_histogram(b)
        assert strip_metadata(a) == strip_metadata(b)


def span_ms(events, *names) -> float:
    return sum(e["dur"] for e in events if e["name"] in names) / 1e3


@pytest.mark.parametrize("engine", ["auto", "ell"])
def test_plan_spans_and_record_time_the_same_intervals(engine):
    mat = G.shuffle(G.stencil_3d(6), seed=3)
    problem = SpmvProblem(mat, hints={"schemes": ["baseline", "rcm",
                                                  "rcm_blocked"]})
    with obs.tracing() as buf:
        pl = plan(problem, reorder="auto", engine=engine, cache=False)
        op = pl.build(cache=False)
    events = buf.flush()
    names = [e["name"] for e in events]
    assert names.count("plan.reorder") == 2      # baseline runs none
    assert names.count("plan.permute") == 2
    assert names.count("plan.tune") == 3
    assert pl.reorder_ms > 0
    assert pl.reorder_ms == pytest.approx(
        span_ms(events, "plan.reorder", "plan.permute"), rel=1e-9)
    assert pl.tune_ms == pytest.approx(span_ms(events, "plan.tune"),
                                       rel=1e-9)
    x = np.ones(mat.shape[1], np.float32)
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(x))), mat.spmv(x),
                               rtol=1e-5)


def test_timed_spans_feed_the_record_with_tracing_off():
    mat = G.shuffle(G.stencil_3d(6), seed=3)
    assert not obs.enabled()
    pl = plan(SpmvProblem(mat), reorder="rcm", engine="ell", cache=False)
    assert pl.reorder_ms > 0 and pl.tune_ms > 0


def test_sharded_plan_reorder_ms_is_its_spans():
    mat = G.shuffle(G.stencil_3d(6), seed=3)
    with obs.tracing() as buf:
        pl = plan(SpmvProblem(mat), reorder="rcm", engine="csr",
                  cache=False, topology=Topology(devices=2))
    events = buf.flush()
    assert "plan.partition" in [e["name"] for e in events]
    assert pl.reorder_ms == pytest.approx(
        span_ms(events, "plan.reorder", "plan.permute", "plan.partition"),
        rel=1e-9)
    assert pl.tune_ms == pytest.approx(span_ms(events, "plan.tune"),
                                       rel=1e-9)


def test_build_permutes_under_a_span():
    mat = G.shuffle(G.stencil_3d(5), seed=1)
    pl = plan(SpmvProblem(mat), reorder="rcm", engine="csr", cache=False)
    pl._rmat = None                          # as a plan loaded from disk
    with obs.tracing() as buf:
        pl.build(cache=False)
    build = [e for e in buf.flush() if e["name"] == "plan.build"][0]
    permute = [e for e in buf.flush() if e["name"] == "plan.permute"]
    assert len(permute) == 1 and permute[0]["parent"] == build["id"]
