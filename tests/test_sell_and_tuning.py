"""SELL-C-σ engine, OSKI-style autotuner, and persistent operator cache.

Covers the PR's acceptance criteria:
  * cross-engine equivalence (csr/ell/dia/sell/bell/bcsr vs the dense numpy
    oracle) over the generator suites — including the power-law row-skew
    generator — under every reorder scheme in PAPER_SCHEMES
  * SELL beats padded-ELL storage by >= 2x on power-law skew
  * build_operator(mat, engine="auto") returns a tuned operator with a plan
  * the second spmv_bench invocation on the same (matrix, scheme) hits the
    operator cache (no reconversion / re-tune)
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.reorder import api as reorder_api
from repro.core.sparse.csr import CSRMatrix
from repro.core.sparse.sell import (pick_chunk_width, sell_padded_nnz,
                                    sell_to_dense, to_sell)
from repro.core.spmv.ops import DeviceELL, build_operator, make_engine
from repro.matrices import generators as G

ENGINES = ["csr", "ell", "dia", "sell", "bell", "bcsr"]

MATS = {
    "banded": lambda: G.banded(64, 3, 0),
    "stencil": lambda: G.stencil_2d(8, seed=1),
    "rmat": lambda: G.rmat(6, 4, 2),
    "powerlaw": lambda: G.power_law(96, alpha=1.8, seed=3),
    "sbm": lambda: G.shuffle(G.sbm(96, 4, 0.2, 0.01, seed=4), seed=5),
}


def _check_engine(mat, engine, x, want, tol=1e-5):
    kw = {"block_shape": (4, 4)} if engine in ("bell", "bcsr", "sell") else {}
    op = build_operator(mat, engine, **kw)
    got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < tol, engine


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("matname", list(MATS))
@pytest.mark.parametrize("scheme", ["baseline"] + reorder_api.PAPER_SCHEMES)
def test_cross_engine_equivalence(engine, matname, scheme):
    """Every engine x matrix family x paper scheme must match the oracle."""
    mat = MATS[matname]()
    if scheme != "baseline":
        perm = reorder_api.reorder(mat, scheme, cache=False)
        mat = mat.permute(perm)
    x = np.random.default_rng(0).standard_normal(mat.n)
    want = mat.spmv(x)  # numpy oracle == dense oracle (test_sparse_formats)
    _check_engine(mat, engine, x, want)


@given(st.integers(8, 80), st.integers(0, 5))
@settings(max_examples=10, deadline=None)
def test_property_sell_matches_oracle_on_skew(m, seed):
    mat = G.power_law(max(m, 8), alpha=1.8, seed=seed)
    x = np.random.default_rng(seed).standard_normal(mat.n)
    want = mat.spmv(x)
    for c, sigma, w in [(4, 8, 8), (8, 64, 16), (8, 1, 4)]:
        op = build_operator(mat, "sell", block_shape=(c, w), sell_sigma=sigma)
        got = np.asarray(op(jnp.asarray(x, jnp.float32)))
        scale = np.abs(want).max() + 1e-9
        assert np.abs(got - want).max() / scale < 1e-5, (c, sigma, w)


def _ragged_matrix(m: int = 1100, seed: int = 8) -> CSRMatrix:
    """A power-law matrix with m not a multiple of 128, every 7th row
    empty, and one row of min(1050, m - 10) nonzeros: over many chunks,
    and cut into pieces at (C, W) = (128, 8)."""
    a = G.power_law(m, alpha=1.9, seed=seed).to_dense()
    a[::7] = 0.0
    rng = np.random.default_rng(seed)
    long = min(1050, m - 10)
    a[m // 2, rng.choice(m, long, replace=False)] = rng.standard_normal(long)
    return CSRMatrix.from_dense(a)


def test_sell_roundtrip_and_perm():
    mat = _ragged_matrix(300, seed=7)
    s = to_sell(mat, c=8, sigma=64, w=16)
    # rows on lanes: a chunk is [W, C]
    assert s.chunk_vals.shape == s.chunk_cols.shape == (s.num_chunks, 16, 8)
    assert np.allclose(sell_to_dense(s), mat.to_dense())
    # row_perm restricted to real rows holds each row once per piece of at
    # most C·W elements, and an empty row not at all
    counts = mat.row_nnz()
    assert counts.max() > 8 * 16 and (counts == 0).any()
    real = s.row_perm[s.row_perm < mat.m]
    assert np.array_equal(np.bincount(real, minlength=mat.m),
                          -(-counts // (8 * 16)))
    assert s.padded_nnz == sell_padded_nnz(mat, 8, 64, 16)


@pytest.mark.parametrize("use_kernel", ["ref", "interpret"])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("c,w,sigma", [(128, 8, 1), (128, 8, 1024),
                                       (128, 8, "m"), (128, 32, "m"),
                                       (8, 16, 64)])
def test_sell_interpret_kernel_matches_ref(c, w, sigma, k, use_kernel):
    """Rows on lanes against the float64 oracle, on the kernel (interpret)
    and on the jnp reference: empty rows, m off the slice height, a row
    over many chunks, σ = 1, 8·C and m, SpMV (k = 1) and SpMM (k = 8)."""
    mat = _ragged_matrix()
    assert mat.m % 128 and (mat.row_nnz() == 0).any()
    sigma = mat.m if sigma == "m" else sigma
    op = make_engine(mat, "sell", block_shape=(c, w), sell_sigma=sigma,
                     use_kernel=use_kernel)
    assert op.chunk_vals.shape[1:] == (w, c)
    assert mat.row_nnz().max() > 4 * w        # the long row spans chunks
    # it is stored as pieces where it is longer than C·W
    assert (op.split_row.shape[0] > 0) == (mat.row_nnz().max() > c * w)
    rng = np.random.default_rng(k)
    if k == 1:
        x = rng.standard_normal(mat.n)
        want = mat.spmv(x)
        got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    else:
        x = rng.standard_normal((mat.n, k))
        want = mat.to_dense() @ x
        got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
    assert got.shape == want.shape
    assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-5


def test_plan_store_rebuilds_an_old_layout_sell_entry(tmp_path, monkeypatch):
    """A SELL entry written with [T, C, W] chunks (no layout in its meta)
    reads as a miss: the operator is rebuilt, right, and stored anew."""
    import json

    from repro.api import Plan, SpmvProblem, plan

    store = tmp_path / "plans"
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(store))
    mat = _ragged_matrix(300)
    pl = plan(SpmvProblem(mat, hints={"use_kernel": "ref"}),
              reorder="baseline", engine="sell")
    pl.build()
    jpath, zpath = store / f"{pl.key}.json", store / f"{pl.key}.npz"
    rec = json.loads(jpath.read_text())
    assert rec["op"]["meta"].pop("layout") == "rows_on_lanes"
    jpath.write_text(json.dumps(rec))
    z = dict(np.load(zpath))
    for name in ("op__chunk_vals", "op__chunk_cols"):
        z[name] = z[name].transpose(0, 2, 1)
    del z["op__split_pos"], z["op__split_row"]
    np.savez(zpath, **z)

    old = Plan.load(pl.key, mat=mat)
    assert old is not None and old.cache_hit
    op = old.build()
    assert not op.build_info["cache_hit"]
    x = np.random.default_rng(3).standard_normal(mat.n)
    got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    want = mat.spmv(x)
    assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-5
    assert Plan.load(pl.key, mat=mat).build().build_info["cache_hit"]


def test_sell_spans_count_chunks_and_slots():
    from repro import obs

    mat = _ragged_matrix(300)
    op = make_engine(mat, "sell", block_shape=(128, 8), sell_sigma=mat.m,
                     use_kernel="interpret")
    with obs.tracing() as buf:
        op(jnp.ones(mat.n, jnp.float32))
        op.matmul(jnp.ones((mat.n, 8), jnp.float32))
    got = {ev["name"]: ev["args"] for ev in buf.flush()
           if ev["name"] in ("kernel.spmv", "kernel.spmm")}
    want = {"chunks": op.chunk_vals.shape[0],
            "slots": int(np.prod(op.chunk_vals.shape))}
    for name in ("kernel.spmv", "kernel.spmm"):
        assert {a: got[name][a] for a in want} == want, name


def test_sell_beats_ell_padding_2x_on_power_law():
    """Acceptance: >= 2x fewer stored elements than padded ELL on skew."""
    mat = G.power_law(4096, alpha=1.9, seed=0)
    ell_pad = DeviceELL(mat).padded_nnz
    w = pick_chunk_width(mat)
    sell_pad = sell_padded_nnz(mat, c=8, sigma=mat.m, w=w)
    assert sell_pad * 2 <= ell_pad, (sell_pad, ell_pad)
    # and the actual built format agrees with the prediction
    op = build_operator(mat, "sell", block_shape=(8, w), sell_sigma=mat.m)
    assert op.padded_nnz == sell_pad


def test_auto_engine_returns_tuned_operator():
    mat = G.power_law(512, alpha=1.9, seed=1)
    op = build_operator(mat, "auto")
    assert hasattr(op, "plan")
    assert op.plan.engine in ENGINES + ["dense"]
    assert op.plan.source == "model"
    assert op.plan.costs  # every candidate was scored
    x = np.random.default_rng(1).standard_normal(mat.n)
    want = mat.spmv(x)
    got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-5


def test_auto_engine_probe_mode():
    mat = G.banded(256, 4, 0)
    op = build_operator(mat, "auto", probe=True)
    assert op.plan.source == "probe"
    assert op.plan.probe_ms and all(v > 0 for v in op.plan.probe_ms.values())


def test_tuner_prefers_ell_on_uniform_rows_and_not_on_skew():
    from repro.core.spmv.tune import tune

    # a full band is diagonal storage's own case (17 diagonals, no
    # index array); the same band under a random symmetric order keeps
    # its uniform rows but scatters them over thousands of diagonals,
    # and padded ELL is then the cheapest
    band = G.banded(2048, 8, 0)
    assert tune(band).engine == "dia"
    assert tune(G.shuffle(band, seed=1)).engine == "ell"
    skew = tune(G.power_law(2048, alpha=1.8, seed=0))
    # on heavy skew neither padded ELL nor DIA may win
    assert skew.engine != "ell" and skew.engine != "dia"


def test_operator_cache_hit(tmp_path, monkeypatch):
    """Acceptance: second spmv_bench invocation on the same (matrix, scheme)
    reloads the tuned operator — no reconversion, no re-tune. use_store=False
    (--fresh) forces the re-MEASURE so the plan-store reload is what's
    exercised; with the result store on, the second invocation skips even
    the measurement (store_hit)."""
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path / "opcache"))
    monkeypatch.setenv("REPRO_REORDER_CACHE", str(tmp_path / "reorder"))
    from repro.launch.spmv_bench import run_single

    r1 = run_single("smoke_powerlaw", "rcm", iters=2, write_results=False,
                    use_store=False)
    r2 = run_single("smoke_powerlaw", "rcm", iters=2, write_results=False,
                    use_store=False)
    assert not r1["cache_hit"]
    assert r2["cache_hit"]
    assert r2["tune_ms"] == 0.0 and r2["build_ms"] == 0.0
    assert r2["engine"] == r1["engine"]
    # a different scheme is a different cache entry
    r3 = run_single("smoke_powerlaw", "baseline", iters=2,
                    write_results=False, use_store=False)
    assert not r3["cache_hit"]
    # result-store layer: the same cell measured above is now served
    # without any new measurement
    r4 = run_single("smoke_powerlaw", "rcm", iters=2, write_results=False)
    assert r4["store_hit"] and not r2["store_hit"]
    assert r4["spmv_ios_ms"] == r2["spmv_ios_ms"]


def test_operator_cache_roundtrip_all_engines(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path))
    from repro.core.spmv.opcache import build_cached

    mat = G.power_law(256, alpha=1.9, seed=2)
    x = np.random.default_rng(2).standard_normal(mat.n)
    want = mat.spmv(x)
    for eng in ENGINES + ["dense", "auto"]:
        kw = {"block_shape": (4, 4)} if eng in ("bell", "bcsr", "sell") else {}
        _, i1 = build_cached(mat, eng, **kw)
        op, i2 = build_cached(mat, eng, **kw)
        assert not i1["cache_hit"] and i2["cache_hit"], eng
        got = np.asarray(op(jnp.asarray(x, jnp.float32)))
        assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-4, eng


def test_cache_disabled_via_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", "off")
    from repro.core.spmv.opcache import build_cached

    mat = G.banded(64, 2, 0)
    _, i1 = build_cached(mat, "csr")
    _, i2 = build_cached(mat, "csr")
    assert not i1["cache_hit"] and not i2["cache_hit"]


def test_power_law_generator_is_skewed_and_symmetric():
    mat = G.power_law(2048, alpha=1.8, seed=0)
    # duplicate edges sum in different orders for (i,j) vs (j,i): structure
    # is exactly symmetric, values only to fp addition order
    assert mat.is_symmetric(tol=1e-9)
    counts = mat.row_nnz()
    assert counts.max() >= 8 * np.median(counts)  # genuine hub rows
