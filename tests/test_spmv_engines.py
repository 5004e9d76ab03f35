"""SpMV engines vs oracles: csr/ell/dia/bell/bcsr/dense, dtypes, SpMM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sparse.csr import CSRMatrix
from repro.core.spmv.ops import build_operator
from repro.matrices import generators as G

ENGINES = ["csr", "ell", "dia", "dense", "bell", "bcsr"]

MATS = {
    "banded": lambda: G.banded(96, 3, 0),
    "rmat": lambda: G.rmat(7, 4, 1),
    "stencil": lambda: G.stencil_2d(10, seed=2),
    "singleton": lambda: CSRMatrix.from_dense(np.diag([1.0, 2.0, 3.0])),
}


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("matname", list(MATS))
def test_engine_matches_numpy(engine, matname):
    mat = MATS[matname]()
    x = np.random.default_rng(0).standard_normal(mat.n)
    want = mat.spmv(x)
    kw = {"block_shape": (4, 4)} if engine in ("bell", "bcsr") else {}
    op = build_operator(mat, engine, **kw)
    got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < 1e-5, (engine, matname)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    mat = G.stencil_2d(8, seed=1)
    x = np.random.default_rng(1).standard_normal(mat.n)
    op = build_operator(mat, "bell", dtype=dtype, block_shape=(4, 4))
    got = np.asarray(op(jnp.asarray(x, dtype)), dtype=np.float64)
    want = mat.spmv(x)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < tol


@given(st.integers(8, 64), st.sampled_from([2, 3, 5]), st.integers(0, 6))
@settings(max_examples=15, deadline=None)
def test_property_engines_agree(m, deg, seed):
    mat = G.random_uniform(m, deg, seed=seed)
    x = np.random.default_rng(seed).standard_normal(mat.n)
    outs = []
    for engine in ["csr", "ell", "bell"]:
        kw = {"block_shape": (4, 4)} if engine == "bell" else {}
        op = build_operator(mat, engine, **kw)
        outs.append(np.asarray(op(jnp.asarray(x, jnp.float32))))
    for o in outs[1:]:
        assert np.allclose(o, outs[0], atol=1e-3 * (np.abs(outs[0]).max() + 1))


def test_reordered_spmv_same_result():
    """Reordering must never change the math: P^T (PAP^T) (Px) == Ax."""
    from repro.core.reorder import api

    mat = G.shuffle(G.banded(256, 4, 0), 1)
    x = np.random.default_rng(2).standard_normal(mat.n)
    want = mat.spmv(x)
    perm = api.reorder(mat, "rcm", cache=False)
    rmat = mat.permute(perm)
    op = build_operator(rmat, "csr")
    y_perm = np.asarray(op(jnp.asarray(x[perm], jnp.float32)))
    got = np.empty_like(y_perm)
    got[perm] = y_perm  # scatter back: y = P^T y'
    assert np.abs(got - want).max() < 1e-3
