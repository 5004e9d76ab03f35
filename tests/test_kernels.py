"""Per-kernel allclose vs ref.py oracles, sweeping shapes & dtypes
(interpret=True executes the Pallas kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sparse.bell import to_bcsr, to_block_ell
from repro.core.sparse.csr import CSRMatrix
from repro.kernels.bcsr_spmv.kernel import bcsr_spmm
from repro.kernels.bcsr_spmv.ops import BcsrOperator, pad_empty_rows
from repro.kernels.bcsr_spmv.ref import bcsr_spmm_ref
from repro.kernels.bell_spmv.kernel import bell_spmm
from repro.kernels.bell_spmv.ops import BellOperator
from repro.kernels.bell_spmv.ref import bell_spmm_ref
from repro.kernels.segments import row_aligned_segments
from repro.matrices import generators as G


def _mat(kind, seed):
    if kind == "banded":
        return G.banded(72, 3, seed)
    if kind == "rmat":
        return G.rmat(6, 4, seed)
    return G.stencil_2d(9, seed=seed)


@pytest.mark.parametrize("kind", ["banded", "rmat", "stencil"])
@pytest.mark.parametrize("bm,bn", [(4, 4), (8, 8), (4, 16), (16, 4)])
@pytest.mark.parametrize("nv", [1, 3])
def test_bell_kernel_shape_sweep(kind, bm, bn, nv):
    mat = _mat(kind, 0)
    host = to_block_ell(mat, bm, bn)
    rng = np.random.default_rng(1)
    ncb = (mat.n + bn - 1) // bn
    x2d = jnp.asarray(rng.standard_normal((ncb, bn, nv)), jnp.float32)
    blocks = jnp.asarray(host.blocks, jnp.float32)
    cols = jnp.asarray(host.block_cols)
    got = bell_spmm(blocks, cols.reshape(-1), x2d, interpret=True)
    want = bell_spmm_ref(blocks, cols, x2d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["banded", "rmat", "stencil"])
@pytest.mark.parametrize("bm,bn", [(4, 4), (8, 8), (8, 16)])
def test_bcsr_kernel_shape_sweep(kind, bm, bn):
    mat = _mat(kind, 2)
    host = pad_empty_rows(to_bcsr(mat, bm, bn))
    rng = np.random.default_rng(3)
    ncb = (mat.n + bn - 1) // bn
    x2d = jnp.asarray(rng.standard_normal((ncb, bn, 1)), jnp.float32)
    blocks = jnp.asarray(host.blocks, jnp.float32)
    got = bcsr_spmm(blocks, jnp.asarray(host.block_rows), jnp.asarray(host.block_cols),
                    x2d, host.num_block_rows,
                    row_aligned_segments(host.block_rowptr), interpret=True)
    want = bcsr_spmm_ref(blocks, jnp.asarray(host.block_rows),
                         jnp.asarray(host.block_cols), x2d, host.num_block_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.05)])
def test_bell_kernel_dtypes(dtype, tol):
    mat = _mat("stencil", 4)
    host = to_block_ell(mat, 8, 8)
    rng = np.random.default_rng(5)
    ncb = (mat.n + 7) // 8
    x2d = jnp.asarray(rng.standard_normal((ncb, 8, 1)), dtype)
    blocks = jnp.asarray(host.blocks, dtype)
    cols = jnp.asarray(host.block_cols)
    got = np.asarray(bell_spmm(blocks, cols.reshape(-1), x2d, interpret=True),
                     np.float64)
    want = np.asarray(bell_spmm_ref(blocks, cols, x2d), np.float64)
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < tol


def test_bcsr_empty_row_padding():
    """Matrix with an all-zero row band: kernel must still define y there."""
    d = np.zeros((24, 24))
    d[0, 0] = 1.0
    d[20, 4] = 2.0  # rows 8..15 empty -> empty block row at bm=8
    mat = CSRMatrix.from_dense(d)
    op = BcsrOperator(to_bcsr(mat, 8, 8), use_kernel="interpret")
    x = jnp.asarray(np.arange(24, dtype=np.float32))
    got = np.asarray(op(x))
    assert np.allclose(got, d @ np.arange(24.0), atol=1e-5)


@given(st.integers(8, 48), st.integers(0, 5))
@settings(max_examples=8, deadline=None)
def test_property_bell_vs_numpy(m, seed):
    mat = G.random_uniform(m, 3, seed=seed)
    x = np.random.default_rng(seed).standard_normal(mat.n)
    op = BellOperator(to_block_ell(mat, 4, 4), use_kernel="interpret")
    got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    want = mat.spmv(x)
    assert np.abs(got - want).max() < 1e-4 * (np.abs(want).max() + 1)


@pytest.mark.parametrize("engine", ["bell", "bcsr", "sell"])
def test_smem_segments_match_oracle(engine, monkeypatch):
    """With a tiny SMEM index budget every kernel runs as several
    pallas_calls (one per segment of whole rows); the concatenated rows
    must equal the one-call result."""
    from repro.core.spmv.ops import make_engine
    from repro.kernels import segments

    monkeypatch.setattr(segments, "SMEM_INDEX_BYTES", 32)
    mat = G.stencil_2d(13, seed=4)
    op = make_engine(mat, engine, use_kernel="interpret")
    if engine == "bell":
        nbr, kk = op.blocks.shape[:2]
        assert len(segments.row_aligned_segments(
            np.arange(nbr + 1) * kk, n_arrays=1)) > 1
    else:
        assert len(op.segments) > 1
    rng = np.random.default_rng(5)
    x = rng.standard_normal(mat.n).astype(np.float32)
    xk = rng.standard_normal((mat.n, 3)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(x))), mat.spmv(x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(op.matmul(jnp.asarray(xk))),
                               np.stack([mat.spmv(c) for c in xk.T], 1),
                               rtol=1e-5, atol=1e-5)
