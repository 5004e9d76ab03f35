"""The roofline's bytes: hand counts, and independence from the plan."""
import numpy as np
import pytest

from bench import least_bytes
from bench.generators import hpcg27

HPCG = {"diagonal": 26.0, "off_diagonal": -1.0}


def test_hpcg27_grid4_hand_count():
    rowptr, cols, vals, shape = hpcg27.build(dict(HPCG, nx=4, ny=4, nz=4))
    n = 4 ** 3
    assert shape == (n, n) and cols.size == 1000 == (3 * 4 - 2) ** 3
    assert least_bytes.spmv(cols.size, n, n) == 4 * (1000 + 64 + 64)
    assert least_bytes.cg_iteration(cols.size, n) == 4 * (1000 + 6 * 64)


def test_hpcg27_104_per_iteration():
    n, nnz = 104 ** 3, (3 * 104 - 2) ** 3
    assert (n, nnz) == (1_124_864, 29_791_000)
    assert least_bytes.cg_iteration(nnz, n) == 119_164_000 + 26_996_736
    assert least_bytes.cg_iteration(nnz, n) / 819e9 == pytest.approx(
        1.7846e-4, rel=1e-3)


@pytest.mark.parametrize("nnz,m,n", [(1000, 64, 64), (29_791_000,
                                     1_124_864, 1_124_864), (10, 3, 4)])
def test_spmm_is_spmv_at_k1_and_linear_in_k(nnz, m, n):
    assert least_bytes.spmm(nnz, m, n, 1) == least_bytes.spmv(nnz, m, n)
    step = least_bytes.spmm(nnz, m, n, 2) - least_bytes.spmm(nnz, m, n, 1)
    assert step == 4 * (n + m)
    for k in (3, 8, 100):
        assert least_bytes.spmm(nnz, m, n, k) == \
            least_bytes.spmm(nnz, m, n, 1) + (k - 1) * step


def test_itemsize_scales_every_term():
    assert least_bytes.spmv(10, 3, 4, itemsize=8) == 8 * 17
    assert least_bytes.cg_iteration(10, 3, itemsize=2) == 2 * 28
    assert least_bytes.spmm(10, 3, 4, 100, itemsize=2) == 2 * 710


@pytest.mark.parametrize("reorder,engine", [
    ("baseline", "csr"), ("baseline", "ell"), ("rcm", "ell"),
    ("rcm", "csr"), ("auto", "auto")])
def test_loop_bytes_do_not_depend_on_the_plan(reorder, engine):
    """The same matrix under any scheme and engine reads the same least
    bytes: a plan that pads or permutes moves no roofline."""
    import jax.numpy as jnp

    from repro.api import SpmvProblem, plan
    from repro.core.sparse.csr import CSRMatrix

    from bench import matrices
    from bench.loops import cg, pagerank, tspr

    rowptr, cols, vals, shape = matrices.generate(
        dict(HPCG, generator="hpcg27", nx=5, ny=4, nz=3), "shuffle-3")
    mat = CSRMatrix(rowptr.astype(np.int32), cols, vals, shape)
    op = plan(SpmvProblem(mat), reorder=reorder, engine=engine,
              cache=False).build(cache=False)
    tr = {"max_iter": 7, "check_units": 1, "damping": 0.85, "k": 4,
          "set_share": 0.125}
    assert cg.Loop(op, mat, tr, 1, jnp.float32).least_bytes() == \
        7 * least_bytes.cg_iteration(mat.nnz, 60)
    assert pagerank.Loop(op, mat, tr, 1, jnp.float32).least_bytes() == \
        least_bytes.spmv(mat.nnz, 60, 60)
    assert tspr.Loop(op, mat, tr, 1, jnp.float32).least_bytes() == \
        least_bytes.spmm(mat.nnz, 60, 60, 4)



def test_tspr16_hand_count():
    """g500-s21's 16-topic block: the values once, 16 columns of X and Y."""
    nnz, n = 63_539_900, 2 ** 21
    assert least_bytes.spmm(nnz, n, n, 16) == 4 * (nnz + 16 * 2 * n) \
        == 522_595_056
