"""The diagonal (DIA) engine and its price.

* `dia` against the dense oracle, SpMV and SpMM (k = 1, 4), on HPCG's
  27-point grid in its own numbering and shuffled, a banded matrix,
  rectangular matrices, one with empty rows and offsets at both far
  corners, and power-law rows;
* `diagonals` counted exactly (against np.unique);
* the tuner picks `dia` where the order leaves few diagonals and not on
  the same grid shuffled, on power-law rows, or under a topology;
* a `dia` plan round-trips the plan store, and a structural delta that
  adds diagonals to an auto `dia` plan re-tunes it;
* `cg_solve` takes a `dia` operator's values as an argument of its
  program, and a static engine's arrays stay compiled in.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SpmvProblem, Topology, plan
from repro.core.measure.cg import cg_solve
from repro.core.sparse.csr import CSRMatrix
from repro.core.spmv.delta import StructureDelta
from repro.core.spmv.ops import make_engine
from repro.core.spmv.plan import Plan
from repro.core.spmv.tune import matrix_features, tune
from repro.matrices import generators as G


@pytest.fixture()
def stores(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans"))
    monkeypatch.setenv("REPRO_REORDER_CACHE", str(tmp_path / "reorder"))
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path / "opcache"))


def hpcg(nx=6, ny=6, nz=5) -> CSRMatrix:
    """HPCG's 27-point operator (diagonal 26, neighbours -1) in its own
    lexicographic numbering, row (iz * ny + iy) * nx + ix."""
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                z, y, x = iz + dz, iy + dy, ix + dx
                ok = ((0 <= z) & (z < nz) & (0 <= y) & (y < ny)
                      & (0 <= x) & (x < nx))
                rows.append(((iz * ny + iy) * nx + ix)[ok])
                cols.append(((z * ny + y) * nx + x)[ok])
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    m = nx * ny * nz
    return CSRMatrix.from_coo(rows, cols, np.where(rows == cols, 26.0, -1.0),
                              (m, m))


def rectangular() -> CSRMatrix:
    d = np.random.default_rng(3).standard_normal((9, 14))
    d[np.random.default_rng(4).random(d.shape) < 0.6] = 0.0
    return CSRMatrix.from_dense(d)


def corners() -> CSRMatrix:
    """Empty rows, and entries on the two outermost offsets of a 10 x 7
    matrix: A[9, 0] (offset -9) and A[0, 6] (offset 6)."""
    d = np.zeros((10, 7))
    d[9, 0], d[0, 6] = 2.0, -3.0
    d[4, 3], d[5, 1], d[7, 6] = 1.5, 0.5, -1.0
    return CSRMatrix.from_dense(d)


MATS = {
    "hpcg_natural": hpcg,
    "banded": lambda: G.banded(64, 3, 0),
    "rectangular": rectangular,
    "rectangular_tall": lambda: CSRMatrix.from_dense(
        rectangular().to_dense().T),
    "corners": corners,
    "hpcg_shuffled": lambda: G.shuffle(hpcg(), seed=1),
    "powerlaw": lambda: G.power_law(300, alpha=1.8, seed=0),
}


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("matname", list(MATS))
def test_dia_matches_dense_oracle(matname, k):
    mat = MATS[matname]()
    op = make_engine(mat, "dia")
    dense = mat.to_dense()
    rng = np.random.default_rng(k)
    if k == 1:
        x = rng.standard_normal(mat.n)
        got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    else:
        x = rng.standard_normal((mat.n, k))
        got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
    want = dense @ x
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.abs(got - want).max() <= 1e-5 * (np.abs(want).max() + 1e-9)


def test_dia_stores_one_row_a_distinct_diagonal():
    mat = corners()
    op = make_engine(mat, "dia")
    assert op.offsets == (-9, -4, -1, 6)
    diags = np.asarray(op.dia_vals).reshape(4, 10)
    assert diags[0, 9] == 2.0 and diags[-1, 0] == -3.0
    # every position off the matrix, or with no entry, holds 0
    assert int(np.count_nonzero(diags)) == mat.nnz
    empty = make_engine(CSRMatrix.from_dense(np.zeros((4, 3))), "dia")
    assert empty.offsets == () and np.array_equal(
        np.asarray(empty(jnp.ones(3))), np.zeros(4, np.float32))


@pytest.mark.parametrize("matname", list(MATS))
def test_diagonals_counted_exactly(matname):
    mat = MATS[matname]()
    rows = np.repeat(np.arange(mat.m), mat.row_nnz())
    want = np.unique(mat.cols.astype(np.int64) - rows).size
    assert matrix_features(mat)["diagonals"] == want
    assert len(make_engine(mat, "dia").offsets) == want


def test_tuner_picks_dia_where_the_order_leaves_few_diagonals():
    natural = hpcg()
    assert matrix_features(natural)["diagonals"] == 27
    assert tune(natural).engine == "dia"
    pl = plan(SpmvProblem(natural), reorder="auto", cache=False)
    assert pl.label() == "baseline+dia"
    # the same operator shuffled, and skewed rows: far more diagonals
    assert tune(G.shuffle(natural, seed=1)).engine != "dia"
    assert plan(SpmvProblem(G.shuffle(natural, seed=1)), reorder="auto",
                cache=False).tune.engine != "dia"
    assert tune(G.power_law(2048, alpha=1.8, seed=0)).engine != "dia"


def test_sharded_plans_keep_their_panel_engines(stores):
    pl = plan(SpmvProblem(hpcg()), reorder="baseline",
              topology=Topology(devices=4), partition="static")
    assert pl.topology is not None and pl.tune.engine in ("bell", "csr")


def test_plan_store_round_trips_a_dia_plan(stores):
    mat = hpcg()
    pl = plan(SpmvProblem(mat), reorder="auto")
    op = pl.build()
    assert pl.tune.engine == "dia"
    pl2 = Plan.load(pl.key, mat=mat)
    assert pl2 is not None and pl2.tune.to_json() == pl.tune.to_json()
    op2 = pl2.build()
    assert op2.build_info["cache_hit"] and op2.build_info["build_ms"] == 0.0
    assert op2.unwrap().offsets == op.unwrap().offsets
    x = jnp.asarray(np.random.default_rng(5).standard_normal(mat.n),
                    jnp.float32)
    assert np.array_equal(np.asarray(op(x)), np.asarray(op2(x)))


def test_delta_that_adds_diagonals_retunes_a_dia_plan():
    mat = hpcg()
    pl = plan(SpmvProblem(mat), reorder="baseline", cache=False)
    assert pl.tune.engine == "dia"
    rows = np.repeat(np.arange(mat.m), mat.row_nnz())
    cols = mat.cols.astype(np.int64)
    # dropping entries leaves the priced diagonals: the decision stands
    drop = StructureDelta(del_rows=rows[:2], del_cols=cols[:2])
    kept = pl.apply_delta(drop)
    assert kept.tune is pl.tune and kept.tune_ms == 0.0
    # two entries two columns off (offsets +2 and -2, inside the band)
    # add two diagonals: re-tuned, and what it builds is right
    grow = StructureDelta(add_rows=[0, 2], add_cols=[2, 0],
                          add_vals=[0.5, 0.5])
    pl2 = pl.apply_delta(grow)
    assert pl2.tune is not pl.tune
    assert pl2.tune.features["diagonals"] == 29
    new = grow.apply_to(mat)
    x = np.random.default_rng(6).standard_normal(mat.n)
    got = np.asarray(pl2.build(cache=False)(jnp.asarray(x, jnp.float32)))
    want = new.to_dense() @ x
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_cg_solve_takes_dia_values_as_an_argument():
    mat = hpcg()
    op = plan(SpmvProblem(mat), reorder="baseline", cache=False).build(
        cache=False)
    assert op.plan.tune.engine == "dia"
    assert jax.tree_util.tree_leaves(op) == [op.inner.dia_vals]
    b = jnp.asarray(np.random.default_rng(7).standard_normal(mat.n),
                    jnp.float32)
    lowered = cg_solve.lower(op, b, max_iter=3, tol=0.0)
    shapes = [a.shape for a in jax.tree_util.tree_leaves(lowered.args_info)]
    assert (27 * mat.m,) in shapes
    x = cg_solve(op, b, max_iter=40, tol=0.0).x
    want = np.linalg.solve(mat.to_dense(), np.asarray(b, np.float64))
    assert np.abs(np.asarray(x) - want).max() <= 1e-4 * np.abs(want).max()


def test_cg_solve_keeps_a_static_engine_compiled_in():
    mat = hpcg()
    op = plan(SpmvProblem(mat), reorder="baseline", engine="ell",
              cache=False).build(cache=False)
    assert jax.tree_util.tree_leaves(op) == []
    b = jnp.ones(mat.n, jnp.float32)
    lowered = cg_solve.lower(op, b, max_iter=3, tol=0.0)
    assert [a.shape for a in jax.tree_util.tree_leaves(
        lowered.args_info)] == [(mat.n,), ()]
