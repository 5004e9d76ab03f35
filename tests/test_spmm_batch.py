"""Batched multi-vector SpMV (SpMM) engine layer + streaming service.

Acceptance coverage for the SpMM PR:
  * cross-engine SpMM equivalence — for every engine and k in {1, 3, 8},
    `operator.matmul(X)` matches the column-stacked k-fold SpMV oracle on
    the paper suite generators (including power-law skew), baseline and
    reordered;
  * the k-tiled SELL SpMM Pallas kernel (interpret mode) == jnp oracle,
    including k that is not a multiple of the k-tile;
  * the k-aware tuner: cost(k=1) is the SpMV model, matrix bytes amortize
    over k, plans record k and restore through the opcache;
  * the micro-batching service returns per-request results identical to
    unbatched execution while actually coalescing;
  * block CG consumes one SpMM per iteration and matches per-column CG.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.measure.cg import block_cg_solve, cg_solve
from repro.core.reorder import api as reorder_api
from repro.core.spmv.ops import build_operator
from repro.core.spmv.tune import candidate_cost, matrix_features, tune
from repro.kernels.sell_spmm.ops import pick_k_tile
from repro.matrices import generators as G
from repro.serving.spmv_service import SpmvService

ENGINES = ["csr", "ell", "dia", "sell", "bell", "bcsr", "dense"]

MATS = {
    "banded": lambda: G.banded(64, 3, 0),
    "stencil": lambda: G.stencil_2d(8, seed=1),
    "rmat": lambda: G.rmat(6, 4, 2),
    "powerlaw": lambda: G.power_law(96, alpha=1.8, seed=3),
}


def _oracle(mat, x_block):
    return np.stack([mat.spmv(x_block[:, j])
                     for j in range(x_block.shape[1])], axis=1)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("matname", list(MATS))
@pytest.mark.parametrize("k", [1, 3, 8])
def test_cross_engine_spmm_equivalence(engine, matname, k):
    """Acceptance: matmul == column-stacked SpMV oracle, every engine."""
    mat = MATS[matname]()
    x = np.random.default_rng(0).standard_normal((mat.n, k))
    want = _oracle(mat, x)
    kw = {"block_shape": (4, 4)} if engine in ("bell", "bcsr", "sell") else {}
    op = build_operator(mat, engine, **kw)
    got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
    assert got.shape == want.shape
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < 1e-5, engine


@pytest.mark.parametrize("scheme", ["rcm", "metis"])
def test_spmm_equivalence_under_reordering(scheme):
    mat = MATS["powerlaw"]()
    perm = reorder_api.reorder(mat, scheme, cache=False)
    rmat = mat.permute(perm)
    x = np.random.default_rng(1).standard_normal((rmat.n, 8))
    want = _oracle(rmat, x)
    for engine in ("csr", "sell"):
        kw = {"block_shape": (4, 4)} if engine == "sell" else {}
        op = build_operator(rmat, engine, **kw)
        got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
        scale = np.abs(want).max() + 1e-9
        assert np.abs(got - want).max() / scale < 1e-5, (scheme, engine)


def test_matmul_1d_input_degrades_to_spmv():
    mat = MATS["banded"]()
    x = np.random.default_rng(2).standard_normal(mat.n)
    for engine in ENGINES:
        kw = {"block_shape": (4, 4)} if engine in ("bell", "bcsr", "sell") else {}
        op = build_operator(mat, engine, **kw)
        a = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
        b = np.asarray(op(jnp.asarray(x, jnp.float32)))
        assert a.shape == (mat.m,) and np.array_equal(a, b), engine


@pytest.mark.parametrize("k", [1, 5, 8, 20])
def test_sell_spmm_ktiled_interpret_matches_ref(k):
    """The k-tiled Pallas kernel (interpret mode on CPU) == jnp oracle,
    including k not a multiple of the lane tile (padding path)."""
    mat = G.power_law(128, alpha=1.9, seed=8)
    x = np.random.default_rng(8).standard_normal((mat.n, k))
    outs = []
    for uk in ("ref", "interpret"):
        op = build_operator(mat, "sell", block_shape=(8, 16), use_kernel=uk)
        outs.append(np.asarray(op.matmul(jnp.asarray(x, jnp.float32))))
    assert np.allclose(outs[0], outs[1],
                       atol=1e-5 * (np.abs(outs[0]).max() + 1))


def test_pick_k_tile():
    assert pick_k_tile(1) == 8
    assert pick_k_tile(8) == 8
    assert pick_k_tile(9) == 16
    assert pick_k_tile(128) == 128
    assert pick_k_tile(1000) == 128  # multiple passes over the matrix


# --------------------------------------------------------------------------
# k-aware tuning
# --------------------------------------------------------------------------
def test_cost_model_amortizes_matrix_bytes_over_k():
    mat = G.power_law(2048, alpha=1.8, seed=0)
    feat = matrix_features(mat)
    for engine in ("csr", "ell", "sell"):
        kw = {"sell_pad": mat.nnz} if engine == "sell" else {}
        c1 = candidate_cost(feat, engine, **kw)
        c8 = candidate_cost(feat, engine, k=8, **kw)
        c32 = candidate_cost(feat, engine, k=32, **kw)
        # total grows with k, amortized per-vector cost strictly falls
        assert c1 < c8 < c32
        assert c32 / 32 < c8 / 8 < c1


def test_cost_model_k1_is_the_spmv_model():
    """k defaults must not perturb the existing per-SpMV ranking."""
    banded = tune(G.banded(2048, 8, 0))
    shuffled = tune(G.shuffle(G.banded(2048, 8, 0), seed=1))
    skew = tune(G.power_law(2048, alpha=1.8, seed=0))
    assert banded.engine == "dia" and shuffled.engine == "ell"
    assert skew.engine not in ("ell", "dia")
    assert banded.k == 1 and "@k" not in banded.label()


def test_tuned_plan_records_k_and_label():
    mat = G.power_law(512, alpha=1.9, seed=1)
    op = build_operator(mat, "auto", k=8)
    assert op.plan.k == 8 and op.plan.label().endswith("@k8")
    x = np.random.default_rng(1).standard_normal((mat.n, 8))
    got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
    want = _oracle(mat, x)
    assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-5


def test_k_shifts_engine_choice_when_gather_dominates():
    """The point of k-aware tuning: once the matrix stream is amortized and
    the gather line-overage is shared across the k-tile, a padded format
    can lose its k=1 win (or vice versa). Use a synthetic feature vector
    where the shift is provable rather than hunting for a generator."""
    feat = {"m": 4096, "n": 4096, "nnz": 32768, "row_nnz_max": 9,
            "row_nnz_cv": 0.1, "avg_row_bandwidth": 700.0,
            "block_fill": 0.05, "nonempty_blocks": 3000,
            "block_row_max": 12, "num_block_rows": 512}
    c_csr = {k: candidate_cost(feat, "csr", k=k) for k in (1, 64)}
    c_ell = {k: candidate_cost(feat, "ell", k=k) for k in (1, 64)}
    # csr (no padding, heavy gather) vs ell (padding, same gather model):
    # relative gap must move toward the low-footprint engine as k grows
    gap1 = c_ell[1] / c_csr[1]
    gap64 = c_ell[64] / c_csr[64]
    assert gap1 != pytest.approx(gap64), "k must reshape the ranking"


def test_probe_mode_with_k():
    mat = G.banded(256, 4, 0)
    op = build_operator(mat, "auto", probe=True, k=4)
    assert op.plan.source == "probe" and op.plan.k == 4
    assert op.plan.probe_ms and all(v > 0 for v in op.plan.probe_ms.values())


# --------------------------------------------------------------------------
# Micro-batching service
# --------------------------------------------------------------------------
def _service_mats():
    return {"banded": G.banded(256, 4, seed=1),
            "powerlaw": G.power_law(512, alpha=1.9, seed=6)}


def test_service_results_match_unbatched(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path))
    mats = _service_mats()
    rng = np.random.default_rng(0)
    with SpmvService(max_batch=8, window_ms=100.0) as svc:
        for key, m in mats.items():
            svc.register(key, m)
        pending = []
        for _ in range(24):
            key = ("banded", "powerlaw")[rng.integers(2)]
            x = rng.standard_normal(mats[key].n)
            pending.append((key, x, svc.submit(key, x)))
        svc.flush()
        stats = svc.stats()
        for key, x, fut in pending:
            got = np.asarray(fut.result(timeout=10))
            # identical to unbatched execution through the same operator
            alone = np.asarray(svc.operator(key)(jnp.asarray(x, jnp.float32)))
            scale = np.abs(alone).max() + 1e-9
            assert np.abs(got - alone).max() / scale < 1e-5, key
            # and correct vs the numpy oracle
            want = mats[key].spmv(x)
            assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-4
    assert stats["requests"] == 24
    assert stats["batches"] < 24, "burst must coalesce"
    assert stats["batch_size_max"] > 1


def test_service_batches_cap_and_window():
    mats = _service_mats()
    with SpmvService(max_batch=4, window_ms=150.0, engine="csr",
                     cache=False) as svc:
        svc.register("banded", mats["banded"])
        rng = np.random.default_rng(1)
        futs = [svc.submit("banded", rng.standard_normal(mats["banded"].n))
                for _ in range(11)]
        svc.flush()
        for f in futs:
            f.result(timeout=10)
        s = svc.stats()
    # 11 requests, cap 4 -> at least ceil(11/4) = 3 dispatches and the cap
    # is never exceeded; the exact split may vary if a CI scheduler stall
    # expires a window early, so only the invariants are asserted
    assert s["batch_size_sum"] == 11
    assert s["batch_size_max"] <= 4
    assert 3 <= s["batches"] < 11          # cap respected, coalescing real


def test_service_rejects_unknown_key_and_closed():
    svc = SpmvService(max_batch=2, window_ms=1.0)
    svc.register("banded", _service_mats()["banded"])
    with pytest.raises(KeyError):
        svc.submit("nope", np.zeros(4))
    # malformed x is rejected at submit — it must never poison a batch
    with pytest.raises(ValueError):
        svc.submit("banded", np.zeros(255))
    with pytest.raises(ValueError):
        svc.submit("banded", np.zeros((256, 2)))
    svc.close()
    with pytest.raises(RuntimeError):
        svc.submit("banded", np.zeros(256))


def test_service_reregister_invalidates_operator():
    """Re-registering a key (after flush) must drop the memoized operator —
    requests after the swap are answered from the NEW matrix; a swap while
    requests are pending is refused."""
    a = G.banded(256, 4, seed=1)
    b = G.banded(256, 4, seed=9)
    x = np.random.default_rng(5).standard_normal(256)
    with SpmvService(max_batch=2, window_ms=1.0, engine="csr",
                     cache=False) as svc:
        svc.register("m", a)
        fut = svc.submit("m", x)
        ya = fut.result(timeout=10)
        svc.flush()
        svc.register("m", b)
        yb = svc.submit("m", x).result(timeout=10)
    assert np.abs(ya - a.spmv(x)).max() / (np.abs(ya).max() + 1e-9) < 1e-5
    assert np.abs(yb - b.spmv(x)).max() / (np.abs(yb).max() + 1e-9) < 1e-5
    assert not np.allclose(ya, yb)


def test_service_refuses_reregister_with_pending_requests():
    a = G.banded(256, 4, seed=1)
    b = G.banded(256, 4, seed=9)
    svc = SpmvService(max_batch=8, window_ms=5000.0, engine="csr",
                      cache=False)
    svc.register("m", a)
    svc.submit("m", np.zeros(256))   # parked in the (huge) batch window
    with pytest.raises(RuntimeError, match="pending"):
        svc.register("m", b)
    with svc._cv:
        svc._queues["m"].clear()
        svc._stop = True
        svc._cv.notify_all()
    svc._worker.join(timeout=10)


def test_service_backpressure_bounds_queue():
    mats = _service_mats()
    # max_queue < max_batch and a huge window: the dispatcher keeps waiting
    # for a full batch, so the queue deterministically fills to max_queue
    # and the next submit must be rejected with backpressure
    svc = SpmvService(max_batch=8, window_ms=5000.0, engine="csr",
                      cache=False, max_queue=4)
    svc.register("banded", mats["banded"])
    x = np.zeros(256)
    futs = [svc.submit("banded", x) for _ in range(4)]
    with pytest.raises(RuntimeError, match="backpressure"):
        svc.submit("banded", x)
    with svc._cv:
        svc._queues["banded"].clear()   # drop pending so close() is instant
        svc._stop = True
        svc._cv.notify_all()
    svc._worker.join(timeout=10)
    assert all(not f.done() for f in futs)  # dropped, never mis-resolved


def test_service_uses_k_specialized_plan(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path))
    mats = _service_mats()
    with SpmvService(max_batch=16, window_ms=1.0) as svc:
        svc.register("powerlaw", mats["powerlaw"])
        op = svc.operator("powerlaw")
    assert op.plan.k == 16


def test_serve_sim_end_to_end(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path))
    from repro.launch.spmv_bench import run_serve_sim

    rec = run_serve_sim(matrices=("smoke_banded", "smoke_powerlaw"),
                        requests=12, max_batch=4, window_ms=50.0,
                        engine="csr", write_results=False)
    assert rec["ok"] and rec["batches"] <= 12
    assert rec["coalesce_ratio"] >= 1.0


# --------------------------------------------------------------------------
# Block CG — the solver consumer of the SpMM path
# --------------------------------------------------------------------------
def test_block_cg_matches_per_column_cg():
    mat = G.banded(256, 4, seed=1)       # diagonally dominant -> SPD
    op = build_operator(mat, "csr")
    b = jnp.asarray(np.random.default_rng(0).standard_normal((mat.n, 4)),
                    jnp.float32)
    res = block_cg_solve(op.matmul, b, max_iter=200, tol=1e-6)
    assert np.all(np.asarray(res.residual) < 1e-5)
    for j in range(4):
        single = cg_solve(op, b[:, j], max_iter=200, tol=1e-6)
        dx = np.abs(np.asarray(res.x[:, j]) - np.asarray(single.x)).max()
        assert dx < 1e-3, j


def test_block_cg_freezes_converged_columns():
    """A column whose RHS is zero converges at iteration 0 and must stay
    exactly zero while the others keep iterating."""
    mat = G.banded(128, 3, seed=2)
    op = build_operator(mat, "csr")
    rng = np.random.default_rng(3)
    b = np.asarray(rng.standard_normal((mat.n, 3)), np.float32)
    b[:, 1] = 0.0
    res = block_cg_solve(op.matmul, jnp.asarray(b), max_iter=200, tol=1e-6)
    assert np.array_equal(np.asarray(res.x[:, 1]), np.zeros(mat.n))
    assert np.all(np.asarray(res.residual) < 1e-5)
