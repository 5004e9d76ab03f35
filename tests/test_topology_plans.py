"""Topology-aware plans: the unified facade over distributed SpMV.

Covers the PR's acceptance criteria that don't need a multi-device
process (those run in tests/test_distributed_spmv.py subprocesses):
  * partitioner plugin registry (duplicates refused, custom partitioners
    participate in planning end-to-end)
  * content keys: a 1-device topology hashes identically to no topology
    (single-device caches never fork); topology/partition are otherwise
    key-relevant
  * sharded plan save/load round-trips (perm + panel starts + operator
    arrays, pid.tid tmp+rename discipline) with zero re-tune
  * ShardedOperator correctness on the simulated single-device path for
    every layout x partitioner x engine, SpMM included, permuted opt-out
  * the joint (partition x scheme) selection reacts to structure
  * the "parallel" experiment cell kind: campaign through Runner +
    ResultStore, 100% store hits on re-run
  * SpmvService sharded-key registration (original-index-space requests)
"""
import glob
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (PARTITIONER_REGISTRY, Plan, ShardedOperator,
                       SpmvProblem, Topology, plan, plan_key,
                       register_partitioner)
from repro.core.sparse.partition import nnz_balanced_partition
from repro.matrices import generators as G


@pytest.fixture()
def stores(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans"))
    monkeypatch.setenv("REPRO_REORDER_CACHE", str(tmp_path / "reorder"))
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path / "opcache"))
    monkeypatch.setenv("REPRO_RESULT_STORE", str(tmp_path / "results"))
    return tmp_path


def _mat(m=192, seed=0):
    return G.shuffle(G.banded(m, 3, seed=seed), seed=seed + 1)


def _oracle_check(op, mat, k=0, tol=1e-5, seed=0):
    rng = np.random.default_rng(seed)
    if k:
        x = rng.standard_normal((mat.n, k))
        want = mat.to_dense() @ x
        got = np.asarray(op.matmul(jnp.asarray(x, jnp.float32)))
    else:
        x = rng.standard_normal(mat.n)
        want = mat.spmv(x)
        got = np.asarray(op(jnp.asarray(x, jnp.float32)))
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < tol, err


# -- Topology type ---------------------------------------------------------

def test_topology_validation():
    assert Topology(devices=1).trivial
    t = Topology(devices=8, layout="2d_panels")
    assert t.mesh_shape == (4, 2) and t.mesh_axes == ("data", "model")
    assert Topology(devices=6, layout="1d_rows").mesh_shape == (6,)
    with pytest.raises(ValueError):
        Topology(devices=0)
    with pytest.raises(ValueError):
        Topology(devices=4, layout="3d_torus")
    with pytest.raises(ValueError):
        Topology(devices=4, layout="2d_panels", mesh_shape=(3, 2))
    with pytest.raises(ValueError):
        Topology(devices=4, layout="1d_rows", mesh_shape=(2, 2))
    # json round trip
    t2 = Topology.from_json(t.to_json())
    assert t2 == t


# -- content keys ----------------------------------------------------------

def test_one_device_topology_key_equals_plain_key(stores):
    """Satellite: a 1-device topology must hash to the SAME plan key as
    no topology, so single-device caches never fork."""
    mat = _mat()
    p = SpmvProblem(mat)
    k_plain = plan_key(p, "rcm", "csr", False, 0)
    k_triv = plan_key(p, "rcm", "csr", False, 0,
                      topology=Topology(devices=1))
    assert k_plain == k_triv
    pl = plan(p, reorder="rcm", engine="csr",
              topology=Topology(devices=1))
    assert pl.topology is None and pl.key == k_plain
    # and the stored entry is shared: a plain plan() re-request hits it
    pl2 = plan(p, reorder="rcm", engine="csr")
    assert pl2.cache_hit and pl2.key == pl.key


def test_sharded_key_normalizes_probe(stores):
    """Sharded plans are model-based: probe must not fork their store
    entries (probe=True and probe=False hash identically under a
    non-trivial topology, but stay distinct single-device)."""
    mat = _mat()
    p = SpmvProblem(mat)
    topo = Topology(devices=4)
    k_noprobe = plan_key(p, "rcm", "csr", False, 0, topology=topo,
                         partition="static", partitioners=["static"])
    k_probe = plan_key(p, "rcm", "csr", True, 0, topology=topo,
                       partition="static", partitioners=["static"])
    assert k_noprobe == k_probe
    assert plan_key(p, "rcm", "csr", True, 0) != \
        plan_key(p, "rcm", "csr", False, 0)


def test_topology_and_partition_are_key_relevant(stores):
    mat = _mat()
    p = SpmvProblem(mat)
    base = plan_key(p, "rcm", "csr", False, 0)
    keys = {
        plan_key(p, "rcm", "csr", False, 0, topology=Topology(devices=4),
                 partition="static", partitioners=["static"]),
        plan_key(p, "rcm", "csr", False, 0, topology=Topology(devices=8),
                 partition="static", partitioners=["static"]),
        plan_key(p, "rcm", "csr", False, 0,
                 topology=Topology(devices=8, layout="2d_panels"),
                 partition="static", partitioners=["static"]),
        plan_key(p, "rcm", "csr", False, 0, topology=Topology(devices=8),
                 partition="nnz_balanced",
                 partitioners=["nnz_balanced"]),
    }
    assert len(keys) == 4 and base not in keys


# -- partitioner registry --------------------------------------------------

def test_partitioner_registry_builtins():
    for name in ("static", "nnz_balanced", "chunked_cyclic", "metis_cut"):
        assert name in PARTITIONER_REGISTRY
    assert PARTITIONER_REGISTRY["static"].auto_candidate
    assert PARTITIONER_REGISTRY["nnz_balanced"].auto_candidate
    assert not PARTITIONER_REGISTRY["chunked_cyclic"].auto_candidate
    assert PARTITIONER_REGISTRY["metis_cut"].reorders


def test_partitioner_duplicate_registration_refused():
    with pytest.raises(ValueError):
        @register_partitioner("static")
        def _dup(mat, p, seed=0):           # pragma: no cover
            return None, None


def test_custom_partitioner_participates_in_planning(stores):
    """A just-registered plugin partitioner is immediately selectable —
    and wins partition='auto' when its cost is lowest."""
    name = "test_reversed_static"
    if name not in PARTITIONER_REGISTRY:
        @register_partitioner(name, description="test plugin")
        def _reversed_static(mat, p, seed=0):
            from repro.core.sparse.partition import static_partition

            return (np.arange(mat.m - 1, -1, -1, dtype=np.int64),
                    static_partition(mat, p))

    mat = _mat()
    pl = plan(SpmvProblem(mat), reorder="baseline", engine="csr",
              topology=Topology(devices=4), partition=name)
    assert pl.partitioner == name
    assert pl.perm is not None           # the plugin's grouping perm rode in
    op = pl.build()
    _oracle_check(op, mat)


# -- sharded plans: selection, round-trip, simulated execution -------------

@pytest.mark.parametrize("layout", ["1d_rows", "2d_panels"])
@pytest.mark.parametrize("engine", ["bell", "csr"])
def test_sharded_operator_simulated_oracle(layout, engine, stores):
    mat = _mat()
    pl = plan(SpmvProblem(mat), reorder="rcm", engine=engine,
              topology=Topology(devices=4, layout=layout),
              partition="nnz_balanced")
    op = pl.build()
    assert isinstance(op, ShardedOperator)
    assert op.simulated                   # 1-device pytest process
    assert op.topology.layout == layout
    _oracle_check(op, mat)
    _oracle_check(op, mat, k=3)           # SpMM path
    # permuted opt-out: reordered-space in, reordered-space out
    rmat = pl.reordered_matrix()
    xr = np.random.default_rng(3).standard_normal(mat.n)
    got = np.asarray(op(jnp.asarray(xr, jnp.float32), permuted=True))
    want = rmat.spmv(xr)
    assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 1e-5
    # unwrap() is the permuted-space view harnesses time
    got2 = np.asarray(op.unwrap()(jnp.asarray(xr, jnp.float32)))
    assert np.array_equal(got, got2)


@pytest.mark.parametrize("partition",
                         ["static", "nnz_balanced", "chunked_cyclic_c16",
                          "metis_cut"])
def test_every_partitioner_plans_and_executes(partition, stores):
    mat = G.power_law(256, alpha=1.8, seed=2)
    pl = plan(SpmvProblem(mat), reorder="baseline", engine="csr",
              topology=Topology(devices=4), partition=partition)
    assert pl.partitioner == partition
    assert pl.panel_starts.size == 5
    _oracle_check(pl.build(), mat, tol=1e-4)


def test_sharded_roundtrip_zero_retune(stores):
    """Acceptance: save -> load -> build reuses the stored layout arrays
    (no re-partition/re-conversion) and pays zero plan time."""
    mat = _mat(256)
    pl = plan(SpmvProblem(mat, k=4), reorder="rcm", engine="auto",
              topology=Topology(devices=8), partition="auto")
    op = pl.build()                       # persists the operator payload
    pl2 = Plan.load(pl.key, mat=mat)
    assert pl2 is not None and pl2.cache_hit
    assert pl2.plan_ms == 0.0 and pl2.tune_ms == 0.0 \
        and pl2.reorder_ms == 0.0
    assert pl2.partitioner == pl.partitioner
    assert pl2.topology == pl.topology
    assert np.array_equal(pl2.panel_starts, pl.panel_starts)
    assert pl2.comm == pl.comm
    op2 = pl2.build()
    assert op2.build_info["cache_hit"] and op2.build_info["build_ms"] == 0.0
    x = np.random.default_rng(0).standard_normal(mat.n)
    assert np.array_equal(np.asarray(op(jnp.asarray(x, jnp.float32))),
                          np.asarray(op2(jnp.asarray(x, jnp.float32))))
    # a fresh plan() request for the same problem is a pure cache hit too
    pl3 = plan(SpmvProblem(mat, k=4), reorder="rcm", engine="auto",
               topology=Topology(devices=8), partition="auto")
    assert pl3.cache_hit


def test_sharded_store_write_discipline(stores):
    """Satellite: sharded entries follow the shared pid.tid tmp+rename
    convention — no orphaned tmp files, npz+json pairs only."""
    mat = _mat()
    pl = plan(SpmvProblem(mat), reorder="rcm", engine="csr",
              topology=Topology(devices=4), partition="static")
    pl.build()
    d = str(stores / "plans")
    assert not glob.glob(os.path.join(d, "*.tmp"))
    assert os.path.exists(os.path.join(d, pl.key + ".json"))
    assert os.path.exists(os.path.join(d, pl.key + ".npz"))
    z = np.load(os.path.join(d, pl.key + ".npz"))
    assert "panel_starts" in z.files      # plan-level split
    assert any(k.startswith("op__") for k in z.files)   # operator payload


def test_joint_partition_selection_prefers_balance_on_skew(stores):
    """partition='auto' on a skewed matrix picks nnz_balanced over static
    (the LI term dominates); the per-candidate costs are recorded."""
    mat = G.power_law(512, alpha=1.6, seed=0)
    pl = plan(SpmvProblem(mat), reorder="baseline", engine="csr",
              topology=Topology(devices=8), partition="auto")
    assert pl.partitioner == "nnz_balanced", pl.partition_costs
    assert any(key.startswith("baseline+static")
               for key in pl.partition_costs)
    st = nnz_balanced_partition(pl.reordered_matrix(), 8)
    assert np.array_equal(pl.panel_starts, st)


def test_sharded_plan_rejects_bad_requests(stores):
    mat = _mat()
    with pytest.raises(ValueError):      # engine outside the panel set
        plan(SpmvProblem(mat), reorder="baseline", engine="sell",
             topology=Topology(devices=4))
    rect = G.banded(64, 2, seed=0)
    rect = rect.__class__(rowptr=rect.rowptr, cols=rect.cols,
                          vals=rect.vals, shape=(64, 128))
    with pytest.raises(ValueError):      # non-square
        plan(SpmvProblem(rect), reorder="baseline",
             topology=Topology(devices=4))
    with pytest.raises(KeyError):        # unknown partitioner
        plan(SpmvProblem(mat), reorder="baseline",
             topology=Topology(devices=4), partition="nope")
    pl = plan(SpmvProblem(mat), reorder="baseline",
              topology=Topology(devices=4))
    with pytest.raises(ValueError, match="1 devices for a 4-device mesh"):
        pl.build(devices=jax.devices()[:1])


def test_cg_through_sharded_operator(stores):
    from repro.core.measure import cg
    from repro.core.sparse.csr import CSRMatrix

    dense = G.banded(128, 3, seed=1).to_dense()
    dense = (dense + dense.T) / 2 + 6.0 * np.eye(128)
    r, c = np.nonzero(dense)
    spd = CSRMatrix.from_coo(r, c, dense[r, c], (128, 128))
    b = np.random.default_rng(0).standard_normal(128)
    res, op = cg.solve_problem(spd, jnp.asarray(b, jnp.float32),
                               reorder="rcm", engine="csr", max_iter=300,
                               tol=1e-6, topology=Topology(devices=4),
                               partition="nnz_balanced")
    assert isinstance(op, ShardedOperator)
    x = np.asarray(res.x, np.float64)
    assert np.abs(spd.spmv(x) - b).max() < 1e-3


# -- the "parallel" experiment cell kind -----------------------------------

def test_parallel_cell_kind_campaign_resumes(stores):
    from repro.experiments import (ExperimentSpec, MeasurePolicy,
                                   ResultStore, Runner)
    from repro.experiments.cells import parallel_variant

    spec = ExperimentSpec(
        name="t_par", matrices=("smoke_banded", "smoke_powerlaw"),
        schemes=("baseline", "rcm"), engines=("csr",), ps=(4,),
        kind="parallel",
        variants=(parallel_variant("1d_rows", "nnz_balanced"),),
        policy=MeasurePolicy(iters=2, warmup=0, verify=True,
                             with_yax=False, with_parallel=False,
                             with_metrics=False))
    store = ResultStore()
    rep = Runner(spec, store=store, verbose=False).run()
    assert rep.measured == 4 and rep.reused == 0
    for rec in rep.records:
        assert rec["partitioner"] == "nnz_balanced"
        assert rec["comm_schedule"] in ("all_gather", "halo")
        assert rec["comm_bytes_per_spmv"] > 0
        assert rec["li"] >= 1.0
        assert rec["verify_rel_err"] < 1e-4
        assert rec["modelled_par_ms"] > 0
        assert rec["simulated"]          # 1-device pytest process
    # resumability: identical spec re-run measures nothing
    rep2 = Runner(spec, store=store, verbose=False).run()
    assert rep2.measured == 0 and rep2.reused == 4
    # the scheme axis is honored: rcm cells see reduced cut on banded
    cut_base = rep2.cell("smoke_banded", "baseline")["cut_volume"]
    cut_rcm = rep2.cell("smoke_banded", "rcm")["cut_volume"]
    assert cut_rcm <= cut_base


def test_parallel_cell_kind_rejects_single_device(stores):
    from repro.experiments import Cell, MeasurePolicy
    from repro.experiments.cells import measure_parallel_cell

    pol = tuple(sorted(MeasurePolicy().resolve("").items()))
    cell = Cell(kind="parallel", matrix="<adhoc>", scheme="baseline",
                engine="csr", dtype="float32", p=1, k=1,
                variant="1d_rows:static", policy=pol)
    with pytest.raises(ValueError, match="p >= 2"):
        measure_parallel_cell(cell, _mat())


# -- service: sharded keys -------------------------------------------------

def test_service_sharded_key_original_space(stores):
    from repro.serving.spmv_service import SpmvService

    mat = _mat(160)
    rng = np.random.default_rng(5)
    with SpmvService(engine="csr", reorder="rcm", max_batch=4,
                     window_ms=2.0) as svc:
        svc.register("plain", mat)
        svc.register("sharded", mat, topology=Topology(devices=4))
        xs = [rng.standard_normal(mat.n) for _ in range(8)]
        futs = [(x, svc.submit("sharded", x)) for x in xs]
        futs += [(x, svc.submit("plain", x)) for x in xs[:2]]
        svc.flush()
        for x, fut in futs:
            want = mat.spmv(x)
            got = np.asarray(fut.result(timeout=10))
            assert np.abs(got - want).max() / \
                (np.abs(want).max() + 1e-9) < 1e-4
        op = svc.operator("sharded")
        assert isinstance(op, ShardedOperator)
        assert op.topology.devices == 4


# -- no shims on the facade path -------------------------------------------

def test_sharded_facade_uses_no_shims(stores):
    from repro.launch.spmv_bench import run_parallel

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        rec = run_parallel("smoke_banded", "rcm", engine="auto", devices=4,
                           layout="2d_panels", partition="nnz_balanced",
                           iters=2, write_results=False)
    assert rec["verify_rel_err"] < 1e-4
    assert rec["devices"] == 4 and rec["layout"] == "2d_panels"
