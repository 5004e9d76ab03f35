"""chip_smoke.py: refuses to run without a TPU, and its phases pass at a
tiny size on the CPU (Pallas kernels in interpret mode; the four-device
phases on virtual host devices)."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sps

from repro import obs

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(tmp_path, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_PLAN_CACHE=str(tmp_path / "plans"),
               REPRO_REORDER_CACHE=str(tmp_path / "reorder"),
               REPRO_OPERATOR_CACHE="off")
    env.update(extra)
    return env


@pytest.fixture
def stores(tmp_path, monkeypatch):
    for k, v in _env(tmp_path).items():
        if k.startswith("REPRO_"):
            monkeypatch.setenv(k, v)


def test_no_tpu_fails_without_result(tmp_path):
    r = subprocess.run([sys.executable, str(SCRIPT)], cwd=ROOT,
                       env=_env(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = _env(tmp_path)
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phases_pass_small(stores, capsys):
    cs = _load()
    mat = cs.make_matrix(6)
    with obs.tracing() as buf:
        cs.run_one_chip(mat, use_kernel="interpret")
    assert cs.check_spans(buf.flush(), "interpret") > 0
    out = capsys.readouterr().out
    assert "FAIL" not in out
    for name in ("plan baseline auto", "plan rcm auto", "engine bell rcm",
                 "engine bcsr rcm", "engine sell rcm", "engine dia baseline",
                 "cg rcm", "service rcm"):
        assert f"[{name}] pass" in out, name


def _faulty(S, fault: str):
    """S with the entries a broken operator would lose set to zero."""
    m = S.shape[0]
    rows = np.repeat(np.arange(m), np.diff(S.indptr))
    keep = np.ones(S.nnz, bool)
    if fault == "diagonal_only":
        keep = rows == S.indices
    elif fault == "dropped_slot":        # each row's last off-diagonal slot
        off = np.flatnonzero(rows != S.indices)
        last = np.full(m, -1)
        np.maximum.at(last, rows[off], off)
        keep[last[last >= 0]] = False
    elif fault == "zeroed_halo":         # 4 row panels, no x from the others
        keep = rows * 4 // m == S.indices * 4 // m
    return sps.csr_matrix((S.data * keep, S.indices, S.indptr), shape=S.shape)


class _HostOp:
    """An operator that applies a host matrix in f32."""

    def __init__(self, B):
        self.B = B.astype(np.float32)

    def __call__(self, x):
        return jnp.asarray(self.B @ np.asarray(x))

    matmul = __call__


@pytest.mark.parametrize("fault", ["none", "diagonal_only", "dropped_slot",
                                   "zeroed_halo"])
def test_spmv_check_sees_lost_off_diagonal(fault, capsys):
    """The smoke matrix's diagonal does not drown its off-diagonal part
    (the ratio is the same at every grid size): an operator that loses
    off-diagonal entries fails the phase; the exact one passes."""
    cs = _load()
    S = cs.host_ref(cs.hpcg_matrix(8))
    # six neighbours of weight <= 1: the diagonal does not grow with the grid
    assert S.diagonal().max() <= 6 + cs.DIAG_MARGIN
    rng = np.random.default_rng(0)
    x = rng.standard_normal(S.shape[1]).astype(np.float32)
    xk = rng.standard_normal((S.shape[1], cs.K)).astype(np.float32)
    ref, refk = S @ x.astype(np.float64), S @ xk.astype(np.float64)
    op = _HostOp(_faulty(S, fault))
    if fault == "none":
        with cs.phase("exact") as rec:
            cs._exercise(op, jnp.asarray(x), jnp.asarray(xk), ref, refk, rec)
        assert "[exact] pass" in capsys.readouterr().out
        return
    with pytest.raises(AssertionError, match="relative error"):
        with cs.phase(fault) as rec:
            cs._exercise(op, jnp.asarray(x), jnp.asarray(xk), ref, refk, rec)
    assert f"[{fault}] FAIL" in capsys.readouterr().out


def test_cg_check_refuses_diagonal_solve():
    """A solve that ignores the off-diagonal part misses CG_CHECK."""
    cs = _load()
    S = cs.host_ref(cs.hpcg_matrix(8))
    b = np.random.default_rng(0).standard_normal(S.shape[0])
    x = b / S.diagonal()
    assert np.linalg.norm(b - S @ x) / np.linalg.norm(b) > 1e3 * cs.CG_CHECK


def test_check_spans_refuses_other_modes():
    cs = _load()
    ev = {"name": "kernel.spmv", "args": {"engine": "bell",
                                          "use_kernel": "ref"}}
    with pytest.raises(AssertionError, match="use_kernel='ref'"):
        cs.check_spans([ev], "pallas")
    with pytest.raises(AssertionError, match="no Pallas engine ran"):
        cs.check_spans([], "pallas")


def test_four_chip_phases_pass_on_virtual_devices(tmp_path):
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n"
            "import chip_smoke as cs\n"
            "cs.run_four_chips(cs.make_matrix(8))\n")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(tmp_path,
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    for name in ("sharded 1d_rows baseline", "sharded 1d_rows rcm",
                 "sharded 2d_panels rcm", "router 2x2-device meshes"):
        assert f"[{name}] pass" in r.stdout, name
    assert "'a': [0, 1], 'b': [2, 3]" in r.stdout
