"""repro.compile_cache: where JAX's persistent compile cache goes.

Each case runs in a fresh interpreter: the cache directory is process
state that JAX fixes at its first compile."""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

_COMPILE = ("import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0))"
            ".block_until_ready()\n")


def _run(code: str, **env) -> str:
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    r = subprocess.run([sys.executable, "-c", code], env=full,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_default_dir_is_in_the_checkout():
    from repro import compile_cache

    assert compile_cache.DEFAULT_DIR == ROOT / ".cache" / "jax_compile"


def test_env_dir_is_used_as_is(tmp_path):
    out = _run("from repro import compile_cache\n"
               "print(compile_cache.enable())\n" + _COMPILE,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_default_dir_receives_entries(tmp_path):
    # the default path, pointed into tmp so the test leaves the checkout
    # alone; enable() must set it when the env var is absent
    out = _run("from repro import compile_cache\n"
               f"compile_cache.DEFAULT_DIR = {str(tmp_path)!r}\n"
               "print(compile_cache.enable())\n" + _COMPILE)
    assert out.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_second_process_hits_the_cache(tmp_path):
    code = ("from repro import compile_cache, obs\n"
            "compile_cache.enable()\n" + _COMPILE +
            "print(obs.snapshot()['counters'].get('compile_cache.hits', 0))\n")
    _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    hits = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert int(hits.strip().splitlines()[-1]) >= 1


def test_library_import_leaves_the_cache_off():
    out = _run("import jax\n"
               "import repro.api, repro.serving.spmv_service, "
               "repro.launch.spmv_bench\n"
               "print(jax.config.jax_compilation_cache_dir)\n")
    assert out.strip() == "None"



_SHARDED = """
import jax, numpy as np
from repro import compile_cache, obs
from repro.api import SpmvProblem, Topology, plan
from repro.matrices import generators
compile_cache.enable()
mat = generators.shuffle(generators.stencil_3d(8), seed=1)
pl = plan(SpmvProblem(mat), reorder="rcm", topology=Topology(devices=2))
x = np.random.default_rng(0).standard_normal(mat.shape[1]).astype(np.float32)
for devs in (jax.devices()[2:4], jax.devices()[0:2]):
    op = pl.build(devices=devs)
    h0 = obs.REGISTRY.total("compile_cache.hits")
    y = np.asarray(op(x))
    assert np.allclose(y, mat.to_scipy() @ x, rtol=1e-4, atol=1e-4)
    print(obs.REGISTRY.total("compile_cache.hits") - h0)
"""


def test_off_first_mesh_is_not_persisted(tmp_path):
    """A mesh on devices [2, 3] compiles its programs afresh in every
    process (the v5e halts on such programs read back from the cache);
    the same plan on [0, 1] reads its programs back."""
    env = dict(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_PLAN_CACHE=str(tmp_path / "plans"),
               REPRO_REORDER_CACHE=str(tmp_path / "reorder"),
               REPRO_OPERATOR_CACHE="off")
    _run(_SHARDED, **env)
    off_first, first = map(int, _run(_SHARDED, **env).split()[-2:])
    assert off_first == 0
    assert first > 0
