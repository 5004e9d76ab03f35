"""`correct` at a size a test can hold: sound runs pass, the control (the
program's own bfloat16 path, one precision below the configuration's
float32) fails, and so does a run whose timed path is broken underneath:
a step that returns its state unchanged, half of the answer left out, an
answer altered where it is produced, one PageRank step wrong in a long
chain. Every cell of the real manifest gets these tests. The faults are
planted in `Operator._apply`, which the operator's call and its `matmul`
share, so they reach a loop over either, and act alike on an answer of
shape [m] and one of shape [m, k]. The cells run on one chip, so there is
no exchange between chips to leave out."""
import jax.numpy as jnp
import pytest

from repro.core.spmv import plan as plan_mod

from bench import harness
from bench.tests import fixtures

CELLS = fixtures.manifest_workloads()
REAL = plan_mod.Operator._apply


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixtures.tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_sound_run_is_correct(root, cell, seed):
    line = fixtures.run(root, cell, seed=seed)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(root, cell):
    line = fixtures.run(root, cell, dtype=jnp.bfloat16)
    assert line["correct"] is False and line["failed"] > 0


def unchanged(self, engine, x, permuted):
    return x


def half_dropped(self, engine, x, permuted):
    y = REAL(self, engine, x, permuted)
    return y.at[y.shape[0] // 2:].set(0)


def one_altered(self, engine, x, permuted):
    y = REAL(self, engine, x, permuted)
    return y.at[jnp.unravel_index(jnp.argmax(jnp.abs(y)), y.shape)].multiply(
        1.01)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_dropped, one_altered],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    monkeypatch.setattr(plan_mod.Operator, "_apply", fault)
    line = fixtures.run(root, cell)
    assert line["correct"] is False


def test_chain_catches_one_wrong_step(root, monkeypatch):
    """One step of twelve doubles its answer. The sample of steps may miss
    it; the whole chain, rerun from x0, does not."""
    bench = harness.Bench(root)
    cell = bench.cell(fixtures.PR_CELL)
    _, _, loop = harness.setup(bench, cell, seed=5)
    calls = []

    def fourth_doubled(self, engine, x, permuted):
        calls.append(1)
        y = REAL(self, engine, x, permuted)
        return 2 * y if len(calls) == 4 else y

    monkeypatch.setattr(plan_mod.Operator, "_apply", fourth_doubled)
    for i in range(12):
        loop.dispatch(i).block_until_ready()
    found = loop.check()
    assert len(found["chain_err"]) == 1
    assert found["chain_err"][0] > cell.limits["chain_err"]
