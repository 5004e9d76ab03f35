"""The per-layer readers of device seconds by program scope: each reads
its number from `Reading.scopes`, here from the trace recorded on a TPU
v5e (tests/data/scoped.xplane.pb), and gives None on a Reading without
scopes. A traced run hands the scopes of its trace to the readers."""
import os

import pytest

from bench import harness, trace_scopes
from bench.tests import fixtures

REPO = os.path.dirname(fixtures.BENCH)
SCOPED = os.path.join(fixtures.BENCH, "tests", "data", "scoped.xplane.pb")
READERS = ("cg.spmv_s", "cg.permute_s", "spmv.gather_ms")


def reading(scopes, units=3):
    return harness.Reading(spans=[], compile_setup_s=0.0, units=units,
                           least_bytes_per_unit=0, peaks=None, trace=None,
                           scopes=scopes)


def read(name, r):
    return harness.Bench(REPO).reader(name).read(r)


@pytest.fixture(scope="module")
def recorded():
    return trace_scopes.reduce(trace_scopes.load_file(SCOPED))


def test_readers_on_the_recorded_trace(recorded):
    s, units = recorded["scopes"], 4
    r = reading(s, units)
    assert read("cg.spmv_s", r) == pytest.approx(s["spmv"] / units)
    assert read("cg.permute_s", r) == pytest.approx(
        s["spmv.permute"] / units)
    assert read("spmv.gather_ms", r) == pytest.approx(
        1e3 * s["spmv.gather"] / units)
    for name in READERS:
        assert 0 < read(name, r)
    busy_per_unit = recorded["busy_s"] / units
    assert read("cg.spmv_s", r) <= busy_per_unit
    assert read("cg.permute_s", r) <= busy_per_unit
    assert read("spmv.gather_ms", r) <= 1e3 * busy_per_unit


@pytest.mark.parametrize("name", READERS)
def test_no_scopes_reads_nothing(name):
    assert read(name, reading(None)) is None


def test_permute_reads_zero_where_no_op_carries_it():
    r = reading({"spmv": 6.0, "spmv.engine": 6.0, "spmv.gather": 5.0}, 2)
    assert read("cg.permute_s", r) == 0.0
    assert read("cg.spmv_s", r) == 3.0
    assert read("spmv.gather_ms", r) == 2500.0


def test_a_missing_scope_reads_nothing():
    """A trace whose ops carry no program scope (an executable from a
    cache that another build filled) reads None, not 0."""
    r = reading({"jit(cg_solve)": 6.0})
    for name in READERS:
        assert read(name, r) is None


def test_traced_run_hands_its_scopes_to_the_readers(tmp_path, monkeypatch,
                                                    recorded):
    """The tiny CG cell, traced; its trace's scopes replaced by the
    recorded TPU trace's (the CPU's trace has no TPU plane)."""
    root = fixtures.tiny_root(str(tmp_path))
    monkeypatch.setattr(trace_scopes, "load",
                        lambda trace_dir: trace_scopes.load_file(SCOPED))
    line = fixtures.run(root, fixtures.CG_CELL, trace=True)
    m, units = line["metrics"], line["attempted"]
    s = recorded["scopes"]
    assert m["cg.spmv_s"]["value"] == pytest.approx(s["spmv"] / units)
    assert m["cg.permute_s"]["value"] == pytest.approx(
        s["spmv.permute"] / units)
    assert m["cg.spmv_s"]["unit"] == "s"
    assert "spmv.gather_ms" not in m          # not listed for a CG cell
    plain = fixtures.run(root, fixtures.CG_CELL)
    assert set(plain["metrics"]) == {"setup_s", "cg_solve_s"}
