"""The harness finds a cell's files by name, and a cell, a configuration,
a traffic mix and a per-layer metric are each added as new files plus new
BENCHMARK.json entries, without editing a file that is there."""
import hashlib
import importlib
import json
import os
import re
import shutil

import jax.numpy as jnp
import pytest

from repro.core.spmv import plan as plan_mod

from bench import harness
from bench.tests import fixtures, test_correctness

REPO = os.path.dirname(fixtures.BENCH)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixtures.tiny_root(str(tmp_path_factory.mktemp("bench")))


def test_cell_files_found_by_name(root):
    cell = harness.Bench(root).cell(fixtures.CG_CELL)
    assert cell.config == fixtures.tiny_config("hpcg27-104")
    assert cell.traffic == fixtures.tiny_traffic("cg50-shuffled")
    assert cell.traffic["max_iter"] == 8
    assert set(cell.limits) == {"x_err"}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "cg_solve_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == [
        m["name"] for m in harness.Bench(root).manifest["per_layer"]
        if fixtures.CG_CELL in m.get("workloads", [fixtures.CG_CELL])]
    assert {"plan_s", "compile_s", "cg.roofline", "device.idle.cg",
            "cg.spmv_s", "cg.permute_s", "reorder_s", "tune_s",
            "lower_s"} <= set(names)


@pytest.mark.parametrize("k", [None, 8])
def test_traffic_k_reaches_the_plan(tmp_path, k):
    """A traffic's `k` is the width the plan is priced for; a traffic
    without one plans at k = 1, under the label it has always had."""
    root = fixtures.tiny_root(str(tmp_path))
    bench = harness.Bench(root)
    cell = bench.cell(fixtures.CG_CELL)
    if k is not None:
        cell.traffic["k"] = k
    _, op, _ = harness.setup(bench, cell, seed=3)
    label = op.plan.label()
    if k is None:
        assert op.plan.k == 1 and "@k" not in label
    else:
        assert op.plan.k == k and label.endswith(f"@k{k}")


def test_unknown_workload_names_the_known_ones(root):
    with pytest.raises(KeyError, match=fixtures.CG_CELL):
        harness.Bench(root).cell("no.such-cell")


def test_every_reader_is_found(root):
    bench = harness.Bench(root)
    for m in bench.manifest["per_layer"]:
        assert callable(bench.reader(m["name"]).read)


def test_real_manifest_names_files_that_exist():
    bench = harness.Bench(REPO)
    for w in bench.manifest["workloads"]:
        cell = bench.cell(w["name"])
        loop = cell.traffic["loop"]
        assert os.path.exists(os.path.join(bench.dir, "loops", loop + ".py"))
        mod = importlib.import_module(f"bench.loops.{loop}")
        for method in ("warm_up", "dispatch", "least_bytes", "check"):
            assert callable(getattr(mod.Loop, method))
        assert cell.limits
        assert cell.traffic["metric"] in {m["name"] for m in cell.end_to_end}


def digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_added_cell_config_traffic_and_metric(tmp_path):
    root = fixtures.tiny_root(str(tmp_path))
    before = digest(os.path.join(root, "bench"))
    fixtures.write_json(
        os.path.join(root, "bench", "configs", "hpcg27-5.json"),
        dict(fixtures.tiny_config("hpcg27-104"), nx=5, ny=5, nz=4))
    fixtures.write_json(
        os.path.join(root, "bench", "traffic", "cg4-natural.json"),
        dict(fixtures.tiny_traffic("cg50-shuffled"), order="natural",
             max_iter=4, reorder="baseline"))
    fixtures.write_json(
        os.path.join(root, "bench", "cells", "hpcg27-5.cg4-natural.json"),
        {"limits": {"x_err": 1e-3}})
    with open(os.path.join(root, "bench", "metrics", "setup.units.py"),
              "w") as f:
        f.write("def read(reading):\n    return float(reading.units)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "hpcg27-5", "source": "test",
        "file": "bench/configs/hpcg27-5.json", "reduced": [], "why": "new"})
    manifest["workloads"].append({
        "name": "hpcg27-5.cg4-natural", "config": "hpcg27-5",
        "traffic": "cg4-natural", "chips": 1, "why": "new"})
    manifest["end_to_end"][1]["workloads"].append("hpcg27-5.cg4-natural")
    manifest["per_layer"].append({
        "name": "setup.units", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "cg_solve_s",
        "workloads": ["hpcg27-5.cg4-natural"]})
    fixtures.write_json(os.path.join(root, "BENCHMARK.json"), manifest)

    line = fixtures.run(root, "hpcg27-5.cg4-natural", trace=True)
    after = digest(os.path.join(root, "bench"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert line["correct"] is True
    assert line["metrics"]["setup.units"]["value"] == line["attempted"]
    assert {"plan_s", "compile_s"} <= set(line["metrics"])
    plain = fixtures.run(root, "hpcg27-5.cg4-natural")
    assert set(plain["metrics"]) == {"setup_s", "cg_solve_s"}


def copy_benchmark(dst) -> str:
    """The real benchmark's files, copied to `dst`, to add to."""
    shutil.copytree(fixtures.BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    return str(dst)


def test_tiny_root_takes_a_new_cell_with_no_test_edit(tmp_path, monkeypatch):
    """A cell added as new files and manifest entries only (an existing
    configuration, the 16-topic traffic over `op.matmul`, its limits, its
    name in a metric's list) runs in the tests' tiny checkout with no file
    of the tests edited: sound at k = 16, and neither its bfloat16 control
    nor a fault planted in `Operator._apply`, which `op.matmul` runs."""
    repo = copy_benchmark(tmp_path / "repo")
    cell = "g500-s21.tspr16"
    fixtures.write_json(
        os.path.join(repo, "bench", "cells", cell + ".json"),
        fixtures.read_json(os.path.join(repo, "bench", "cells",
                                        fixtures.PR_CELL + ".json")))
    manifest = fixtures.read_json(os.path.join(repo, "BENCHMARK.json"))
    manifest["workloads"].append({
        "name": cell, "config": "g500-s21", "traffic": "tspr16", "chips": 1,
        "why": "new"})
    for m in manifest["end_to_end"]:
        if m["name"] == "spmv_ms":
            m["workloads"].append(cell)
    fixtures.write_json(os.path.join(repo, "BENCHMARK.json"), manifest)

    root = fixtures.tiny_root(str(tmp_path / "tiny"), repo=repo)
    assert fixtures.manifest_workloads(root) == \
        fixtures.manifest_workloads(REPO) + [cell]
    line = fixtures.run(root, cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "spmv_ms"}
    assert fixtures.run(root, cell, dtype=jnp.bfloat16)["correct"] is False
    monkeypatch.setattr(plan_mod.Operator, "_apply",
                        test_correctness.half_dropped)
    assert fixtures.run(root, cell)["correct"] is False


def test_tiny_root_refuses_a_config_without_a_tiny_file(tmp_path):
    repo = copy_benchmark(tmp_path / "repo")
    manifest = fixtures.read_json(os.path.join(repo, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "g500-s22", "source": "test",
        "file": "bench/configs/g500-s22.json", "reduced": [], "why": "new"})
    fixtures.write_json(os.path.join(repo, "BENCHMARK.json"), manifest)
    missing = os.path.join(repo, "bench", "tests", "tiny", "configs",
                           "g500-s22.json")
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        fixtures.tiny_root(str(tmp_path / "tiny"), repo=repo)
