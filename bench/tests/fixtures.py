"""A benchmark checkout in miniature, for the tests: the real manifest with
its real cell names and files, each configuration replaced by a tiny one
and each traffic mix shortened where it needs it, so that a whole run
fits a test on the CPU.

The tiny counterparts are files found by the real names:

    tests/tiny/configs/<config>.json    the whole tiny configuration, one
                                        for each configuration of the
                                        manifest
    tests/tiny/traffic/<traffic>.json   keys merged onto the real traffic
                                        file, only where it needs them

So adding a configuration means adding its tiny file under
`tests/tiny/configs/`, and nothing else in the tests; adding a cell over
a configuration that has one, or a traffic mix that runs at a tiny size
as it is, needs no file here at all. Every cell of the manifest gets the
correctness tests of `test_correctness.py`.
"""
from __future__ import annotations

import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CG_CELL = "hpcg27-104.cg50-shuffled"
CG_NATURAL_CELL = "hpcg27-104.cg50-natural"
PR_CELL = "g500-s21.pagerank"


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(name: str, bench: str = BENCH) -> dict:
    """The tiny counterpart of configuration `name`; a configuration
    without one is an error that names the file to add."""
    path = os.path.join(bench, "tests", "tiny", "configs", name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {name!r} has no tiny counterpart for the tests: "
            f"add {path}")
    return read_json(path)


def tiny_traffic(name: str, bench: str = BENCH) -> dict:
    """The real traffic file `name` with its tiny overrides, if any."""
    tr = read_json(os.path.join(bench, "traffic", name + ".json"))
    path = os.path.join(bench, "tests", "tiny", "traffic", name + ".json")
    if os.path.exists(path):
        tr.update(read_json(path))
    return tr


def manifest_workloads(repo: str = REPO) -> list:
    """The names of the cells in `repo`'s BENCHMARK.json."""
    return [w["name"]
            for w in read_json(os.path.join(repo, "BENCHMARK.json"))
            ["workloads"]]


def tiny_root(tmp: str, repo: str = REPO) -> str:
    """A checkout under `tmp` with `repo`'s manifest, cell names, metric
    readers and limits, its configurations replaced by their tiny files and
    its traffic by the real files with their tiny overrides."""
    bench = os.path.join(repo, "bench")
    root = os.path.join(tmp, "checkout")
    out = os.path.join(root, "bench")
    manifest = read_json(os.path.join(repo, "BENCHMARK.json"))
    shutil.copytree(os.path.join(bench, "metrics"),
                    os.path.join(out, "metrics"))
    shutil.copytree(os.path.join(bench, "cells"), os.path.join(out, "cells"))
    for conf in manifest["configs"]:
        name = conf["name"]
        conf["file"] = f"bench/configs/{name}.json"
        write_json(os.path.join(root, conf["file"]), tiny_config(name, bench))
    for name in {w["traffic"] for w in manifest["workloads"]}:
        write_json(os.path.join(out, "traffic", name + ".json"),
                   tiny_traffic(name, bench))
    write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return root


def run(root: str, workload: str, seed: int = 7, seconds: float = 0.3,
        trace: bool = False, dtype=None) -> dict:
    """One run of a tiny cell in this process (no look for a chip)."""
    from bench import harness

    bench = harness.Bench(root)
    return harness.run_cell(bench, bench.cell(workload), seed, seconds,
                            trace, time.perf_counter(), log=lambda s: None,
                            dtype=dtype)
