"""A benchmark checkout in miniature, for the tests: the real manifest's
shape and files, with tiny configurations and short CG sets, so that a
whole run fits a test on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONFIGS = {
    "hpcg27-6": {"generator": "hpcg27", "nx": 6, "ny": 6, "nz": 5,
                 "diagonal": 26.0, "off_diagonal": -1.0, "dtype": "float32"},
    "g500-s8": {"generator": "graph500", "scale": 8, "edgefactor": 16,
                "A": 0.57, "B": 0.19, "C": 0.19, "edge_seed": 20,
                "label_seed": 21, "dtype": "float32"},
}
TINY_TRAFFIC = {
    "cg8-shuffled": {"loop": "cg", "order": "shuffle-1", "reorder": "auto",
                     "engine": "auto", "max_iter": 8, "check_units": 2,
                     "metric": "cg_solve_s"},
    "cg8-natural": {"loop": "cg", "order": "natural", "reorder": "auto",
                    "engine": "auto", "max_iter": 8, "check_units": 2,
                    "metric": "cg_solve_s"},
}
CG_CELL = "hpcg27-6.cg8-shuffled"
CG_NATURAL_CELL = "hpcg27-6.cg8-natural"
PR_CELL = "g500-s8.pagerank"


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp: str) -> str:
    """A checkout under `tmp` whose BENCHMARK.json names the tiny cells,
    one for each real cell; the metric readers, the PageRank traffic and the cells' limits
    are the real files."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    os.path.join(root, "bench", "traffic"))
    for name, conf in TINY_CONFIGS.items():
        write_json(os.path.join(root, "bench", "configs", name + ".json"),
                   conf)
    for name, tr in TINY_TRAFFIC.items():
        write_json(os.path.join(root, "bench", "traffic", name + ".json"), tr)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    rename = {"hpcg27-104.cg50-shuffled": CG_CELL,
              "hpcg27-104.cg50-natural": CG_NATURAL_CELL,
              "g500-s21.pagerank": PR_CELL}
    os.makedirs(os.path.join(root, "bench", "cells"))
    for real_cell, cell in rename.items():
        shutil.copy(os.path.join(BENCH, "cells", real_cell + ".json"),
                    os.path.join(root, "bench", "cells", cell + ".json"))
    manifest = dict(real)
    manifest["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in TINY_CONFIGS]
    manifest["workloads"] = [
        {"name": CG_CELL, "config": "hpcg27-6", "traffic": "cg8-shuffled",
         "chips": 1, "why": "tiny"},
        {"name": CG_NATURAL_CELL, "config": "hpcg27-6",
         "traffic": "cg8-natural", "chips": 1, "why": "tiny"},
        {"name": PR_CELL, "config": "g500-s8", "traffic": "pagerank",
         "chips": 1, "why": "tiny"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
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
