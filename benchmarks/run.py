"""Benchmark orchestrator — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only figXX,...]
  PYTHONPATH=src python -m benchmarks.run --smoke   # CI: tiny end-to-end pass

--smoke runs a tiny measurement CAMPAIGN (smoke-tier matrices x
{baseline, rcm} with the autotuned engine, interpret-mode kernels on CPU)
through the experiment harness: reorder -> tune -> build -> operator
store -> IOS timing with a per-cell original-index-space oracle gate.
It then re-runs the identical spec and asserts 100% result-store hits
(the resumability invariant), writes the campaign CSV, and emits the
top-level BENCH_spmv.json trajectory summary. Exit status is nonzero on
any failure. --matrices restricts the smoke grid (CI's 2-matrix x
2-scheme job)."""
from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

MODULES = [
    "fig01_banded_shuffle",
    "fig03_ios_yax",
    "fig04_scheduling",
    "fig05_profiles",
    "fig06_speedup_stacks",
    "fig07_pairwise",
    "fig08_consistency",
    "fig09_10_load_imbalance",
    "fig11_nnz_balanced",
    "table1_rcm_vs_metis",
    "bell_formats",
    "moe_dispatch",
    "roofline",
    "spmm_batch",
    "corpus_scale",
    "workloads",
]

BENCH_SUMMARY_PATH = os.path.join(os.path.dirname(__file__), "..",
                                  "BENCH_spmv.json")


def smoke_spec(matrices=None):
    from repro.experiments import ExperimentSpec, MeasurePolicy
    from repro.matrices import suite

    return ExperimentSpec(
        name="smoke", matrices=tuple(matrices or suite.smoke_names()),
        schemes=("baseline", "rcm"), engines=("auto",),
        # interpret-mode keeps the Pallas kernel path covered on CPU
        # whenever the tuner picks a kernel engine; verify gates every
        # cell on the numpy oracle in the ORIGINAL index space (this also
        # exercises the operator's carried permutation); probe exercises
        # the empirical tuner path so a traced smoke run carries the full
        # plan -> probe -> build -> kernel span nest
        policy=MeasurePolicy(iters=3, warmup=1, with_yax=False,
                             with_parallel=False, with_metrics=False,
                             verify=True, probe=True,
                             use_kernel="interpret"))


def smoke(matrices=None) -> int:
    """Tiny end-to-end campaign + resumability check for CI.
    Returns failure count."""
    from . import common

    spec = smoke_spec(matrices)
    store = common.result_store()
    rep = common.Runner(spec, store=store, verbose=False,
                        on_error="record").run()
    print("name,us_per_call,derived")
    for rec in rep.records:
        derived = {"engine": rec.get("engine", "?"),
                   "ms": round(rec.get("seq_ios_ms", float("nan")), 3),
                   "store": "hit" if rec["store_reused"] else "miss+measure",
                   "verify_rel_err": round(rec.get("verify_rel_err", -1.0),
                                           8)}
        print(f"{rec['matrix']}_{rec['scheme']},"
              f"{rec['runner_wall_s'] * 1e6:.0f},"
              f"\"{json.dumps(derived)}\"", flush=True)
    failures = len(rep.failures)
    for f in rep.failures:
        print(f"{f['label']},0,\"ERROR: {f['error']}\"", flush=True)
        print(f["traceback"], flush=True)

    if not failures:
        # the resumability invariant: an identical second invocation is
        # served ENTIRELY from the result store
        rep2 = common.Runner(spec, store=store, verbose=False).run()
        if rep2.measured != 0 or rep2.reused != len(spec.cells()):
            print(f"RESUME FAILED: second run measured={rep2.measured} "
                  f"reused={rep2.reused} (want 0/{len(spec.cells())})",
                  flush=True)
            failures += 1
        else:
            print(f"# resume: {rep2.reused}/{len(spec.cells())} cells "
                  f"served from the store (0 re-measured)", flush=True)
        rep = rep2 if not failures else rep

    # campaign CSV + the top-level trajectory summary
    rows = [[r["matrix"], r["scheme"], r.get("engine", "?"),
             r.get("plan_label", "?"), round(r.get("seq_ios_ms", -1), 4),
             round(r.get("seq_ios_gflops", -1), 4),
             round(r.get("verify_rel_err", -1), 8)] for r in rep.records]
    common.write_csv(os.path.join(common.RESULTS_DIR, "smoke_campaign.csv"),
                     ["matrix", "scheme", "engine", "plan_label",
                      "seq_ios_ms", "seq_ios_gflops", "verify_rel_err"],
                     rows)
    summary = rep.write_bench_summary(os.path.abspath(BENCH_SUMMARY_PATH))
    print(f"# BENCH_spmv.json: geomean={summary['geomean']} "
          f"speedup={summary.get('speedup_vs_baseline', {})}", flush=True)
    return failures


def smoke_parallel_spec(matrices=None, devices: int = 8):
    from repro.experiments import ExperimentSpec, MeasurePolicy
    from repro.experiments.cells import parallel_variant

    if devices < 2:
        raise SystemExit(f"--smoke-parallel needs --devices >= 2, "
                         f"got {devices}")
    return ExperimentSpec(
        name="smoke_parallel",
        matrices=tuple(matrices or ("smoke_banded", "smoke_powerlaw")),
        schemes=("baseline", "rcm"), engines=("auto",), ps=(devices,),
        kind="parallel",
        variants=(parallel_variant("1d_rows", "nnz_balanced"),
                  parallel_variant("2d_panels", "nnz_balanced")),
        # verify gates every cell on the ShardedOperator's original-
        # index-space oracle; on an 8-device host (XLA_FLAGS in CI) this
        # exercises the real shard_map collectives, not the simulation
        policy=MeasurePolicy(iters=3, warmup=1, verify=True,
                             with_yax=False, with_parallel=False,
                             with_metrics=False))


def smoke_parallel(matrices=None, devices: int = 8) -> int:
    """Distributed-smoke campaign + resumability check for CI.
    Returns failure count."""
    from . import common

    spec = smoke_parallel_spec(matrices, devices)
    store = common.result_store()
    rep = common.Runner(spec, store=store, verbose=False,
                        on_error="record").run()
    print("name,us_per_call,derived")
    for rec in rep.records:
        derived = {"layout": rec["layout"], "engine": rec.get("engine", "?"),
                   "sched": rec.get("comm_schedule", "?"),
                   "comm_B": rec.get("comm_bytes_per_spmv"),
                   "par_ms": round(rec.get("modelled_par_ms",
                                           float("nan")), 3),
                   "sim": rec.get("simulated"),
                   "store": "hit" if rec["store_reused"] else "miss+measure",
                   "verify_rel_err": round(rec.get("verify_rel_err", -1.0),
                                           8)}
        print(f"{rec['matrix']}_{rec['scheme']}_{rec['layout']}"
              f"_{rec['partitioner']},"
              f"{rec['runner_wall_s'] * 1e6:.0f},"
              f"\"{json.dumps(derived)}\"", flush=True)
    failures = len(rep.failures)
    for f in rep.failures:
        print(f"{f['label']},0,\"ERROR: {f['error']}\"", flush=True)
        print(f["traceback"], flush=True)

    if not failures:
        # resumability: an identical second invocation is served ENTIRELY
        # from the result store (the sharded plan store makes even a
        # --fresh re-measure reload its operators, but this asserts the
        # stronger cell-level invariant)
        rep2 = common.Runner(spec, store=store, verbose=False).run()
        if rep2.measured != 0 or rep2.reused != len(spec.cells()):
            print(f"RESUME FAILED: second run measured={rep2.measured} "
                  f"reused={rep2.reused} (want 0/{len(spec.cells())})",
                  flush=True)
            failures += 1
        else:
            print(f"# resume: {rep2.reused}/{len(spec.cells())} cells "
                  f"served from the store (0 re-measured)", flush=True)
        rep = rep2 if not failures else rep

    rows = [[r["matrix"], r["scheme"], r["layout"], r["partitioner"],
             r.get("engine", "?"), r.get("comm_schedule", "?"),
             r.get("comm_bytes_per_spmv", -1),
             round(r.get("li", -1.0), 4),
             round(r.get("modelled_par_ms", -1.0), 4),
             round(r.get("verify_rel_err", -1.0), 8)]
            for r in rep.records]
    common.write_csv(os.path.join(common.RESULTS_DIR,
                                  "smoke_parallel_campaign.csv"),
                     ["matrix", "scheme", "layout", "partitioner", "engine",
                      "comm_schedule", "comm_bytes_per_spmv", "li",
                      "modelled_par_ms", "verify_rel_err"],
                     rows)
    return failures


def smoke_serve_spec(matrices=None):
    from repro.experiments import ExperimentSpec, MeasurePolicy
    from repro.experiments.cells import serve_variant

    # three overload scenarios, all rate >> capacity with Zipf-skewed
    # keys and an operator footprint past the memory budget (the ISSUE 6
    # soak shape): one per shedding policy, the degrade one with a
    # value-update mix on bursty arrivals
    variants = (
        serve_variant(rate_rps=4000, requests=160, n_keys=5, zipf_s=1.1,
                      budget_mb=0.02, max_queue=8, window_ms=1.0,
                      overload="reject"),
        serve_variant(rate_rps=4000, requests=160, n_keys=5, zipf_s=1.1,
                      budget_mb=0.02, max_queue=8, window_ms=1.0,
                      overload="shed-oldest"),
        serve_variant(arrival="bursty", rate_rps=2000, requests=120,
                      n_keys=3, update_frac=0.25, budget_mb=0.02,
                      max_queue=16, window_ms=1.0,
                      overload="degrade-to-k1"),
    )
    return ExperimentSpec(
        name="smoke_serve", matrices=tuple(matrices or ("smoke_banded",)),
        schemes=("baseline",), engines=("auto",), ks=(8,), kind="serve",
        variants=variants,
        policy=MeasurePolicy(iters=1, warmup=0, with_yax=False,
                             with_parallel=False, with_metrics=False,
                             use_kernel="interpret"))


SERVE_SLO_PATH = os.path.join(os.path.dirname(__file__), "results",
                              "serve_slo.json")


def smoke_serve(matrices=None) -> int:
    """Traffic-sim soak campaign for CI: three overload scenarios through
    the 'serve' cell kind, hard-asserting the hardening invariants —
    every future resolves, resident bytes never exceed the budget,
    counters balance, overload sheds only via typed retryable errors,
    the LRU evicts and reloads, and the update mix value-swaps without
    replanning. Writes the SLO summary JSON (the CI artifact) and checks
    result-store resumability. Returns failure count."""
    from . import common

    spec = smoke_serve_spec(matrices)
    store = common.result_store()
    rep = common.Runner(spec, store=store, verbose=False,
                        on_error="record").run()
    print("name,us_per_call,derived")
    failures = len(rep.failures)
    for f in rep.failures:
        print(f"{f['label']},0,\"ERROR: {f['error']}\"", flush=True)
        print(f["traceback"], flush=True)
    for rec in rep.records:
        derived = {"variant": rec["variant"],
                   "ok": rec["ok"], "shed": rec["shed"],
                   "rejected": rec["rejected"], "errors": rec["errors"],
                   "unresolved": rec["unresolved"],
                   "p99_ms": round(rec["p99_ms"], 2),
                   "coalesce": round(rec["coalesce_ratio"], 2),
                   "evictions": rec["evictions"],
                   "reloads": rec["op_reloads"],
                   "swaps": rec["value_swaps"],
                   "store": "hit" if rec["store_reused"] else "miss+measure"}
        print(f"{rec['matrix']}_{rec['variant']},"
              f"{rec['runner_wall_s'] * 1e6:.0f},"
              f"\"{json.dumps(derived)}\"", flush=True)
        # per-cell hard invariants (the acceptance criteria):
        bad = []
        if rec["unresolved"]:
            bad.append(f"unresolved={rec['unresolved']} futures")
        if not rec["budget_ok"]:
            bad.append(f"resident_bytes_max={rec['resident_bytes_max']} "
                       f"exceeded budget={rec['memory_budget_bytes']}")
        if not rec["counters_balanced"]:
            bad.append("stats counters do not balance")
        if rec["errors"]:
            bad.append(f"{rec['errors']} non-typed request errors")
        if (rec["rejected"] or rec["shed"]) \
                and not rec["retry_after_positive"]:
            bad.append("overload error without positive retry_after_ms")
        if bad:
            failures += 1
            print(f"SOAK INVARIANT FAILED [{rec['variant']}]: "
                  f"{'; '.join(bad)}", flush=True)
    if rep.records and not failures:
        # campaign-level: the overload scenarios must actually overload
        # (shed/reject), thrash the LRU (evict + reload zero-re-tune)
        # and value-swap without replanning
        tot = {k: sum(r[k] for r in rep.records)
               for k in ("shed", "rejected", "evictions", "op_reloads",
                         "value_swaps", "updates", "replans")}
        if tot["shed"] + tot["rejected"] == 0:
            failures += 1
            print("SOAK UNDERLOADED: no request was shed or rejected — "
                  "the scenarios no longer exceed capacity", flush=True)
        if tot["evictions"] == 0 or tot["op_reloads"] == 0:
            failures += 1
            print(f"SOAK LRU NOT EXERCISED: evictions={tot['evictions']} "
                  f"plan-store reloads={tot['op_reloads']}", flush=True)
        if tot["updates"] and (tot["value_swaps"] == 0 or tot["replans"]):
            failures += 1
            print(f"SOAK VALUE-SWAP FAILED: updates={tot['updates']} "
                  f"swaps={tot['value_swaps']} replans={tot['replans']} "
                  f"(updates must swap values without replanning)",
                  flush=True)

    if not failures:
        # resumability: the identical spec re-runs entirely from the store
        rep2 = common.Runner(spec, store=store, verbose=False).run()
        if rep2.measured != 0 or rep2.reused != len(spec.cells()):
            print(f"RESUME FAILED: second run measured={rep2.measured} "
                  f"reused={rep2.reused} (want 0/{len(spec.cells())})",
                  flush=True)
            failures += 1
        else:
            print(f"# resume: {rep2.reused}/{len(spec.cells())} cells "
                  f"served from the store (0 re-measured)", flush=True)

    rows = [[r["matrix"], r["variant"], r["ok"], r["shed"], r["rejected"],
             r["errors"], r["unresolved"],
             round(r["p50_ms"], 3), round(r["p99_ms"], 3),
             round(r["coalesce_ratio"], 3), r["evictions"],
             r["op_reloads"], r["value_swaps"], r["resident_bytes_max"]]
            for r in rep.records]
    common.write_csv(os.path.join(common.RESULTS_DIR,
                                  "smoke_serve_campaign.csv"),
                     ["matrix", "variant", "ok", "shed", "rejected",
                      "errors", "unresolved", "p50_ms", "p99_ms",
                      "coalesce_ratio", "evictions", "op_reloads",
                      "value_swaps", "resident_bytes_max"],
                     rows)
    summary = {"failures": failures, "cells": len(spec.cells()),
               "records": rep.records}
    os.makedirs(os.path.dirname(SERVE_SLO_PATH), exist_ok=True)
    with open(SERVE_SLO_PATH, "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(f"# serve SLO summary -> {os.path.relpath(SERVE_SLO_PATH)}",
          flush=True)
    return failures


def smoke_route_spec(matrices=None, devices: int = 8):
    from repro.experiments import ExperimentSpec, MeasurePolicy
    from repro.experiments.cells import route_variant

    d = max(2, min(4, devices // 2))
    # two fleet scenarios: budgeted bin-pack with a structure-delta +
    # value-swap mix (the mid-soak shard-replan shape), and a
    # comm-model-aware placement over wider meshes
    variants = (
        route_variant(rate_rps=600, requests=120, n_keys=4,
                      update_frac=0.1, structure_frac=0.08,
                      devices=d, meshes=2, policy="bin_pack",
                      budget_mb=4.0, window_ms=1.0),
        route_variant(rate_rps=600, requests=80, n_keys=3,
                      structure_frac=0.05, devices=d, meshes=2,
                      policy="comm_aware", window_ms=1.0),
    )
    return ExperimentSpec(
        name="smoke_route", matrices=tuple(matrices or ("smoke_banded",)),
        schemes=("baseline",), engines=("auto",), ks=(4,), kind="route",
        variants=variants,
        policy=MeasurePolicy(iters=1, warmup=0, with_yax=False,
                             with_parallel=False, with_metrics=False,
                             use_kernel="interpret"))


ROUTE_SUMMARY_PATH = os.path.join(os.path.dirname(__file__), "results",
                                  "route_smoke.json")


def _route_delta_vs_replan() -> int:
    """Hard-assert `Plan.apply_delta` is measurably cheaper than a full
    replan of the edited matrix, pinned by the delta.applies counter.
    Returns failure count."""
    import numpy as np

    from repro import obs
    from repro.api import SpmvProblem, plan
    from repro.core.spmv.delta import StructureDelta
    from repro.matrices import generators as G

    mat = G.banded(4096, 24, seed=0)
    pl = plan(SpmvProblem(mat), reorder="rcm", cache=False)
    rows = np.repeat(np.arange(mat.shape[0], dtype=np.int64),
                     np.diff(mat.rowptr.astype(np.int64)))
    pick = np.arange(0, mat.nnz, max(mat.nnz // 64, 1))[:64]
    delta = StructureDelta(del_rows=rows[pick],
                           del_cols=mat.cols.astype(np.int64)[pick])
    applies0 = obs.counter("delta.applies").value
    t0 = time.perf_counter()
    pl2 = pl.apply_delta(delta)
    delta_ms = (time.perf_counter() - t0) * 1e3
    applies1 = obs.counter("delta.applies").value
    new_mat = delta.apply_to(mat)
    t0 = time.perf_counter()
    pl3 = plan(SpmvProblem(new_mat), reorder="rcm", cache=False)
    replan_ms = (time.perf_counter() - t0) * 1e3
    fails = 0
    if applies1 != applies0 + 1:
        fails += 1
        print(f"DELTA COUNTER FAILED: delta.applies moved "
              f"{applies1 - applies0}, want 1", flush=True)
    if pl2.key == pl.key or tuple(pl2.mat_shape) != tuple(new_mat.shape) \
            or pl2.mat_nnz != new_mat.nnz:
        fails += 1
        print("DELTA PLAN FAILED: apply_delta did not re-key the plan "
              "onto the edited structure", flush=True)
    if delta_ms >= replan_ms:
        fails += 1
        print(f"DELTA NOT CHEAPER: apply_delta {delta_ms:.2f} ms >= "
              f"full replan {replan_ms:.2f} ms", flush=True)
    print(f"# delta-vs-replan: apply_delta {delta_ms:.2f} ms vs "
          f"plan() {replan_ms:.2f} ms ({replan_ms / max(delta_ms, 1e-9):.1f}x"
          f"); replanned scheme={pl3.scheme}", flush=True)
    return fails


def _route_sibling_p99_flat(devices: int) -> int:
    """Soak one mesh with two keys; trigger a background shard replan on
    one and hard-assert the SIBLING key's p99 stays flat (the
    non-stalling replan pillar). Returns failure count."""
    import numpy as np

    from repro.core.spmv.topology import Topology
    from repro.matrices import generators as G
    from repro.router import MeshSpec, RoutedSpmvService
    from repro.serving.traffic import _deletion_delta

    def p99(samples):
        s = sorted(samples)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    mesh = MeshSpec("m0", Topology(devices=max(2, min(4, devices // 2))))
    sib_mat = G.banded(1024, 16, seed=1)
    hot_mat = G.banded(2048, 32, seed=2)
    rng = np.random.default_rng(3)
    x = rng.standard_normal(sib_mat.shape[1])

    def lat_run(svc, n):
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            svc.submit("sib", x).result(timeout=60)
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    fails = 0
    with RoutedSpmvService([mesh], max_batch=4, window_ms=0.5,
                           use_kernel="interpret") as rt:
        rt.register("sib", sib_mat, mesh="m0")
        rt.register("hot", hot_mat, mesh="m0")
        rt.operator("sib")
        rt.operator("hot")
        base = lat_run(rt, 40)
        fut = rt.update_structure(
            "hot", delta=_deletion_delta(hot_mat, rng, frac=0.01))
        during = lat_run(rt, 40)          # sibling serves while replanning
        fut.result(timeout=120)
        st = rt.stats()
        if st["replans"] != 1 or st["replan_errors"]:
            fails += 1
            print(f"SIBLING REPLAN FAILED: replans={st['replans']} "
                  f"errors={st['replan_errors']} (want exactly 1 clean "
                  f"background replan)", flush=True)
        p_base, p_during = p99(base), p99(during)
        # generous noise envelope for CI: the non-stalling property fails
        # CATASTROPHICALLY when broken (sibling gates on the replan), so
        # 5x + 50 ms separates broken from noisy cleanly
        if p_during > 5.0 * p_base + 50.0:
            fails += 1
            print(f"SIBLING P99 NOT FLAT: {p_during:.2f} ms during replan "
                  f"vs {p_base:.2f} ms baseline", flush=True)
        print(f"# sibling p99: {p_base:.2f} ms baseline -> "
              f"{p_during:.2f} ms during background replan", flush=True)
    return fails


def smoke_route(matrices=None, devices: int = 8) -> int:
    """Multi-shard router soak for CI: routed-fleet traffic through the
    'route' cell kind, hard-asserting the router invariants — every
    future (requests AND replans) resolves, counters balance, no device
    exceeds its per-device budget, the mid-soak shard replan leaves the
    sibling key's p99 flat, and delta-apply is measurably cheaper than a
    full replan. Writes the route summary JSON (the CI artifact) and
    checks result-store resumability. Returns failure count."""
    from . import common

    spec = smoke_route_spec(matrices, devices)
    store = common.result_store()
    rep = common.Runner(spec, store=store, verbose=False,
                        on_error="record").run()
    print("name,us_per_call,derived")
    failures = len(rep.failures)
    for f in rep.failures:
        print(f"{f['label']},0,\"ERROR: {f['error']}\"", flush=True)
        print(f["traceback"], flush=True)
    for rec in rep.records:
        derived = {"variant": rec["variant"], "ok": rec["ok"],
                   "unresolved": rec["unresolved"],
                   "replans_landed": rec["replans_landed"],
                   "replan_unresolved": rec["replan_unresolved"],
                   "per_device_ok": rec["per_device_ok"],
                   "placement": rec["placement"],
                   "assignments": rec["assignments"],
                   "store": "hit" if rec["store_reused"] else "miss+measure"}
        print(f"{rec['matrix']}_{rec['variant']},"
              f"{rec['runner_wall_s'] * 1e6:.0f},"
              f"\"{json.dumps(derived)}\"", flush=True)
        bad = []
        if rec["unresolved"] or rec["replan_unresolved"]:
            bad.append(f"unresolved futures: requests="
                       f"{rec['unresolved']} replans="
                       f"{rec['replan_unresolved']}")
        if rec["errors"] or rec["replan_errors"]:
            bad.append(f"errors: requests={rec['errors']} "
                       f"replans={rec['replan_errors']}")
        if not rec["per_device_ok"] or not rec["budget_ok"]:
            bad.append(f"per-device budget violated (per_device_ok="
                       f"{rec['per_device_ok']} budget_ok="
                       f"{rec['budget_ok']})")
        if not rec["counters_balanced"]:
            bad.append("stats counters do not balance")
        if rec["structure_updates"] \
                and rec["replans_landed"] != rec["structure_updates"]:
            bad.append(f"{rec['structure_updates']} structure updates but "
                       f"{rec['replans_landed']} replans landed")
        if rec["placement"] != "bin_pack" \
                and len(set(rec["assignments"].values())) < 2:
            # bin_pack is best-fit and legitimately packs one mesh; the
            # load-spreading policies must actually spread
            bad.append(f"placement degenerate: all keys on one mesh "
                       f"({rec['assignments']})")
        if bad:
            failures += 1
            print(f"ROUTE INVARIANT FAILED [{rec['variant']}]: "
                  f"{'; '.join(bad)}", flush=True)

    if not failures:
        failures += _route_sibling_p99_flat(devices)
        failures += _route_delta_vs_replan()

    if not failures:
        # resumability: the identical spec re-runs entirely from the store
        rep2 = common.Runner(spec, store=store, verbose=False).run()
        if rep2.measured != 0 or rep2.reused != len(spec.cells()):
            print(f"RESUME FAILED: second run measured={rep2.measured} "
                  f"reused={rep2.reused} (want 0/{len(spec.cells())})",
                  flush=True)
            failures += 1
        else:
            print(f"# resume: {rep2.reused}/{len(spec.cells())} cells "
                  f"served from the store (0 re-measured)", flush=True)

    rows = [[r["matrix"], r["variant"], r["placement"], r["ok"],
             r["unresolved"], r["structure_updates"], r["replans_landed"],
             r["value_swaps"], int(r["per_device_ok"]),
             json.dumps(r["assignments"])]
            for r in rep.records]
    common.write_csv(os.path.join(common.RESULTS_DIR,
                                  "smoke_route_campaign.csv"),
                     ["matrix", "variant", "placement", "ok", "unresolved",
                      "structure_updates", "replans_landed", "value_swaps",
                      "per_device_ok", "assignments"],
                     rows)
    summary = {"failures": failures, "cells": len(spec.cells()),
               "records": rep.records}
    os.makedirs(os.path.dirname(ROUTE_SUMMARY_PATH), exist_ok=True)
    with open(ROUTE_SUMMARY_PATH, "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(f"# route summary -> {os.path.relpath(ROUTE_SUMMARY_PATH)}",
          flush=True)
    return failures


def main() -> None:
    import contextlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--smoke-parallel", action="store_true",
                    help="distributed-smoke campaign over the 'parallel' "
                         "cell kind (topology-aware plans)")
    ap.add_argument("--smoke-serve", action="store_true",
                    help="traffic-sim soak campaign over the 'serve' cell "
                         "kind (hardened-service invariants)")
    ap.add_argument("--smoke-route", action="store_true",
                    help="multi-shard router soak over the 'route' cell "
                         "kind (placement, per-device budgets, delta "
                         "shard replans)")
    ap.add_argument("--smoke-workloads", action="store_true",
                    help="dynamic-sparsity campaign over the 'workload' "
                         "cell kind (moe/attn/gnn streams + amortization "
                         "invariants)")
    ap.add_argument("--devices", type=int, default=8,
                    help="device count for --smoke-parallel/--smoke-route")
    ap.add_argument("--matrices", default="",
                    help="comma-separated matrix names (restricts --smoke)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="record phase-attributed spans for the whole run: "
                         ".jsonl -> raw event log, anything else -> "
                         "Chrome-trace JSON (load in ui.perfetto.dev)")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()

    @contextlib.contextmanager
    def traced():
        if not args.trace:
            yield
            return
        from repro import obs

        with obs.tracing() as buf:
            yield
        obs.write_trace(args.trace, buf.flush())
        print(f"# trace: {len(buf)} span events -> {args.trace}",
              flush=True)

    if args.smoke_parallel:
        mats = [m for m in args.matrices.split(",") if m] or None
        with traced():
            rc = 1 if smoke_parallel(mats, args.devices) else 0
        raise SystemExit(rc)
    if args.smoke_serve:
        mats = [m for m in args.matrices.split(",") if m] or None
        with traced():
            rc = 1 if smoke_serve(mats) else 0
        raise SystemExit(rc)
    if args.smoke_route:
        mats = [m for m in args.matrices.split(",") if m] or None
        with traced():
            rc = 1 if smoke_route(mats, args.devices) else 0
        raise SystemExit(rc)
    if args.smoke_workloads:
        from . import workloads as workloads_mod

        mats = [m for m in args.matrices.split(",") if m] or None
        with traced():
            rc = 1 if workloads_mod.smoke(mats) else 0
        raise SystemExit(rc)
    if args.smoke:
        mats = [m for m in args.matrices.split(",") if m] or None
        with traced():
            rc = 1 if smoke(mats) else 0
        raise SystemExit(rc)
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            derived = mod.run(quick=args.quick)
            us = (time.time() - t0) * 1e6
            print(f"{name},{us:.0f},\"{json.dumps(derived, default=str)}\"",
                  flush=True)
        except Exception as e:
            failures += 1
            us = (time.time() - t0) * 1e6
            print(f"{name},{us:.0f},\"ERROR: {type(e).__name__}: {e}\"",
                  flush=True)
            traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
