import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ must precede jax import (same rule as dryrun.py).
#
# Distributed-SpMV dry-run: the paper's own workload (--arch spmv) on the
# production mesh. Lowers the 1-D (row panels + x all-gather) and 2-D
# (rows x cols + partial-y reduce) layouts for a synthetic 4.2M-row matrix
# and reports the collective bytes of each — the DESIGN.md §4 / EXPERIMENTS
# beyond-paper comparison, measured from HLO rather than claimed.

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.spmv import ref
from . import hlo_cost
from .mesh import make_production_mesh

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results")


def _fname(name: str) -> str:
    """Filesystem-safe matrix tag: corpus://group/name -> corpus_group_name
    (corpus names carry URL-ish separators that would split the path)."""
    import re

    return re.sub(r"[:/]+", "_", name).strip("_")

# synthetic production matrix: 4.19M rows, ~16 nnz/row, 8x128 bricks
M_ROWS = 1 << 22
BM, BN = 8, 128
K_1D = 32          # padded blocks per block-row (1-D panels)
ITERS = 8          # CG-like repeated SpMV (xs swap)


def lower_1d(mesh: Mesh):
    n_dev = mesh.devices.size
    nbr_l = M_ROWS // n_dev // BM
    panel_n = M_ROWS // n_dev
    axes = tuple(mesh.axis_names)

    def step(blocks, cols, x):
        def body(b, c, xl):
            def one(x_local, _):
                # CG dataflow: the updated (panel-sharded) vector must be
                # re-gathered EVERY iteration — the 1-D layout's cost.
                xs = jax.lax.all_gather(x_local, axes, tiled=True)
                y = ref.spmv_bell(b[0], c[0], xs.reshape(-1, BN, 1))
                return y.reshape(-1)[:panel_n], None
            xf, _ = jax.lax.scan(one, xl[0], None, length=ITERS)
            return xf[None]
        f = jax.shard_map(body, mesh=mesh, in_specs=(P(axes), P(axes), P(axes)),
                          out_specs=P(axes))
        return f(blocks, cols, x)

    blocks = jax.ShapeDtypeStruct((n_dev, nbr_l, K_1D, BM, BN), jnp.float32,
                                  sharding=NamedSharding(mesh, P(axes)))
    cols = jax.ShapeDtypeStruct((n_dev, nbr_l, K_1D), jnp.int32,
                                sharding=NamedSharding(mesh, P(axes)))
    x = jax.ShapeDtypeStruct((n_dev, panel_n), jnp.float32,
                             sharding=NamedSharding(mesh, P(axes)))
    return jax.jit(step).lower(blocks, cols, x)


def lower_2d(mesh: Mesh):
    d, m = mesh.shape["data"], mesh.shape["model"]
    nbr_l = M_ROWS // d // BM
    seg_n = M_ROWS // m
    k2 = max(K_1D // m, 1) * (1 if K_1D // m else 1)
    k2 = max(K_1D // m, 2)

    def step(blocks, cols, x_segs):
        def body(b, c, xl):
            def one(x_, _):
                y = ref.spmv_bell(b[0, 0], c[0, 0], x_.reshape(-1, BN, 1))
                y = jax.lax.psum(y.reshape(-1), "model")     # combine partials
                # next x segment for THIS model rank = slice of y (CG swap):
                x_next = jax.lax.dynamic_slice_in_dim(
                    y, 0, seg_n // d if seg_n // d else seg_n, 0)
                x_next = jax.lax.all_gather(x_next, "data", tiled=True)
                return x_next[:x_.shape[0]], None
            x0 = jax.lax.pcast(xl[0], ("data",), to="varying")
            xf, _ = jax.lax.scan(one, x0, None, length=ITERS)
            return xf[None]
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("data", "model"), P("data", "model"),
                                    P("model")),
                          out_specs=P("model"), check_vma=False)
        return f(blocks, cols, x_segs)

    blocks = jax.ShapeDtypeStruct((d, m, nbr_l, k2, BM, BN), jnp.float32,
                                  sharding=NamedSharding(mesh, P("data", "model")))
    cols = jax.ShapeDtypeStruct((d, m, nbr_l, k2), jnp.int32,
                                sharding=NamedSharding(mesh, P("data", "model")))
    x = jax.ShapeDtypeStruct((m, seg_n), jnp.float32,
                             sharding=NamedSharding(mesh, P("model")))
    return jax.jit(step).lower(blocks, cols, x)


def lower_halo(mesh: Mesh, halo: int = 128):
    """RCM-enabled halo exchange (bandwidth <= halo after reordering):
    two ring permutes instead of the all-gather; K=2 blocks per block row
    (banded structure)."""
    n_dev = mesh.devices.size
    panel_n = M_ROWS // n_dev
    nbr_l = panel_n // BM
    axes = tuple(mesh.axis_names)
    axname = axes if len(axes) > 1 else axes[0]

    def step(blocks, cols, x):
        def body(b, c, xl):
            def one(x_local, _):
                nd = n_dev  # static: ring pairs must be concrete
                fwd = [(i, (i + 1) % nd) for i in range(nd)]
                bwd = [((i + 1) % nd, i) for i in range(nd)]
                lh = jax.lax.ppermute(x_local[-halo:], axname, fwd)
                rh = jax.lax.ppermute(x_local[:halo], axname, bwd)
                xw = jnp.concatenate([lh, x_local, rh])
                y = ref.spmv_bell(b[0], c[0], xw.reshape(-1, BN, 1))
                return y.reshape(-1)[:panel_n], None
            xf, _ = jax.lax.scan(one, xl[0], None, length=ITERS)
            return xf[None]
        f = jax.shard_map(body, mesh=mesh, in_specs=(P(axes), P(axes), P(axes)),
                          out_specs=P(axes))
        return f(blocks, cols, x)

    k_halo = 2  # banded window spans <= 2 column blocks per block row
    blocks = jax.ShapeDtypeStruct((n_dev, nbr_l, k_halo, BM, BN), jnp.float32,
                                  sharding=NamedSharding(mesh, P(axes)))
    cols = jax.ShapeDtypeStruct((n_dev, nbr_l, k_halo), jnp.int32,
                                sharding=NamedSharding(mesh, P(axes)))
    x = jax.ShapeDtypeStruct((n_dev, panel_n), jnp.float32,
                             sharding=NamedSharding(mesh, P(axes)))
    return jax.jit(step).lower(blocks, cols, x)


def run_parallel(matrix: str, scheme: str = "baseline", engine: str = "auto",
                 devices: int = 8, layout: str = "1d_rows",
                 partition: str = "nnz_balanced", iters: int = 6,
                 write_results: bool = True, k: int = 1,
                 use_store: bool = True) -> dict:
    """Sharded-SpMV benchmark for one (matrix, scheme, topology) cell.

    One one-cell "parallel"-kind ExperimentSpec through the experiment
    harness — the same content-addressed result store as the fig09-11
    campaigns, so a repeat invocation is a pure store hit. The cell plans
    through the topology-aware facade (partition x scheme x engine joint
    selection when either is "auto"), verifies the ShardedOperator
    against the numpy oracle in the ORIGINAL index space, and reports the
    modelled collective bytes of the chosen schedule next to the
    modelled-parallel timing."""
    from ..experiments import ExperimentSpec, MeasurePolicy, ResultStore, \
        Runner
    from ..experiments.cells import parallel_variant

    if devices < 2:
        raise ValueError(f"--devices must be >= 2 in parallel mode, "
                         f"got {devices}")
    spec = ExperimentSpec(
        name="spmv_parallel_single", matrices=(matrix,), schemes=(scheme,),
        engines=(engine,), ps=(devices,), ks=(k,), kind="parallel",
        variants=(parallel_variant(layout, partition),),
        policy=MeasurePolicy(iters=iters, verify=True, with_yax=False,
                             with_parallel=False, with_metrics=False))
    store = ResultStore(results_dir=RESULTS)
    if not use_store:                       # --fresh: force a re-measure
        store.delete(spec.cells()[0].key())
    rep = Runner(spec, store=store, verbose=False).run()
    cr = rep.records[0]
    rec = {
        "matrix": matrix, "scheme": scheme,
        "resolved_scheme": cr["resolved_scheme"],
        "engine": cr["engine"], "plan_label": cr["plan_label"],
        "devices": devices, "layout": layout,
        "partitioner": cr["partitioner"],
        "store_hit": cr["store_reused"], "cell_key": cr["cell_key"],
        "comm_schedule": cr["comm_schedule"],
        "comm_bytes_per_spmv": cr["comm_bytes_per_spmv"],
        "li": cr["li"], "cut_volume": cr["cut_volume"],
        "halo_width": cr["halo_width"],
        "reorder_ms": cr["reorder_ms"], "tune_ms": cr["tune_ms"],
        "modelled_par_ms": cr["modelled_par_ms"],
        "gflops": cr["gflops"],
        "verify_rel_err": cr["verify_rel_err"],
        "simulated": cr["simulated"],
    }
    print(f"[spmv-parallel] {matrix}/{scheme} {layout} p={devices} "
          f"partition={rec['partitioner']} engine={rec['engine']} "
          f"sched={rec['comm_schedule']} "
          f"comm={rec['comm_bytes_per_spmv']:.0f}B li={rec['li']:.3f} "
          f"par_ms={rec['modelled_par_ms']:.3f} "
          f"store_hit={rec['store_hit']} sim={rec['simulated']} "
          f"err={rec['verify_rel_err']:.2e}", flush=True)
    if write_results:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(
            RESULTS, f"spmv_parallel_{_fname(matrix)}_{scheme}_{layout}"
                     f"_p{devices}.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def run_single(matrix: str, scheme: str = "baseline", engine: str = "auto",
               iters: int = 12, probe=False,
               write_results: bool = True, k: int = 1,
               use_store: bool = True) -> dict:
    """Single-node tuned SpMV/SpMM benchmark for one (matrix, scheme) cell.

    One one-cell ExperimentSpec through the experiment harness
    (repro.experiments), measured into the SAME content-addressed result
    store the benchmark campaigns use: the first invocation pays reorder +
    tune + format conversion (the plan store persists those) and the
    measurement itself; a repeat invocation is served entirely from the
    result store (`store_hit=true`, zero new measurement). `--fresh`
    (use_store=False) forces a re-measure. Plan-time and run-time are
    reported separately (paper §3 methodology — preprocessing is never
    folded into SpMV time).

    scheme may be "auto": the planner jointly selects (scheme, engine);
    the resolved choice is reported as `resolved_scheme`.

    k > 1 (--spmm) times the k-RHS SpMM path `op.matmul(X[n, k])` with a
    k-specialized tuning plan and reports amortized per-vector time.

    probe accepts the full plan() mode set: False (cost model), True
    (--probe: top candidates), "learned" (--learned: the TuneAdvisor
    shortlist), "exhaustive".
    """
    from ..experiments import (ExperimentSpec, MeasurePolicy, ResultStore,
                               Runner)

    if k < 1:
        raise ValueError(f"--spmm batch width must be >= 1, got {k}")
    spec = ExperimentSpec(
        name="spmv_single", matrices=(matrix,), schemes=(scheme,),
        engines=(engine,), ks=(k,),
        policy=MeasurePolicy(iters=iters, probe=probe, with_yax=False,
                             with_parallel=False, with_metrics=False))
    store = ResultStore(results_dir=RESULTS)
    if not use_store:                       # --fresh: force a re-measure
        store.delete(spec.cells()[0].key())
    rep = Runner(spec, store=store, verbose=False).run()
    cr = rep.records[0]
    store_hit = cr["store_reused"]
    med = cr["spmm_ms"]
    rec = {
        "matrix": matrix,
        "scheme": scheme,
        "resolved_scheme": cr["resolved_scheme"],
        "engine": cr["engine"],
        "plan_label": cr["plan_label"],
        "cache_hit": cr["op_cache_hit"],
        "store_hit": store_hit,
        "cell_key": cr["cell_key"],
        "k": k,
        "reorder_ms": cr["reorder_ms"],
        "tune_ms": cr["tune_ms"],
        "build_ms": cr["format_build_ms"],
        "load_ms": cr["op_load_ms"],
        "spmv_ios_ms": med,
        "per_vector_ms": cr["per_vector_ms"],
        "spmv_ios_gflops": cr.get("spmm_gflops", cr.get("seq_ios_gflops")),
    }
    tag = "spmm" if k > 1 else "spmv"
    print(f"[{tag}-single] {matrix}/{scheme} engine={rec['engine']} k={k} "
          f"store_hit={store_hit} cache_hit={rec['cache_hit']} plan_ms="
          f"{rec['tune_ms'] + rec['build_ms'] + rec['load_ms']:.1f} "
          f"{tag}_ms={med:.3f} per_vec_ms={rec['per_vector_ms']:.3f}",
          flush=True)
    if write_results:
        os.makedirs(RESULTS, exist_ok=True)
        suffix = f"_k{k}" if k > 1 else ""      # SpMM never clobbers SpMV
        out = os.path.join(
            RESULTS, f"spmv_single_{_fname(matrix)}_{scheme}{suffix}.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def run_serve_sim(matrices=("smoke_banded", "smoke_powerlaw", "smoke_rmat"),
                  requests: int = 48, max_batch: int = 8,
                  window_ms: float = 20.0, engine: str = "auto",
                  reorder: str = "baseline", seed: int = 0,
                  write_results: bool = True) -> dict:
    """Serving simulation: a burst of mixed (matrix, x) requests through the
    micro-batching SpmvService (serving/spmv_service.py). Verifies every
    response against the numpy oracle and reports coalescing stats.

    reorder != "baseline" exercises the permutation-carrying operators:
    the service reorders internally for locality while requests and
    responses stay in the ORIGINAL index space (the oracle check still
    compares against the unreordered matrix)."""
    from ..matrices import suite
    from ..serving.spmv_service import SpmvService

    mats = {name: suite.get(name) for name in matrices}
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    with SpmvService(engine=engine, reorder=reorder, max_batch=max_batch,
                     window_ms=window_ms) as svc:
        for name, mat in mats.items():
            svc.register(name, mat)
        pending = []
        for _ in range(requests):
            name = list(matrices)[rng.integers(len(matrices))]
            x = rng.standard_normal(mats[name].n)
            pending.append((name, x, svc.submit(name, x)))
        svc.flush()
        stats = svc.stats()
        max_rel_err = 0.0
        for name, x, fut in pending:
            want = mats[name].spmv(x)
            got = np.asarray(fut.result(timeout=10))
            scale = float(np.abs(want).max()) + 1e-9
            max_rel_err = max(max_rel_err,
                              float(np.abs(got - want).max()) / scale)
    wall_ms = (time.perf_counter() - t0) * 1e3
    rec = {
        "matrices": list(matrices),
        "reorder": reorder,
        "requests": requests,
        "max_batch": max_batch,
        "window_ms": window_ms,
        "wall_ms": wall_ms,
        "batches": stats["batches"],
        "avg_batch": stats["avg_batch"],
        "batch_size_max": stats["batch_size_max"],
        "coalesce_ratio": stats["coalesce_ratio"],
        "avg_wait_ms": stats["avg_wait_ms"],
        "p50_ms": stats["slo"]["p50_ms"],
        "p95_ms": stats["slo"]["p95_ms"],
        "p99_ms": stats["slo"]["p99_ms"],
        "throughput_rps": stats["slo"]["throughput_rps"],
        "wakeups": stats["wakeups"],
        "max_rel_err": max_rel_err,
        "ok": max_rel_err < 1e-4,
    }
    print(f"[serve-sim] {requests} requests over {len(matrices)} matrices -> "
          f"{rec['batches']} SpMM dispatches (avg batch "
          f"{rec['avg_batch']:.1f}, max {rec['batch_size_max']}), "
          f"max_rel_err={max_rel_err:.2e}", flush=True)
    if write_results:
        os.makedirs(RESULTS, exist_ok=True)
        with open(os.path.join(RESULTS, "spmv_serve_sim.json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def run_serve_traffic(matrix: str = "smoke_powerlaw",
                      arrival: str = "poisson", rate_rps: float = 500.0,
                      requests: int = 200, n_keys: int = 4,
                      zipf_s: float = 1.1, update_frac: float = 0.1,
                      structure_frac: float = 0.0,
                      budget_mb: float = 0.0, max_batch: int = 8,
                      window_ms: float = 2.0, max_queue: int = 32,
                      overload: str = "reject", engine: str = "auto",
                      reorder: str = "baseline", devices: int = 1,
                      layout: str = "1d_rows", meshes: int = 2,
                      placement: str = "bin_pack", seed: int = 0,
                      write_results: bool = True) -> dict:
    """Open-loop traffic run against the hardened service (one scenario,
    driven directly — the campaign-shaped path is `benchmarks/run.py
    --smoke-serve` / `--smoke-route`). The matrix is registered under
    n_keys service keys with Zipf-skewed traffic; a budget_mb > 0 memory
    budget makes the operator LRU (eviction + zero-re-tune plan-store
    reload) part of the scenario, update_frac > 0 mixes in no-replan
    value swaps, structure_frac > 0 mixes in StructureDelta background
    replans. devices > 1 serves the keys SHARDED from a
    RoutedSpmvService fleet (`meshes` meshes of `devices` devices each,
    keys placed by `placement`; budget_mb then bounds every DEVICE, not
    the fleet). Reports outcome counts, SLO percentiles and the
    hardening invariants (`ok` = every future — requests and replans —
    resolved + budget respected + counters balance)."""
    from ..matrices import suite
    from ..serving import traffic
    from ..serving.spmv_service import SpmvService

    mat = suite.get(matrix)
    pattern = traffic.TrafficPattern(
        arrival=arrival, rate_rps=rate_rps, requests=requests,
        n_keys=n_keys, zipf_s=zipf_s, update_frac=update_frac,
        structure_frac=structure_frac, seed=seed)
    budget = None if budget_mb <= 0 else int(budget_mb * (1 << 20))
    keys = [f"{matrix}#{i}" for i in range(n_keys)]
    routed = devices > 1
    if routed:
        from ..core.spmv.topology import Topology
        from ..router import MeshSpec, RoutedSpmvService

        fleet = [MeshSpec(f"mesh{i}",
                          Topology(devices=devices, layout=layout),
                          budget_per_device=budget)
                 for i in range(meshes)]
        svc = RoutedSpmvService(fleet, policy=placement, engine=engine,
                                reorder=reorder, max_batch=max_batch,
                                window_ms=window_ms, max_queue=max_queue,
                                overload=overload)
    else:
        svc = SpmvService(engine=engine, reorder=reorder,
                          max_batch=max_batch, window_ms=window_ms,
                          max_queue=max_queue, memory_budget_bytes=budget,
                          overload=overload)
    with svc:
        for k in keys:
            svc.register(k, mat)
        summary = traffic.run_open_loop(svc, {k: mat for k in keys},
                                        pattern)
        svc.flush()
        stats = svc.stats()
    if routed:
        # fleet rollup: worst-mesh SLO, summed build/reload counters
        per = [m["service"] for m in stats["per_mesh"].values()]
        slo = {k: max(s["slo"][k] for s in per)
               for k in ("p50_ms", "p95_ms", "p99_ms", "shed_rate",
                         "eviction_rate")}
        coalesce = max(s["coalesce_ratio"] for s in per)
        op_builds = sum(s["op_builds"] for s in per)
        op_reloads = sum(s["op_reloads"] for s in per)
        resident_max = max(s["resident_bytes_max"] for s in per)
    else:
        slo = stats["slo"]
        coalesce = stats["coalesce_ratio"]
        op_builds = stats["op_builds"]
        op_reloads = stats["op_reloads"]
        resident_max = stats["resident_bytes_max"]
    rec = {
        "matrix": matrix, "n_keys": n_keys, "arrival": arrival,
        "rate_rps": rate_rps, "requests": requests, "zipf_s": zipf_s,
        "update_frac": update_frac, "structure_frac": structure_frac,
        "overload": overload,
        "memory_budget_bytes": budget or 0,
        "offered": summary["offered"], "ok_count": summary["ok"],
        "shed": summary["shed"], "rejected": summary["rejected"],
        "errors": summary["errors"], "unresolved": summary["unresolved"],
        "updates": summary["updates"],
        "structure_updates": summary["structure_updates"],
        "replans_landed": summary["replans_landed"],
        "replan_errors": summary["replan_errors"],
        "replan_unresolved": summary["replan_unresolved"],
        "offered_rps": summary["offered_rps"],
        "achieved_rps": summary["achieved_rps"],
        "p50_ms": slo["p50_ms"], "p95_ms": slo["p95_ms"],
        "p99_ms": slo["p99_ms"], "shed_rate": slo["shed_rate"],
        "eviction_rate": slo["eviction_rate"],
        "coalesce_ratio": coalesce,
        "op_builds": op_builds, "op_reloads": op_reloads,
        "evictions": stats["evictions"],
        "value_swaps": stats["value_swaps"],
        "resident_bytes_max": resident_max,
        "budget_ok": summary["budget_ok"],
        "counters_balanced": (
            stats["requests"] == stats["results"] + stats["sheds"]
            + stats["errors"] and stats["pending"] == 0),
        "ok": (summary["unresolved"] == 0
               and summary["replan_unresolved"] == 0
               and summary["budget_ok"]
               and stats.get("per_device_ok", True)
               and stats["requests"] == stats["results"] + stats["sheds"]
               + stats["errors"]),
    }
    if routed:
        rec.update({
            "devices": devices, "layout": layout, "meshes": meshes,
            "placement": placement, "replans": stats["replans"],
            "per_device_ok": bool(stats["per_device_ok"]),
            "assignments": dict(stats["routing"]["assignments"]),
        })
    fleet_tag = (f" [{meshes}x{devices}dev {layout} {placement}]"
                 if routed else "")
    print(f"[serve-traffic] {matrix} x{n_keys} keys {arrival}@"
          f"{rate_rps:g}rps {overload}{fleet_tag}: ok={rec['ok_count']} "
          f"shed={rec['shed']} rejected={rec['rejected']} "
          f"errors={rec['errors']} unresolved={rec['unresolved']} | "
          f"p50={rec['p50_ms']:.2f}ms p99={rec['p99_ms']:.2f}ms "
          f"coalesce={rec['coalesce_ratio']:.2f} "
          f"evictions={rec['evictions']} reloads={rec['op_reloads']} "
          f"swaps={rec['value_swaps']} "
          f"replans={rec['replans_landed']} budget_ok={rec['budget_ok']}",
          flush=True)
    if write_results:
        os.makedirs(RESULTS, exist_ok=True)
        with open(os.path.join(RESULTS, "spmv_serve_traffic.json"),
                  "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--matrix", default="",
                    help="single-node mode: suite matrix name")
    ap.add_argument("--scheme", default="baseline")
    ap.add_argument("--engine", default="auto")
    ap.add_argument("--probe", action="store_true",
                    help="empirically probe top tuner candidates")
    ap.add_argument("--learned", action="store_true",
                    help="probe only the TuneAdvisor shortlist mined from "
                         "prior campaign cells (plan(probe='learned'))")
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--spmm", type=int, default=1, metavar="K",
                    help="batch width: time K-RHS SpMM instead of SpMV")
    ap.add_argument("--fresh", action="store_true",
                    help="bypass the result store and re-measure the cell")
    ap.add_argument("--devices", type=int, default=1,
                    help="sharded mode: plan a Topology over N devices "
                         "(simulated when the host has fewer)")
    ap.add_argument("--layout", default=None,
                    choices=["1d_rows", "2d_panels"],
                    help="sharded layout (with --devices; default 1d_rows)")
    ap.add_argument("--partition", default=None,
                    help="partitioner name or 'auto' (with --devices; "
                         "default nnz_balanced)")
    ap.add_argument("--serve-sim", action="store_true",
                    help="micro-batching service simulation over smoke "
                         "matrices")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=20.0)
    ap.add_argument("--serve-reorder", default="baseline",
                    help="reordering scheme the service applies internally "
                         "(requests stay in the original index space)")
    ap.add_argument("--serve-traffic", action="store_true",
                    help="open-loop traffic run against the hardened "
                         "service (arrivals, Zipf keys, budgets, shedding)")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "uniform", "bursty"])
    ap.add_argument("--rate", type=float, default=500.0,
                    help="mean offered arrival rate (requests/s)")
    ap.add_argument("--keys", type=int, default=4,
                    help="distinct service keys (Zipf-skewed traffic)")
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--update-frac", type=float, default=0.1,
                    help="fraction of arrivals that are value updates")
    ap.add_argument("--structure-frac", type=float, default=0.0,
                    help="fraction of arrivals that are StructureDelta "
                         "background replans (with --serve-traffic)")
    ap.add_argument("--meshes", type=int, default=2,
                    help="fleet size for routed --serve-traffic "
                         "(--devices > 1: meshes x devices)")
    ap.add_argument("--placement", default="bin_pack",
                    help="router placement policy for routed "
                         "--serve-traffic (bin_pack, nnz_balance, "
                         "comm_aware, or any @register_placement name)")
    ap.add_argument("--budget-mb", type=float, default=0.0,
                    help="operator memory budget in MiB (0 = unbudgeted)")
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--overload", default="reject",
                    choices=["reject", "shed-oldest", "degrade-to-k1"])
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="record phase-attributed spans (repro.obs): "
                         ".jsonl -> raw event log, anything else -> "
                         "Chrome-trace JSON (load in ui.perfetto.dev)")
    args = ap.parse_args()
    from .. import compile_cache

    compile_cache.enable()
    if not args.trace:
        _dispatch(ap, args)
        return
    from .. import obs

    try:
        with obs.tracing() as buf:
            _dispatch(ap, args)
    finally:
        obs.write_trace(args.trace, buf.flush())
        print(f"# trace: {len(buf)} span events -> {args.trace}",
              flush=True)


def _dispatch(ap, args):
    if args.probe and args.learned:
        ap.error("--probe and --learned are mutually exclusive probe modes")
    probe = "learned" if args.learned else args.probe
    if args.serve_traffic:
        if args.spmm != 1 or probe:
            ap.error("--serve-traffic does not combine with "
                     "--spmm/--probe")
        # --devices > 1 serves routed SHARDED keys from a
        # RoutedSpmvService fleet (--meshes x --devices, --layout,
        # --placement); budget_mb then bounds every device
        rec = run_serve_traffic(
            matrix=args.matrix or "smoke_powerlaw", arrival=args.arrival,
            rate_rps=args.rate, requests=args.requests, n_keys=args.keys,
            zipf_s=args.zipf, update_frac=args.update_frac,
            structure_frac=args.structure_frac,
            budget_mb=args.budget_mb, max_batch=args.max_batch,
            window_ms=args.window_ms, max_queue=args.max_queue,
            overload=args.overload, engine=args.engine,
            reorder=args.serve_reorder, devices=args.devices,
            layout=args.layout or "1d_rows", meshes=args.meshes,
            placement=args.placement)
        if not rec["ok"]:
            raise SystemExit(
                f"serve-traffic invariants FAILED: "
                f"unresolved={rec['unresolved']} "
                f"replan_unresolved={rec['replan_unresolved']} "
                f"budget_ok={rec['budget_ok']} "
                f"per_device_ok={rec.get('per_device_ok', True)} "
                f"counters_balanced={rec['counters_balanced']}")
        return
    if args.serve_sim:
        if args.matrix or args.spmm != 1 or probe:
            ap.error("--serve-sim does not combine with "
                     "--matrix/--spmm/--probe")
        rec = run_serve_sim(requests=args.requests, max_batch=args.max_batch,
                            window_ms=args.window_ms, engine=args.engine,
                            reorder=args.serve_reorder)
        if not rec["ok"]:
            raise SystemExit(
                f"serve-sim verification FAILED: max_rel_err="
                f"{rec['max_rel_err']:.2e}")
        return
    if args.devices <= 1 and (args.layout or args.partition):
        ap.error("--layout/--partition require --devices > 1 "
                 "(sharded single-cell mode)")
    if args.devices > 1 and not args.matrix:
        ap.error("--devices requires --matrix (sharded single-cell mode)")
    if args.matrix and args.devices > 1:
        if probe:
            ap.error("--devices does not combine with --probe "
                     "(sharded plans are model-based)")
        run_parallel(args.matrix, args.scheme, args.engine,
                     devices=args.devices,
                     layout=args.layout or "1d_rows",
                     partition=args.partition or "nnz_balanced",
                     iters=args.iters, k=args.spmm,
                     use_store=not args.fresh)
        return
    if args.matrix:
        run_single(args.matrix, args.scheme, args.engine, iters=args.iters,
                   probe=probe, k=args.spmm,
                   use_store=not args.fresh)
        return
    if args.spmm != 1 or probe:
        ap.error("--spmm/--probe/--learned require --matrix "
                 "(single-cell mode)")
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    out = {}
    for name, fn in [("1d", lower_1d), ("2d", lower_2d), ("halo", lower_halo)]:
        with mesh:
            lowered = fn(mesh)
            compiled = lowered.compile()
        walk = hlo_cost.analyze_text(compiled.as_text())
        out[name] = {
            "flops": walk["flops"],
            "collectives": {k: int(v) for k, v in walk["collectives"].items()},
        }
        print(f"[spmv-{name}] flops/dev={walk['flops']:.3e} "
              f"coll wire/dev={walk['collectives'].get('wire', 0):.3e} B "
              f"(per {ITERS} SpMVs)", flush=True)
    r = (out["1d"]["collectives"].get("wire", 0)
         / max(out["2d"]["collectives"].get("wire", 1), 1))
    out["wire_ratio_1d_over_2d"] = r
    rh = (out["1d"]["collectives"].get("wire", 0)
          / max(out["halo"]["collectives"].get("wire", 1), 1))
    out["wire_ratio_1d_over_halo"] = rh
    print(f"[spmv] 1d/2d wire ratio: {r:.1f}x; 1d/halo: {rh:.0f}x")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "spmv_distributed.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
