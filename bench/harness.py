"""Finds a cell's files by name and runs it: set-up, window, check, metrics.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under the benchmark's directory,
found by the name that `BENCHMARK.json` gives:

    configs/<config>.json     the configuration's sizes; `generator` names
                              the module under generators/ that builds it
    traffic/<traffic>.json    the traffic mix: row order, plan request,
                              the loop under loops/ and its parameters;
                              `k` (default 1), the width of the block the
                              loop hands to the operator, which the plan
                              is priced for (`SpmvProblem.k`)
    cells/<workload>.json     the limits of the cell's correctness checks
    metrics/<metric>.py       a per-layer metric's reader: read(reading)
                              returns a number, or None where it finds
                              nothing to read

So a later change adds a cell, a configuration, a traffic mix or a metric
by adding files and entries, without editing one that is there.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import time

MANIFEST = "BENCHMARK.json"
BENCH_DIR = "bench"
TO_UNIT = {"s": 1.0, "ms": 1e3, "us": 1e6}


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config_file: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


class Bench:
    """The manifest of a checkout, and the files it names."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, BENCH_DIR)
        self.manifest = _read_json(os.path.join(root, MANIFEST))

    def cell(self, workload: str) -> Cell:
        m = self.manifest
        cells = {w["name"]: w for w in m["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {MANIFEST}; "
                           f"known: {sorted(cells)}")
        w = cells[workload]
        conf = {c["name"]: c for c in m["configs"]}[w["config"]]
        conf_file = os.path.join(self.root, conf["file"])

        def mine(metric):
            return workload in metric.get("workloads", [workload])

        return Cell(
            name=workload, chips=int(w["chips"]),
            config_name=conf["name"], config_file=conf_file,
            config=_read_json(conf_file),
            traffic=_read_json(os.path.join(self.dir, "traffic",
                                            w["traffic"] + ".json")),
            limits=_read_json(os.path.join(self.dir, "cells",
                                           workload + ".json"))["limits"],
            end_to_end=[e for e in m["end_to_end"] if mine(e)],
            per_layer=[p for p in m["per_layer"] if mine(p)])

    def reader(self, metric: str):
        """The module bench/metrics/<metric>.py (loaded by path: metric
        names hold dots)."""
        path = os.path.join(self.dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def configure_env() -> None:
    """Before the program is imported: its plan and operator stores off
    (planning runs uncached, as for a new matrix, and writes nothing to
    their default directories under /tmp), and the TPU runtime's log files
    off."""
    os.environ["REPRO_PLAN_CACHE"] = "off"
    os.environ["REPRO_OPERATOR_CACHE"] = "off"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CompileClock:
    """Backend compilations JAX reports: how many, and their seconds."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.count += 1


@dataclasses.dataclass
class Reading:
    """What the per-layer readers read: set-up spans and compile time,
    the traced window reduced (trace_reduce.reduce, or None), and the
    window's device seconds under each program scope segment
    (trace_scopes.reduce's `scopes`, or None where the trace has no window
    or the XPlane proto is missing)."""
    spans: list
    compile_setup_s: float
    units: int
    least_bytes_per_unit: int
    peaks: dict | None
    trace: dict | None
    scopes: dict | None = None

    def scope_per_unit_s(self, scope: str):
        """Device seconds under `scope` per unit of the window; None where
        the trace has no scopes or no op carries `scope`."""
        if self.scopes is None or scope not in self.scopes:
            return None
        return self.scopes[scope] / self.units

    def roofline_pct(self):
        """Least bytes of the window's work at peak bandwidth, over the
        device's busy time, in percent."""
        if not self.trace or not self.peaks or self.trace["busy_s"] <= 0:
            return None
        least_s = (self.units * self.least_bytes_per_unit
                   / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / self.trace["busy_s"]

    def idle_pct(self):
        if not self.trace or self.trace["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])


def setup(bench: Bench, cell: Cell, seed: int, dtype=None):
    """Load the matrix, plan, build and warm up; returns (mat, op, loop).
    Each phase is a profiler annotation of its own."""
    import jax
    import jax.numpy as jnp

    from repro.api import SpmvProblem, plan

    from bench import matrices

    dtype = jnp.dtype(dtype or cell.config["dtype"])
    tr = cell.traffic
    matrices.ensure(bench.root, cell.config_name, cell.config_file,
                    tr["order"])
    with jax.profiler.TraceAnnotation("bench.setup.load"):
        mat = matrices.load(matrices.cache_path(bench.root, cell.config_name,
                                                tr["order"]))
    with jax.profiler.TraceAnnotation("bench.setup.plan"):
        pl = plan(SpmvProblem(mat, k=int(tr.get("k", 1)), dtype=dtype),
                  reorder=tr["reorder"],
                  engine=tr["engine"], probe=False, cache=False)
    with jax.profiler.TraceAnnotation("bench.setup.build"):
        op = pl.build(cache=False)
    loop_mod = importlib.import_module(f"bench.loops.{tr['loop']}")
    loop = loop_mod.Loop(op, mat, tr, seed, dtype)
    with jax.profiler.TraceAnnotation("bench.setup.warmup"):
        loop.warm_up()
    return mat, op, loop


def window(loop, seconds: float) -> tuple:
    """Run units back to back for `seconds`, one in flight while the host
    waits on the one before; returns (units, seconds until the last one
    finished)."""
    import jax

    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with ann("bench.window"):
        prev, i = None, 0
        while True:
            with ann("bench.dispatch"):
                cur = loop.dispatch(i)
            i += 1
            if prev is not None:
                with ann("bench.wait"):
                    prev.block_until_ready()
            prev = cur
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("bench.wait"):
            prev.block_until_ready()
    return i, time.perf_counter() - t0


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(bench: Bench, cell: Cell, seed: int, seconds: float,
             trace: bool, t_start: float, log=print, dtype=None) -> dict:
    """One run of a cell in this process; returns the result line (the
    checks last). `t_start` is the process's start on the perf_counter
    clock: set-up runs from it to the first timed unit. `dtype` replaces
    the configuration's compute dtype (the control's lower precision)."""
    import jax

    from repro import obs

    from bench import peaks, trace_reduce, trace_scopes

    devices = jax.devices()[:cell.chips]
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    with obs.tracing() as spans:
        mat, op, loop = setup(bench, cell, seed, dtype)
    setup_s = time.perf_counter() - t_start
    compile_setup = (clock.secs, clock.count)
    log(f"[setup] {setup_s:.3f} s; plan {op.plan.label()}, modelled "
        f"bytes by scheme {op.plan.scheme_costs}; {mat.shape[0]} rows, "
        f"{mat.nnz} nnz; compiled {compile_setup[1]} programs in "
        f"{compile_setup[0]:.3f} s")

    trace_dir = os.path.join(bench.root, ".cache", "bench", "trace")
    if trace:
        # the program's spans on through the window: they annotate the trace
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            with obs.tracing():
                units, elapsed = window(loop, seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        units, elapsed = window(loop, seconds)
    in_window = (clock.count - compile_setup[1], clock.secs - compile_setup[0])
    fallback = obs.REGISTRY.total("kernel.ref_fallback")
    log(f"[window] {units} units in {elapsed:.6f} s; compiled "
        f"{in_window[0]} programs in {in_window[1]:.3f} s inside it; "
        f"kernel.ref_fallback {fallback:g}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    if devices[0].platform == "tpu" and fallback:
        raise RuntimeError("a Pallas engine ran its jnp reference on a TPU")

    reduced = scoped = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        scoped = trace_scopes.reduce(trace_scopes.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    least = loop.least_bytes()
    del op
    t_check = time.perf_counter()
    found = loop.check()
    log(f"[check] " + ", ".join(f"{len(v)} {k}" for k, v in found.items())
        + f" against the float64 reference in "
        f"{time.perf_counter() - t_check:.3f} s")
    checks = {name: {"value": max(values), "limit": cell.limits[name]}
              for name, values in found.items()}
    # the units found wrong by the check that found the most
    failed = max(sum(v > cell.limits[name] for v in vs)
                 for name, vs in found.items())
    correct = all(found.values()) and failed == 0

    metrics = {}
    if trace:
        kind_peaks = (peaks.peaks(device["kind"])
                      if device["platform"] == "tpu" else None)
        reading = Reading(spans=spans.flush(),
                          compile_setup_s=compile_setup[0], units=units,
                          least_bytes_per_unit=least, peaks=kind_peaks,
                          trace=reduced,
                          scopes=None if scoped is None else scoped["scopes"])
        for m in cell.per_layer:
            value = bench.reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        per_unit = {"setup_s": setup_s,
                    cell.traffic["metric"]: elapsed / units}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": per_unit[m["name"]]
                                  * TO_UNIT[m["unit"]], "unit": m["unit"]}
    line = {"correct": correct, "attempted": units, "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return line
