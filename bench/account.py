#!/usr/bin/env python3
"""Where one run of a cell spends its set-up and its window, by layer.

    python3 bench/account.py --workload <name> --seed <n> --seconds <s>

Runs the cell's set-up and window as `bench/run.py --trace 1` does
(bench/harness.py), with two differences: the program's obs spans stay on
through the window too, so a profiler trace shows them beside the device's
operations, and the trace is kept, under `.cache/bench/account/<name>/`.
No check against the reference runs: this is an account, not a result.

The last line of standard output is one JSON object:

    setup    seconds by phase, summing to `setup_s`: start-up (process
             start to the set-up call: Python's imports, JAX's start and
             its device query, the matrix file's check, the program's
             import and its compile cache), load, the planner's reorder
             and tune spans and the rest of `plan`, `plan.build`,
             warm-up; in each phase the seconds JAX spent tracing and
             lowering (`runtime.trace`/`runtime.lower`) and compiling
             (`runtime.compile`); and the set-up metrics as their readers
             compute them
    window   units, seconds, busy and window seconds, device seconds under
             each scope segment (bench/trace_scopes.py) in all and per
             unit, the top ops outside `spmv`, idle gaps by program span,
             and `trace_reduce`'s top device ops for comparison

Needs a TPU, like run.py: without one it exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETUP_METRICS = ("plan_s", "compile_s", "reorder_s", "tune_s", "lower_s")
LOWER = ("runtime.trace", "runtime.lower")


def _clip_s(spans: list, names, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] (µs) under the union of the named spans."""
    from bench import program_spans

    clipped = [dict(e, ts=max(e["ts"], t0),
                    dur=min(e["ts"] + e["dur"], t1) - max(e["ts"], t0))
               for e in spans if e["name"] in names
               and e["ts"] < t1 and e["ts"] + e["dur"] > t0]
    return program_spans.union_s(clipped, names)


def setup_account(spans: list, start_us: float, call_us: float,
                  end_us: float) -> dict:
    """Set-up's phases from its spans (µs on the perf_counter clock)."""
    from bench import program_spans

    first = {}
    for e in spans:
        first.setdefault(e["name"], e)
    plan, build = first["plan"], first["plan.build"]
    p0, p1 = plan["ts"], plan["ts"] + plan["dur"]
    b1 = build["ts"] + build["dur"]
    inside = [e for e in spans if p0 <= e["ts"] and e["ts"] + e["dur"] <= p1]
    reorder = program_spans.union_s(inside, ("plan.reorder", "plan.permute"))
    tune = program_spans.union_s(inside, ("plan.tune",))
    phases = {"start-up": (start_us, call_us), "load": (call_us, p0),
              "plan": (p0, p1), "build": (build["ts"], b1),
              "warm-up": (b1, end_us)}
    out = {}
    for name, (t0, t1) in phases.items():
        out[name] = {"s": (t1 - t0) / 1e6,
                     "lower_s": _clip_s(spans, LOWER, t0, t1),
                     "compile_s": _clip_s(spans, ("runtime.compile",),
                                          t0, t1)}
    out["plan"].update(reorder_s=reorder, tune_s=tune,
                       other_s=plan["dur"] / 1e6 - reorder - tune)
    out["between"] = (end_us - start_us) / 1e6 - sum(
        v["s"] for v in out.values())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, matrices, trace_reduce, trace_scopes

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    harness.configure_env()
    t_jax = time.perf_counter()
    import jax

    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"account: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s).",
              file=sys.stderr)
        return 3
    gen_s = matrices.ensure(ROOT, cell.config_name, cell.config_file,
                            cell.traffic["order"])
    t_ensure = time.perf_counter()
    from repro import compile_cache, obs

    compile_cache.enable()
    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    call = time.perf_counter()
    with obs.tracing() as setup_spans:
        mat, op, loop = harness.setup(bench, cell, args.seed)
    end = time.perf_counter()
    spans = setup_spans.flush()

    trace_dir = os.path.join(ROOT, ".cache", "bench", "account",
                             args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with obs.tracing() as window_spans:
            units, elapsed = harness.window(loop, args.seconds)
    finally:
        jax.profiler.stop_trace()
    scoped = trace_scopes.reduce(trace_scopes.load(trace_dir))
    plain = trace_reduce.reduce(trace_reduce.load(trace_dir))

    reading = harness.Reading(spans=spans, compile_setup_s=clock.secs,
                              units=units, least_bytes_per_unit=0,
                              peaks=None, trace=plain)
    setup = setup_account(spans, (T_START + gen_s) * 1e6, call * 1e6,
                          end * 1e6)
    setup["setup_s"] = end - T_START - gen_s
    setup["start-up"].update(
        python_s=t_jax - T_START, jax_start_s=t_devices - t_jax,
        matrix_check_s=t_ensure - t_devices - gen_s,
        program_import_s=call - t_ensure)
    setup["metrics"] = {m: bench.reader(m).read(reading)
                        for m in SETUP_METRICS}
    window = {"units": units, "elapsed_s": elapsed,
              "program_spans": len(window_spans)}
    if scoped is not None:
        window.update(scoped)
        window["per_unit_s"] = {k: v / units
                                for k, v in scoped["scopes"].items()}
    if plain is not None:
        window["device_ops"] = plain["device_ops"]
        window["idle_gaps"] = plain["idle_gaps"]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "plan": op.plan.label(), "setup": setup,
                      "window": window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
