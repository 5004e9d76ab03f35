"""Observability layer (repro.obs): spans, metrics registry, exporters,
and the perf-regression gate.

Covers the PR's correctness contract:

* span nesting within a thread and across threads (distinct tids, each
  thread its own tree);
* exception safety — a span exited by an unwinding exception records an
  ``error`` attribute, and a child left open by a raise is force-closed
  (``unclosed``) when its parent exits;
* deterministic ``TraceBuffer.flush()`` ordering;
* the disabled path stays near-free (micro-benchmark bound);
* ``obs.timed`` reads the clock with tracing off, ``obs.record`` emits
  only with it on; an enabled span is a profiler annotation of its name,
  so it shows in a ``jax.profiler`` trace; JAX's trace / lower / compile
  stages become ``runtime.*`` spans named by program;
* Chrome-trace export validates (B/E balance per tid, pid/tid present)
  and round-trips through the CLI gate;
* ``MeasurePolicy.resolve()`` key stability — the ``trace`` knob is
  absent unless set, so untraced campaigns keep their cell keys;
* ``SpmvService.stats()`` reconciles with the obs registry (legacy keys
  preserved, counters are the same objects);
* ``regress.compare``: pass, fail on an injected 2x slowdown, and the
  cross-scale refusal.
"""
from __future__ import annotations

import copy
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.experiments.regress import compare, main as regress_main
from repro.experiments.spec import MeasurePolicy


@pytest.fixture(autouse=True)
def _no_leaked_sinks():
    yield
    assert not obs.enabled(), "a test leaked an installed trace sink"


# ---------------------------------------------------------------- spans

def test_span_nesting_single_thread():
    with obs.tracing() as buf:
        with obs.span("outer", layer="test"):
            with obs.span("inner") as sp:
                sp.set(k=3)
    evs = buf.flush()
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner"}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["parent"] == outer["id"]
    assert outer["parent"] is None
    assert inner["args"] == {"k": 3}
    assert outer["args"] == {"layer": "test"}
    # containment: inner starts no earlier and ends no later than outer
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert evs[0]["pid"] == evs[1]["pid"]


def test_span_nesting_across_threads():
    # the workers overlap, so no thread id is reused by a later worker
    barrier = threading.Barrier(3)

    def worker(i):
        with obs.span("worker", idx=i):
            with obs.span("child", idx=i):
                barrier.wait(timeout=30)

    with obs.tracing() as buf:
        with obs.span("main_root"):
            ts = [threading.Thread(target=worker, args=(i,), daemon=True)
                  for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    evs = buf.flush()
    tids = {e["tid"] for e in evs}
    assert len(tids) == 4          # main + 3 workers
    root = next(e for e in evs if e["name"] == "main_root")
    for e in evs:
        if e["name"] == "worker":
            # each thread owns its own tree: workers are roots on their
            # tid, never children of another thread's span
            assert e["parent"] is None
            assert e["tid"] != root["tid"]
        if e["name"] == "child":
            parent = next(x for x in evs if x["id"] == e["parent"])
            assert parent["name"] == "worker"
            assert parent["tid"] == e["tid"]
            assert parent["args"]["idx"] == e["args"]["idx"]


def test_span_exception_records_error():
    with obs.tracing() as buf:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("nope")
    (ev,) = buf.flush()
    assert ev["name"] == "boom"
    assert ev["args"]["error"] == "ValueError"


def test_dangling_child_force_closed():
    # A span entered but never exited (raise between enter and manual
    # bookkeeping) must still export when its parent closes.
    with obs.tracing() as buf:
        with obs.span("parent"):
            sp = obs.span("left_open", stage="probe")
            sp.__enter__()
            # ... probe raises here; nobody calls sp.__exit__ ...
    evs = buf.flush()
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"parent", "left_open"}
    assert by_name["left_open"]["args"]["unclosed"] is True
    assert by_name["left_open"]["parent"] == by_name["parent"]["id"]
    # stack is clean afterwards: a new root really is a root
    with obs.tracing() as buf2:
        with obs.span("fresh_root"):
            pass
    assert buf2.flush()[0]["parent"] is None


def test_timed_span_reads_the_clock_with_tracing_off():
    assert not obs.enabled()
    with obs.timed("plan.reorder", scheme="rcm") as sp:
        time.sleep(0.002)
    assert sp.ms >= 2.0
    with obs.tracing() as buf:
        with obs.span("outer"):
            with obs.timed("inner") as inner:
                pass
    evs = {e["name"]: e for e in buf.flush()}
    assert evs["inner"]["parent"] == evs["outer"]["id"]
    assert evs["inner"]["dur"] == pytest.approx(inner.ms * 1e3)


def test_record_emits_only_while_enabled():
    t1 = time.perf_counter_ns()
    obs.record("runtime.compile", t1 - 5000, t1, fun_name="f")   # dropped
    with obs.tracing() as buf:
        with obs.span("warm_up"):
            obs.record("runtime.compile", t1 - 5000, t1, fun_name="f")
    evs = {e["name"]: e for e in buf.flush()}
    assert set(evs) == {"warm_up", "runtime.compile"}
    rc = evs["runtime.compile"]
    assert rc["dur"] == pytest.approx(5.0) and rc["args"] == {"fun_name": "f"}
    assert rc["parent"] == evs["warm_up"]["id"]


def test_enabled_span_is_a_profiler_annotation(tmp_path):
    """On the profiler's clock, beside the device ops: the span's name on
    the host plane, with its obs id as the `obs_span` stat."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing() as buf:
            with obs.span("obs.test.annotated"):
                jnp.ones(8).block_until_ready()
        with obs.span("obs.test.disabled"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    planes = jax.profiler.ProfileData.from_file(path).planes
    names = [ev.name for p in planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events]
    assert names.count("obs.test.annotated") == 1
    assert "obs.test.disabled" not in names
    assert buf.flush()[0]["name"] == "obs.test.annotated"


def test_runtime_spans_name_each_program():
    import jax
    import jax.numpy as jnp

    from repro import compile_cache

    compile_cache.listen()
    compile_cache.listen()                     # idempotent

    def obs_probe_fn(x):
        return jnp.cos(x) * 3.0 + 1.0

    jax.jit(obs_probe_fn)(jnp.ones(5)).block_until_ready()   # not traced
    jax.clear_caches()
    with obs.tracing() as buf:
        with obs.span("warm_up"):
            jax.jit(obs_probe_fn)(jnp.ones(7)).block_until_ready()
    evs = buf.flush()
    mine = {e["name"]: e for e in evs
            if "obs_probe_fn" in e["args"].get("fun_name", "")}
    assert set(mine) == {"runtime.trace", "runtime.lower", "runtime.compile"}
    warm = [e for e in evs if e["name"] == "warm_up"][0]
    for e in mine.values():
        assert e["parent"] == warm["id"] and e["dur"] > 0
        assert warm["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= warm["ts"] + warm["dur"] + 1.0
    assert mine["runtime.trace"]["ts"] < mine["runtime.lower"]["ts"] \
        < mine["runtime.compile"]["ts"]


def test_flush_order_deterministic():
    with obs.tracing() as buf:
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
    order1 = [e["id"] for e in buf.flush()]
    order2 = [e["id"] for e in buf.flush()]
    assert order1 == order2
    assert order1 == sorted(order1)    # sequential spans: ts-ordered


def test_disabled_span_is_near_noop():
    assert not obs.enabled()
    sp = obs.span("hot", a=1)
    assert sp is obs.span("hot2")      # shared singleton, no allocation
    n = 20000
    best = float("inf")
    for _ in range(5):                 # best-of-5 derisks CI noise
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with obs.span("hot.path", key="k"):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    assert best < 1000, f"disabled span costs {best:.0f}ns (>1us)"


def test_multiple_sinks_and_enabled_flag():
    b1, b2 = obs.TraceBuffer(), obs.TraceBuffer()
    obs.install_sink(b1)
    try:
        obs.install_sink(b2)
        try:
            with obs.span("both"):
                pass
        finally:
            obs.remove_sink(b2)
        assert obs.enabled()           # b1 still installed
        with obs.span("one"):
            pass
    finally:
        obs.remove_sink(b1)
    assert not obs.enabled()
    assert [e["name"] for e in b1.flush()] == ["both", "one"]
    assert [e["name"] for e in b2.flush()] == ["both"]


# -------------------------------------------------------------- metrics

def test_registry_counters_labels_total_snapshot():
    reg = obs.Registry() if hasattr(obs, "Registry") else None
    # module-level registry API (what the instrumentation uses)
    obs.counter("t.hits", shard="a").inc()
    obs.counter("t.hits", shard="a").inc(2)
    obs.counter("t.hits", shard="b").inc()
    obs.gauge("t.resident").set(10)
    obs.gauge("t.resident").max(7)     # no-op, 7 < 10
    try:
        snap = obs.snapshot()
        assert snap["counters"]["t.hits{shard=a}"] == 3
        assert snap["counters"]["t.hits{shard=b}"] == 1
        assert obs.REGISTRY.total("t.hits") == 4
        assert snap["gauges"]["t.resident"] == 10
    finally:
        obs.reset()
    assert reg is None or isinstance(reg, object)


def test_registry_get_or_create_identity():
    try:
        c1 = obs.counter("t.same", x="1")
        c2 = obs.counter("t.same", x="1")
        assert c1 is c2
        assert obs.counter("t.same", x="2") is not c1
    finally:
        obs.reset()


# ------------------------------------------------------------ exporters

def _sample_events():
    with obs.tracing() as buf:
        with obs.span("a", k=1):
            with obs.span("b"):
                pass
    return buf.flush()


def test_chrome_trace_export_and_validate(tmp_path):
    evs = _sample_events()
    trace = obs.to_chrome_trace(evs)
    dur = obs.validate_chrome_trace(trace)
    bs = [e for e in dur if e["ph"] == "B"]
    es = [e for e in dur if e["ph"] == "E"]
    assert len(bs) == len(es) == 2
    assert all("pid" in e and "tid" in e for e in trace["traceEvents"])
    # metadata names the thread
    ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert ms and ms[0]["name"] == "thread_name"
    # file round-trip + CLI gate
    p = tmp_path / "t.json"
    obs.write_trace(str(p), evs)
    assert obs.validate_chrome_trace(str(p))
    from repro.obs.export import main as export_main

    assert export_main([str(p), "--require-span", "a",
                        "--require-span", "b"]) == 0
    assert export_main([str(p), "--require-span", "zzz"]) == 1


def test_chrome_trace_zero_duration_stays_balanced():
    evs = _sample_events()
    for e in evs:
        e["dur"] = 0.0                 # degenerate: all spans collapse
    obs.validate_chrome_trace(obs.to_chrome_trace(evs))


def test_validate_rejects_unbalanced():
    trace = {"traceEvents": [
        {"ph": "B", "name": "x", "ts": 0, "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="unbalanced"):
        obs.validate_chrome_trace(trace)
    with pytest.raises(ValueError, match="pid/tid"):
        obs.validate_chrome_trace({"traceEvents": [{"ph": "B", "name": "x",
                                                    "ts": 0}]})


def test_jsonl_export(tmp_path):
    evs = _sample_events()
    p = tmp_path / "t.jsonl"
    obs.write_trace(str(p), evs)       # extension-dispatched
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert [ln["name"] for ln in lines] == [e["name"] for e in evs]
    assert all("id" in ln and "args" in ln for ln in lines)


# ---------------------------------------------- policy key stability

def test_measure_policy_trace_key_stability():
    off = MeasurePolicy().resolve("")
    assert "trace" not in off          # untraced campaigns keep their keys
    on = MeasurePolicy(trace=True).resolve("")
    assert on["trace"] is True
    assert {k: v for k, v in on.items() if k != "trace"} == off


# ------------------------------------------- service stats reconciliation

def test_service_stats_reconciles_with_registry():
    from repro.matrices import suite
    from repro.serving.spmv_service import SpmvService

    mat = suite.get("smoke_banded")
    rng = np.random.default_rng(0)
    with SpmvService(engine="csr", max_batch=4, window_ms=5.0) as svc:
        svc.register("m", mat)
        futs = [svc.submit("m", rng.standard_normal(mat.n))
                for _ in range(6)]
        svc.flush()
        for f in futs:
            f.result(timeout=10)
        sid = svc.sid
    # read both views after shutdown: the dispatcher no longer ticks
    # time-driven counters (wakeups), so the cut is stable
    stats = svc.stats()
    snap = obs.snapshot()["counters"]
    # legacy keys preserved, and each is a view over the labelled counter
    for key in ("requests", "batches", "dispatches", "results", "sheds",
                "errors", "wakeups", "op_builds", "evictions"):
        assert stats[key] == snap[f"service.{key}{{service={sid}}}"], key
    assert stats["requests"] == 6 and stats["results"] == 6
    assert obs.REGISTRY.total("service.requests") >= 6
    # derived legacy fields still present
    assert "avg_batch" in stats and "slo" in stats
    assert isinstance(stats["batch_hist"], dict)


def test_plan_store_counters_move(tmp_path, monkeypatch):
    """Planning twice through the facade moves the unified cache
    counters (plan_store + opcache) — the scattered ad-hoc fields are
    gone, the registry is the single source. Fresh store dirs so the
    first plan is a guaranteed write and the second a guaranteed hit."""
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans"))
    monkeypatch.setenv("REPRO_REORDER_CACHE", str(tmp_path / "reorder"))
    monkeypatch.setenv("REPRO_OPERATOR_CACHE", str(tmp_path / "opcache"))
    from repro import api
    from repro.matrices import suite

    before = {n: obs.REGISTRY.total(n)
              for n in ("plan_store.writes", "plan_store.hits")}
    mat = suite.get("smoke_stencil")
    problem = api.SpmvProblem(mat)
    pl1 = api.plan(problem, reorder="baseline", engine="csr")
    pl1.build()
    pl2 = api.plan(problem, reorder="baseline", engine="csr")
    pl2.build()
    assert obs.REGISTRY.total("plan_store.writes") > before["plan_store.writes"]
    assert obs.REGISTRY.total("plan_store.hits") > before["plan_store.hits"]


def test_service_dispatcher_spans_nest_on_own_thread():
    """serve.dispatch/execute come from the dispatcher thread (its own
    tid, its own span tree): execute nests under dispatch, and neither
    parents onto the submitting thread's spans."""
    from repro.matrices import suite
    from repro.serving.spmv_service import SpmvService

    mat = suite.get("smoke_banded")
    rng = np.random.default_rng(1)
    with obs.tracing() as buf:
        with obs.span("caller"):
            with SpmvService(engine="csr", max_batch=4,
                             window_ms=5.0) as svc:
                svc.register("m", mat)
                futs = [svc.submit("m", rng.standard_normal(mat.n))
                        for _ in range(4)]
                svc.flush()
                for f in futs:
                    f.result(timeout=10)
    evs = buf.flush()
    by_id = {e["id"]: e for e in evs}
    caller = next(e for e in evs if e["name"] == "caller")
    dispatches = [e for e in evs if e["name"] == "serve.dispatch"]
    executes = [e for e in evs if e["name"] == "serve.execute"]
    submits = [e for e in evs if e["name"] == "serve.submit"]
    assert dispatches and executes and len(submits) == 4
    for e in submits:                  # submit runs on the caller thread
        assert e["tid"] == caller["tid"]
        assert e["parent"] == caller["id"]
    for e in dispatches:               # dispatcher owns its own tree
        assert e["tid"] != caller["tid"]
        assert e["parent"] is None
    for e in executes:
        parent = by_id[e["parent"]]
        assert parent["name"] == "serve.dispatch"
        assert parent["tid"] == e["tid"]


# ------------------------------------------------------------ regression

def _summary(geo_base=0.06, geo_rcm=0.05, run_ms=0.14, iters=3):
    return {
        "schema": 1, "campaign": "smoke", "field": "seq_ios_gflops",
        "geomean": {"baseline": geo_base, "rcm": geo_rcm},
        "speedup_vs_baseline": {"rcm": geo_rcm / geo_base},
        "scale": {"matrices": ["a", "b"], "max_m": 1024, "iters": iters,
                  "warmup": 1, "use_kernel": "interpret",
                  "representative": False},
        "plan_run": {"median_plan_ms": 4.0, "median_run_ms": run_ms,
                     "median_amortized_ms": 0.2, "amortize_iters": 100},
        "phases": {"median_tune_ms": 1.0},
    }


def test_regress_pass_and_improvement():
    res = compare(_summary(), _summary(geo_base=0.07))
    assert res["comparable"] and not res["regressions"]
    assert res["checks"] >= 4
    assert any("geomean[baseline]" in s for s in res["improvements"])


def test_regress_fails_on_2x_slowdown():
    cur = _summary(geo_base=0.03, geo_rcm=0.025, run_ms=0.28)
    res = compare(_summary(), cur)
    assert res["comparable"]
    names = " ".join(res["regressions"])
    assert "geomean[baseline]" in names
    assert "plan_run.median_run_ms" in names


def test_regress_portable_gates_only_ratios():
    # uniform 2x slowdown preserves speedup ratios: portable mode (for a
    # baseline committed from another machine) must NOT fail on it...
    cur = _summary(geo_base=0.03, geo_rcm=0.025, run_ms=0.28)
    res = compare(_summary(), cur, portable=True)
    assert res["comparable"] and not res["regressions"]
    assert any("machine-bound" in s for s in res["notes"])
    # ...but a collapsed rcm speedup still fails portable mode
    bad = _summary(geo_rcm=0.02)       # speedup 0.33 vs baseline 0.83
    res = compare(_summary(), bad, portable=True)
    assert any("speedup_vs_baseline" in s for s in res["regressions"])


def test_regress_refuses_cross_scale():
    res = compare(_summary(), _summary(iters=50))
    assert not res["comparable"]
    assert any("scale.iters" in s for s in res["scale_mismatch"])
    # a stamp-less (pre-gate) summary is incomparable, not silently passed
    old = _summary()
    del old["scale"]
    assert not compare(old, _summary())["comparable"]


def test_regress_cli_exit_codes(tmp_path):
    base, cur = _summary(), _summary()
    slow = copy.deepcopy(cur)
    slow["geomean"] = {k: v / 2 for k, v in slow["geomean"].items()}
    xscale = copy.deepcopy(cur)
    xscale["scale"]["iters"] = 99
    paths = {}
    for name, obj in [("base", base), ("cur", cur), ("slow", slow),
                      ("xscale", xscale)]:
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(obj))
        paths[name] = str(p)
    argv = ["--baseline", paths["base"], "--current"]
    assert regress_main(argv + [paths["cur"]]) == 0
    assert regress_main(argv + [paths["slow"]]) == 1
    assert regress_main(argv + [paths["xscale"]]) == 2
    assert regress_main(argv + [str(tmp_path / "missing.json")]) == 2
