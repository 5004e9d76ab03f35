"""Device seconds by program scope and idle gaps by program span, from the
XPlane proto: on a hand-made trace, on the TPU trace that
test_trace_reduce.py reads (tests/data/small.xplane.pb, where the new
reduction must read the same busy and window), and on one recorded on a
TPU v5e with the program's scopes and spans (tests/data/scoped.xplane.pb,
bench/tests/record_scoped_trace.py). Also the set-up account of
bench/account.py on hand-made spans."""
import os
import subprocess
import sys

import pytest

from bench import trace_reduce, trace_scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))

pb2 = trace_scopes._xplane_pb2()


def hand_space():
    """Window 0..100 ns. Device ops (ns): 10..30 under spmv/spmv.permute,
    20..40 under spmv/spmv.engine/spmv.gather, 50..60 under no scope,
    0..100 a while loop holding them, 0..5 a loop XLA added (no tf_op),
    95..120 spmv.engine crossing the window's end. Program spans: outer
    0..100, inner 40..70."""
    space = pb2.XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    ops = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=0)
    specs = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              "jit(f)/while/body/spmv/spmv.permute/gather:", 10, 20),
             ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              "jit(f)/while/body/spmv/spmv.engine/spmv.gather/gather:",
              20, 20),
             ("%add.3 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)",
              "jit(f)/while/body/add:", 50, 10),
             ("%while.4 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b",
              "jit(f)/while:", 0, 100),
             ("%while.6 = (s32[8]{0}) while((s32[8]{0}) %u), body=%c",
              "", 0, 5),
             ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              "jit(f)/while/body/spmv/spmv.engine/mul:", 95, 25)]
    for i, (name, tf_op, start, dur) in enumerate(specs, start=1):
        md = dev.event_metadata[i]
        md.id, md.name = i, name
        md.stats.add(metadata_id=1, str_value=tf_op)
        ops.events.add(metadata_id=i, offset_ps=start * 1000,
                       duration_ps=dur * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    host.stat_metadata[1].id, host.stat_metadata[1].name = 1, "obs_span"
    py = host.lines.add(id=1, name="python", timestamp_ns=0)
    for i, (name, start, dur, span) in enumerate(
            [("bench.window", 0, 100, None), ("outer", 0, 100, 1),
             ("inner", 40, 30, 2), ("bench.wait", 60, 40, None)], start=1):
        md = host.event_metadata[i]
        md.id, md.name = i, name
        e = py.events.add(metadata_id=i, offset_ps=start * 1000,
                          duration_ps=dur * 1000)
        if span is not None:
            e.stats.add(metadata_id=1, int64_value=span)
    return space


def test_scope_path_drops_the_primitive():
    assert trace_scopes.scope_path("jit(f)/spmv/spmv.engine/gather:") == \
        ["jit(f)", "spmv", "spmv.engine"]
    assert trace_scopes.scope_path("") == []


def test_scopes_are_unions_clipped_to_the_window():
    r = trace_scopes.reduce(hand_space())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(100e-9)     # the loop covers it
    s = r["scopes"]
    # spmv: 10..40 and 95..100
    assert s["spmv"] == pytest.approx(35e-9)
    assert s["spmv.permute"] == pytest.approx(20e-9)
    assert s["spmv.engine"] == pytest.approx(25e-9)
    assert s["spmv.gather"] == pytest.approx(20e-9)
    assert s["jit(f)"] == pytest.approx(100e-9)
    assert "gather:" not in s and "mul:" not in s


def test_ops_outside_a_scope_leave_out_loops():
    r = trace_scopes.reduce(hand_space())
    assert [name for name, _ in r["outside_ops"]] == [
        "add.3 add f32[8] [jit(f)/while/body/add:]"]
    assert r["outside_ops"][0][1] == pytest.approx(10e-9)


def test_gaps_are_labelled_by_the_innermost_program_span():
    space = hand_space()
    ops = space.planes[0].lines[0]
    del ops.events[3:5]           # no loops: gaps 0..10, 40..50, 60..95
    r = trace_scopes.reduce(space)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert dict(r["idle_gaps_by_span"]) == pytest.approx(
        {"inner (1 gaps)": 10e-9, "outer (2 gaps)": 45e-9})


def test_no_window_reads_nothing():
    space = hand_space()
    del space.planes[1]
    assert trace_scopes.reduce(space) is None
    assert trace_scopes.reduce(None) is None


def test_recorded_trace_reads_as_trace_reduce_does():
    """The existing recorded trace: the same busy and window as
    trace_reduce, and every op under its jitted program's segment."""
    plain = trace_reduce.reduce(trace_reduce.load_file(SMALL))
    r = trace_scopes.reduce(trace_scopes.load_file(SMALL))
    assert r["busy_s"] == plain["busy_s"]
    assert r["window_s"] == plain["window_s"]
    assert r["scopes"] == {"jit(<lambda>)": pytest.approx(plain["busy_s"])}
    ((label, idle),) = r["idle_gaps_by_span"]
    assert label.startswith(trace_scopes.NO_SPAN + " (")
    assert idle == pytest.approx(plain["window_s"] - plain["busy_s"])


def test_recorded_scoped_trace():
    """Three eager SELL SpMVs and one CG under a non-identity plan,
    recorded on a TPU v5e with the program's spans on (157,085 bytes)."""
    plain = trace_reduce.reduce(trace_reduce.load_file(SCOPED))
    r = trace_scopes.reduce(trace_scopes.load_file(SCOPED))
    assert r["busy_s"] == plain["busy_s"]
    assert r["window_s"] == plain["window_s"]
    s = r["scopes"]
    for scope in ("spmv", "spmv.permute", "spmv.engine", "spmv.gather"):
        assert s[scope] > 0, scope
    assert s["spmv.permute"] + s["spmv.engine"] <= s["spmv"] * (1 + 1e-9)
    assert s["spmv"] <= r["busy_s"]
    # the SELL gather runs in its own jitted program, outside `spmv`
    assert s["jit(sell_spmm)"] > 0
    assert s["jit(cg_solve)"] > s["spmv"]
    assert not any(name.startswith("while")
                   for name, _ in r["outside_ops"])
    labels = {label.split(" (")[0] for label, _ in r["idle_gaps_by_span"]}
    assert {"kernel.spmv", "record.cg"} <= labels
    assert sum(t for _, t in r["idle_gaps_by_span"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_the_proto_module_loads_without_tensorflow():
    code = ("import sys; sys.path.insert(0, '.');"
            "from bench import trace_scopes;"
            "assert trace_scopes._xplane_pb2() is not None;"
            "assert not [m for m in sys.modules if m == 'tensorflow' or "
            "m.startswith('tensorflow.')], 'tensorflow was imported'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_setup_account_sums_to_setup():
    from bench import account

    def ev(name, ts, dur):
        return {"name": name, "ts": ts, "dur": dur, "args": {}}

    spans = [ev("plan", 10, 50), ev("plan.reorder", 12, 20),
             ev("plan.permute", 32, 3), ev("plan.tune", 40, 10),
             ev("runtime.trace", 45, 2), ev("plan.build", 61, 9),
             ev("runtime.trace", 75, 10), ev("runtime.lower", 85, 5),
             ev("runtime.compile", 90, 6)]
    a = account.setup_account(spans, 0.0, 4.0, 100.0)
    assert a["start-up"]["s"] == pytest.approx(4e-6)
    assert a["load"]["s"] == pytest.approx(6e-6)
    assert a["plan"]["s"] == pytest.approx(50e-6)
    assert a["plan"]["reorder_s"] == pytest.approx(23e-6)
    assert a["plan"]["tune_s"] == pytest.approx(10e-6)
    assert a["plan"]["other_s"] == pytest.approx(17e-6)
    assert a["plan"]["lower_s"] == pytest.approx(2e-6)
    assert a["warm-up"]["s"] == pytest.approx(30e-6)
    assert a["warm-up"]["lower_s"] == pytest.approx(15e-6)
    assert a["warm-up"]["compile_s"] == pytest.approx(6e-6)
    total = sum(v["s"] for k, v in a.items() if k != "between") \
        + a["between"]
    assert total == pytest.approx(100e-6)
    assert a["between"] == pytest.approx(1e-6)
