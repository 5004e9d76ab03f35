"""Profiler trace -> device seconds per program scope, and idle gaps by
program span.

The program names its layers with `jax.named_scope` (`spmv`,
`spmv.permute`, `spmv.engine`, `spmv.gather`); the name reaches the
`tf_op` stat of every device operation traced inside it, as one segment
of a path such as `jit(cg_solve)/while/body/spmv/spmv.engine/...`.
`jax.profiler.ProfileData` does not expose that stat, so this module reads
the `.xplane.pb` with the XPlane protobuf module that ships with the
installed TensorFlow wheel, loaded by its file path: TensorFlow itself is
never imported into the process that holds the chip.

Device seconds under a scope are the union of the intervals of the
operations whose path holds that segment, clipped to the host annotation
`bench.window` and averaged over the devices that ran any, as
`trace_reduce` computes busy time. While tracing is on, each program span
is a host annotation carrying the stat `obs_span` (repro.obs), so an idle
gap can be labelled by the innermost program span the host was in.
"""
from __future__ import annotations

import collections
import glob
import importlib.util
import os

from bench import trace_reduce

NO_SPAN = "no program span"
TOP = 10
# ops that hold other ops (a loop holds its body's), by JAX primitive or
# by HLO opcode (ops XLA adds carry no `tf_op`): left out of the top list
# of ops outside a scope, whose time they would count twice
_HOLDERS = ("while", "cond", "conditional", "call")


def _xplane_pb2():
    """The XPlane protobuf module, or None where it is not installed."""
    try:
        spec = importlib.util.find_spec("tensorflow")
    except (ImportError, ValueError):
        return None
    if spec is None or not spec.submodule_search_locations:
        return None
    for base in spec.submodule_search_locations:
        path = os.path.join(base, "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        if os.path.exists(path):
            mspec = importlib.util.spec_from_file_location(
                "bench_xplane_pb2", path)
            mod = importlib.util.module_from_spec(mspec)
            mspec.loader.exec_module(mod)
            return mod
    return None


def load_file(path: str):
    """The XSpace of one `.xplane.pb`, or None without the proto module."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def load(trace_dir: str):
    """The XSpace of the newest trace under `trace_dir`."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(files[-1])


def _stats(plane, stats) -> dict:
    out = {}
    for s in stats:
        name = plane.stat_metadata[s.metadata_id].name
        kind = s.WhichOneof("value")
        if kind == "ref_value":
            out[name] = plane.stat_metadata[s.ref_value].name
        elif kind is not None:
            out[name] = getattr(s, kind)
    return out


def _events(plane, line):
    """(metadata, start_ns, duration_ns, event) of a line, on the clock
    and at the resolution `jax.profiler.ProfileData` gives them."""
    for e in line.events:
        yield (plane.event_metadata[e.metadata_id],
               float(line.timestamp_ns + e.offset_ps // 1000),
               float(e.duration_ps // 1000), e)


def _path(tf_op: str) -> list:
    return (tf_op.rsplit(":", 1)[0] if tf_op.endswith(":")
            else tf_op).split("/")


def scope_path(tf_op: str) -> list:
    """The scope segments of an op's path, without the primitive's name:
    "jit(f)/spmv/spmv.engine/gather:" -> [jit(f), spmv, spmv.engine]."""
    return _path(tf_op)[:-1]


def device_ops(space) -> dict:
    """{device plane: [(op name, tf_op, start_ns, duration_ns)]}."""
    out = {}
    for plane in space.planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        tf_op = {}
        ops = []
        for line in plane.lines:
            if line.name != trace_reduce._OPS_LINE:
                continue
            for md, s, d, _ in _events(plane, line):
                if md.id not in tf_op:
                    tf_op[md.id] = str(_stats(plane, md.stats)
                                       .get("tf_op", ""))
                ops.append((trace_reduce.op_name(md.name), tf_op[md.id],
                            s, d))
        if ops:
            out[plane.name] = ops
    return out


def host_annotations(space) -> tuple:
    """([(name, start, end)] of `bench.*` annotations, [(name, start, end)]
    of program spans: host events with the `obs_span` stat)."""
    bench, program = [], []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for md, s, d, e in _events(plane, line):
                if md.name.startswith("bench."):
                    bench.append((md.name, s, s + d))
                elif any(plane.stat_metadata[st.metadata_id].name
                         == "obs_span" for st in e.stats):
                    program.append((md.name, s, s + d))
    return bench, program


def _innermost(spans, t: float) -> str:
    inside = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(inside)[1] if inside else NO_SPAN


def reduce(space, outside: str = "spmv") -> dict | None:
    """Of the traced window: `busy_s`, `window_s`, `devices`;
    `scopes`, device seconds under each scope segment; `outside_ops`, the
    top ops whose path lacks the segment `outside`, with their seconds;
    `idle_gaps_by_span`, idle seconds by the innermost program span. None
    where the trace holds no window or no device operation in it."""
    if space is None:
        return None
    bench, program = host_annotations(space)
    windows = [(s, e) for name, s, e in bench if name == trace_reduce.WINDOW]
    ops = device_ops(space)
    if not windows or not ops:
        return None
    w0, w1 = windows[-1]
    busy = []
    scopes = collections.Counter()
    left_out = collections.Counter()
    gaps = collections.defaultdict(lambda: [0.0, 0])
    for events in ops.values():
        clipped = [(max(s, w0), min(s + d, w1), name, tf_op)
                   for name, tf_op, s, d in events if s < w1 and s + d > w0]
        merged = trace_reduce.merge((s, e) for s, e, _, _ in clipped)
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged) / 1e9)
        by_scope = collections.defaultdict(list)
        for s, e, name, tf_op in clipped:
            path = scope_path(tf_op)
            for seg in set(path):
                by_scope[seg].append((s, e))
            holder = (_path(tf_op)[-1] in _HOLDERS
                      or name.split(".")[0].split(" ")[0] in _HOLDERS)
            if outside not in path and not holder:
                left_out[f"{name} [{tf_op}]"] += (e - s) / 1e9
        for seg, ivs in by_scope.items():
            merged_seg = trace_reduce.merge(ivs)
            scopes[seg] += sum(e - s for s, e in merged_seg) / 1e9
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                slot = gaps[_innermost(program, (g0 + g1) / 2)]
                slot[0] += (g1 - g0) / 1e9
                slot[1] += 1
    if not busy:
        return None
    ndev = len(busy)
    top_out = sorted(left_out.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "busy_s": sum(busy) / ndev,
        "window_s": (w1 - w0) / 1e9,
        "devices": ndev,
        "scopes": {seg: t / ndev for seg, t in sorted(scopes.items())},
        "outside_ops": [[name, t / ndev] for name, t in top_out],
        "idle_gaps_by_span": [[f"{label} ({n} gaps)", t / ndev]
                              for label, (t, n) in top_gaps],
    }
