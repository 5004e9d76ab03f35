"""Problem → Plan → Operator: the staged SpMV pipeline (OSKI's tune-time API).

The paper's core loop — reorder, convert, tune, measure — as three stages:

    problem = SpmvProblem(mat, k=8)                  # what to multiply
    pl      = plan(problem, reorder="auto")          # serializable decision
    op      = pl.build()                             # device operator

`plan()` jointly selects (scheme x engine x shape x k): for each candidate
reordering scheme it computes the *permuted* matrix's structural features
and scores every registered engine's candidate grid with the k-aware cost
model (core/spmv/tune.py) — the per-scheme structural deltas (bandwidth,
block fill, row-nnz spread) are exactly what moves the engine choice, so
scheme and engine are decided together rather than scheme being caller-side
preprocessing. Candidate schemes/engines come from the plugin registries
(core/registry.py); `hints={"schemes": [...]}` widens the scheme search.

Plans are content-addressed in ONE persistent store (REPRO_PLAN_CACHE,
default /tmp/repro_plans) that subsumes the separate reorder cache and
operator cache of the legacy entry points: an entry holds the plan record,
the permutation, and the built operator's device arrays, so `Plan.save` /
`Plan.load` round-trip a tuned operator across processes with zero re-tune
and zero re-conversion. Writes are tmp+rename atomic (the .json lands last
and gates the read — opcache.py's convention).

The built operator CARRIES its permutation: `op(x)` / `op.matmul(X)` take
vectors in the ORIGINAL index space and return results in the original
index space (internally x is gathered through perm and y scattered back
through iperm), eliminating the hand-carried permutation footgun. The
measurement harness opts out with `op(x, permuted=True)` (or times
`op.unwrap()`), which runs in the reordered space like the legacy path.

The same facade covers a device mesh: `plan(problem,
topology=Topology(...), partition=...)` widens the joint selection to
(partition x scheme x engine x shape x k) with the communication-volume
cost model (topology.py), and `build()` returns a ShardedOperator
(distributed.py) carrying perm + panel starts + collective schedule —
same store, same original-index-space contract. Topology/partition join
the content key ONLY when non-trivial, so single-device caches never
fork.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import threading
import time
from typing import Any, Optional

import numpy as np

from ... import obs
from .. import registry
from ..sparse import partition as partition_mod
from ..sparse.csr import CSRMatrix
from . import tune as tune_mod
from . import topology as topology_mod
from .topology import Topology
from .tune import TunePlan

_OFF = ("off", "0", "none", "")


def _store_dir() -> str:
    """Plan-store directory. Falls back to a `plans/` sibling under
    REPRO_OPERATOR_CACHE when only that is set (hermetic test/CI runs that
    repoint the legacy caches get a hermetic plan store for free); "off"
    in either variable disables the store."""
    d = os.environ.get("REPRO_PLAN_CACHE")
    if d is not None:
        return d
    opd = os.environ.get("REPRO_OPERATOR_CACHE")
    if opd is not None:
        return opd if opd.lower() in _OFF else os.path.join(opd, "plans")
    return "/tmp/repro_plans"


def store_enabled() -> bool:
    return _store_dir().lower() not in _OFF


@dataclasses.dataclass(frozen=True)
class SpmvProblem:
    """What to multiply: the matrix, the expected RHS batch width, the
    compute dtype, and free-form planning hints.

    hints (all optional):
      seed        — reordering seed (default 0)
      schemes     — scheme names plan(reorder="auto") should consider
                    (default: every registered scheme with auto_candidate)
      block_shape — (bm, bn) / (C, W) for fixed block engines (default
                    (8, 128); sell's (128, 8), a slice's rows on lanes)
      sell_sigma  — σ sort window for the fixed sell engine
      use_kernel  — "auto" | "pallas" | "interpret" | "ref"
      nnz_bucket  — CSR nnz padding bucket
    """

    mat: CSRMatrix
    k: int = 1
    dtype: Any = None
    hints: dict = dataclasses.field(default_factory=dict)

    def dtype_name(self) -> str:
        return "float32" if self.dtype is None else np.dtype(self.dtype).name


def _mat_key(mat: CSRMatrix) -> str:
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(mat.rowptr).tobytes())
    h.update(np.ascontiguousarray(mat.cols).tobytes())
    h.update(np.ascontiguousarray(mat.vals).tobytes())
    h.update(f"{tuple(mat.shape)}".encode())
    return h.hexdigest()[:20]


def structure_key(mat: CSRMatrix) -> str:
    """sha1 over the STRUCTURE only (rowptr + cols + shape, never vals).

    Everything a plan decides — scheme permutation, engine, block shape,
    σ window — is a function of the sparsity pattern alone, so two
    matrices with equal structure_key can share one Plan: swapping the
    values is a rebuild (`Plan.rebuild`), never a replan. This is the
    hash the serving layer's dynamic-matrix path keys on."""
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(mat.rowptr).tobytes())
    h.update(np.ascontiguousarray(mat.cols).tobytes())
    h.update(f"{tuple(mat.shape)}".encode())
    return h.hexdigest()[:20]


def values_key(mat: CSRMatrix) -> str:
    """sha1 over the VALUES only — structure_key's complement.

    (structure_key, values_key) identifies a matrix's full content
    without hashing it as one blob, which is what a dynamic-structure
    consumer (workloads.WorkloadSession) needs to tell "same structure,
    same values → reuse the built Operator" apart from "same structure,
    new values → Plan.rebuild"."""
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(mat.vals).tobytes())
    return h.hexdigest()[:20]


def _block_shape(hints: dict, engine: str) -> tuple:
    """The hinted block shape, else the engine's own: one (8, 128) tile
    for the block engines, and for sell (C, W) = (128, 8) chunks."""
    return tuple(hints.get("block_shape",
                           (128, 8) if engine == "sell" else (8, 128)))


def plan_key(problem: SpmvProblem, reorder: str, engine: str,
             probe, seed: int, schemes=None, topology=None,
             partition: str = "auto", partitioners=None) -> str:
    """sha1 over matrix content + the full plan request.

    k steers the auto-engine choice AND (through the per-scheme cost
    deltas) the auto-scheme choice, so it is normalized out only when
    BOTH axes are fixed (a k-sweep over one engine+scheme is a single
    entry — opcache.py's rule); a sharded topology keeps k too (the
    compute/collective trade-off moves with the batch width). `schemes`
    is the resolved candidate set for reorder="auto": two requests
    searching different scheme sets are different plans, even on the
    same matrix.

    Topology joins the key ONLY when non-trivial: a Topology(devices=1)
    request hashes identically to no topology at all, so single-device
    caches never fork (asserted in tests/test_topology_plans.py).
    Sharded plans are model-based, so `probe` is normalized out of their
    keys (a probe=True request builds the identical plan — one entry).
    Probe modes hash distinctly (False / True / "learned" / "exhaustive"
    are different searches, so different plans).
    """
    topo = topology_mod.normalize(topology)
    k = problem.k if (engine == "auto" or reorder == "auto"
                      or topo is not None) else 1
    probe = probe if topo is None else False
    hints = problem.hints
    h = hashlib.sha1()
    h.update(_mat_key(problem.mat).encode())
    h.update(f"{reorder}:{tuple(schemes or ())}:{seed}:{engine}:"
             f"{problem.dtype_name()}:"
             f"{_block_shape(hints, engine)}:"
             f"{hints.get('sell_sigma')}:{int(hints.get('nnz_bucket', 0))}:"
             f"{probe}:{int(k)}".encode())
    if topo is not None:
        h.update(json.dumps(topo.key_dict(), sort_keys=True).encode())
        h.update(f":{partition}:{tuple(partitioners or ())}".encode())
    return h.hexdigest()[:20]


class Operator:
    """Permutation-carrying SpMV/SpMM operator.

    `op(x)` and `op.matmul(X)` accept vectors in the ORIGINAL index space:
    x is gathered through `perm` before the reordered-space engine runs and
    the result is scattered back through `iperm`, so callers never permute
    by hand. `permuted=True` opts out (x already in the reordered space,
    result returned in the reordered space) — the measurement harness path.
    For a baseline/identity plan both paths are the same single engine call.

    A pytree: the engine and the permutation arrays are its children, so
    a jitted solver that takes the operator as an argument (`cg_solve`)
    reads the arrays of a pytree engine (`dia`) as arguments of its
    program; an engine registered static is compiled in as before. The
    rest rides along with the operator itself, compared by identity, so
    each operator is traced anew, as a static argument was.
    """

    def __init__(self, inner, perm: Optional[np.ndarray], plan: "Plan",
                 build_info: Optional[dict] = None):
        import jax.numpy as jnp

        _register_operator_pytree()
        self.inner = inner
        self.plan = plan
        self.build_info = build_info or {}
        if perm is not None and np.array_equal(perm, np.arange(perm.size)):
            perm = None                     # identity: skip the gathers
        self._perm_np = perm
        if perm is None:
            self._perm = self._iperm = None
        else:
            iperm = np.empty_like(perm)
            iperm[perm] = np.arange(perm.size, dtype=perm.dtype)
            self._perm = jnp.asarray(perm, jnp.int32)
            self._iperm = jnp.asarray(iperm, jnp.int32)

    @property
    def perm(self) -> Optional[np.ndarray]:
        """perm[i] = original row at reordered position i (None = identity)."""
        return self._perm_np

    @property
    def iperm(self) -> Optional[np.ndarray]:
        """iperm[r] = reordered position of original row r (None = identity)."""
        if self._perm_np is None:
            return None
        return np.asarray(self._iperm)

    @property
    def shape(self) -> tuple:
        inner = self.inner
        if hasattr(inner, "shape"):
            return tuple(inner.shape)
        if hasattr(inner, "m"):
            return (inner.m, inner.n)
        a = inner.a  # DeviceDense
        return tuple(a.shape)

    def unwrap(self):
        """The bare reordered-space engine operator (equivalent to calling
        with permuted=True) — what the measurement harness times."""
        return self.inner

    def __call__(self, x, permuted: bool = False):
        return self._apply(self.inner, x, permuted)

    def matmul(self, x, permuted: bool = False):
        """x: [n, k] -> y: [m, k], original index space unless permuted."""
        return self._apply(self.inner.matmul, x, permuted)

    def _apply(self, engine, x, permuted: bool):
        """One application under the device scopes `spmv` (all of it),
        `spmv.permute` (the two permutation gathers) and `spmv.engine` (the
        engine's call). A scope reaches the ops traced inside it, so they
        name device time where this runs under jit."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("spmv"):
            if self._perm is None or permuted:
                with jax.named_scope("spmv.engine"):
                    return engine(x)
            with jax.named_scope("spmv.permute"):
                xr = jnp.take(x, self._perm, axis=0)
            with jax.named_scope("spmv.engine"):
                yr = engine(xr)
            with jax.named_scope("spmv.permute"):
                return jnp.take(yr, self._iperm, axis=0)

    def tree_flatten(self):
        return (self.inner, self._perm, self._iperm), self

    @classmethod
    def tree_unflatten(cls, src, children):
        op = object.__new__(cls)
        op.__dict__.update(src.__dict__)
        op.inner, op._perm, op._iperm = children
        return op


@functools.cache
def _register_operator_pytree() -> None:
    # here, not at import: this module does not import JAX
    import jax

    jax.tree_util.register_pytree_node_class(Operator)


@dataclasses.dataclass
class Plan:
    """A serializable pipeline decision: which scheme, which engine/shape,
    for which problem — plus the permutation that realizes the scheme.

    `build()` materializes the operator (from the plan store when possible,
    otherwise by permute + format conversion) — never by re-tuning.
    """

    scheme: str
    seed: int
    engine_request: str               # what the caller asked ("auto"/fixed)
    tune: TunePlan                    # resolved engine decision
    k: int
    dtype_name: str
    probe: bool
    use_kernel: str
    nnz_bucket: int
    mat_shape: tuple
    mat_nnz: int
    key: str                          # plan-store content key
    scheme_costs: dict = dataclasses.field(default_factory=dict)
    reorder_ms: float = 0.0
    tune_ms: float = 0.0
    plan_ms: float = 0.0
    cache_hit: bool = False           # this plan was loaded, not computed
    advisor_confidence: float = 0.0   # probe="learned": nearest-neighbor
    #                                   trust in (0, 1]; 0 = no knowledge
    perm: Optional[np.ndarray] = None  # None = identity
    # -- topology-aware (sharded) plans ------------------------------------
    topology: Optional[Topology] = None          # None = single device
    partitioner: str = ""                        # resolved partitioner name
    panel_starts: Optional[np.ndarray] = None    # [P+1] reordered-row split
    comm: dict = dataclasses.field(default_factory=dict)   # collective model
    partition_costs: dict = dataclasses.field(default_factory=dict)
    _mat: Optional[CSRMatrix] = dataclasses.field(
        default=None, repr=False, compare=False)
    _rmat: Optional[CSRMatrix] = dataclasses.field(
        default=None, repr=False, compare=False)
    _op_state: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    def label(self) -> str:
        base = f"{self.scheme}+{self.tune.label()}"
        if self.topology is None:
            return base
        return (f"{base}+{self.partitioner}@{self.topology.layout}"
                f"p{self.topology.devices}")

    # -- serialization -----------------------------------------------------
    def to_json(self) -> dict:
        return {
            "scheme": self.scheme, "seed": self.seed,
            "engine_request": self.engine_request,
            "tune": self.tune.to_json(), "k": self.k,
            "dtype_name": self.dtype_name, "probe": self.probe,
            "use_kernel": self.use_kernel, "nnz_bucket": self.nnz_bucket,
            "mat_shape": list(self.mat_shape), "mat_nnz": self.mat_nnz,
            "key": self.key, "scheme_costs": self.scheme_costs,
            "reorder_ms": self.reorder_ms, "tune_ms": self.tune_ms,
            "plan_ms": self.plan_ms,
            "advisor_confidence": self.advisor_confidence,
            "topology": None if self.topology is None
            else self.topology.to_json(),
            "partitioner": self.partitioner, "comm": self.comm,
            "partition_costs": self.partition_costs,
        }

    @staticmethod
    def from_json(d: dict, perm: Optional[np.ndarray] = None,
                  mat: Optional[CSRMatrix] = None,
                  panel_starts: Optional[np.ndarray] = None) -> "Plan":
        return Plan(scheme=d["scheme"], seed=d["seed"],
                    engine_request=d["engine_request"],
                    tune=TunePlan.from_json(d["tune"]), k=d["k"],
                    dtype_name=d["dtype_name"], probe=d["probe"],
                    use_kernel=d["use_kernel"], nnz_bucket=d["nnz_bucket"],
                    mat_shape=tuple(d["mat_shape"]), mat_nnz=d["mat_nnz"],
                    key=d["key"], scheme_costs=d.get("scheme_costs", {}),
                    reorder_ms=d.get("reorder_ms", 0.0),
                    tune_ms=d.get("tune_ms", 0.0),
                    plan_ms=d.get("plan_ms", 0.0),
                    advisor_confidence=d.get("advisor_confidence", 0.0),
                    topology=Topology.from_json(d.get("topology")),
                    partitioner=d.get("partitioner", ""),
                    panel_starts=panel_starts,
                    comm=d.get("comm", {}),
                    partition_costs=d.get("partition_costs", {}),
                    perm=perm, _mat=mat)

    def save(self, op=None, path: Optional[str] = None) -> str:
        """Persist this plan (and, if given, a built operator's device
        arrays) to the plan store. Returns the entry's json path."""
        d = (os.path.dirname(path) or ".") if path else _store_dir()
        os.makedirs(d, exist_ok=True)
        base = (path[:-5] if path and path.endswith(".json")
                else os.path.join(d, self.key))
        arrays: dict = {}
        if self.perm is not None:
            arrays["perm"] = np.asarray(self.perm, np.int64)
        if self.panel_starts is not None:
            arrays["panel_starts"] = np.asarray(self.panel_starts, np.int64)
        rec = {"plan": self.to_json(), "op": None}
        if op is None and self._op_state is not None:
            # _op_state arrays were de-prefixed at load time; re-prefix so
            # the written entry round-trips (and can never collide with
            # the "perm" array)
            op_rec, op_arrays = self._op_state
            rec["op"] = op_rec
            arrays.update({f"op__{k}": v for k, v in op_arrays.items()})
        elif op is not None:
            meta, op_arrays = op.state()
            rec["op"] = {"cls": type(op).__name__, "meta": meta}
            arrays.update({f"op__{k}": v for k, v in op_arrays.items()})
        # tmp+rename, npz first, json LAST (gates the read) — the opcache
        # convention; tmp names carry pid AND thread id
        tag = f"{os.getpid()}.{threading.get_ident()}"
        ztmp = f"{base}.{tag}.npz.tmp"
        with open(ztmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(ztmp, base + ".npz")
        jtmp = f"{base}.{tag}.json.tmp"
        with open(jtmp, "w") as f:
            json.dump(rec, f)
        os.replace(jtmp, base + ".json")
        obs.counter("plan_store.writes").inc()
        return base + ".json"

    @staticmethod
    def load(key_or_path: str, mat: Optional[CSRMatrix] = None
             ) -> Optional["Plan"]:
        """Load a plan (and any stored operator payload) by store key or
        explicit `<path>.json`. Returns None on miss/corruption — the
        store is persistent across code versions, so unreadable entries
        are treated as absent, never fatal."""
        if key_or_path.endswith(".json"):
            base = key_or_path[:-5]
        else:
            base = os.path.join(_store_dir(), key_or_path)
        jpath, zpath = base + ".json", base + ".npz"
        if not (os.path.exists(jpath) and os.path.exists(zpath)):
            obs.counter("plan_store.misses").inc()
            return None
        try:
            with open(jpath) as f:
                rec = json.load(f)
            z = np.load(zpath)
            perm = z["perm"] if "perm" in z.files else None
            starts = (z["panel_starts"] if "panel_starts" in z.files
                      else None)
            pl = Plan.from_json(rec["plan"], perm=perm, mat=mat,
                                panel_starts=starts)
            if rec.get("op"):
                op_arrays = {k[len("op__"):]: z[k] for k in z.files
                             if k.startswith("op__")}
                pl._op_state = (rec["op"], op_arrays)
            pl.cache_hit = True
            # this invocation paid none of the plan-time costs (paper
            # methodology: preprocessing accounting must reflect THIS
            # run); the originals remain in the on-disk record
            pl.tune_ms = 0.0
            pl.reorder_ms = 0.0
            pl.plan_ms = 0.0
            obs.counter("plan_store.hits").inc()
            return pl
        except Exception:
            obs.counter("plan_store.misses").inc()
            return None

    # -- materialization ---------------------------------------------------
    def reordered_matrix(self) -> CSRMatrix:
        """The problem matrix in the plan's reordered index space."""
        if self._rmat is None:
            if self._mat is None:
                raise ValueError("plan has no attached matrix; pass mat= to "
                                 "Plan.load or use plan(problem, ...)")
            self._rmat = (self._mat if self.perm is None
                          else _permute(self._mat, self.perm,
                                        scheme=self.scheme)[0])
        return self._rmat

    def _restore_operator(self, dtype):
        """Operator from stored device arrays (no conversion, no matrix)."""
        if self._op_state is None:
            return None
        op_rec, arrays = self._op_state
        cls = _operator_registry().get(op_rec["cls"])
        if cls is None:
            return None
        try:
            op = cls.from_state(op_rec["meta"], arrays, dtype=dtype)
        except Exception:
            return None
        # restored kernel choice must match THIS process's backend (an
        # entry written on TPU may be reloaded on CPU and vice versa)
        if getattr(op, "use_kernel", None) is not None:
            from ...kernels import resolve_use_kernel

            op.use_kernel = resolve_use_kernel(self.use_kernel,
                                               self.tune.engine)
        op.plan = self.tune
        return op

    def build(self, cache: bool = True, devices=None):
        """Materialize the operator this plan describes: a permutation-
        carrying Operator for single-device plans, a ShardedOperator for
        topology-aware plans (perm + panel starts + collective schedule)
        whose mesh is `devices` (default: the process's first ones;
        single-device plans ignore it). Store hit -> device arrays reload
        (load_ms); miss -> permute + format conversion (build_ms) and the
        complete entry (plan + perm + operator payload) is persisted.
        Never re-tunes."""
        with obs.span("plan.build", key=self.key, scheme=self.scheme,
                      engine=self.tune.engine) as sp:
            op = self._build_impl(cache, devices)
            info = getattr(op, "build_info", None) or {}
            sp.set(cache_hit=bool(info.get("cache_hit")))
            return op

    def _build_impl(self, cache: bool, devices):
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype_name)
        info = {"cache_hit": False, "key": self.key,
                "tune_ms": self.tune_ms, "build_ms": 0.0, "load_ms": 0.0,
                "engine": self.tune.engine, "plan": self.tune.to_json()}
        use_store = cache and store_enabled()
        if self.topology is not None:
            return self._build_sharded(dt, info, use_store, devices)
        inner = None
        if use_store:
            t0 = time.perf_counter()
            if self._op_state is None and self.cache_hit:
                # a freshly computed plan cannot have an op payload in the
                # store yet (plan() just wrote the plan-only entry) — only
                # a loaded plan re-consults the store for arrays
                stored = Plan.load(self.key, mat=self._mat)
                if stored is not None and stored._op_state is not None:
                    self._op_state = stored._op_state
            inner = self._restore_operator(dt)
            if inner is not None:
                info["load_ms"] = (time.perf_counter() - t0) * 1e3
                info["cache_hit"] = True
        if inner is None:
            t0 = time.perf_counter()
            inner = tune_mod.build_from_plan(
                self.reordered_matrix(), self.tune, dtype=dt,
                use_kernel=self.use_kernel, nnz_bucket=self.nnz_bucket)
            info["build_ms"] = (time.perf_counter() - t0) * 1e3
            if use_store:
                self.save(op=inner)
        return Operator(inner, self.perm, self, build_info=info)

    def rebuild(self, mat: CSRMatrix, use_kernel: Optional[str] = None,
                devices=None):
        """Operator for a matrix with the SAME sparsity structure but
        (possibly) different values, under this plan's frozen decision:
        permute through the carried perm, convert with the already-chosen
        (engine, shape) — no re-tune, no re-plan, and NO store write (the
        plan store is content-addressed over values; publishing swapped
        values under the old key would poison it).

        The dynamic-matrix path of the serving layer: `update_values`
        (and re-register with an unchanged `structure_key`) is a rebuild,
        never a replan. Sharded plans rebuild too: the frozen partition,
        panel split and collective schedule are reused and only the
        per-device arrays are repacked (no re-partition, no re-tune) —
        what the multi-shard router's value-swap path runs, on the mesh
        `devices` as in `build`. Raises ValueError on a structure
        mismatch."""
        import jax.numpy as jnp

        if tuple(mat.shape) != tuple(self.mat_shape) \
                or mat.nnz != self.mat_nnz:
            raise ValueError(
                f"rebuild() needs the plan's structure "
                f"({self.mat_shape}, nnz={self.mat_nnz}); got "
                f"({tuple(mat.shape)}, nnz={mat.nnz}) — replan instead")
        dt = jnp.dtype(self.dtype_name)
        with obs.span("plan.rebuild", key=self.key,
                      engine=self.tune.engine,
                      sharded=self.topology is not None):
            rmat = (mat if self.perm is None
                    else _permute(mat, self.perm, scheme=self.scheme)[0])
            t0 = time.perf_counter()
            if self.topology is not None:
                from . import distributed

                layout = distributed.build_sharded_layout(
                    rmat, self.topology, self.panel_starts,
                    engine=self.tune.engine,
                    block_shape=self.tune.block_shape,
                    schedule=self.comm.get("schedule", "all_gather"),
                    halo=int(self.comm.get("halo", 0)))
                info = {"cache_hit": False, "key": self.key,
                        "tune_ms": 0.0,
                        "build_ms": (time.perf_counter() - t0) * 1e3,
                        "load_ms": 0.0, "engine": self.tune.engine,
                        "plan": self.tune.to_json(), "value_swap": True,
                        "comm": dict(self.comm),
                        "partitioner": self.partitioner}
                return distributed.ShardedOperator(
                    layout, self.perm, plan=self, build_info=info,
                    devices=devices)
            inner = tune_mod.build_from_plan(
                rmat, self.tune, dtype=dt,
                use_kernel=(self.use_kernel if use_kernel is None
                            else use_kernel),
                nnz_bucket=self.nnz_bucket)
            info = {"cache_hit": False, "key": self.key, "tune_ms": 0.0,
                    "build_ms": (time.perf_counter() - t0) * 1e3,
                    "load_ms": 0.0, "engine": self.tune.engine,
                    "plan": self.tune.to_json(), "value_swap": True}
        return Operator(inner, self.perm, self, build_info=info)

    def apply_delta(self, delta, *, max_churn: Optional[float] = None,
                    max_bw_growth: Optional[float] = None) -> "Plan":
        """A NEW Plan for this plan's matrix edited by a StructureDelta
        (core/spmv/delta.py), reusing the frozen tuning decision and
        permutation — the amortization tier between `rebuild` (values
        only) and a full replan (new search).

        An empty delta returns this plan unchanged (no counters move).
        A small delta (nnz churn <= max_churn AND bandwidth growth <=
        max_bw_growth, defaults delta.MAX_CHURN / delta.MAX_BW_GROWTH)
        returns the edited plan under a `plan.delta` span, counting
        `delta.applies`; appended rows extend the permutation with
        identity tail positions. Past either threshold the frozen
        decision is stale: DeltaTooLarge is raised (counting
        `delta.fallbacks`) and the caller replans. Sharded plans accept
        same-shape deltas only (panel split indexes a fixed row count)
        and reuse partitioner + panel_starts + schedule, so build() after
        apply_delta repacks arrays without any new search."""
        from . import delta as delta_mod

        kw = {}
        if max_churn is not None:
            kw["max_churn"] = max_churn
        if max_bw_growth is not None:
            kw["max_bw_growth"] = max_bw_growth
        return delta_mod.apply_delta(self, delta, **kw)

    def _build_sharded(self, dt, info: dict, use_store: bool, devices):
        """Topology-aware build: restore the ShardedOperator's layout
        arrays from the plan store when possible, otherwise chop the
        reordered matrix into per-device arrays and persist the entry."""
        from . import distributed

        info["comm"] = dict(self.comm)
        info["partitioner"] = self.partitioner
        if use_store:
            t0 = time.perf_counter()
            if self._op_state is None and self.cache_hit:
                stored = Plan.load(self.key, mat=self._mat)
                if stored is not None and stored._op_state is not None:
                    self._op_state = stored._op_state
            if self._op_state is not None:
                op_rec, arrays = self._op_state
                if op_rec.get("cls") == "ShardedOperator":
                    try:
                        op = distributed.ShardedOperator.from_state(
                            op_rec["meta"], arrays, perm=self.perm,
                            plan=self, build_info=info, devices=devices)
                        info["load_ms"] = (time.perf_counter() - t0) * 1e3
                        info["cache_hit"] = True
                        return op
                    except Exception:
                        pass            # unreadable payload -> rebuild
        t0 = time.perf_counter()
        layout = distributed.build_sharded_layout(
            self.reordered_matrix(), self.topology, self.panel_starts,
            engine=self.tune.engine, block_shape=self.tune.block_shape,
            schedule=self.comm.get("schedule", "all_gather"),
            halo=int(self.comm.get("halo", 0)))
        op = distributed.ShardedOperator(layout, self.perm, plan=self,
                                         build_info=info, devices=devices)
        info["build_ms"] = (time.perf_counter() - t0) * 1e3
        if use_store:
            self.save(op=op)
        return op


def _permute(mat: CSRMatrix, perm, **attrs) -> tuple:
    """(mat.permute(perm), its milliseconds), under a `plan.permute` span."""
    with obs.timed("plan.permute", shape=str(tuple(mat.shape)),
                   nnz=int(mat.nnz), **attrs) as sp:
        rmat = mat.permute(perm)
    return rmat, sp.ms


def _reorder_scheme(mat: CSRMatrix, scheme: str, seed: int,
                    cache: bool) -> tuple:
    """(perm, reordered matrix, ms): the scheme's `plan.reorder` span and
    its `plan.permute` span, the milliseconds being theirs. `baseline` is
    the identity and runs neither."""
    from ..reorder import api as reorder_api

    if scheme == "baseline":
        return None, mat, 0.0
    perm, ms = reorder_api.reorder_timed(mat, scheme, seed, cache=cache)
    if perm is None:
        return None, mat, ms
    rmat, pms = _permute(mat, perm, scheme=scheme)
    return perm, rmat, ms + pms


def _operator_registry() -> dict:
    """Operator classes speaking the state()/from_state() protocol
    (opcache.py's set). Imported lazily: kernels pull in pallas."""
    from ...kernels.bcsr_spmv.ops import BcsrOperator
    from ...kernels.bell_spmv.ops import BellOperator
    from ...kernels.sell_spmv.ops import SellOperator
    from .ops import DeviceCSR, DeviceDense, DeviceDIA, DeviceELL

    return {c.__name__: c for c in
            (DeviceCSR, DeviceELL, DeviceDIA, DeviceDense, SellOperator,
             BellOperator, BcsrOperator)}


def _auto_schemes(hints: dict) -> list:
    names = hints.get("schemes")
    if names is None:
        names = [s.name for s in registry.SCHEME_REGISTRY.values()
                 if s.auto_candidate]
    return list(names)


def _partition_candidates(partition) -> list:
    """Resolve the partition request to a candidate-name list."""
    if partition == "auto":
        names = partition_mod.auto_partitioners()
        if not names:
            raise ValueError("no registered partitioner is auto_candidate")
        return names
    if isinstance(partition, str):
        return [partition]
    return list(partition)


def plan(problem: SpmvProblem, reorder: str = "auto", engine: str = "auto",
         probe=False, cache: bool = True, topology=None,
         partition="auto") -> Plan:
    """See _plan_decide — this wrapper only adds the root "plan" span
    (scheme/engine decision, store consultation, probe runs all nest
    under it)."""
    with obs.span("plan", shape=str(tuple(problem.mat.shape)),
                  nnz=int(problem.mat.nnz), reorder=reorder,
                  engine=engine, probe=str(probe), k=int(problem.k)) as sp:
        pl = _plan_decide(problem, reorder, engine, probe, cache,
                          topology, partition)
        sp.set(scheme=pl.scheme, engine_chosen=pl.tune.engine,
               cache_hit=bool(pl.cache_hit), key=pl.key)
        return pl


def _plan_decide(problem: SpmvProblem, reorder: str = "auto",
                 engine: str = "auto", probe=False,
                 cache: bool = True, topology=None,
                 partition="auto") -> Plan:
    """Stage 1+2 of the pipeline: decide (scheme, engine, shape) — and,
    given a topology, the row partition — for the problem and return the
    serializable Plan.

    reorder   — a registered scheme name, or "auto" to jointly search the
              auto-candidate schemes (hints["schemes"] overrides the set):
              each candidate is permuted, its structural features recomputed,
              and every engine candidate re-scored on them, so the winner is
              the (scheme, engine, shape) argmin of modelled bytes at the
              problem's k.
    engine    — a registered engine name, or "auto" for the OSKI-style
              tuner. Sharded plans execute per-device "bell" or "csr"
              panels; "auto" picks between them.
    probe     — one of tune.PROBE_MODES: False (model only), True (time
              the model's top candidates), "exhaustive" (time every
              candidate), "learned" (time the corpus TuneAdvisor's
              nearest-neighbor shortlist mined from prior ResultStore
              campaigns; the plan carries `advisor_confidence`).
              Auto-scheme selection stays model-based; the winning
              scheme is re-tuned with the requested probe mode. Sharded
              plans are model-based only.
    cache     — consult/populate the persistent plan store.
    topology  — a Topology (core/spmv/topology.py); devices=1/None plans
              single-device. Non-trivial topologies extend the joint
              search to (partition x scheme x engine x shape x k) with
              the communication-volume cost model: per candidate the
              modelled wall cost is max-device compute bytes (engine cost
              x load imbalance / devices) + collective bytes (all-gather
              vs halo exchange vs 2-D reduce — topology.comm_model).
    partition — a registered partitioner name (incl. the parameterized
              chunked_cyclic_c<chunk> form), a list of names, or "auto"
              to search the auto-candidate partitioners.
    """
    from . import ops  # noqa: F401 — ensure built-in engines are registered
    from ..reorder import api  # noqa: F401 — and built-in schemes

    if probe not in tune_mod.PROBE_MODES:
        raise ValueError(
            f"probe must be one of {tune_mod.PROBE_MODES}, got {probe!r}")
    t_start = time.perf_counter()
    mat = problem.mat
    hints = problem.hints
    seed = int(hints.get("seed", 0))
    use_kernel = hints.get("use_kernel", "auto")
    nnz_bucket = int(hints.get("nnz_bucket", 0))
    block_shape = _block_shape(hints, engine)
    sell_sigma = hints.get("sell_sigma")
    k = max(int(problem.k), 1)
    topo = topology_mod.normalize(topology)

    # validate names up front (KeyError with the known set)
    if engine != "auto":
        registry.get_engine(engine)
    schemes = _auto_schemes(hints) if reorder == "auto" else [reorder]
    if not schemes:
        raise ValueError("no candidate schemes: hints['schemes'] is empty "
                         "and no registered scheme is auto_candidate")
    for s in schemes:
        registry.get_scheme(s)
    partitioners = None
    if topo is not None:
        if mat.m != mat.n:
            raise ValueError(f"sharded plans need a square matrix "
                             f"(conformal x partition), got {mat.shape}")
        if engine not in ("auto", "bell", "csr"):
            raise ValueError(f"sharded plans execute 'bell' or 'csr' "
                             f"panel engines (or 'auto'), got {engine!r}")
        partitioners = _partition_candidates(partition)
        for name in partitioners:
            partition_mod.resolve_partitioner(name)

    key = plan_key(problem, reorder, engine, probe, seed,
                   schemes=schemes if reorder == "auto" else None,
                   topology=topo, partition=str(partition),
                   partitioners=partitioners)
    if cache and store_enabled():
        hit = Plan.load(key, mat=mat)
        if hit is not None:
            hit._mat = mat
            # use_kernel is a runtime execution choice, not plan identity:
            # the requesting process's preference wins (an entry stored by
            # an interpret-mode CI run must not pin later runs to it)
            hit.use_kernel = use_kernel
            return hit

    if topo is not None:
        return _plan_sharded(problem, reorder, engine, cache, topo,
                             partitioners, schemes, key, seed, use_kernel,
                             nnz_bucket, block_shape, t_start)

    dtype_name = problem.dtype_name()
    reorder_ms = tune_ms = 0.0
    best = None                       # (cost, scheme, perm, rmat, tuneplan)
    scheme_costs: dict = {}
    for s in schemes:
        perm, rmat, ms = _reorder_scheme(mat, s, seed, cache)
        reorder_ms += ms
        if engine == "auto":
            # single explicit scheme: probe directly (the legacy tune path);
            # multi-scheme search stays model-based until a winner exists
            tp = tune_mod.tune(rmat,
                               probe=(probe if len(schemes) == 1 else False),
                               use_kernel=use_kernel, k=k)
            cost = tp.cost_bytes
            tune_ms += tp.tune_ms
        else:
            with obs.timed("plan.tune", shape=str(tuple(rmat.shape)),
                           nnz=int(rmat.nnz), probe="False", k=int(k),
                           engine=engine, source="fixed") as tsp:
                feat = tune_mod.matrix_features(rmat)
                sp = None
                if engine == "sell":
                    from ..sparse.sell import sell_padded_nnz

                    c, w = block_shape
                    sg = 8 * c if sell_sigma is None else sell_sigma
                    sp = sell_padded_nnz(rmat, c, sg, w)
                cost = tune_mod.candidate_cost(feat, engine, block_shape,
                                               sell_sigma, sp, k=k)
                tp = tune_mod.fixed_plan(engine, block_shape, sell_sigma,
                                         k=k)
            tune_ms += tsp.ms
        scheme_costs[s] = float(cost)
        if best is None or cost < best[0]:
            best = (cost, s, perm, rmat, tp)
    _, scheme, perm, rmat, tp = best
    if probe and engine == "auto" and tp.source not in ("probe", "learned"):
        # model picked the scheme; OSKI's empirical search refines the
        # engine choice on the winner only (probing every scheme would
        # time the planner, not the SpMV) — in the caller's probe mode,
        # so "learned"/"exhaustive" reach the winner's tune too
        tp = tune_mod.tune(rmat, probe=probe, use_kernel=use_kernel, k=k)
        tune_ms += tp.tune_ms

    pl = Plan(scheme=scheme, seed=seed, engine_request=engine, tune=tp,
              k=k, dtype_name=dtype_name, probe=probe, use_kernel=use_kernel,
              nnz_bucket=nnz_bucket, mat_shape=tuple(mat.shape),
              mat_nnz=mat.nnz, key=key, scheme_costs=scheme_costs,
              reorder_ms=reorder_ms, tune_ms=tune_ms,
              plan_ms=(time.perf_counter() - t_start) * 1e3,
              advisor_confidence=float(
                  (tp.advisor or {}).get("confidence", 0.0)),
              perm=None if perm is None else np.asarray(perm, np.int64),
              _mat=mat, _rmat=rmat)
    if cache and store_enabled():
        pl.save()
    return pl


def _plan_sharded(problem: SpmvProblem, reorder: str, engine: str,
                  cache: bool, topo: Topology, partitioners: list,
                  schemes: list, key: str, seed: int, use_kernel: str,
                  nnz_bucket: int, block_shape: tuple,
                  t_start: float) -> Plan:
    """The topology-aware joint search: (partition x scheme x engine) argmin
    of modelled wall bytes = max-device compute (engine cost x load
    imbalance / devices) + collective bytes (topology.comm_model). The
    winner's composed permutation (scheme ∘ partitioner grouping) and
    panel split ride on the Plan, so build() needs no re-decision."""
    mat = problem.mat
    k = max(int(problem.k), 1)
    dtype_name = problem.dtype_name()
    dsize = int(np.dtype(dtype_name).itemsize)
    engines = ("bell", "csr") if engine == "auto" else (engine,)
    reorder_ms = tune_ms = 0.0
    best = None        # (cost, scheme, perm, rmat2, starts, pname, eng, comm)
    scheme_costs: dict = {}
    partition_costs: dict = {}
    for s in schemes:
        perm, rmat, ms = _reorder_scheme(mat, s, seed, cache)
        reorder_ms += ms
        best_s = None
        feat_rmat = None     # non-reordering partitioners all score the
        # scheme's own rmat: one feature scan serves them all
        for pname in partitioners:
            cname, pfn = partition_mod.resolve_partitioner(pname)
            with obs.timed("plan.partition", partitioner=cname,
                           scheme=s) as psp:
                perm2, starts = pfn(rmat, topo.row_devices, seed)
            reorder_ms += psp.ms
            rmat2 = rmat
            if perm2 is None:
                perm_total = perm
            else:
                rmat2, ms = _permute(rmat, perm2, scheme=s, partitioner=cname)
                reorder_ms += ms
                perm_total = (np.asarray(perm2, np.int64) if perm is None
                              else np.asarray(perm, np.int64)[perm2])
            with obs.timed("plan.tune", shape=str(tuple(rmat2.shape)),
                           nnz=int(rmat2.nnz), k=int(k), partitioner=cname,
                           sharded=True) as tsp:
                if rmat2 is rmat:
                    if feat_rmat is None:
                        feat_rmat = tune_mod.matrix_features(rmat)
                    feat = feat_rmat
                else:
                    feat = tune_mod.matrix_features(rmat2)
                comm = topology_mod.comm_model(rmat2, starts, topo, dsize, k,
                                               block_shape)
                for eng in engines:
                    compute = tune_mod.candidate_cost(feat, eng, block_shape,
                                                      None, None, k=k)
                    cost = (compute * comm["li"] / topo.devices
                            + comm["bytes_per_spmv"])
                    partition_costs[f"{s}+{cname}+{eng}"] = float(cost)
                    if best is None or cost < best[0]:
                        best = (cost, s, perm_total, rmat2, starts, cname, eng,
                                float(compute), comm)
                    if best_s is None or cost < best_s:
                        best_s = float(cost)
            tune_ms += tsp.ms
        scheme_costs[s] = best_s
    _, scheme, perm_total, rmat2, starts, pname, eng, compute, comm = best
    tp = TunePlan(engine=eng, block_shape=tuple(block_shape),
                  sell_sigma=None, cost_bytes=compute, costs={},
                  features={}, source="model", k=k)
    pl = Plan(scheme=scheme, seed=seed, engine_request=engine, tune=tp,
              k=k, dtype_name=dtype_name, probe=False,
              use_kernel=use_kernel, nnz_bucket=nnz_bucket,
              mat_shape=tuple(mat.shape), mat_nnz=mat.nnz, key=key,
              scheme_costs=scheme_costs, reorder_ms=reorder_ms,
              tune_ms=tune_ms,
              plan_ms=(time.perf_counter() - t_start) * 1e3,
              topology=topo, partitioner=pname,
              panel_starts=np.asarray(starts, np.int64), comm=comm,
              partition_costs=partition_costs,
              perm=(None if perm_total is None
                    else np.asarray(perm_total, np.int64)),
              _mat=mat, _rmat=rmat2)
    if cache and store_enabled():
        pl.save()
    return pl
