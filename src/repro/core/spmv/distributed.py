"""Sharded SpMV — the distributed execution layer of the pipeline facade.

Since PR 5 this module is the BUILD side of topology-aware plans
(DESIGN.md "Topology-aware planning"): `repro.api.plan(problem,
topology=Topology(...))` decides (partition x scheme x engine x shape x k)
with the communication-volume cost model (core/spmv/topology.py), and
`Plan.build()` calls `build_sharded_layout` + `ShardedOperator` here.

Layouts (both operate on uniform padded row panels so every device runs
the same program):

* 1d_rows   — row panels over a flat mesh; x row-sharded and either
              ALL-GATHERED each SpMV (the CG dataflow) or assembled by two
              nearest-neighbour ring permutes when the plan's reordering
              made the halo legal (the paper's data-movement story as a
              collective-schedule choice).
* 2d_panels — rows over "data", columns over "model"; each device holds
              an (m/D x n/M) brick and only its x segment; partial y is
              all-reduced over "model".

Per-device engines: "bell" (Block-ELL bricks — the MXU format) and "csr"
(padded gather + segment-sum — the paper's Listing 4 semantics), chosen
by the planner like any other engine axis.

`ShardedOperator` accepts ORIGINAL-index-space vectors (it carries the
plan's composed permutation AND the panel-padding map), supports
`matmul(X[n, k])` and CG, round-trips through the content-addressed plan
store, and runs on a real device mesh when the process has enough devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8 in tests/CI) or on a
bit-equivalent single-device simulation otherwise (`op.simulated`, which
logs a warning and counts `sharded.simulated` when it is not forced). The
mesh takes the first devices of the process unless the build is given its
own (`Plan.build(devices=)`; the router gives each mesh a slice); the
panels are placed on the mesh once, with a NamedSharding.

The pre-PR-5 entry points (plan_1d / spmv_1d / plan_2d / spmv_2d /
plan_halo_1d / spmv_halo_1d) remain as DeprecationWarning shims over the
legacy internals with no in-src callers; see the README migration table.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import warnings
from typing import Optional, Tuple

import numpy as np

from ... import compile_cache, obs
from ..sparse.bell import to_block_ell
from ..sparse.csr import CSRMatrix
from ..sparse.partition import (nnz_balanced_partition, partition_to_owner,
                                static_partition)
from .topology import Topology, padded_panel_rows

_log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Sharded layout: host-side arrays for one (matrix, topology, partition)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedLayout:
    """Everything `ShardedOperator` needs to execute, all host numpy:
    per-device engine arrays, the panel split, the padding index maps and
    the collective schedule. Built once per plan; round-trips through the
    plan store via ShardedOperator.state()/from_state()."""

    engine: str                  # "bell" | "csr"
    arrays: dict                 # engine arrays (leading axes = mesh axes)
    panel_starts: np.ndarray     # [P+1] row offsets in the reordered space
    padmap: np.ndarray           # [m] padded slot of reordered row r
    pad_idx: np.ndarray          # [n_pad] reordered row per slot (m = pad)
    shape: tuple                 # original (m, n), square
    topology: Topology
    schedule: str                # "all_gather" | "halo" | "psum"
    halo: int
    h_pad: int
    n_pad: int
    seg_n: int                   # 2d x-segment width (0 for 1d)
    block_shape: tuple


def _index_maps(starts: np.ndarray, m: int, h_pad: int):
    """padmap[r] = padded slot of reordered row r; pad_idx[slot] = r (or m
    for a padding slot, which gathers the appended zero)."""
    starts = np.asarray(starts, dtype=np.int64)
    p = starts.size - 1
    owner = partition_to_owner(starts, m).astype(np.int64)
    padmap = owner * h_pad + (np.arange(m, dtype=np.int64) - starts[owner])
    pad_idx = np.full(p * h_pad, m, dtype=np.int64)
    pad_idx[padmap] = np.arange(m, dtype=np.int64)
    return padmap, pad_idx


def _pack_bell_panels(subs: list, bm: int, bn: int):
    """Uniform Block-ELL arrays over a list of equal-shape CSR panels
    (shared K = max block count — the legacy Plan1D packing, factored)."""
    bells = [to_block_ell(sub, bm, bn) for sub in subs]
    kmax = max(b.k for b in bells)
    nbr = bells[0].num_block_rows
    blocks = np.zeros((len(subs), nbr, kmax, bm, bn),
                      dtype=subs[0].vals.dtype)
    cols = np.zeros((len(subs), nbr, kmax), dtype=np.int32)
    for i, b in enumerate(bells):
        blocks[i, :b.num_block_rows, :b.k] = b.blocks
        cols[i, :b.num_block_rows, :b.k] = b.block_cols
    return blocks, cols


def _pack_csr_panels(entries: list, h_pad: int):
    """Uniform padded COO-CSR arrays over per-device (rows, cols, vals)
    triples: nnz padded to the max with (row=h_pad-1, col=0, val=0) —
    sorted row_ids preserved, contribution exactly zero."""
    nnz_pad = max(max((r.size for r, _, _ in entries), default=0), 1)
    n_dev = len(entries)
    row_ids = np.full((n_dev, nnz_pad), h_pad - 1, dtype=np.int32)
    cols = np.zeros((n_dev, nnz_pad), dtype=np.int32)
    vals = np.zeros((n_dev, nnz_pad),
                    dtype=entries[0][2].dtype if entries else np.float64)
    for i, (r, c, v) in enumerate(entries):
        row_ids[i, :r.size] = r
        cols[i, :c.size] = c
        vals[i, :v.size] = v
    return row_ids, cols, vals


def build_sharded_layout(rmat: CSRMatrix, topology: Topology,
                         panel_starts: np.ndarray, engine: str = "bell",
                         block_shape: tuple = (8, 128),
                         schedule: str = "all_gather",
                         halo: int = 0) -> ShardedLayout:
    """Chop the (already reordered) matrix into per-device arrays for the
    topology's layout. Columns are remapped through the same panel-padding
    map as rows (conformal x partition), so the device program never sees
    the ragged panel heights."""
    m, n = rmat.shape
    if m != n:
        raise ValueError(f"sharded plans need a square matrix (conformal "
                         f"x partition), got {rmat.shape}")
    if engine not in ("bell", "csr"):
        raise ValueError(f"sharded engines are 'bell'/'csr', got {engine!r}")
    bm, bn = block_shape
    starts = np.asarray(panel_starts, dtype=np.int64)
    d, mm = topology.row_devices, topology.col_devices
    if starts.size != d + 1:
        raise ValueError(f"panel_starts has {starts.size - 1} panels for "
                         f"{d} row devices")
    h_pad = padded_panel_rows(starts, bm, bn, col_devices=mm)
    n_pad = d * h_pad
    padmap, pad_idx = _index_maps(starts, m, h_pad)
    rp = rmat.rowptr.astype(np.int64)
    rows_p = padmap[np.repeat(np.arange(m, dtype=np.int64), np.diff(rp))]
    cols_p = padmap[rmat.cols.astype(np.int64)]
    vals = rmat.vals
    seg_n = 0

    if topology.layout == "1d_rows":
        if schedule == "halo":
            halo = int(halo)
            if halo % bn or halo > h_pad:
                raise ValueError(f"halo {halo} must be a multiple of "
                                 f"bn={bn} and <= h_pad={h_pad}")
            width = h_pad + 2 * halo
        else:
            schedule, halo, width = "all_gather", 0, n_pad
        panel = rows_p // h_pad
        subs, csr_entries = [], []
        for p in range(d):
            sel = panel == p
            lrows = rows_p[sel] - p * h_pad
            lcols = cols_p[sel] - (p * h_pad - halo if schedule == "halo"
                                   else 0)
            if schedule == "halo" and sel.any():
                if lcols.min() < 0 or lcols.max() >= width:
                    raise ValueError(
                        "halo window violated after padding; the plan's "
                        "comm model and the layout builder disagree")
            if engine == "bell":
                subs.append(CSRMatrix.from_coo(lrows, lcols, vals[sel],
                                               (h_pad, width)))
            else:
                csr_entries.append((lrows, lcols, vals[sel]))
        if engine == "bell":
            blocks, bcols = _pack_bell_panels(subs, bm, bn)
            arrays = {"blocks": blocks, "block_cols": bcols}
        else:
            row_ids, ccols, cvals = _pack_csr_panels(csr_entries, h_pad)
            arrays = {"row_ids": row_ids, "cols": ccols, "vals": cvals}
    else:                                    # 2d_panels
        schedule, halo = "psum", 0
        seg_n = n_pad // mm
        panel = rows_p // h_pad
        seg = cols_p // seg_n
        subs, csr_entries = [], []
        for p in range(d):
            for q in range(mm):
                sel = (panel == p) & (seg == q)
                lrows = rows_p[sel] - p * h_pad
                lcols = cols_p[sel] - q * seg_n
                if engine == "bell":
                    subs.append(CSRMatrix.from_coo(lrows, lcols, vals[sel],
                                                   (h_pad, seg_n)))
                else:
                    csr_entries.append((lrows, lcols, vals[sel]))
        if engine == "bell":
            blocks, bcols = _pack_bell_panels(subs, bm, bn)
            arrays = {"blocks": blocks.reshape((d, mm) + blocks.shape[1:]),
                      "block_cols": bcols.reshape((d, mm) + bcols.shape[1:])}
        else:
            row_ids, ccols, cvals = _pack_csr_panels(csr_entries, h_pad)
            arrays = {"row_ids": row_ids.reshape(d, mm, -1),
                      "cols": ccols.reshape(d, mm, -1),
                      "vals": cvals.reshape(d, mm, -1)}

    return ShardedLayout(engine=engine, arrays=arrays, panel_starts=starts,
                         padmap=padmap, pad_idx=pad_idx, shape=(m, n),
                         topology=topology, schedule=schedule, halo=halo,
                         h_pad=h_pad, n_pad=n_pad, seg_n=seg_n,
                         block_shape=(bm, bn))


# ---------------------------------------------------------------------------
# Device-side local kernels (shared by the shard_map bodies AND the
# single-device simulation, so both execute the same math)
# ---------------------------------------------------------------------------
def _bell_local(blocks, bcols, xw, bn):
    """One device's Block-ELL panel SpMM: xw [win, nv] -> y [h_pad, nv].
    Accumulates at promote(x.dtype, f32) so fp64 plans keep fp64."""
    import jax
    import jax.numpy as jnp

    x2d = xw.reshape(-1, bn, xw.shape[-1])
    gathered = x2d[bcols]                            # [nbr, K, bn, nv]
    acc = jnp.promote_types(xw.dtype, jnp.float32)
    y = jnp.einsum("rkij,rkjv->riv", blocks, gathered,
                   preferred_element_type=acc,
                   precision=jax.lax.Precision.HIGHEST).astype(xw.dtype)
    return y.reshape(-1, xw.shape[-1])


def _csr_local(row_ids, cols, vals, xw, h_pad):
    """One device's padded-COO panel SpMM: xw [win, nv] -> y [h_pad, nv]."""
    import jax

    prod = vals[:, None] * xw[cols]                  # [nnz_pad, nv]
    return jax.ops.segment_sum(prod, row_ids, num_segments=h_pad,
                               indices_are_sorted=True)


def _local_y(engine, arrs: tuple, xw, h_pad: int, bn: int):
    if engine == "bell":
        return _bell_local(arrs[0], arrs[1], xw, bn)
    return _csr_local(arrs[0], arrs[1], arrs[2], xw, h_pad)


_ARRAY_ORDER = {"bell": ("blocks", "block_cols"),
                "csr": ("row_ids", "cols", "vals")}
# the ShardedOperator's gather (x in) and scatter (y out) index maps
_INDEX_MAPS = ("_in_idx", "_in_idx_r", "_out_idx", "_out_idx_r")


# ---------------------------------------------------------------------------
# ShardedOperator
# ---------------------------------------------------------------------------
class _ReorderedView:
    """`unwrap()` counterpart of Operator.unwrap(): the same sharded
    execution, reordered index space in and out (what harnesses time)."""

    def __init__(self, op: "ShardedOperator"):
        self._op = op

    def __call__(self, x):
        return self._op(x, permuted=True)

    def matmul(self, x):
        return self._op.matmul(x, permuted=True)

    @property
    def shape(self):
        return self._op.shape


@functools.cache
def _register_static() -> None:
    # a static pytree, so that `cg_solve` takes it as its operator; here,
    # not at import: this module does not import JAX
    import jax

    jax.tree_util.register_static(ShardedOperator)


class ShardedOperator:
    """Permutation- and topology-carrying distributed SpMV/SpMM operator.

    `op(x)` / `op.matmul(X)` take ORIGINAL-index-space vectors: x is
    gathered through the composed (scheme ∘ partitioner) permutation and
    the panel-padding map in ONE fused gather, the sharded step runs, and
    y scatters back the same way. `permuted=True` opts out of the
    permutation (x already in the reordered space; padding still applies).

    Execution: a shard_map over the topology's mesh when the process has
    enough devices, otherwise a single-device simulation (`op.simulated`)
    that runs the identical local kernels over a vmapped panel axis —
    same math, no mesh — so sharded plans stay usable (service, CG,
    verification) in single-device processes. An unforced simulation is
    logged and counted (`sharded.simulated`), never silent.
    """

    def __init__(self, layout: ShardedLayout, perm: Optional[np.ndarray],
                 plan=None, build_info: Optional[dict] = None,
                 devices=None):
        import jax.numpy as jnp

        _register_static()
        if devices is not None and len(devices) != layout.topology.devices:
            raise ValueError(f"{len(devices)} devices for a "
                             f"{layout.topology.devices}-device mesh")

        self.layout = layout
        self.plan = plan
        self.build_info = build_info or {}
        m = layout.shape[0]
        if perm is not None and np.array_equal(perm, np.arange(perm.size)):
            perm = None
        self._perm_np = None if perm is None else np.asarray(perm, np.int64)
        pad_idx = layout.pad_idx
        if perm is None:
            in_idx = pad_idx
            out_idx = layout.padmap
        else:
            perm_ext = np.append(np.asarray(perm, np.int64), m)
            in_idx = perm_ext[pad_idx]          # pad slots gather x_ext[m]=0
            iperm = np.empty(m, dtype=np.int64)
            iperm[perm] = np.arange(m, dtype=np.int64)
            out_idx = layout.padmap[iperm]
        self._in_idx = jnp.asarray(in_idx, jnp.int32)
        self._in_idx_r = jnp.asarray(pad_idx, jnp.int32)
        self._out_idx = jnp.asarray(out_idx, jnp.int32)
        self._out_idx_r = jnp.asarray(layout.padmap, jnp.int32)
        self._dev = None                        # engine arrays, lazy
        self._dtype = None
        self._fns = {}                          # nv -> jitted step
        self._devices = None if devices is None else tuple(devices)
        self.force_simulated = False            # testing/debug override

    # -- facade surface ----------------------------------------------------
    @property
    def shape(self) -> tuple:
        return tuple(self.layout.shape)

    @property
    def topology(self) -> Topology:
        return self.layout.topology

    @property
    def perm(self) -> Optional[np.ndarray]:
        return self._perm_np

    @property
    def iperm(self) -> Optional[np.ndarray]:
        if self._perm_np is None:
            return None
        iperm = np.empty_like(self._perm_np)
        iperm[self._perm_np] = np.arange(self._perm_np.size)
        return iperm

    @property
    def panel_starts(self) -> np.ndarray:
        return self.layout.panel_starts

    @property
    def simulated(self) -> bool:
        import jax

        return self.force_simulated or (
            self._devices is None
            and len(jax.devices()) < self.layout.topology.devices)

    @property
    def devices(self) -> list:
        """The devices of this operator's mesh (empty when simulated)."""
        import jax

        if self.simulated:
            return []
        if self._devices is not None:
            return list(self._devices)
        return list(jax.devices()[:self.layout.topology.devices])

    def unwrap(self) -> _ReorderedView:
        return _ReorderedView(self)

    # -- execution ---------------------------------------------------------
    def _device_arrays(self, dtype):
        """Engine arrays on the device(s): each device's panels placed
        once on the mesh (leading axes = mesh axes), or on the default
        device when simulated. On a mesh the gather/scatter index maps
        move there too, one copy per device, so a mesh that does not hold
        the default device never reads from it."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if self._dev is None or self._dtype != dtype:
            lay = self.layout
            sharding = (None if self.simulated else
                        NamedSharding(self._mesh(), P(*lay.topology.mesh_axes)))
            if sharding is not None:
                rep = NamedSharding(sharding.mesh, P())
                for name in _INDEX_MAPS:
                    setattr(self, name,
                            jax.device_put(getattr(self, name), rep))
            dev = []
            for name in _ARRAY_ORDER[lay.engine]:
                a = lay.arrays[name]
                a = np.asarray(a, dtype if np.issubdtype(a.dtype, np.floating)
                               else None)
                dev.append(jnp.asarray(a) if sharding is None
                           else jax.device_put(a, sharding))
            self._dev = tuple(dev)
            self._dtype = dtype
        return self._dev

    def _mesh(self):
        from jax.sharding import Mesh

        topo = self.layout.topology
        devs = np.array(self.devices, dtype=object)
        return Mesh(devs.reshape(topo.mesh_shape), topo.mesh_axes)

    def _make_fn(self, nv: int):
        """Jitted padded-space step xp [n_pad, nv] -> yp [n_pad? d*h_pad,
        nv] for this batch width (mesh or simulated)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        lay = self.layout
        topo = lay.topology
        d, mm = topo.row_devices, topo.col_devices
        h_pad, halo, bn = lay.h_pad, lay.halo, lay.block_shape[1]
        engine, n_pad, seg_n = lay.engine, lay.n_pad, lay.seg_n
        n_arr = len(_ARRAY_ORDER[engine])

        if not self.simulated:
            mesh = self._mesh()
            ax, = topo.mesh_axes[:1]
            if topo.layout == "1d_rows":
                def body(*ops):
                    arrs, xs = ops[:-1], ops[-1][0]         # xs [h_pad, nv]
                    if lay.schedule == "halo" and halo:
                        fwd = [(i, (i + 1) % d) for i in range(d)]
                        bwd = [((i + 1) % d, i) for i in range(d)]
                        lh = jax.lax.ppermute(xs[-halo:], ax, fwd)
                        rh = jax.lax.ppermute(xs[:halo], ax, bwd)
                        xw = jnp.concatenate([lh, xs, rh])
                    elif lay.schedule == "halo":
                        xw = xs
                    else:
                        xw = jax.lax.all_gather(xs, ax, tiled=True)
                    y = _local_y(engine, tuple(a[0] for a in arrs), xw,
                                 h_pad, bn)
                    return y[None]

                f = jax.shard_map(body, mesh=mesh,
                                  in_specs=(P(ax),) * n_arr + (P(ax),),
                                  out_specs=P(ax))

                def step(arrs, xp):
                    yp = f(*arrs, xp.reshape(d, h_pad, nv))
                    return yp.reshape(n_pad, nv)
            else:
                rax, cax = topo.mesh_axes

                def body(*ops):
                    arrs, xs = ops[:-1], ops[-1][0]         # xs [seg_n, nv]
                    y = _local_y(engine, tuple(a[0, 0] for a in arrs), xs,
                                 h_pad, bn)
                    return jax.lax.psum(y, cax)[None]

                f = jax.shard_map(body, mesh=mesh,
                                  in_specs=(P(rax, cax),) * n_arr + (P(cax),),
                                  out_specs=P(rax))

                def step(arrs, xp):
                    yp = f(*arrs, xp.reshape(mm, seg_n, nv))
                    return yp.reshape(n_pad, nv)
        else:
            if topo.layout == "1d_rows":
                if lay.schedule == "halo":
                    win = (np.arange(-halo, h_pad + halo)[None, :]
                           + np.arange(d)[:, None] * h_pad) % n_pad
                    win_idx = jnp.asarray(win, jnp.int32)

                    def step(arrs, xp):
                        xw = xp[win_idx]                   # [d, win, nv]
                        y = jax.vmap(
                            lambda *a: _local_y(engine, a[:-1], a[-1],
                                                h_pad, bn))(*arrs, xw)
                        return y.reshape(n_pad, nv)
                else:
                    def step(arrs, xp):
                        y = jax.vmap(
                            lambda *a: _local_y(engine, a, xp, h_pad, bn),
                        )(*arrs)
                        return y.reshape(n_pad, nv)
            else:
                def step(arrs, xp):
                    xw = xp.reshape(mm, seg_n, nv)
                    inner = jax.vmap(
                        lambda *a: _local_y(engine, a[:-1], a[-1],
                                            h_pad, bn))

                    def per_row(*a):
                        return inner(*a, xw).sum(axis=0)   # psum over model

                    y = jax.vmap(per_row)(*arrs)           # [d, h_pad, nv]
                    return y.reshape(n_pad, nv)

        return jax.jit(lambda arrs, xp: step(arrs, xp))

    def _exec(self, x, permuted: bool, batched: bool):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        lay = self.layout
        with obs.span("sharded.spmv", engine=lay.engine,
                      schedule=lay.schedule, devices=lay.topology.devices,
                      simulated=self.simulated), self._compile_scope():
            if self.simulated:
                x = jnp.asarray(x)
            else:
                # the gather runs where the panels live
                x = jax.device_put(x, NamedSharding(self._mesh(), P()))
            x2 = x if batched else x[:, None]
            nv = int(x2.shape[1])
            dtype = x2.dtype
            arrs = self._device_arrays(dtype)
            with obs.span("sharded.gather_x", schedule=lay.schedule):
                zero = jnp.zeros((1, nv), dtype)
                xe = jnp.concatenate([x2, zero], axis=0)
                xp = jnp.take(xe,
                              self._in_idx_r if permuted else self._in_idx,
                              axis=0)
            key = (nv, self.simulated)
            fn = self._fns.get(key)
            if fn is None:
                if self.simulated and not self.force_simulated:
                    obs.counter("sharded.simulated").inc()
                    _log.warning(
                        "%d devices for a %d-device topology: sharded "
                        "SpMV is simulated on one device", len(jax.devices()),
                        lay.topology.devices)
                fn = self._fns[key] = self._make_fn(nv)
            # one fused jit: per-device compute + the plan's collective
            # (all-gather / halo ring permutes / 2-D all-reduce)
            with obs.span("sharded.exec", schedule=lay.schedule,
                          halo=int(lay.halo)):
                yp = fn(arrs, xp)
            with obs.span("sharded.scatter_y", schedule=lay.schedule):
                y = jnp.take(yp,
                             self._out_idx_r if permuted else self._out_idx,
                             axis=0)
            return y if batched else y[:, 0]

    def _compile_scope(self):
        """A mesh off the process's first devices compiles its programs
        without persisting them (see repro.compile_cache)."""
        import jax

        devs = self._devices
        if devs is None or list(devs) == jax.devices()[:len(devs)]:
            return contextlib.nullcontext()
        return compile_cache.not_persisted()

    def __call__(self, x, permuted: bool = False):
        return self._exec(x, permuted, batched=getattr(x, "ndim", 1) == 2)

    def matmul(self, x, permuted: bool = False):
        """x: [n, k] -> y: [m, k], original index space unless permuted."""
        return self._exec(x, permuted,
                          batched=getattr(x, "ndim", 2) == 2)

    # -- plan-store protocol ----------------------------------------------
    def state(self):
        lay = self.layout
        meta = {"engine": lay.engine, "topology": lay.topology.to_json(),
                "schedule": lay.schedule, "halo": int(lay.halo),
                "h_pad": int(lay.h_pad), "n_pad": int(lay.n_pad),
                "seg_n": int(lay.seg_n), "shape": list(lay.shape),
                "block_shape": list(lay.block_shape)}
        arrays = dict(lay.arrays)
        arrays["panel_starts"] = np.asarray(lay.panel_starts, np.int64)
        return meta, arrays

    @classmethod
    def from_state(cls, meta, arrays, dtype=None, perm=None, plan=None,
                   build_info=None, devices=None):
        topo = Topology.from_json(meta["topology"])
        starts = np.asarray(arrays["panel_starts"], np.int64)
        m = int(meta["shape"][0])
        padmap, pad_idx = _index_maps(starts, m, int(meta["h_pad"]))
        eng_arrays = {k: np.asarray(v) for k, v in arrays.items()
                      if k != "panel_starts"}
        layout = ShardedLayout(
            engine=meta["engine"], arrays=eng_arrays, panel_starts=starts,
            padmap=padmap, pad_idx=pad_idx, shape=tuple(meta["shape"]),
            topology=topo, schedule=meta["schedule"],
            halo=int(meta["halo"]), h_pad=int(meta["h_pad"]),
            n_pad=int(meta["n_pad"]), seg_n=int(meta["seg_n"]),
            block_shape=tuple(meta["block_shape"]))
        return cls(layout, perm, plan=plan, build_info=build_info,
                   devices=devices)


# ---------------------------------------------------------------------------
# Legacy internals (pre-PR-5 layout builders) + deprecation shims
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Plan1D:
    """Global arrays for the legacy 1-D layout (leading axis = panels)."""

    blocks: np.ndarray       # [P, nbr_l, K, bm, bn]
    block_cols: np.ndarray   # [P, nbr_l, K]
    row_offset: np.ndarray   # [P] first row of each panel
    panel_rows: int          # uniform (padded) rows per panel
    shape: Tuple[int, int]
    block_shape: Tuple[int, int]


def _legacy_plan_1d(mat: CSRMatrix, num_devices: int, bm: int = 8,
                    bn: int = 128, balanced: bool = True) -> Plan1D:
    starts = (nnz_balanced_partition(mat, num_devices) if balanced
              else static_partition(mat, num_devices))
    heights = np.diff(starts)
    h = int(heights.max())
    h_pad = ((h + bm - 1) // bm) * bm
    rp = mat.rowptr.astype(np.int64)
    panels = []
    for p in range(num_devices):
        r0, r1 = int(starts[p]), int(starts[p + 1])
        s, e = rp[r0], rp[r1]
        panels.append(CSRMatrix(
            rowptr=(rp[r0:r1 + 1] - s).astype(np.int32),
            cols=mat.cols[s:e], vals=mat.vals[s:e],
            shape=(r1 - r0, mat.n)))
    bells = [to_block_ell(sub, bm, bn) for sub in panels]
    k = max(b.k for b in bells)
    nbr_l = h_pad // bm
    blocks = np.zeros((num_devices, nbr_l, k, bm, bn), dtype=mat.vals.dtype)
    cols = np.zeros((num_devices, nbr_l, k), dtype=np.int32)
    for p, b in enumerate(bells):
        blocks[p, :b.num_block_rows, :b.k] = b.blocks
        cols[p, :b.num_block_rows, :b.k] = b.block_cols
    return Plan1D(blocks=blocks, block_cols=cols,
                  row_offset=starts[:-1].astype(np.int64), panel_rows=h_pad,
                  shape=mat.shape, block_shape=(bm, bn))


def _legacy_spmv_1d(mesh, axis_names: Tuple[str, ...]):
    import jax
    from jax.sharding import PartitionSpec as P

    from . import ref

    ax = axis_names

    def local(blocks, block_cols, x_panels):
        xs = jax.lax.all_gather(x_panels[0], ax, tiled=True)
        bm, bn = blocks.shape[-2], blocks.shape[-1]
        y = ref.spmv_bell(blocks[0], block_cols[0], xs.reshape(-1, bn, 1))
        return y.reshape(1, -1)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(ax), P(ax), P(ax)), out_specs=P(ax))
    return jax.jit(f)


def _legacy_plan_2d(mat: CSRMatrix, d: int, m_axis: int, bm: int = 8,
                    bn: int = 128, balanced: bool = True):
    starts = (nnz_balanced_partition(mat, d) if balanced
              else static_partition(mat, d))
    seg_n = ((mat.n + m_axis - 1) // m_axis + bn - 1) // bn * bn
    heights = np.diff(starts)
    h_pad = ((int(heights.max()) + bm - 1) // bm) * bm
    nbr_l = h_pad // bm
    rp = mat.rowptr.astype(np.int64)
    bricks = []
    kmax = 1
    for p in range(d):
        r0, r1 = int(starts[p]), int(starts[p + 1])
        s, e = rp[r0], rp[r1]
        cols = mat.cols[s:e].astype(np.int64)
        rows = np.repeat(np.arange(r1 - r0), np.diff(rp[r0:r1 + 1]))
        row_bricks = []
        for q in range(m_axis):
            c0, c1 = q * seg_n, (q + 1) * seg_n
            keep = (cols >= c0) & (cols < c1)
            sub = CSRMatrix.from_coo(rows[keep], cols[keep] - c0,
                                     mat.vals[s:e][keep], (r1 - r0, seg_n))
            bell = to_block_ell(sub, bm, bn)
            kmax = max(kmax, bell.k)
            row_bricks.append(bell)
        bricks.append(row_bricks)
    blocks = np.zeros((d, m_axis, nbr_l, kmax, bm, bn), dtype=mat.vals.dtype)
    bcols = np.zeros((d, m_axis, nbr_l, kmax), dtype=np.int32)
    for p in range(d):
        for q in range(m_axis):
            b = bricks[p][q]
            blocks[p, q, :b.num_block_rows, :b.k] = b.blocks
            bcols[p, q, :b.num_block_rows, :b.k] = b.block_cols
    return blocks, bcols, seg_n, h_pad, starts


def _legacy_spmv_2d(mesh, row_axis: str = "data", col_axis: str = "model"):
    import jax
    from jax.sharding import PartitionSpec as P

    from . import ref

    def local(blocks, block_cols, x_segs):
        bm, bn = blocks.shape[-2], blocks.shape[-1]
        x2d = x_segs[0].reshape(-1, bn, 1)
        y = ref.spmv_bell(blocks[0, 0], block_cols[0, 0], x2d)
        y = jax.lax.psum(y, col_axis)
        return y.reshape(1, -1)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(row_axis, col_axis), P(row_axis, col_axis),
                                P(col_axis)),
                      out_specs=P(row_axis))
    return jax.jit(f)


def _legacy_plan_halo_1d(mat: CSRMatrix, num_devices: int, bm: int = 8,
                         bn: int = 128):
    from ..sparse.metrics import bandwidth as _bandwidth

    assert mat.m % num_devices == 0, "equal panels required"
    panel_n = mat.m // num_devices
    bw = _bandwidth(mat)
    halo = ((bw + bn - 1) // bn) * bn
    if halo >= panel_n:
        raise ValueError(
            f"bandwidth {bw} too wide for halo exchange at P={num_devices} "
            f"(panel {panel_n}); reorder first (RCM) or use plan_1d")
    rp = mat.rowptr.astype(np.int64)
    panels = []
    kmax = 1
    win_n = panel_n + 2 * halo
    for p in range(num_devices):
        r0, r1 = p * panel_n, (p + 1) * panel_n
        s, e = rp[r0], rp[r1]
        cols = mat.cols[s:e].astype(np.int64) - (r0 - halo)
        assert cols.min() >= 0 and cols.max() < win_n, "bandwidth violated"
        rows = np.repeat(np.arange(r1 - r0), np.diff(rp[r0:r1 + 1]))
        sub = CSRMatrix.from_coo(rows, cols, mat.vals[s:e],
                                 (panel_n, win_n))
        bell = to_block_ell(sub, bm, bn)
        kmax = max(kmax, bell.k)
        panels.append(bell)
    nbr_l = (panel_n + bm - 1) // bm
    blocks = np.zeros((num_devices, nbr_l, kmax, bm, bn), dtype=mat.vals.dtype)
    bcols = np.zeros((num_devices, nbr_l, kmax), dtype=np.int32)
    for p, pnl in enumerate(panels):
        blocks[p, :pnl.num_block_rows, :pnl.k] = pnl.blocks
        bcols[p, :pnl.num_block_rows, :pnl.k] = pnl.block_cols
    return blocks, bcols, halo, panel_n


def _legacy_spmv_halo_1d(mesh, axis_names: Tuple[str, ...], halo: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from . import ref

    ax = axis_names if len(axis_names) > 1 else axis_names[0]
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))

    def local(blocks, block_cols, x_panels):
        x = x_panels[0]
        right_edge = x[-halo:]
        left_edge = x[:halo]
        fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        bwd = [((i + 1) % n_dev, i) for i in range(n_dev)]
        left_halo = jax.lax.ppermute(right_edge, ax, fwd)
        right_halo = jax.lax.ppermute(left_edge, ax, bwd)
        xw = jnp.concatenate([left_halo, x, right_halo])
        bm, bn = blocks.shape[-2], blocks.shape[-1]
        y = ref.spmv_bell(blocks[0], block_cols[0], xw.reshape(-1, bn, 1))
        return y.reshape(1, -1)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(ax), P(ax), P(ax)), out_specs=P(ax))
    return jax.jit(f)


def _shim(name: str):
    warnings.warn(
        f"core.spmv.distributed.{name}() is deprecated; plan through "
        f"repro.api — plan(SpmvProblem(mat), topology=Topology(devices=P, "
        f"layout=...)).build() returns a ShardedOperator that owns the "
        f"layout, permutation and collective schedule",
        DeprecationWarning, stacklevel=3)


def plan_1d(mat: CSRMatrix, num_devices: int, bm: int = 8, bn: int = 128,
            balanced: bool = True) -> Plan1D:
    """Deprecated shim over the legacy 1-D layout builder."""
    _shim("plan_1d")
    return _legacy_plan_1d(mat, num_devices, bm=bm, bn=bn, balanced=balanced)


def spmv_1d(mesh, axis_names: Tuple[str, ...]):
    """Deprecated shim over the legacy 1-D all-gather step builder."""
    _shim("spmv_1d")
    return _legacy_spmv_1d(mesh, axis_names)


def plan_2d(mat: CSRMatrix, d: int, m_axis: int, bm: int = 8, bn: int = 128,
            balanced: bool = True):
    """Deprecated shim over the legacy 2-D layout builder."""
    _shim("plan_2d")
    return _legacy_plan_2d(mat, d, m_axis, bm=bm, bn=bn, balanced=balanced)


def spmv_2d(mesh, row_axis: str = "data", col_axis: str = "model"):
    """Deprecated shim over the legacy 2-D step builder."""
    _shim("spmv_2d")
    return _legacy_spmv_2d(mesh, row_axis=row_axis, col_axis=col_axis)


def plan_halo_1d(mat: CSRMatrix, num_devices: int, bm: int = 8,
                 bn: int = 128):
    """Deprecated shim over the legacy halo-exchange layout builder."""
    _shim("plan_halo_1d")
    return _legacy_plan_halo_1d(mat, num_devices, bm=bm, bn=bn)


def spmv_halo_1d(mesh, axis_names: Tuple[str, ...], halo: int):
    """Deprecated shim over the legacy halo-exchange step builder."""
    _shim("spmv_halo_1d")
    return _legacy_spmv_halo_1d(mesh, axis_names, halo)
