"""Unified jit'd SpMV engine dispatch.

Engines (each a builder in the plugin registry, core/registry.py):
  csr    — gather + segment-sum (paper Listing 4 semantics; the CPU
           measurement engine for the reproduction study)
  ell    — padded row-major ELLPACK
  dia    — diagonal storage: one contiguous window of x per distinct
           col − row, no index array (orders with few diagonals)
  sell   — SELL-C-σ Pallas kernel (TPU) / jnp oracle (CPU)
  bell   — Block-ELL Pallas kernel (TPU) / jnp oracle (CPU)
  bcsr   — BCSR Pallas kernel (TPU) / jnp oracle (CPU)
  dense  — dense matmul (tiny matrices / sanity only)

`make_engine(mat, name)` is the registry-dispatched factory; engine="auto"
runs the OSKI-style tuner (core/spmv/tune.py), whose cost model and
candidate grids are themselves registry metadata (`cost_fn` /
`candidates_fn` on each EngineSpec), so a plugin engine registered with
@register_engine participates in tuning and planning with no change here.
The staged pipeline entry point — problem in, serializable plan out,
permutation-carrying operator built from the plan — is repro.api.

Every operator also exposes `matmul(x)` — the multi-vector SpMM path
(y[m, k] = A @ x[n, k]) that amortizes the matrix stream over k right-hand
sides; the batched serving front-end lives in serving/spmv_service.py.

`build_operator` is a deprecated shim over `make_engine` kept for external
callers.
"""
from __future__ import annotations

import functools
import warnings
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .. import registry
from ..registry import register_engine
from ..sparse.bell import to_bcsr, to_block_ell
from ..sparse.csr import CSRMatrix
from ..sparse.sell import to_sell
from . import ref, tune

Engine = Literal["csr", "ell", "dia", "sell", "bell", "bcsr", "dense",
                 "auto"]


@functools.partial(jax.jit, static_argnames=("m",))
def _csr_matvec(row_ids, cols, vals, x, m):
    return ref.spmv_csr(row_ids, cols, vals, x, m)


@functools.partial(jax.jit, static_argnames=("m",))
def _csr_matmul(row_ids, cols, vals, x, m):
    return ref.spmm_csr(row_ids, cols, vals, x, m)


@jax.jit
def _ell_matvec(ell_cols, ell_vals, x):
    return ref.spmv_ell(ell_cols, ell_vals, x)


@jax.jit
def _ell_matmul(ell_cols, ell_vals, x):
    return ref.spmm_ell(ell_cols, ell_vals, x)


@functools.partial(jax.jit, static_argnames=("offsets", "m"))
def _dia_apply(dia_vals, x, offsets, m):
    return ref.spmv_dia(dia_vals, x, offsets, m)


@jax.tree_util.register_static
class DeviceCSR:
    """Device-resident CSR (COO-expanded) operator.

    nnz_bucket > 0 pads nnz up to the next multiple (val=0, row=0, col=0 —
    result-neutral) so panels of similar size share one XLA compilation.
    """

    def __init__(self, mat: CSRMatrix, dtype=jnp.float32, nnz_bucket: int = 0):
        self.m, self.n = mat.shape
        self.nnz = mat.nnz
        row_ids = np.repeat(np.arange(mat.m, dtype=np.int32), mat.row_nnz())
        cols = mat.cols.astype(np.int32)
        vals = mat.vals
        if nnz_bucket:
            pad = (-mat.nnz) % nnz_bucket
            if pad:
                # pad with (row=m-1, col=0, val=0): keeps row_ids sorted
                # (segment_sum indices_are_sorted) and adds exactly 0.
                row_ids = np.concatenate(
                    [row_ids, np.full(pad, self.m - 1, np.int32)])
                cols = np.concatenate([cols, np.zeros(pad, np.int32)])
                vals = np.concatenate([vals, np.zeros(pad, vals.dtype)])
        self.row_ids = jnp.asarray(row_ids)
        self.cols = jnp.asarray(cols)
        self.vals = jnp.asarray(vals, dtype=dtype)

    def __call__(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmv", engine="csr"):
            return _csr_matvec(self.row_ids, self.cols, self.vals, x,
                               self.m)

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k]: one gather/segment-sum pass serves all k
        vectors (the matrix stream is paid once, not k times)."""
        if x.ndim == 1:
            return self(x)
        with obs.span("kernel.spmm", engine="csr", k=int(x.shape[1])):
            return _csr_matmul(self.row_ids, self.cols, self.vals, x,
                               self.m)

    # -- operator-cache protocol (opcache.py) ------------------------------
    def state(self):
        meta = {"m": self.m, "n": self.n, "nnz": self.nnz}
        arrays = {"row_ids": np.asarray(self.row_ids),
                  "cols": np.asarray(self.cols),
                  "vals": np.asarray(self.vals)}
        return meta, arrays

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.m, op.n, op.nnz = meta["m"], meta["n"], meta["nnz"]
        op.row_ids = jnp.asarray(arrays["row_ids"])
        op.cols = jnp.asarray(arrays["cols"])
        op.vals = jnp.asarray(arrays["vals"], dtype=dtype)
        return op


@jax.tree_util.register_static
class DeviceELL:
    def __init__(self, mat: CSRMatrix, dtype=jnp.float32):
        self.m, self.n = mat.shape
        counts = mat.row_nnz()
        k = max(int(counts.max()), 1)
        cols = np.zeros((mat.m, k), dtype=np.int32)
        vals = np.zeros((mat.m, k), dtype=np.float64)
        rp = mat.rowptr.astype(np.int64)
        # vectorized scatter: element e of row r lands at (r, e - rowptr[r])
        r = np.repeat(np.arange(mat.m), counts)
        j = np.arange(mat.nnz) - np.repeat(rp[:-1], counts)
        cols[r, j] = mat.cols
        vals[r, j] = mat.vals
        # slot-major [K, m] on the device (ref.spmv_ell)
        self.ell_cols = jnp.asarray(cols.T)
        self.ell_vals = jnp.asarray(vals.T, dtype=dtype)
        self.padded_nnz = mat.m * k

    def __call__(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmv", engine="ell"):
            return _ell_matvec(self.ell_cols, self.ell_vals, x)

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k] (batched padded-ELL contraction)."""
        if x.ndim == 1:
            return self(x)
        with obs.span("kernel.spmm", engine="ell", k=int(x.shape[1])):
            return _ell_matmul(self.ell_cols, self.ell_vals, x)

    def state(self):
        meta = {"m": self.m, "n": self.n, "padded_nnz": self.padded_nnz}
        # keys name the layout: a row-major entry written before the
        # slot-major switch fails to load and is rebuilt
        return meta, {"slot_cols": np.asarray(self.ell_cols),
                      "slot_vals": np.asarray(self.ell_vals)}

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.m, op.n = meta["m"], meta["n"]
        op.padded_nnz = meta["padded_nnz"]
        op.ell_cols = jnp.asarray(arrays["slot_cols"])
        op.ell_vals = jnp.asarray(arrays["slot_vals"], dtype=dtype)
        return op


@jax.tree_util.register_pytree_node_class
class DeviceDIA:
    """Diagonal (DIA) operator: `offsets`, the distinct col − row sorted
    ascending (static), and `dia_vals [ndiag * m]`, slot-major like ELL
    (diagonal d is elements d·m to (d + 1)·m); positions outside the
    matrix hold 0. One dimension, not [ndiag, m]: a TPU tiles a 2-D array
    (8, 128), so each row would be copied into x's 1-D layout before the
    multiply; flat, every diagonal is a contiguous window in that layout
    and an SpMV is one loop fusion.

    Built in O(nnz + m + n) with no sort: a presence table over the
    m + n − 1 possible offsets gives each its slot, and one bincount
    scatters the values (duplicates sum, as in CSR).

    A pytree whose one leaf is `dia_vals`: a jitted solver that takes the
    operator as an argument (`cg_solve`) reads the values as an argument
    of its program, not as a constant compiled into it."""

    def __init__(self, mat: CSRMatrix, dtype=jnp.float32):
        self.m, self.n = mat.shape
        r = np.repeat(np.arange(mat.m, dtype=np.int64), mat.row_nnz())
        diag = mat.cols.astype(np.int64) - r + (mat.m - 1)
        present = np.zeros(max(mat.m + mat.n - 1, 0), dtype=bool)
        present[diag] = True
        idx = np.flatnonzero(present)
        self.offsets = tuple(int(d) for d in idx - (mat.m - 1))
        slot = np.zeros(present.size, dtype=np.int64)
        slot[idx] = np.arange(idx.size)
        vals = np.bincount(slot[diag] * mat.m + r, weights=mat.vals,
                           minlength=idx.size * mat.m)
        self.dia_vals = jnp.asarray(vals, dtype=dtype)

    def __call__(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmv", engine="dia",
                      diagonals=len(self.offsets)):
            return _dia_apply(self.dia_vals, x, self.offsets, self.m)

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k]: each diagonal's values scale one
        window of k-wide rows of x."""
        if x.ndim == 1:
            return self(x)
        with obs.span("kernel.spmm", engine="dia", k=int(x.shape[1]),
                      diagonals=len(self.offsets)):
            return _dia_apply(self.dia_vals, x, self.offsets, self.m)

    def state(self):
        meta = {"m": self.m, "n": self.n, "offsets": list(self.offsets)}
        return meta, {"dia_vals": np.asarray(self.dia_vals)}

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.m, op.n = meta["m"], meta["n"]
        op.offsets = tuple(int(d) for d in meta["offsets"])
        op.dia_vals = jnp.asarray(arrays["dia_vals"], dtype=dtype)
        return op

    def tree_flatten(self):
        return (self.dia_vals,), (self.m, self.n, self.offsets)

    @classmethod
    def tree_unflatten(cls, meta, leaves):
        op = object.__new__(cls)
        op.m, op.n, op.offsets = meta
        (op.dia_vals,) = leaves
        return op


@jax.tree_util.register_static
class DeviceDense:
    def __init__(self, mat: CSRMatrix, dtype=jnp.float32):
        self.a = jnp.asarray(mat.to_dense(), dtype=dtype)

    def __call__(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmv", engine="dense"):
            return self.a @ x

    def matmul(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmm", engine="dense"):
            return self.a @ x

    def state(self):
        return {}, {"a": np.asarray(self.a)}

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.a = jnp.asarray(arrays["a"], dtype=dtype)
        return op


# -- engine registry entries (registration order = tuner candidate order) --

@register_engine("csr", supports_spmm=True, device="any",
                 cost_fn=tune.cost_csr, candidates_fn=tune.cands_default,
                 description="COO-expanded gather + segment-sum")
def _build_csr(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
               sell_sigma=None, use_kernel: str = "auto",
               nnz_bucket: int = 0):
    return DeviceCSR(mat, dtype, nnz_bucket=nnz_bucket)


@register_engine("ell", supports_spmm=True, device="any",
                 cost_fn=tune.cost_ell, candidates_fn=tune.cands_default,
                 description="padded row-major ELLPACK")
def _build_ell(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
               sell_sigma=None, use_kernel: str = "auto",
               nnz_bucket: int = 0):
    return DeviceELL(mat, dtype)


@register_engine("dia", supports_spmm=True, device="any",
                 cost_fn=tune.cost_dia, candidates_fn=tune.cands_default,
                 description="diagonal storage: shifted windows of x, "
                             "no index array")
def _build_dia(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
               sell_sigma=None, use_kernel: str = "auto",
               nnz_bucket: int = 0):
    return DeviceDIA(mat, dtype)


@register_engine("bell", supports_spmm=True, device="tpu",
                 cost_fn=tune.cost_bell, candidates_fn=tune.cands_default,
                 description="Block-ELL Pallas kernel (ref fallback on CPU)")
def _build_bell(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
                sell_sigma=None, use_kernel: str = "auto",
                nnz_bucket: int = 0):
    from ...kernels.bell_spmv.ops import BellOperator

    return BellOperator(to_block_ell(mat, *block_shape), dtype, use_kernel)


@register_engine("bcsr", supports_spmm=True, device="tpu",
                 cost_fn=tune.cost_bcsr, candidates_fn=tune.cands_default,
                 description="BCSR Pallas kernel (ref fallback on CPU)")
def _build_bcsr(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
                sell_sigma=None, use_kernel: str = "auto",
                nnz_bucket: int = 0):
    from ...kernels.bcsr_spmv.ops import BcsrOperator

    return BcsrOperator(to_bcsr(mat, *block_shape), dtype, use_kernel)


@register_engine("sell", supports_spmm=True, device="tpu",
                 cost_fn=tune.cost_sell, candidates_fn=tune.cands_sell,
                 description="SELL-C-σ Pallas kernel, k-tiled SpMM")
def _build_sell(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
                sell_sigma=None, use_kernel: str = "auto",
                nnz_bucket: int = 0):
    from ...kernels.sell_spmv.ops import SellOperator

    c, w = block_shape
    sigma = 8 * c if sell_sigma is None else sell_sigma
    return SellOperator(to_sell(mat, c=c, sigma=sigma, w=w), dtype,
                        use_kernel)


@register_engine("dense", supports_spmm=True, device="any",
                 cost_fn=tune.cost_dense, candidates_fn=tune.cands_dense,
                 description="dense matmul (tiny matrices / sanity only)")
def _build_dense(mat: CSRMatrix, dtype=jnp.float32, block_shape=(8, 128),
                 sell_sigma=None, use_kernel: str = "auto",
                 nnz_bucket: int = 0):
    return DeviceDense(mat, dtype)


def make_engine(mat: CSRMatrix, engine: Engine = "csr", dtype=jnp.float32,
                block_shape=(8, 128), use_kernel: str = "auto",
                nnz_bucket: int = 0, sell_sigma: int | None = None,
                probe: bool = False, k: int = 1):
    """Factory: host CSRMatrix -> callable device operator y = A @ x,
    dispatched through the engine registry.

    engine="auto" runs the OSKI-style tuner (core/spmv/tune.py): a cost
    model over structural metrics (optionally refined by empirical probing
    when probe=True) picks the engine and its shape parameters; the chosen
    plan is attached to the returned operator as `.plan`.

    k is the expected number of simultaneous right-hand sides (SpMM batch
    width). It only affects tuning — matrix bytes amortize over k vectors,
    shifting the engine choice — never the stored format; every operator's
    `matmul` accepts any k at run time.

    For engine="sell", block_shape is (slice height C, chunk width W) and
    sell_sigma is the σ sort window (default 8 * C).

    Operators built here live in the *given* matrix's index space; the
    permutation-carrying wrapper that accepts original-index-space vectors
    is repro.api.plan(...).build().
    """
    if engine == "auto":
        return tune.build_tuned(mat, dtype=dtype, probe=probe,
                                use_kernel=use_kernel, nnz_bucket=nnz_bucket,
                                k=k)
    spec = registry.get_engine(engine)
    return spec.build(mat, dtype=dtype, block_shape=block_shape,
                      sell_sigma=sell_sigma, use_kernel=use_kernel,
                      nnz_bucket=nnz_bucket)


def build_operator(mat: CSRMatrix, engine: Engine = "csr", dtype=jnp.float32,
                   block_shape=(8, 128), use_kernel: str = "auto",
                   nnz_bucket: int = 0, sell_sigma: int | None = None,
                   probe: bool = False, k: int = 1):
    """Deprecated shim over `make_engine` (same signature and behavior).

    New code plans through repro.api — `plan(SpmvProblem(mat, k=k),
    engine=...).build()` — which adds joint scheme/engine selection, the
    persistent plan store, and permutation-carrying operators; code that
    really wants a bare operator in the matrix's own index space calls
    `make_engine` directly.
    """
    warnings.warn(
        "build_operator() is deprecated; use repro.api.plan(...).build() "
        "(or core.spmv.ops.make_engine for a bare fixed-engine operator)",
        DeprecationWarning, stacklevel=2)
    return make_engine(mat, engine, dtype=dtype, block_shape=block_shape,
                       use_kernel=use_kernel, nnz_bucket=nnz_bucket,
                       sell_sigma=sell_sigma, probe=probe, k=k)
