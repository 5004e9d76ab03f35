"""Pure-jnp SpMV oracles — the correctness references for every engine.

These are deliberately straight-line jnp (no pallas, no shard_map); each
optimized engine (ops.py, kernels/, distributed.py) is tested allclose
against these, which in turn are tested against the numpy CSRMatrix.spmv.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Contractions run at full f32: the TPU's default precision rounds f32
# operands to bf16 on the MXU, which an f32 operator must not do.
HIGHEST = jax.lax.Precision.HIGHEST


def gather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x[idx]: an engine's gather of x, under the `spmv.gather` scope so a
    device trace names its ops."""
    with jax.named_scope("spmv.gather"):
        return x[idx]


def spmv_dense(a_dense: jax.Array, x: jax.Array) -> jax.Array:
    return a_dense @ x


def spmv_csr(row_ids: jax.Array, cols: jax.Array, vals: jax.Array,
             x: jax.Array, m: int) -> jax.Array:
    """CSR-as-COO gather + segment-sum (paper Listing 4 semantics).

    row_ids: int32[nnz] (row of each stored element, nondecreasing)
    """
    prod = vals * gather(x, cols)
    return jax.ops.segment_sum(prod, row_ids, num_segments=m,
                               indices_are_sorted=True)


def spmm_csr(row_ids: jax.Array, cols: jax.Array, vals: jax.Array,
             x: jax.Array, m: int) -> jax.Array:
    """Batched CSR gather + segment-sum: x [n, k] -> y [m, k].

    Same accumulation order per column as spmv_csr — the vectorized k axis
    rides along each gathered element, so the matrix stream (vals/cols/
    row_ids) is paid once for all k vectors.
    """
    prod = vals[:, None] * gather(x, cols)                   # [nnz, k]
    return jax.ops.segment_sum(prod, row_ids, num_segments=m,
                               indices_are_sorted=True)


def spmv_ell(ell_cols: jax.Array, ell_vals: jax.Array, x: jax.Array) -> jax.Array:
    """ELLPACK, slot-major: ell_cols/vals [K, m] (slot j of every row is
    one contiguous row of the arrays), padding has val 0 (col arbitrary).

    Slot-major keeps the long axis minor: on a TPU an [m, K] array pads
    K to 128 lanes, and its scalar gather took the v5e compiler ~100 s at
    a million rows where [K, m] takes about one."""
    return jnp.sum(ell_vals * gather(x, ell_cols), axis=0)


def spmm_ell(ell_cols: jax.Array, ell_vals: jax.Array, x: jax.Array) -> jax.Array:
    """Batched slot-major ELLPACK: ell_cols/vals [K, m], x [n, k] ->
    y [m, k] (one pass over the pads).

    Accumulates at >= the operator dtype (f32 floor), so an f64 operator's
    matmul keeps f64 accuracy like its SpMV __call__ does.

    Peak memory is the gathered [K, m, k] intermediate — the same
    footprint class as spmm_csr's [nnz, k] in ELL's intended near-uniform
    regime (K ~ mean row nnz); on padding-inflated matrices the tuner
    never picks ELL in the first place.
    """
    out_dtype = jnp.promote_types(ell_vals.dtype, x.dtype)   # == __call__'s
    acc = jnp.promote_types(out_dtype, jnp.float32)
    gathered = gather(x, ell_cols)                         # [K, m, k]
    return jnp.einsum("jm,jmv->mv", ell_vals, gathered,
                      preferred_element_type=acc,
                      precision=HIGHEST).astype(out_dtype)


def spmv_dia(dia_vals: jax.Array, x: jax.Array, offsets: tuple,
             m: int) -> jax.Array:
    """Diagonal storage: dia_vals [ndiag * m], diagonal d in
    dia_vals[d * m:(d + 1) * m], its element i holding A[i, i + offsets[d]]
    (0 where that column is outside the matrix); x [n] or [n, k] ->
    y [m] or [m, k].

    x is zero-padded once so that every diagonal reads one static,
    contiguous window of it: no index array and no gather. The products
    are summed in f32 (at least) in ascending offset order, which for an
    interior row is CSR's column order."""
    out_dtype = jnp.promote_types(dia_vals.dtype, x.dtype)
    acc = jnp.promote_types(out_dtype, jnp.float32)
    n = x.shape[0]
    lo = max(0, -min(offsets, default=0))
    hi = max(0, max(offsets, default=0) + m - n)
    xp = jnp.pad(x, [(lo, hi)] + [(0, 0)] * (x.ndim - 1)).astype(acc)
    y = jnp.zeros((m,) + x.shape[1:], acc)
    for d, off in enumerate(offsets):
        v = dia_vals[d * m:(d + 1) * m].astype(acc)
        y = y + (v if x.ndim == 1 else v[:, None]) * xp[lo + off:lo + off + m]
    return y.astype(out_dtype)


def spmv_bell(blocks: jax.Array, block_cols: jax.Array, x2d: jax.Array) -> jax.Array:
    """Block-ELL: blocks [nbr, K, bm, bn]; block_cols [nbr, K];
    x2d [ncb, bn, nv] (x padded & reshaped). Returns y [nbr, bm, nv].

    Padding blocks are all-zero so their contribution vanishes regardless of
    block_cols padding value.
    """
    gathered = gather(x2d, block_cols)              # [nbr, K, bn, nv]
    return jnp.einsum("rkij,rkjv->riv", blocks, gathered,
                      preferred_element_type=jnp.float32,
                      precision=HIGHEST).astype(x2d.dtype)


def spmv_sell(chunk_vals: jax.Array, chunk_cols: jax.Array,
              chunk_slice: jax.Array, x: jax.Array,
              num_slices: int) -> jax.Array:
    """SELL-C-σ, rows on lanes: chunk_vals/cols [T, W, C]; chunk_slice
    int32[T] nondecreasing; x [n_pad, nv]. Returns y [S, C, nv] in slice
    order (caller un-permutes via SellCS.inv_perm).

    Padding slots have val 0 (col 0), so they add exactly 0.
    """
    gathered = gather(x, chunk_cols)                       # [T, W, C, nv]
    partial = jnp.einsum("twc,twcv->tcv", chunk_vals, gathered,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
    y = jax.ops.segment_sum(partial, chunk_slice, num_segments=num_slices,
                            indices_are_sorted=True)
    return y.astype(x.dtype)


def spmv_bcsr(blocks: jax.Array, block_rows: jax.Array, block_cols: jax.Array,
              x2d: jax.Array, num_block_rows: int) -> jax.Array:
    """BCSR: blocks [T, bm, bn], block_rows/cols [T]. Returns [nbr, bm, nv]."""
    gathered = gather(x2d, block_cols)              # [T, bn, nv]
    partial = jnp.einsum("tij,tjv->tiv", blocks, gathered,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
    y = jax.ops.segment_sum(partial, block_rows, num_segments=num_block_rows,
                            indices_are_sorted=True)
    return y.astype(x2d.dtype)
