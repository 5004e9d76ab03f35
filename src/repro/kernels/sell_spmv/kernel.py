"""Pallas TPU kernel: SELL-C-σ SpMV/SpMM (flattened-chunk grid, rows on lanes).

Storage (core/sparse/sell.py): the matrix is a flat list of [W, C] chunks —
W element slots on sublanes x the C rows of one slice on lanes — with a
scalar-prefetched `chunk_slice` map naming the slice each chunk belongs
to. With C = 128 and W a multiple of 8 an f32 chunk is a whole number of
(8, 128) tiles, so values, column ids and gathered x carry no tile padding
in HBM, and one grid step moves a whole slice's worth of rows. Slices with
few nonzeros contribute few chunks, so power-law matrices do O(nnz / C)
grid steps instead of Block-ELL's O(slices * K_max).

The x[col] gather runs in XLA, before the kernel: Mosaic has no
per-element VMEM gather ("Cannot do int indexing on TPU"), and keeping x
whole in VMEM caps n·k. XLA lays the gathered values out as
xg[k, T, W, C], chunk-aligned with the values, and the kernel streams both.

Grid = (k_pad // KB, chunks) per segment. Per step the VPU multiplies a
chunk's values against its KB gathered vectors and reduces over the
sublane (slot) axis into the slice's lane-dense [KB, C] y tile, which
stays resident in VMEM across the (consecutive) chunks of one slice and is
re-initialized when `chunk_slice` changes — the same revisit-consecutive
reduction contract as the BCSR kernel. The matrix stream is paid once per
k-tile, not once per vector. SpMV is KB = k = 1.

`chunk_slice` is prefetched into SMEM, so one pallas_call covers a segment
of whole slices whose chunk ids fit the SMEM budget (kernels/segments.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sell_kernel(chunk_slice_ref, vals_ref, xg_ref, y_ref, *, acc_dtype):
    g = pl.program_id(1)                     # chunk index (inner axis)
    sl = chunk_slice_ref[g]
    prev = chunk_slice_ref[jnp.maximum(g - 1, 0)]
    is_first = jnp.logical_or(g == 0, sl != prev)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    vals = vals_ref[0].astype(acc_dtype)     # [W, C]
    xg = xg_ref[:, 0].astype(acc_dtype)      # [KB, W, C]
    part = jnp.sum(vals[None] * xg, axis=1)             # [KB, C]
    y_ref[0] += part.astype(y_ref.dtype)


def _sell_segment(chunk_vals, xg, chunk_slice, t0: int, t1: int, s0: int,
                  s1: int, kb: int, acc_dtype, interpret: bool):
    _, w, c = chunk_vals.shape
    k_pad = xg.shape[0]
    return pl.pallas_call(
        functools.partial(_sell_kernel, acc_dtype=acc_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k_pad // kb, t1 - t0),
            in_specs=[
                pl.BlockSpec((1, w, c), lambda kt, g, cs: (t0 + g, 0, 0)),
                pl.BlockSpec((kb, 1, w, c),
                             lambda kt, g, cs: (kt, t0 + g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, kb, c),
                                   lambda kt, g, cs: (cs[g], kt, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((s1 - s0, k_pad, c), xg.dtype),
        interpret=interpret,
    )(chunk_slice[t0:t1] - s0, chunk_vals, xg)


@functools.partial(jax.jit, static_argnames=("num_slices", "kb", "segments",
                                             "interpret"))
def sell_spmm(chunk_vals: jax.Array, chunk_cols: jax.Array,
              chunk_slice: jax.Array, x: jax.Array, num_slices: int,
              segments: tuple, kb: int = 1,
              interpret: bool = False) -> jax.Array:
    """y[S, C, k_pad] = SELL(chunk_vals, chunk_cols, chunk_slice) @ x[n, k_pad].

    chunk_vals: [T, W, C] (padding slots are 0)
    chunk_cols: [T, W, C] int32 (padding -> 0, result-neutral via zero vals)
    chunk_slice: int32[T], nondecreasing, covering every slice in [0, S)
    x: [n, k_pad] with k_pad a multiple of kb
    segments: (t0, t1, s0, s1) runs of whole slices
    (segments.row_aligned_segments), covering [0, T) and [0, S)
    """
    k_pad = x.shape[1]
    assert k_pad % kb == 0, (k_pad, kb)
    assert segments[-1][1] == chunk_vals.shape[0] \
        and segments[-1][3] == num_slices, segments[-1]
    # accumulate at >= the operator dtype (f32 floor): an f64 operator's
    # matmul keeps f64 accuracy, same contract as ref.spmm_ell
    acc_dtype = jnp.promote_types(x.dtype, jnp.float32)
    with jax.named_scope("spmv.gather"):
        xg = jnp.take(x.T, chunk_cols, axis=1)         # [k_pad, T, W, C]
    parts = [_sell_segment(chunk_vals, xg, chunk_slice, *seg, kb=kb,
                           acc_dtype=acc_dtype, interpret=interpret)
             for seg in segments]
    y = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return y.transpose(0, 2, 1)
