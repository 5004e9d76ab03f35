"""Pallas TPU kernel: Block-ELL SpMV/SpMM.

TPU-native SpMV (DESIGN.md §3): the matrix is stored as dense (bm x bn) MXU
bricks at the nonempty block positions; the x tile each brick needs is
gathered HBM->VMEM by the *pipeline itself* via a scalar-prefetched
block-column index feeding the BlockSpec index_map — the TPU idiom replacing
the CPU's per-element x[col] gather.

Grid = (rows, K) per segment: the second axis walks the (padded) blocks of
one block row, accumulating into the y tile that stays resident in VMEM
(output revisiting is consecutive => Pallas keeps it on-chip until the row
is done). Reordering quality shows up here exactly as in the paper:
fewer/denser blocks => fewer grid steps and fewer distinct x tiles fetched.

The block-column index is prefetched flat (a 2-D [nbr, K] SMEM array pads
each row to 128 lanes) and one pallas_call covers a segment of block rows
whose indices fit the SMEM budget (kernels/segments.py); the segments'
rows are concatenated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..segments import row_aligned_segments


def _bell_kernel(block_cols_ref, blocks_ref, x_ref, y_ref, *, acc_dtype):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = blocks_ref[0, 0]      # [bm, bn]
    xv = x_ref[0]             # [bn, nv]
    y_ref[0] += jnp.dot(a, xv, preferred_element_type=acc_dtype,
                        precision=jax.lax.Precision.HIGHEST
                        ).astype(y_ref.dtype)


def _bell_segment(blocks, block_cols, x2d, t0: int, t1: int, r0: int, r1: int,
                  interpret: bool):
    _, kk, bm, bn = blocks.shape
    nv = x2d.shape[2]
    return pl.pallas_call(
        functools.partial(_bell_kernel, acc_dtype=jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r1 - r0, kk),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bn),
                             lambda i, k, bc: (r0 + i, k, 0, 0)),
                pl.BlockSpec((1, bn, nv),
                             lambda i, k, bc: (bc[i * kk + k], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, nv), lambda i, k, bc: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r1 - r0, bm, nv), x2d.dtype),
        interpret=interpret,
    )(block_cols[t0:t1], blocks, x2d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bell_spmm(blocks: jax.Array, block_cols: jax.Array, x2d: jax.Array,
              interpret: bool = False) -> jax.Array:
    """y[nbr, bm, nv] = BlockELL(blocks, block_cols) @ x2d[ncb, bn, nv].

    blocks: [nbr, K, bm, bn] (zero padding blocks)
    block_cols: int32 [nbr * K], row-major (padding -> any valid block,
    typically 0). It is flat because on a TPU an [nbr, K] array is
    lane-padded, and flattening it in the program costs the compiler
    time that grows with nbr.
    """
    nbr, kk, bm, bn = blocks.shape
    assert x2d.shape[1] == bn, (x2d.shape, bn)
    assert block_cols.shape == (nbr * kk,), (block_cols.shape, nbr, kk)
    segments = row_aligned_segments(np.arange(nbr + 1) * kk, n_arrays=1)
    parts = [_bell_segment(blocks, block_cols, x2d, *seg, interpret)
             for seg in segments]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
