"""Pallas TPU kernel: BCSR SpMV/SpMM (flattened-grid variant).

Block-ELL pays K_max grid steps for *every* block row — ruinous for
power-law matrices whose block-count distribution is skewed (the paper's
load-imbalance story at tile granularity). This kernel walks the true block
list instead: grid = (blocks,), with scalar-prefetched block_rows /
block_cols driving the BlockSpec index_maps. The output tile for a block
row stays in VMEM across its (consecutive, row-sorted) blocks and is
flushed when the row id changes — the same revisit-consecutive reduction
contract Pallas flash-attention uses.

The two prefetched index arrays live in SMEM, so one pallas_call covers a
segment of whole block rows whose indices fit the SMEM budget
(kernels/segments.py); `segments` comes from the operator, which knows
the block row pointer, and the segments' rows are concatenated.

Requirement: every block row has >= 1 block (builder pads empty rows with an
explicit zero block) so each output tile is written at least once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bcsr_kernel(block_rows_ref, block_cols_ref, blocks_ref, x_ref, y_ref, *,
                 acc_dtype):
    g = pl.program_id(0)
    row = block_rows_ref[g]
    prev = block_rows_ref[jnp.maximum(g - 1, 0)]
    is_first = jnp.logical_or(g == 0, row != prev)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = blocks_ref[0]          # [bm, bn]
    xv = x_ref[0]              # [bn, nv]
    y_ref[0] += jnp.dot(a, xv, preferred_element_type=acc_dtype,
                        precision=jax.lax.Precision.HIGHEST
                        ).astype(y_ref.dtype)


def _bcsr_segment(blocks, block_rows, block_cols, x2d, t0: int, t1: int,
                  r0: int, r1: int, interpret: bool):
    _, bm, bn = blocks.shape
    nv = x2d.shape[2]
    return pl.pallas_call(
        functools.partial(_bcsr_kernel, acc_dtype=jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t1 - t0,),
            in_specs=[
                pl.BlockSpec((1, bm, bn), lambda g, br, bc: (t0 + g, 0, 0)),
                pl.BlockSpec((1, bn, nv), lambda g, br, bc: (bc[g], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, nv),
                                   lambda g, br, bc: (br[g], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r1 - r0, bm, nv), x2d.dtype),
        interpret=interpret,
    )(block_rows[t0:t1] - r0, block_cols[t0:t1], blocks, x2d)


@functools.partial(jax.jit,
                   static_argnames=("num_block_rows", "segments", "interpret"))
def bcsr_spmm(blocks: jax.Array, block_rows: jax.Array, block_cols: jax.Array,
              x2d: jax.Array, num_block_rows: int, segments: tuple,
              interpret: bool = False) -> jax.Array:
    """y[nbr, bm, nv] = BCSR @ x2d[ncb, bn, nv].

    blocks: [T, bm, bn]; block_rows: int32[T] nondecreasing, covering every
    row id in [0, nbr); block_cols: int32[T]. segments: (t0, t1, r0, r1)
    runs of whole block rows (segments.row_aligned_segments), covering
    [0, T) and [0, num_block_rows).
    """
    assert x2d.shape[1] == blocks.shape[2]
    assert segments[-1][1] == blocks.shape[0] \
        and segments[-1][3] == num_block_rows, segments[-1]
    parts = [_bcsr_segment(blocks, block_rows, block_cols, x2d, *seg,
                           interpret=interpret) for seg in segments]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
