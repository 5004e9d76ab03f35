"""Public wrapper for SELL-C-σ SpMM: dense RHS block in, dense block out.

`sell_matmul(op, x)` is what `SellOperator.matmul` dispatches to for 2-D
x — it handles k padding to the k-tile, the un-permute to rows, and the
pallas / interpret / jnp-ref engine choice, mirroring
kernels/sell_spmv/ops.py for the single-vector path. Both paths run the
one SELL kernel (kernels/sell_spmv/kernel.py); here the k-tile is wider
than one vector, so the matrix stream is paid once per KB vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import obs
from ..sell_spmv.kernel import sell_spmm
from ..sell_spmv.ref import sell_spmm_ref

LANES = 128


def pick_k_tile(k: int, lanes: int = LANES) -> int:
    """k-tile width: smallest power of two >= k, clipped to [8, lanes].

    Small batches keep the tile narrow (padding scales with KB); anything
    past one lane row is split into multiple passes over the matrix.
    """
    kb = 8
    while kb < min(max(k, 1), lanes):
        kb *= 2
    return min(kb, lanes)


def sell_matmul(op, x: jax.Array) -> jax.Array:
    """y[m, k] = A @ x[n, k] for a SellOperator `op` (kernels/sell_spmv).

    Only the kernel paths pad k to the lane-aligned k-tile; the jnp-ref
    path needs no alignment and runs on the exact k columns (small service
    batches would otherwise pay up to the tile floor in wasted flops).
    """
    k = x.shape[1]
    with obs.span("kernel.spmm", engine="sell", k=int(k),
                  use_kernel=op.use_kernel, chunks=op.num_chunks,
                  slots=op.padded_nnz) as sp:
        if op.use_kernel in ("pallas", "interpret"):
            kb = pick_k_tile(k)
            sp.set(k_tile=int(kb))
            k_pad = ((k + kb - 1) // kb) * kb
            xp = jnp.pad(x, ((0, 0), (0, k_pad - k)))
            y = sell_spmm(op.chunk_vals, op.chunk_cols, op.chunk_slice, xp,
                          op.num_slices, kb=kb, segments=op.segments,
                          interpret=(op.use_kernel == "interpret"))
        else:
            y = sell_spmm_ref(op.chunk_vals, op.chunk_cols, op.chunk_slice,
                              x, op.num_slices)
        return op.rows(y)[:, :k]
