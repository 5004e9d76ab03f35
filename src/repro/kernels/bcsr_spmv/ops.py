"""Jit'd public wrapper for BCSR SpMV: host format in, vector out."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .. import resolve_use_kernel
from ...core.sparse.bell import BCSR
from ..segments import row_aligned_segments
from .kernel import bcsr_spmm
from .ref import bcsr_spmm_ref


def pad_empty_rows(host: BCSR) -> BCSR:
    """Ensure every block row has >= 1 block (kernel contract): insert an
    explicit zero block (col 0) for each empty block row."""
    counts = np.diff(host.block_rowptr.astype(np.int64))
    empty = np.flatnonzero(counts == 0)
    if empty.size == 0:
        return host
    bm, bn = host.block_shape
    add_blocks = np.zeros((empty.size, bm, bn), dtype=host.blocks.dtype)
    rows = np.concatenate([host.block_rows, empty.astype(np.int32)])
    cols = np.concatenate([host.block_cols, np.zeros(empty.size, np.int32)])
    blocks = np.concatenate([host.blocks, add_blocks], axis=0)
    order = np.argsort(rows, kind="stable")
    rowptr = np.zeros(host.num_block_rows + 1, dtype=np.int64)
    np.add.at(rowptr, rows.astype(np.int64) + 1, 1)
    return BCSR(blocks=blocks[order], block_rows=rows[order],
                block_cols=cols[order],
                block_rowptr=np.cumsum(rowptr).astype(np.int32),
                shape=host.shape, block_shape=host.block_shape)


@jax.tree_util.register_static
class BcsrOperator:
    """Device-resident BCSR operator: y = A @ x."""

    def __init__(self, host: BCSR, dtype=jnp.float32, use_kernel: str = "auto"):
        host = pad_empty_rows(host)
        self.block_shape = host.block_shape
        self.shape = host.shape
        self.nbr = host.num_block_rows
        bm, bn = host.block_shape
        self.ncb = (host.shape[1] + bn - 1) // bn
        self.blocks = jnp.asarray(host.blocks, dtype=dtype)
        self.block_rows = jnp.asarray(host.block_rows, dtype=jnp.int32)
        self.block_cols = jnp.asarray(host.block_cols, dtype=jnp.int32)
        self.segments = row_aligned_segments(host.block_rowptr)
        self.use_kernel = resolve_use_kernel(use_kernel, "bcsr")

    def __call__(self, x: jax.Array) -> jax.Array:
        with obs.span("kernel.spmv", engine="bcsr",
                      use_kernel=self.use_kernel):
            squeeze = x.ndim == 1
            if squeeze:
                x = x[:, None]
            n, nv = x.shape
            bm, bn = self.block_shape
            x2d = jnp.pad(x, ((0, self.ncb * bn - n), (0, 0))) \
                .reshape(self.ncb, bn, nv)
            if self.use_kernel == "pallas":
                y = bcsr_spmm(self.blocks, self.block_rows, self.block_cols,
                              x2d, self.nbr, self.segments)
            elif self.use_kernel == "interpret":
                y = bcsr_spmm(self.blocks, self.block_rows, self.block_cols,
                              x2d, self.nbr, self.segments, interpret=True)
            else:
                y = bcsr_spmm_ref(self.blocks, self.block_rows,
                                  self.block_cols, x2d, self.nbr)
            y = y.reshape(-1, nv)[: self.shape[0]]
            return y[:, 0] if squeeze else y

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k] (vectorized __call__: one stream of the
        flattened block list serves all k vectors)."""
        return self(x)

    def flops(self) -> int:
        t, bm, bn = self.blocks.shape
        return 2 * t * bm * bn

    # -- operator-cache protocol (core/spmv/opcache.py) --------------------
    def state(self):
        meta = {"shape": list(self.shape),
                "block_shape": list(self.block_shape),
                "nbr": self.nbr, "ncb": self.ncb,
                "use_kernel": self.use_kernel}
        return meta, {"blocks": np.asarray(self.blocks),
                      "block_rows": np.asarray(self.block_rows),
                      "block_cols": np.asarray(self.block_cols)}

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.shape = tuple(meta["shape"])
        op.block_shape = tuple(meta["block_shape"])
        op.nbr, op.ncb = meta["nbr"], meta["ncb"]
        op.use_kernel = meta["use_kernel"]
        op.blocks = jnp.asarray(arrays["blocks"], dtype=dtype)
        op.block_rows = jnp.asarray(arrays["block_rows"])
        op.block_cols = jnp.asarray(arrays["block_cols"])
        rowptr = np.zeros(op.nbr + 1, np.int64)
        rowptr[1:] = np.cumsum(np.bincount(
            np.asarray(arrays["block_rows"], np.int64), minlength=op.nbr))
        op.segments = row_aligned_segments(rowptr)
        return op
