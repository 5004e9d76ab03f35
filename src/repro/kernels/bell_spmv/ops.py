"""Jit'd public wrapper for Block-ELL SpMV: host-format in, vector out.

Handles padding/reshaping between the logical (m, n) world and the kernel's
tiled [nbr, K, bm, bn] world, and falls back to the jnp reference on
non-TPU backends unless interpret mode is forced (tests force it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .. import resolve_use_kernel
from ...core.sparse.bell import BlockELL
from .kernel import bell_spmm
from .ref import bell_spmm_ref


@jax.tree_util.register_static
class BellOperator:
    """Device-resident Block-ELL operator: y = A @ x."""

    def __init__(self, host: BlockELL, dtype=jnp.float32, use_kernel: str = "auto"):
        self.block_shape = host.block_shape
        self.shape = host.shape
        bm, bn = host.block_shape
        self.ncb = (host.shape[1] + bn - 1) // bn
        self.blocks = jnp.asarray(host.blocks, dtype=dtype)
        # flat [nbr * K]: the kernel prefetches it 1-D (kernel.py)
        self.block_cols = jnp.asarray(host.block_cols.reshape(-1),
                                      dtype=jnp.int32)
        self.use_kernel = resolve_use_kernel(use_kernel, "bell")

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: [n] or [n, nv] -> y: [m] or [m, nv]."""
        with obs.span("kernel.spmv", engine="bell",
                      use_kernel=self.use_kernel):
            squeeze = x.ndim == 1
            if squeeze:
                x = x[:, None]
            n, nv = x.shape
            bm, bn = self.block_shape
            pad_n = self.ncb * bn - n
            x2d = jnp.pad(x, ((0, pad_n), (0, 0))).reshape(self.ncb, bn, nv)
            if self.use_kernel == "pallas":
                y = bell_spmm(self.blocks, self.block_cols, x2d)
            elif self.use_kernel == "interpret":
                y = bell_spmm(self.blocks, self.block_cols, x2d,
                              interpret=True)
            else:
                y = bell_spmm_ref(self.blocks,
                                  self.block_cols.reshape(self.blocks.shape[:2]),
                                  x2d)
            y = y.reshape(-1, nv)[: self.shape[0]]
            return y[:, 0] if squeeze else y

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k] — the block layout already carries a
        trailing vector axis through the MXU contraction, so the batched
        path IS the vectorized __call__ (each dense brick is streamed once
        for all k vectors)."""
        return self(x)

    def flops(self) -> int:
        """MXU flops per SpMV (2 * padded block volume)."""
        nbr, k, bm, bn = self.blocks.shape
        return 2 * nbr * k * bm * bn

    # -- operator-cache protocol (core/spmv/opcache.py) --------------------
    def state(self):
        meta = {"shape": list(self.shape),
                "block_shape": list(self.block_shape),
                "ncb": self.ncb, "use_kernel": self.use_kernel}
        return meta, {"blocks": np.asarray(self.blocks),
                      "block_cols": np.asarray(self.block_cols)}

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        op = object.__new__(cls)
        op.shape = tuple(meta["shape"])
        op.block_shape = tuple(meta["block_shape"])
        op.ncb = meta["ncb"]
        op.use_kernel = meta["use_kernel"]
        op.blocks = jnp.asarray(arrays["blocks"], dtype=dtype)
        op.block_cols = jnp.asarray(arrays["block_cols"]).reshape(-1)
        return op
