"""Jit'd public wrapper for SELL-C-σ SpMV: host format in, vector out.

Handles the un-permute from slice order to rows (the inv_perm gather, 0
for empty rows, plus the pieces of split rows), the SMEM segments of the
chunk grid and the pallas / interpret / jnp-ref engine choice, mirroring
bell_spmv/ops.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .. import resolve_use_kernel
from ...core.sparse.sell import SellCS
from ..segments import row_aligned_segments
from .kernel import sell_spmm
from .ref import sell_spmm_ref

# The layout a stored operator holds: [T, W, C] chunks, a slice's rows on
# lanes, rows stored as pieces. from_state refuses entries of another
# layout (or none).
LAYOUT = "rows_on_lanes"


def _segments(chunk_slice, num_slices: int) -> tuple:
    """SMEM segments of whole slices over the chunk list."""
    counts = np.bincount(np.asarray(chunk_slice, np.int64),
                         minlength=num_slices)
    return row_aligned_segments(np.concatenate([[0], np.cumsum(counts)]),
                                n_arrays=1)


@jax.tree_util.register_static
class SellOperator:
    """Device-resident SELL-C-σ operator: y = A @ x."""

    def __init__(self, host: SellCS, dtype=jnp.float32, use_kernel: str = "auto"):
        self.shape = host.shape
        self.c = host.c
        self.sigma = host.sigma
        self.w = host.w
        self.num_slices = host.num_slices
        self.chunk_vals = jnp.asarray(host.chunk_vals, dtype=dtype)
        self.chunk_cols = jnp.asarray(host.chunk_cols, dtype=jnp.int32)
        self.chunk_slice = jnp.asarray(host.chunk_slice, dtype=jnp.int32)
        self.inv_perm = jnp.asarray(host.inv_perm, dtype=jnp.int32)
        self.split_pos = jnp.asarray(host.split_pos, dtype=jnp.int32)
        self.split_row = jnp.asarray(host.split_row, dtype=jnp.int32)
        self.segments = _segments(host.chunk_slice, self.num_slices)
        self.use_kernel = resolve_use_kernel(use_kernel, "sell")

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: [n] or [n, nv] -> y: [m] or [m, nv]."""
        with obs.span("kernel.spmv", engine="sell",
                      use_kernel=self.use_kernel, chunks=self.num_chunks,
                      slots=self.padded_nnz):
            squeeze = x.ndim == 1
            if squeeze:
                x = x[:, None]
            nv = x.shape[1]
            if self.use_kernel in ("pallas", "interpret"):
                y = sell_spmm(self.chunk_vals, self.chunk_cols,
                              self.chunk_slice, x, self.num_slices, kb=nv,
                              segments=self.segments,
                              interpret=(self.use_kernel == "interpret"))
            else:
                y = sell_spmm_ref(self.chunk_vals, self.chunk_cols,
                                  self.chunk_slice, x, self.num_slices)
            y = self.rows(y)
            return y[:, 0] if squeeze else y

    def rows(self, y: jax.Array) -> jax.Array:
        """y[S, C, k] in slice order -> [m, k] by row: each row's first
        piece (0 for an empty row, whose position is past the end), plus
        the further pieces of split rows."""
        y = y.reshape(-1, y.shape[-1])
        out = y.at[self.inv_perm].get(mode="fill", fill_value=0)
        return out.at[self.split_row].add(y[self.split_pos])

    def matmul(self, x: jax.Array) -> jax.Array:
        """x: [n, k] -> y: [m, k] via the k-tiled SpMM kernel
        (kernels/sell_spmm): one matrix stream amortized over a lane-aligned
        k-tile of vectors, instead of nv riding along every chunk."""
        if x.ndim == 1:
            return self(x)
        from ..sell_spmm.ops import sell_matmul

        return sell_matmul(self, x)

    # -- operator-cache protocol (core/spmv/opcache.py) --------------------
    def state(self):
        meta = {"shape": list(self.shape), "c": self.c, "sigma": self.sigma,
                "w": self.w, "num_slices": self.num_slices,
                "use_kernel": self.use_kernel, "layout": LAYOUT}
        arrays = {"chunk_vals": np.asarray(self.chunk_vals),
                  "chunk_cols": np.asarray(self.chunk_cols),
                  "chunk_slice": np.asarray(self.chunk_slice),
                  "inv_perm": np.asarray(self.inv_perm),
                  "split_pos": np.asarray(self.split_pos),
                  "split_row": np.asarray(self.split_row)}
        return meta, arrays

    @classmethod
    def from_state(cls, meta, arrays, dtype=jnp.float32):
        if meta.get("layout") != LAYOUT:
            # an entry written before rows on lanes holds its chunks
            # transposed: the stores read the refusal as a miss and rebuild
            raise ValueError(f"stored SELL layout {meta.get('layout')!r}, "
                             f"this code reads {LAYOUT!r}")
        op = object.__new__(cls)
        op.shape = tuple(meta["shape"])
        op.c, op.sigma, op.w = meta["c"], meta["sigma"], meta["w"]
        op.num_slices = meta["num_slices"]
        op.use_kernel = meta["use_kernel"]
        op.chunk_vals = jnp.asarray(arrays["chunk_vals"], dtype=dtype)
        op.chunk_cols = jnp.asarray(arrays["chunk_cols"])
        op.chunk_slice = jnp.asarray(arrays["chunk_slice"])
        op.inv_perm = jnp.asarray(arrays["inv_perm"])
        op.split_pos = jnp.asarray(arrays["split_pos"])
        op.split_row = jnp.asarray(arrays["split_row"])
        op.segments = _segments(arrays["chunk_slice"], op.num_slices)
        return op

    @property
    def num_chunks(self) -> int:
        """Chunks, which is the kernel's grid steps per k-tile."""
        return int(self.chunk_vals.shape[0])

    @property
    def padded_nnz(self) -> int:
        """Stored element count — the format's work/footprint measure."""
        return int(np.prod(self.chunk_vals.shape))

    def flops(self) -> int:
        """VPU flops per SpMV (2 * stored elements, padding included)."""
        return 2 * self.padded_nnz
