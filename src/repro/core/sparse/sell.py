"""SELL-C-σ host-side builder (Kreutzer et al., adapted for TPU lanes).

SELL-C-σ = sliced ELLPACK: rows are grouped into slices of C rows and each
slice is padded only to *its own* widest row, after a σ-window sort that
places rows of similar nnz into the same slice. Padding therefore scales
with the per-slice max instead of the global max — on power-law matrices
(the regime where reordering matters most, and where plain ELL storage
explodes) this is the difference between O(nnz) and O(m * max_deg).

TPU adaptation: rows on lanes. The kernel consumes the slice data as
[W, C] chunks: row r of a slice sits on lane r, and its j-th element in
chunk j // W, on sublane j % W. A slice of width K_s becomes
ceil(K_s / W) chunks; with C = 128 (one lane row) and W a multiple of 8
each f32 chunk is a whole number of (8, 128) tiles, so neither the values,
the column ids nor the gathered x carry tile padding in HBM. All chunks
across all slices are flattened into one array, exactly like the BCSR
kernel's flattened block list, with a scalar-prefetched `chunk_slice` map
saying which slice (and hence which y tile) each chunk accumulates into.
A slice with nothing stored (only where the matrix has no nonzero) still
gets one zero chunk so every output tile is written (same contract as
bcsr pad_empty_rows). Any C and W build: the reference
and interpret paths and small tests use small ones.

What a slice holds are *pieces* of rows, not whole rows. An empty row is
not stored at all: its result is 0. A row longer than C·W elements is cut
into pieces of at most C·W, each stored like a row of its own, and the
pieces' partial sums are added after the multiply. So one hub row of a
power-law graph no longer pads the C - 1 rows of its slice to its length,
and runs of empty rows no longer cost a zero chunk per slice: on the
Graph500 scale-21 graph that is 69.3M stored slots for 63.5M nonzeros,
where whole rows took 87.1M.

The σ-sort is a pure *storage* permutation: `row_perm` maps slice position
-> original row, and `inv_perm` (with `split_pos` / `split_row` for the
further pieces of long rows) undoes it after the multiply. It composes
with (and is independent of) the paper's reordering schemes, which permute
the matrix itself.

Builder is numpy-only and fully vectorized; arrays go to JAX in the ops
layer (kernels/sell_spmv/ops.py).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .csr import CSRMatrix


@dataclasses.dataclass(frozen=True)
class SellCS:
    chunk_cols: np.ndarray    # [T, W, C] int32 column ids (padding -> 0)
    chunk_vals: np.ndarray    # [T, W, C] float  (padding -> 0)
    chunk_slice: np.ndarray   # [T] int32, nondecreasing slice id per chunk
    slice_width: np.ndarray   # [S] int32 true (pre-chunk) width of each slice
    row_perm: np.ndarray      # [S*C] int64: original row of the piece at
                              #   slice position i (>= m: phantom padding)
    inv_perm: np.ndarray      # [m] int64: slice position of row r's first
                              #   piece; S*C (past the end) for an empty row
    split_pos: np.ndarray     # [E] int64: slice positions of the further
    split_row: np.ndarray     # [E] int64:   pieces of long rows, their rows
    shape: tuple              # (m, n)
    c: int                    # slice height (rows on lanes: 128 on a TPU)
    sigma: int                # sort-window size (1 = no sorting)
    w: int                    # chunk width (slots on sublanes: 8k on a TPU)

    @property
    def num_slices(self) -> int:
        return int(self.slice_width.shape[0])

    @property
    def num_chunks(self) -> int:
        return int(self.chunk_slice.shape[0])

    @property
    def padded_nnz(self) -> int:
        """Stored element count (the format's memory/work footprint)."""
        return int(self.chunk_vals.size)

    def density_stats(self) -> dict:
        nnz = int(np.count_nonzero(self.chunk_vals))
        return {
            "num_slices": self.num_slices,
            "num_chunks": self.num_chunks,
            "padded_nnz": self.padded_nnz,
            "fill_ratio": nnz / max(self.padded_nnz, 1),
        }


def sell_padded_nnz(mat: CSRMatrix, c: int = 128, sigma: int = 1024,
                    w: int = 8) -> int:
    """Predict SELL-C-σ stored elements WITHOUT building the format.

    Cheap enough for the autotuner's cost model: the rows' pieces, one
    sort of their lengths per σ-window, then per-slice maxima quantized up
    to the chunk width.
    """
    _, _, length = _pieces(mat.row_nnz(), c * w)
    counts = _sorted_counts(length, c, sigma)
    widths = counts.reshape(-1, c).max(axis=1)
    widths = np.maximum(((widths + w - 1) // w) * w, w)
    return int(widths.sum() * c)


def pick_chunk_width(mat: CSRMatrix, lo: int = 8, hi: int = 128) -> int:
    """Adaptive chunk width: smallest power of two covering the 75th
    percentile row, clipped to [lo, hi]. Small-degree corpora want narrow
    chunks (padding scales with W); lo = 8 keeps W a whole number of f32
    sublane tiles."""
    counts = mat.row_nnz()
    p75 = float(np.percentile(counts, 75)) if counts.size else 1.0
    w = lo
    while w < hi and w < p75:
        w *= 2
    return w


def _pieces(counts: np.ndarray, limit: int) -> tuple:
    """The stored rows: every non-empty row cut into pieces of at most
    `limit` elements, in row order. (row, start, length) per piece."""
    counts = np.asarray(counts, np.int64)
    n_pieces = -(-counts // limit)                   # 0 for an empty row
    row = np.repeat(np.arange(counts.size, dtype=np.int64), n_pieces)
    first = np.cumsum(n_pieces) - n_pieces
    start = (np.arange(row.size, dtype=np.int64)
             - np.repeat(first, n_pieces)) * limit
    return row, start, np.minimum(counts[row] - start, limit)


def _sorted_counts(counts: np.ndarray, c: int, sigma: int) -> np.ndarray:
    """Counts, padded to whole slices, after the σ-window sort."""
    perm = _sigma_sort_perm(counts, c, sigma)
    padded = np.zeros(perm.shape[0], dtype=np.int64)
    padded[:counts.shape[0]] = counts
    return padded[perm]


def _sigma_sort_perm(counts: np.ndarray, c: int, sigma: int) -> np.ndarray:
    """row_perm[i] = original row at slice position i (descending nnz within
    each σ-window; stable, so the reordering scheme's row order is preserved
    among equal-degree rows). Positions beyond m map to phantom rows >= m;
    there is at least one slice.

    Vectorized: all windows sort as rows of one 2-D argsort. Buffer slots
    beyond m_pad carry key -1 and larger indices than any real slot, so the
    stable sort puts them last in their window; dropping indices >= m_pad
    afterwards is exact.
    """
    m = counts.shape[0]
    sigma = max(int(sigma), 1)
    m_pad = max((m + c - 1) // c, 1) * c
    nwin = max((m_pad + sigma - 1) // sigma, 1)
    buf = np.full(nwin * sigma, -1, dtype=np.int64)
    buf[:m] = counts
    order = np.argsort(-buf.reshape(nwin, sigma), axis=1, kind="stable")
    perm = (order + sigma * np.arange(nwin, dtype=np.int64)[:, None]).ravel()
    return perm[perm < m_pad]


def to_sell(mat: CSRMatrix, c: int = 128, sigma: int = 1024,
            w: int = 8) -> SellCS:
    """Build SELL-C-σ with rows on lanes: chunks are [W, C].

    c:     slice height, the chunk's lane axis (128 = one TPU lane row)
    sigma: sort window; multiple of c, sigma=1 disables sorting (pure SELL-C)
    w:     chunk width in elements, the chunk's sublane axis (8 = the f32
           sublane count)

    Rows are stored as pieces of at most c * w elements (module doc).
    """
    m, n = mat.shape
    row, start, length = _pieces(mat.row_nnz(), c * w)
    p = row.shape[0]
    perm = _sigma_sort_perm(length, c, sigma)       # slice position -> piece
    s = perm.shape[0] // c
    real = perm < p                                 # phantom positions: no
    pieces = perm[real]                             #   piece, width 0
    pos_p = np.flatnonzero(real)                    # slice position of each
    length_p = np.zeros(s * c, dtype=np.int64)
    length_p[pos_p] = length[pieces]
    slice_width = length_p.reshape(s, c).max(axis=1).astype(np.int32)

    # chunks per slice (>= 1 so each y tile is written at least once)
    chunks_per_slice = np.maximum((slice_width + w - 1) // w, 1).astype(np.int64)
    chunk_start = np.concatenate([[0], np.cumsum(chunks_per_slice)])
    t = int(chunk_start[-1])

    chunk_cols = np.zeros((t, w, c), dtype=np.int32)
    chunk_vals = np.zeros((t, w, c), dtype=mat.vals.dtype)
    chunk_slice = np.repeat(np.arange(s, dtype=np.int32), chunks_per_slice)

    # Vectorized fill. For slice position i = slice*c + lane holding piece
    # perm[i], its element j lands in chunk chunk_start[slice] + j // w at
    # [j % w, lane], and comes from the row's element start + j.
    nnz = mat.nnz
    if nnz:
        rp = mat.rowptr.astype(np.int64)
        cnt_p = length[pieces]
        # ragged per-element indices, in slice-position order:
        ends = np.cumsum(cnt_p)
        j = np.arange(nnz, dtype=np.int64) - np.repeat(ends - cnt_p, cnt_p)
        src = np.repeat(rp[row[pieces]] + start[pieces], cnt_p) + j
        pos = np.repeat(pos_p, cnt_p)                  # slice position
        sl, lane = pos // c, pos % c
        chunk = chunk_start[sl] + j // w
        flat = (chunk * w + j % w) * c + lane
        chunk_cols.reshape(-1)[flat] = mat.cols[src]
        chunk_vals.reshape(-1)[flat] = mat.vals[src]

    piece_pos = np.empty(p, dtype=np.int64)
    piece_pos[pieces] = pos_p
    first = start == 0
    inv_perm = np.full(m, s * c, dtype=np.int64)
    inv_perm[row[first]] = piece_pos[first]
    row_perm = np.full(s * c, m, dtype=np.int64)
    row_perm[pos_p] = row[pieces]
    return SellCS(chunk_cols=chunk_cols, chunk_vals=chunk_vals,
                  chunk_slice=chunk_slice, slice_width=slice_width,
                  row_perm=row_perm, inv_perm=inv_perm,
                  split_pos=piece_pos[~first], split_row=row[~first],
                  shape=(m, n), c=c, sigma=sigma, w=w)


def sell_to_dense(s: SellCS) -> np.ndarray:
    """Debug/test helper: densify (inverse of to_sell up to explicit zeros)."""
    m, n = s.shape
    out = np.zeros((m, n), dtype=s.chunk_vals.dtype)
    ch, ww, lane = np.nonzero(s.chunk_vals)
    pos = s.chunk_slice[ch].astype(np.int64) * s.c + lane
    rows = s.row_perm[pos]
    cols = s.chunk_cols[ch, ww, lane]
    out[rows, cols] = s.chunk_vals[ch, ww, lane]
    return out
