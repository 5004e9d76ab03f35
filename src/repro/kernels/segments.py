"""Grid segmentation for kernels whose index arrays are scalar-prefetched.

A scalar-prefetched array lives in SMEM, which holds 1 MiB on a TPU v5e.
The Block-ELL and BCSR kernels prefetch one int32 per stored block, so at
a million rows one `pallas_call` over the whole matrix asks for several
MiB and the compiler refuses it. These helpers split the grid into
segments of whole block rows whose index arrays stay under a budget; the
ops layer issues one `pallas_call` per segment and concatenates the rows.
"""
from __future__ import annotations

import numpy as np

# Bytes of prefetched int32 indices one pallas_call may hold: half of the
# v5e's 1 MiB SMEM, leaving the rest to Mosaic's own scalars.
SMEM_INDEX_BYTES = 512 * 1024


def max_prefetch_entries(n_arrays: int) -> int:
    """Entries per prefetched int32 array when `n_arrays` share the budget."""
    return SMEM_INDEX_BYTES // (4 * max(int(n_arrays), 1))


def row_aligned_segments(rowptr, n_arrays: int = 2) -> tuple:
    """(t0, t1, r0, r1) runs of whole rows of a row-sorted entry list with
    row pointer `rowptr`, each holding at most the budget's entries."""
    limit = max_prefetch_entries(n_arrays)
    rowptr = np.asarray(rowptr, np.int64)
    nbr = rowptr.size - 1
    segs = []
    r0 = 0
    while r0 < nbr:
        t0 = int(rowptr[r0])
        r1 = min(int(np.searchsorted(rowptr, t0 + limit, side="right")) - 1,
                 nbr)
        if r1 <= r0:
            raise ValueError(
                f"row {r0} holds {int(rowptr[r0 + 1]) - t0} entries, more "
                f"than the {limit} that fit one segment's SMEM budget")
        segs.append((t0, int(rowptr[r1]), r0, r1))
        r0 = r1
    return tuple(segs)
