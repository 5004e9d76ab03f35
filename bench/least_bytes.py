"""The bytes a roofline share is measured against.

Each count is what no implementation of the same arithmetic in the
stated precision can avoid moving through HBM: the stored values once,
and each vector once. Column indices, row pointers, permutations and
padding are left out, because a format may compress or avoid them. The
counts therefore depend only on the matrix's size and nonzeros, never on
the engine, the format or the reordering the planner picks, so a roofline
share rises only when the same work takes less device time.
"""
from __future__ import annotations


def spmv(nnz: int, m: int, n: int, itemsize: int = 4) -> int:
    """y = A x: the values once, x once (n), y once (m)."""
    return spmm(nnz, m, n, 1, itemsize)


def spmm(nnz: int, m: int, n: int, k: int, itemsize: int = 4) -> int:
    """Y = A X over k columns: the values once, and each of the k columns
    of X (n) and of Y (m) once. `spmv` at k = 1."""
    return itemsize * (nnz + k * (n + m))


def cg_iteration(nnz: int, n: int, itemsize: int = 4) -> int:
    """One CG iteration: the values once, and the iterate x, the residual
    r and the direction p each read and written once."""
    return itemsize * (nnz + 6 * n)
