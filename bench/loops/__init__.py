"""The timed loops a traffic mix can name (`"loop"` in its file).

Each module holds one `Loop` class, built from the operator, the matrix,
the traffic's parameters, the run's seed and the compute dtype:

    warm_up()       run every program the window will run, once
    dispatch(i)     enqueue unit i of the window; returns an array that is
                    ready when the unit has finished
    least_bytes()   the roofline's bytes of one unit (bench/least_bytes.py)
    check()         after the window: {name: [one number per answer
                    compared]}, each against the plain host reference
                    in float64

A unit is the work one end-to-end sample stands for: a CG set, a PageRank
iteration.

A traffic file may state `k`, the width of the block its loop hands to
`op.matmul` (default 1); the harness plans the operator for that width
(`SpmvProblem(mat, k=k)`, a plan labelled `@k<k>`), so a loop over
k-column blocks runs an operator the tuner priced for them, and counts
its least bytes with `least_bytes.spmm` over the same k.
"""
from __future__ import annotations

import numpy as np


class Reservoir:
    """A uniform sample of at most `k` of the units offered, drawn from the
    seed, so memory and the reference's time stay bounded however many
    units a window runs."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.seen = 0
        self.kept: list = []

    def offer(self, index: int, item) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append((index, item))
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.kept[j] = (index, item)

    def items(self) -> list:
        return sorted(self.kept, key=lambda kv: kv[0])


def host_matrix(mat):
    """The matrix as scipy CSR in float64, for the reference."""
    import scipy.sparse as sps

    return sps.csr_matrix((mat.vals.astype(np.float64), mat.cols,
                           mat.rowptr), shape=mat.shape)


def max_gap(got: np.ndarray, want: np.ndarray, scale) -> float:
    """max |got - want| / scale; infinite for a non-finite answer."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / scale))
