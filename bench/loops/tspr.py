"""Topic-sensitive PageRank (Haveliwala, "Topic-Sensitive PageRank", WWW
2002): one biased PageRank vector for each of the traffic's `k` topics,
computed together as a power iteration over a block of as many columns
(`k` is also the block width the harness plans the operator for).

Each unit is one application of the program's operator M = A * D^-1 to
the [N, k] block, and the update around it:

    X[:, j] <- d * (M X)[:, j] + (d * dangling_j + (1 - d)) * v_j

v_j is uniform over topic j's teleport set S_j, and dangling_j is the
rank column j holds on vertices with no edges, which goes back to the
column's own teleport set, so every column keeps the sum 1. The sets are
disjoint, each of N * `set_share` vertices, drawn on the device from the
run's seed, and X0 = [v_1 ... v_k]. `op.matmul(X)` is the program's
call; the update around it is the benchmark's own small jitted function.

Assumed: the set share. Haveliwala's topics are the 16 top-level Open
Directory categories, whose sizes have no counterpart on a synthetic
graph, so every topic gets the same share of the vertices.

The check compares two things with float64 on the host, as the PageRank
loop does: a sample of the window's steps, each from its own input
(`step_err`), and the whole chain rerun from X0 (`chain_err`). Each is the
max over vertices and columns of |x - x_ref| / (x_ref + 1 / N): a vertex
that a topic's rank does not reach is held to an absolute error at the
uniform rank's level instead of a division by zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import least_bytes
from bench.loops import Reservoir, host_matrix, max_gap


class Loop:
    def __init__(self, op, mat, traffic: dict, seed: int, dtype):
        self.op, self.mat = op, mat
        self.damping = d = float(traffic["damping"])
        self.k = k = int(traffic["k"])
        n = mat.shape[0]
        size = int(n * float(traffic["set_share"]))
        if not 1 <= size * k <= n:
            raise ValueError(f"{k} disjoint sets of {size} vertices do not "
                             f"fit {n} vertices")
        self.dangling = np.bincount(mat.cols, minlength=n) == 0
        self.mask = jnp.asarray(self.dangling, dtype)

        def topic_of(key):
            """Topic j of each vertex in S_j, -1 elsewhere."""
            chosen = jax.random.permutation(key, n)[:size * k]
            return jnp.full((n,), -1, jnp.int32).at[chosen].set(
                jnp.arange(size * k, dtype=jnp.int32) // size)

        def teleport(topic):
            return jax.nn.one_hot(topic, k, dtype=dtype) / size

        def update(y, x, mask, v):
            lost = jnp.einsum("n,nk->k", mask, x,
                              precision=jax.lax.Precision.HIGHEST)
            return d * y + (d * lost + (1.0 - d)) * v

        self.topic = jax.jit(topic_of)(jax.random.key(seed))
        self.v = self.x = jax.jit(teleport)(self.topic)
        self.units = 0
        self._update = jax.jit(update)
        self.kept = Reservoir(int(traffic["check_units"]), seed)

    def warm_up(self) -> None:
        jax.block_until_ready(self._update(self.op.matmul(self.x), self.x,
                                           self.mask, self.v))

    def dispatch(self, i: int):
        x = self.x
        self.x = self._update(self.op.matmul(x), x, self.mask, self.v)
        self.units += 1
        self.kept.offer(i, (x, self.x))
        return self.x

    def least_bytes(self) -> int:
        m, n = self.mat.shape
        return least_bytes.spmm(self.mat.nnz, m, n, self.k)

    def check(self) -> dict:
        a = host_matrix(self.mat)
        d, n = self.damping, self.mat.shape[0]
        topic = np.asarray(self.topic)
        v = np.zeros((n, self.k))
        inside = topic >= 0
        v[inside, topic[inside]] = 1.0
        v /= v.sum(axis=0)

        def step(x):
            lost = x[self.dangling].sum(axis=0)
            return d * (a @ x) + (d * lost + (1.0 - d)) * v

        errs = []
        for _, (x, x_next) in self.kept.items():
            ref = step(np.asarray(x, np.float64))
            errs.append(max_gap(x_next, ref, ref + 1.0 / n))
        ref = v
        for _ in range(self.units):
            ref = step(ref)
        return {"step_err": errs,
                "chain_err": [max_gap(self.x, ref, ref + 1.0 / n)]}
