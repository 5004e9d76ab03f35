"""Unified reordering API + disk cache.

reorder(mat, scheme, seed) -> permutation (perm[i] = old row at position i)

Schemes (paper §2.1): baseline (identity), random (the Fig. 1 shuffle),
rcm, metis, louvain, patoh. Plus the beyond-paper `rcm_blocked`
(block-fill-aware tie-break — DESIGN.md §10).

Schemes live in the plugin registry (core/registry.py): each is a
`(mat, seed) -> perm` function registered with @register_scheme, and the
pipeline facade (repro.api) plans over whatever is registered. `SCHEMES`
remains as a read-only mapping view for existing callers.

Reordering is plan-time preprocessing (the paper never times it); results
are content-addressed cached on disk so the benchmark suite is re-runnable.
Cache writes are write-then-rename atomic (same tmp-name convention as
core/spmv/opcache.py) so concurrent benchmark runs never read a torn .npy.

`apply_scheme` is a deprecated shim kept for external callers; new code
goes through repro.api.plan(...) whose operators carry the permutation.
"""
from __future__ import annotations

import hashlib
import os
import threading
import warnings
from typing import Iterator, Mapping

import numpy as np

from ... import obs
from ..registry import SCHEME_REGISTRY, get_scheme, register_scheme
from ..sparse.csr import CSRMatrix
from .louvain import louvain_order
from .metis import metis_order, metis_partition
from .patoh import patoh_order, patoh_partition
from .rcm import rcm_order


def _cache_dir() -> str:
    # read per call (not at import) so tests can repoint it via monkeypatch
    return os.environ.get("REPRO_REORDER_CACHE", "/tmp/repro_reorder")


@register_scheme("baseline", auto_candidate=True,
                 description="identity (no reordering)")
def _identity(mat: CSRMatrix, seed: int = 0) -> np.ndarray:
    return np.arange(mat.m, dtype=np.int64)


@register_scheme("metis_nnzbal",
                 description="METIS with degree-weighted (nnz) balance")
def _metis_nnzbal(mat: CSRMatrix, seed: int = 0) -> np.ndarray:
    """METIS with degree-weighted (nnz) balance — the variant that improves
    static LI on skewed graphs (see EXPERIMENTS §Repro claim 7)."""
    return metis_order(mat, seed, degree_weighted=True)


@register_scheme("random", description="random shuffle (paper Fig. 1)")
def _random(mat: CSRMatrix, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).permutation(mat.m).astype(np.int64)


register_scheme("rcm", paper=True, auto_candidate=True,
                description="reverse Cuthill-McKee")(rcm_order)
register_scheme("metis", paper=True,
                description="METIS k-way partition order")(metis_order)
register_scheme("louvain", paper=True,
                description="Louvain community order")(louvain_order)
register_scheme("patoh", paper=True,
                description="PaToH hypergraph partition order")(patoh_order)


@register_scheme("rcm_blocked", auto_candidate=True,
                 description="RCM + block-fill-aware within-window packing")
def _rcm_blocked(mat: CSRMatrix, seed: int = 0, block: int = 8) -> np.ndarray:
    """Beyond-paper: RCM followed by a within-window pass that greedily packs
    rows with similar column-block signatures into the same block-row,
    raising MXU tile density (see benchmarks/roofline + EXPERIMENTS.md §Perf)."""
    base = rcm_order(mat, seed)
    rmat = mat.permute(base)
    m = rmat.m
    win = block * 8
    perm_local = np.arange(m, dtype=np.int64)
    rp = rmat.rowptr.astype(np.int64)
    cols = rmat.cols.astype(np.int64)
    # signature = min col-block touched (cheap proxy for tile overlap);
    # rowptr-gather over all rows at once, empty rows keep the sentinel
    sig = np.full(m, np.iinfo(np.int64).max)
    nonempty = rp[1:] > rp[:-1]
    sig[nonempty] = cols[rp[:-1][nonempty]] // 128
    for w0 in range(0, m, win):
        w1 = min(w0 + win, m)
        rows = np.arange(w0, w1)
        order = np.argsort(sig[w0:w1], kind="stable")
        perm_local[w0:w1] = rows[order]
    return base[perm_local]


class _SchemeView(Mapping):
    """Read-only name -> fn view over the scheme registry (back-compat:
    existing callers index/iterate `SCHEMES` like the old dict)."""

    def __getitem__(self, name: str):
        return get_scheme(name).fn

    def __iter__(self) -> Iterator[str]:
        return iter(SCHEME_REGISTRY)

    def __len__(self) -> int:
        return len(SCHEME_REGISTRY)


SCHEMES = _SchemeView()

PAPER_SCHEMES = [s.name for s in SCHEME_REGISTRY.values() if s.paper]


def _content_key(mat: CSRMatrix, scheme: str, seed: int) -> str:
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(mat.rowptr).tobytes())
    h.update(np.ascontiguousarray(mat.cols).tobytes())
    h.update(f"{scheme}:{seed}".encode())
    return h.hexdigest()[:20]


def reorder(mat: CSRMatrix, scheme: str, seed: int = 0, cache: bool = True) -> np.ndarray:
    return reorder_timed(mat, scheme, seed, cache)[0]


def reorder_timed(mat: CSRMatrix, scheme: str, seed: int = 0,
                  cache: bool = True) -> tuple:
    """(reorder(...), milliseconds of its `plan.reorder` span), the span
    timed whether or not tracing is on: the planner's `reorder_ms`."""
    fn = get_scheme(scheme).fn
    with obs.timed("plan.reorder", scheme=scheme, seed=int(seed),
                   shape=str(tuple(mat.shape))) as sp:
        perm = _reorder(mat, fn, scheme, seed, cache, sp)
    return perm, sp.ms


def _reorder(mat: CSRMatrix, fn, scheme: str, seed: int, cache: bool,
             sp) -> np.ndarray:
    if not cache:
        return fn(mat, seed)
    cache_dir = _cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, _content_key(mat, scheme, seed) + ".npy")
    if os.path.exists(path):
        obs.counter("reorder_cache.hits").inc()
        sp.set(cache_hit=True)
        return np.load(path)
    obs.counter("reorder_cache.misses").inc()
    sp.set(cache_hit=False)
    perm = fn(mat, seed)
    # write-then-rename (opcache.py's tmp-name convention: pid AND
    # thread id) so a concurrent run never reads a torn .npy
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    with open(tmp, "wb") as f:
        np.save(f, perm)
    os.replace(tmp, path)
    return perm


def apply_scheme(mat: CSRMatrix, scheme: str, seed: int = 0, cache: bool = True) -> CSRMatrix:
    """Deprecated: reorder + permute in one call, losing the permutation.

    Use repro.api.plan(SpmvProblem(mat), reorder=scheme) instead — the plan
    carries the permutation and its operator accepts original-index-space
    vectors, so callers no longer hand-permute x/y themselves.
    """
    warnings.warn(
        "apply_scheme() is deprecated; use repro.api.plan(SpmvProblem(mat), "
        "reorder=scheme) — plans carry the permutation (or call "
        "reorder() + mat.permute() explicitly)",
        DeprecationWarning, stacklevel=2)
    perm = reorder(mat, scheme, seed, cache)
    return mat.permute(perm)


PARTITIONERS = {
    "metis": metis_partition,
    "patoh": patoh_partition,
}


def partition_labels(mat: CSRMatrix, scheme: str, k: int, seed: int = 0) -> np.ndarray:
    return PARTITIONERS[scheme](mat, k, seed)
