"""OSKI-style per-matrix engine autotuning (Akbudak et al.; Schubert et al.).

SpMV is bandwidth-bound, so the cheap cost model scores each candidate
(engine, shape) by the bytes it streams per multiply — stored values +
index metadata + an x-gather term scaled by a locality penalty derived from
the structural metrics the paper uses (bandwidth, row-nnz CV, block fill).
The model is exact for the padded formats (their footprint IS their traffic)
and a calibrated proxy for the gather engines.

The model is k-aware (multi-vector SpMM): stored matrix bytes stream once
per multiply while x/y traffic scales with the RHS batch width k, so
`tune(mat, k=8)` can pick a different engine than `tune(mat)` — padding-
heavy formats with regular access win once their footprint is amortized.

The per-engine cost functions and candidate grids are attached to the
engine registry (core/registry.py) as `cost_fn` / `candidates_fn`
capability metadata: `candidate_cost` and `enumerate_candidates` dispatch
over whatever engines are registered, so a plugin engine that ships a cost
model participates in tuning with no change here.

Four tuning modes (the `probe` argument):
  * False        — model: rank candidates by modelled bytes, build the
                   argmin. Free.
  * True         — probe: additionally time the top PROBE_TOP_K
                   candidates (OSKI's empirical search) and build the
                   measured winner.
  * "exhaustive" — time EVERY candidate: ground truth for the learned
                   mode, and the reference the regression tests hold the
                   advisor to.
  * "learned"    — ask the corpus TuneAdvisor (repro.corpus.advisor) for
                   a nearest-neighbor shortlist mined from prior
                   ResultStore campaigns and probe only that (strictly
                   fewer candidates than either probe mode); empty
                   knowledge base falls back to the model's top
                   PROBE_TOP_K and bumps `advisor.fallbacks`.

`build_tuned` is what the engine="auto" build path calls; the chosen
`TunePlan` rides on the returned operator as `.plan` so benchmarks can
report plan-time decisions next to run-time numbers. Persistent reuse of
tuned operators across processes lives in opcache.py; the joint
(scheme x engine) planner is core/spmv/plan.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ... import obs
from .. import registry
from ..sparse import metrics
from ..sparse.csr import CSRMatrix
from ..sparse.sell import pick_chunk_width, sell_padded_nnz

# dense fallback threshold: below this many logical entries the dense
# engine's simplicity beats any sparse format's index traffic
_DENSE_MAX_ENTRIES = 64 * 64
PROBE_TOP_K = 3
PROBE_ITERS = 3

# the values `probe` accepts, here and up through plan()/MeasurePolicy
PROBE_MODES = (False, True, "learned", "exhaustive")

_VAL = 4          # float32 bytes
_IDX = 4          # int32 bytes


@dataclasses.dataclass(frozen=True)
class TunePlan:
    engine: str                       # chosen engine name
    block_shape: tuple                # (bm, bn) bell/bcsr; (C, W) sell
    sell_sigma: Optional[int]         # σ window (sell only)
    cost_bytes: float                 # modelled bytes/SpMM of the choice
    costs: dict                       # candidate label -> modelled bytes
    features: dict                    # structural features the model used
    source: str                       # "model" | "probe" | "learned" | "fixed"
    probe_ms: Optional[dict] = None   # candidate label -> measured ms
    tune_ms: float = 0.0              # wall time spent deciding
    k: int = 1                        # RHS batch width the plan was tuned for
    advisor: Optional[dict] = None    # learned mode: {confidence, predicted,
    #                                   hit, shortlist} (None otherwise)

    def label(self) -> str:
        base = _label(self.engine, self.block_shape, self.sell_sigma)
        return base if self.k == 1 else f"{base}@k{self.k}"

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["block_shape"] = list(self.block_shape)
        return d

    @staticmethod
    def from_json(d: dict) -> "TunePlan":
        d = dict(d)
        d["block_shape"] = tuple(d["block_shape"])
        return TunePlan(**d)


def fixed_plan(engine: str, block_shape: tuple = (8, 128),
               sell_sigma: Optional[int] = None, k: int = 1) -> TunePlan:
    """A TunePlan for an explicitly requested engine (no search). Gives the
    pipeline layer (plan.py) one uniform decision record to serialize."""
    if engine == "sell" and sell_sigma is None:
        sell_sigma = 8 * block_shape[0]
    return TunePlan(engine=engine, block_shape=tuple(block_shape),
                    sell_sigma=sell_sigma, cost_bytes=0.0, costs={},
                    features={}, source="fixed", k=max(int(k), 1))


def _label(engine: str, block_shape: tuple, sigma: Optional[int]) -> str:
    if engine in ("csr", "ell", "dia", "dense"):
        return engine
    if engine == "sell":
        return f"sell_c{block_shape[0]}w{block_shape[1]}s{sigma}"
    return f"{engine}_{block_shape[0]}x{block_shape[1]}"


def diagonal_count(rows: np.ndarray, cols: np.ndarray, m: int,
                   n: int) -> int:
    """The number of distinct col - row among the entries (rows[i],
    cols[i]), by one bincount over the m + n - 1 possible offsets:
    np.unique would sort the nnz, on every scheme of every plan."""
    return int(np.count_nonzero(np.bincount(
        cols - rows + (m - 1), minlength=max(m + n - 1, 0))))


def matrix_features(mat: CSRMatrix, bm: int = 8, bn: int = 128) -> dict:
    """The structural quantities the cost model conditions on."""
    counts = mat.row_nnz()
    mean = float(counts.mean()) if mat.m else 0.0
    cv = float(counts.std() / mean) if mean > 0 else 0.0
    r = np.repeat(np.arange(mat.m, dtype=np.int64), counts)
    c = mat.cols.astype(np.int64)
    nbc = (mat.n + bn - 1) // bn
    bkeys = (r // bm) * nbc + (c // bn)
    ub, bcounts = np.unique(bkeys, return_counts=True) if mat.nnz else (
        np.empty(0, np.int64), np.empty(0, np.int64))
    nblocks = int(ub.size)
    br_counts = np.bincount((ub // nbc).astype(np.int64),
                            minlength=(mat.m + bm - 1) // bm) if nblocks else \
        np.zeros((mat.m + bm - 1) // max(bm, 1), dtype=np.int64)
    return {
        "m": int(mat.m),
        "n": int(mat.n),
        "nnz": int(mat.nnz),
        "row_nnz_max": int(counts.max()) if mat.m else 0,
        "row_nnz_cv": cv,
        "avg_row_bandwidth": metrics.avg_row_bandwidth(mat),
        # bandwidth + envelope feed the advisor's feature space (both are
        # single O(nnz) passes); cost models ignore them
        "bandwidth": metrics.bandwidth(mat),
        "profile_per_row": float(metrics.profile(mat)) / max(mat.m, 1),
        "block_fill": float(mat.nnz / max(nblocks * bm * bn, 1)),
        "nonempty_blocks": nblocks,
        "block_row_max": int(br_counts.max()) if br_counts.size else 0,
        "num_block_rows": int(br_counts.shape[0]),
        "diagonals": diagonal_count(r, c, mat.m, mat.n),
    }


def _gather_penalty(feat: dict, line: int = 128) -> float:
    """Model of x-vector re-read traffic for element-gather engines.

    When the matrix bandwidth is small, consecutive rows touch the same x
    cache lines / VMEM tiles and the effective x traffic approaches one
    read of x; when nonzeros are scattered (shuffled/uniform matrices), each
    nonzero pays a full line fetch. Interpolate on avg row bandwidth
    measured in lines — the quantity RCM minimizes.
    """
    spread = feat["avg_row_bandwidth"] / line
    return 1.0 + min(spread, 8.0)


def _gather(feat: dict, k: int) -> float:
    """k-amortized gather penalty: the k values of a gathered x row are
    contiguous in the [n, k] layout, so the line fetched for one vector's
    element carries its k-tile siblings for free."""
    return 1.0 + (_gather_penalty(feat) - 1.0) / min(k, 32)


# -- per-engine cost models (attached to the registry as cost_fn) ----------
# Signature: (feat, block_shape, sigma, sell_pad, k) -> modelled bytes.
# cost(k) = matrix_bytes + k * per_vector_bytes: stored values and index
# metadata stream ONCE per multiply regardless of k (the SpMM kernels reuse
# each chunk/block across the vector tile), while the x-gather and y-write
# terms scale with k. k=1 reduces exactly to the per-SpMV model.

def cost_dense(feat, block_shape, sigma, sell_pad, k):
    m, n = feat["m"], feat["n"]
    return float(m * n * _VAL + k * (n * _VAL + m * _VAL))


def cost_csr(feat, block_shape, sigma, sell_pad, k):
    # vals + cols + row ids (COO expansion) + k x (gathered x + y)
    m, nnz = feat["m"], feat["nnz"]
    return float(nnz * (_VAL + 2 * _IDX)
                 + k * (nnz * _VAL * _gather(feat, k) * 0.25 + m * _VAL))


def cost_ell(feat, block_shape, sigma, sell_pad, k):
    m = feat["m"]
    pad = m * max(feat["row_nnz_max"], 1)
    return float(pad * (_VAL + _IDX)
                 + k * (pad * _VAL * _gather(feat, k) * 0.25 + m * _VAL))


def cost_sell(feat, block_shape, sigma, sell_pad, k):
    pad = sell_pad if sell_pad is not None else feat["nnz"]
    return float(pad * (_VAL + _IDX)
                 + k * (pad * _VAL * _gather(feat, k) * 0.25
                        + feat["m"] * _VAL))


def cost_dia(feat, block_shape, sigma, sell_pad, k):
    # one value a row of every diagonal, no index; each diagonal reads a
    # contiguous window of x, so x streams once a diagonal, no penalty
    d = feat["diagonals"]
    return float(d * feat["m"] * _VAL
                 + k * (d * feat["n"] * _VAL + feat["m"] * _VAL))


def cost_bell(feat, block_shape, sigma, sell_pad, k):
    bm, bn = block_shape
    pad_blocks = feat["num_block_rows"] * max(feat["block_row_max"], 1)
    return float(pad_blocks * (bm * bn * _VAL + _IDX)
                 + k * (pad_blocks * bn * _VAL + feat["m"] * _VAL))


def cost_bcsr(feat, block_shape, sigma, sell_pad, k):
    bm, bn = block_shape
    blocks = max(feat["nonempty_blocks"], 1)
    return float(blocks * (bm * bn * _VAL + 2 * _IDX)
                 + k * (blocks * bn * _VAL + feat["m"] * _VAL))


# -- per-engine candidate grids (attached as candidates_fn) ----------------
# Signature: (mat, feat) -> [{"block_shape": ..., "sigma": ..., ...}].
# Kept deliberately small — OSKI's lesson is that a handful of well-chosen
# candidates capture the attainable speedup.

def cands_default(mat, feat):
    return [dict(block_shape=(8, 128), sigma=None)]


def cands_sell(mat, feat):
    # rows on lanes: a slice is one lane row (C = 128) and W a multiple of
    # the f32 sublane count, so each chunk is whole (8, 128) tiles
    c = 128
    out = []
    for w in sorted({8, pick_chunk_width(mat)}):
        # σ = whole-matrix sort packs similar-degree rows best; the small
        # window keeps rows near their reordered position (cache locality)
        for sigma in (8 * c, max(int(feat["m"]), 1)):
            out.append(dict(block_shape=(c, w), sigma=sigma,
                            sell_pad=sell_padded_nnz(mat, c, sigma, w)))
    return out


def cands_dense(mat, feat):
    if feat["m"] * feat["n"] <= _DENSE_MAX_ENTRIES:
        return [dict(block_shape=(8, 128), sigma=None)]
    return []


def candidate_cost(feat: dict, engine: str, block_shape: tuple = (8, 128),
                   sigma: Optional[int] = None,
                   sell_pad: Optional[int] = None, k: int = 1) -> float:
    """Modelled bytes streamed per SpMM with k right-hand sides, dispatched
    through the engine registry's cost_fn. Dividing by k gives the
    amortized per-vector cost the spmm_batch benchmark measures."""
    from . import ops  # noqa: F401 — ensure built-in engines are registered

    spec = registry.get_engine(engine)
    if spec.cost_fn is None:
        raise KeyError(f"engine {engine!r} registered without a cost_fn")
    return spec.cost_fn(feat, block_shape, sigma, sell_pad, max(int(k), 1))


def enumerate_candidates(mat: CSRMatrix, feat: dict) -> list[dict]:
    """The (engine, shape) grid the tuner searches: every registered engine
    with a cost model contributes its candidates_fn grid, in registration
    order (built-ins: csr, ell, dia, bell, bcsr, sell, dense)."""
    from . import ops  # noqa: F401 — ensure built-in engines are registered

    cands = []
    for spec in registry.ENGINE_REGISTRY.values():
        if spec.cost_fn is None or spec.candidates_fn is None:
            continue
        for shape in spec.candidates_fn(mat, feat):
            cands.append(dict({"engine": spec.name}, **shape))
    return cands


def tune(mat: CSRMatrix, probe=False, dtype=None,
         use_kernel: str = "auto", k: int = 1, advisor=None) -> TunePlan:
    """Pick (engine, shape) for `mat` at RHS batch width k.

    `probe` is one of PROBE_MODES (see module docstring). `advisor`
    optionally injects a corpus TuneAdvisor for probe="learned"; by
    default the process-wide advisor over the default ResultStore is
    used.
    """
    if probe not in PROBE_MODES:
        raise ValueError(f"probe must be one of {PROBE_MODES}, got {probe!r}")
    with obs.timed("plan.tune", shape=str(tuple(mat.shape)),
                   nnz=int(mat.nnz), probe=str(probe), k=int(k)) as _sp:
        tp = _tune_impl(mat, probe, dtype, use_kernel, k, _sp, advisor)
    # tune_ms is the span's own interval
    return dataclasses.replace(tp, tune_ms=_sp.ms)


def _probe_set(probe, ranked, feat, advisor):
    """The candidates to time, plus the advisor record for learned mode."""
    if probe == "exhaustive":
        return ranked, None
    if probe != "learned":
        return ranked[:PROBE_TOP_K], None
    if advisor is None:
        from ...corpus.advisor import default_advisor
        advisor = default_advisor()
    shortlist, confidence, predicted = advisor.shortlist(feat, ranked)
    if not shortlist:
        obs.counter("advisor.fallbacks").inc()
        return ranked[:PROBE_TOP_K], {"confidence": 0.0, "predicted": None,
                                      "hit": None, "shortlist": 0}
    return shortlist, {"confidence": confidence, "predicted": predicted,
                       "hit": None, "shortlist": len(shortlist)}


def _tune_impl(mat: CSRMatrix, probe, dtype, use_kernel: str, k: int,
               _sp, advisor=None) -> TunePlan:
    k = max(int(k), 1)
    feat = matrix_features(mat)
    cands = enumerate_candidates(mat, feat)
    costs = {}
    for cd in cands:
        costs[_label(cd["engine"], cd["block_shape"], cd["sigma"])] = \
            candidate_cost(feat, cd["engine"], cd["block_shape"], cd["sigma"],
                           cd.get("sell_pad"), k=k)
    ranked = sorted(cands, key=lambda cd: costs[
        _label(cd["engine"], cd["block_shape"], cd["sigma"])])
    probe_ms = None
    best = ranked[0]
    source = "model"
    adv_info = None
    if probe:
        import jax.numpy as jnp

        from ..measure import ios
        from .ops import make_engine

        to_probe, adv_info = _probe_set(probe, ranked, feat, advisor)
        dt = jnp.float32 if dtype is None else dtype
        probe_ms = {}
        best_ms = np.inf
        for cd in to_probe:
            lab = _label(cd["engine"], cd["block_shape"], cd["sigma"])
            with obs.span("plan.probe", candidate=lab,
                          engine=cd["engine"], k=int(k)) as psp:
                op = make_engine(mat, cd["engine"], dtype=dt,
                                 block_shape=cd["block_shape"],
                                 sell_sigma=cd["sigma"],
                                 use_kernel=use_kernel)
                ms = float(np.median(ios.run_ios_batched(
                    op, mat.n, k, iters=PROBE_ITERS, warmup=1, dtype=dt)))
                psp.set(ms=ms)
            probe_ms[lab] = ms
            if ms < best_ms:
                best_ms, best = ms, cd
        winner = _label(best["engine"], best["block_shape"], best["sigma"])
        if adv_info is not None and adv_info["predicted"] is not None:
            # predicted-vs-probed agreement: the advisor's learning signal
            hit = adv_info["predicted"] == winner
            adv_info["hit"] = hit
            obs.counter("advisor.hits" if hit else "advisor.misses").inc()
            source = "learned"
        else:
            source = "probe"
    lab = _label(best["engine"], best["block_shape"], best["sigma"])
    _sp.set(engine=best["engine"], source=source,
            diagonals=feat["diagonals"])
    return TunePlan(engine=best["engine"], block_shape=best["block_shape"],
                    sell_sigma=best["sigma"], cost_bytes=costs[lab],
                    costs=costs, features=feat, source=source,
                    probe_ms=probe_ms, k=k,
                    advisor=adv_info)


def build_from_plan(mat: CSRMatrix, plan: TunePlan, dtype=None,
                    use_kernel: str = "auto", nnz_bucket: int = 0):
    """Materialize the operator a plan describes (used by the op cache and
    the pipeline layer). The plan's k only steered the engine choice; the
    format is k-agnostic."""
    import jax.numpy as jnp

    from .ops import make_engine

    dt = jnp.float32 if dtype is None else dtype
    op = make_engine(mat, plan.engine, dtype=dt,
                     block_shape=plan.block_shape,
                     sell_sigma=plan.sell_sigma, use_kernel=use_kernel,
                     nnz_bucket=nnz_bucket)
    op.plan = plan
    return op


def build_tuned(mat: CSRMatrix, dtype=None, probe: bool = False,
                use_kernel: str = "auto", nnz_bucket: int = 0, k: int = 1):
    """engine="auto" entry point: tune, build, attach the plan."""
    plan = tune(mat, probe=probe, dtype=dtype, use_kernel=use_kernel, k=k)
    return build_from_plan(mat, plan, dtype=dtype, use_kernel=use_kernel,
                           nnz_bucket=nnz_bucket)
