"""Compile each Pallas engine for a described TPU v5e at real shapes.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: per-element VMEM gathers, SMEM overflow from scalar-prefetched
index arrays, VMEM blocks that outgrow the chip. Here each kernel is
lowered and compiled for one chip of a described v5e:2x2 topology —
nothing runs, no chip is needed.

Shapes are those of the HPCG-style operator `stencil_3d(104)` (1,124,864
rows, 7,809,152 nnz) under `rcm`, as the planner builds it with the
default (8, 128) blocks: Block-ELL 140,608 block rows x K = 7, BCSR
592,192 blocks. SELL-C-σ is compiled at the shape of the Graph500 scale-21
PageRank operator, its (C, W) = (128, 8) chunks laid rows on lanes: 85,072
chunks over 2,097,152 columns (at W = 32, the same slots in a quarter of
the chunks). At these sizes the unsegmented
Block-ELL and BCSR kernels were refused ("Ran out of memory in memory
space smem"); each pallas_call now covers one SMEM segment, so the
program holds several.

ELL, the XLA engine the planner picks at these shapes, is compiled too:
its row-major [m, K] scalar gather took the compiler ~100 s here. So is
DIA at HPCG's 27 diagonals on the 104^3 grid in its own numbering: with
its values flat, an SpMV is one loop fusion (a [27, m] array made XLA
copy each (8, 128)-tiled row into x's 1-D layout first).
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spmv import ops
from repro.kernels.bcsr_spmv.kernel import bcsr_spmm
from repro.kernels.bell_spmv.kernel import bell_spmm
from repro.kernels.segments import row_aligned_segments
from repro.kernels.sell_spmv.kernel import sell_spmm

M = 1_124_864                 # rows of stencil_3d(104)
NBR = M // 8                  # block rows at bm = 8
NCB = -(-M // 128)            # column blocks at bn = 128
BELL_K = 7                    # max blocks per block row under rcm
BCSR_T = 592_192              # nonempty (8, 128) blocks under rcm
SELL_SLOTS = 85_072 * 8 * 128  # [W, C] chunks of Graph500 scale 21 at W = 8
SELL_N = 2 ** 21              # its columns
ELL_K = 7                     # max row nnz
HPCG_OFFSETS = tuple(sorted(dz * 104 * 104 + dy * 104 + dx
                            for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                            for dx in (-1, 0, 1)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_kernels(lowered) -> int:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("k", [1, 8])
def test_bell_compiles_for_v5e(sds, k):
    f32 = jnp.float32
    lowered = bell_spmm.lower(sds((NBR, BELL_K, 8, 128), f32),
                              sds((NBR * BELL_K,), jnp.int32),
                              sds((NCB, 128, k), f32))
    n_seg = len(row_aligned_segments(np.arange(NBR + 1) * BELL_K, n_arrays=1))
    assert n_seg > 1
    assert _compiled_kernels(lowered) >= n_seg


@pytest.mark.parametrize("k", [1, 8])
def test_bcsr_compiles_for_v5e(sds, k):
    counts = np.full(NBR, BCSR_T // NBR)
    counts[:BCSR_T - counts.sum()] += 1
    segs = row_aligned_segments(np.concatenate([[0], np.cumsum(counts)]))
    assert len(segs) > 1
    lowered = bcsr_spmm.lower(sds((BCSR_T, 8, 128), jnp.float32),
                              sds((BCSR_T,), jnp.int32),
                              sds((BCSR_T,), jnp.int32),
                              sds((NCB, 128, k), jnp.float32),
                              num_block_rows=NBR, segments=segs)
    assert _compiled_kernels(lowered) >= len(segs)


def _sell_chunk_shapes() -> set:
    """(W, C) of the tuner's SELL candidates on rows of 27 nonzeros."""
    from repro.core.sparse.csr import CSRMatrix
    from repro.core.spmv.tune import cands_sell, matrix_features

    m, deg = 1024, 27
    rng = np.random.default_rng(0)
    cols = np.concatenate([rng.choice(m, deg, replace=False)
                           for _ in range(m)])
    mat = CSRMatrix.from_coo(np.repeat(np.arange(m), deg), cols,
                             np.ones(m * deg, np.float32), (m, m))
    return {(cd["block_shape"][1], cd["block_shape"][0])
            for cd in cands_sell(mat, matrix_features(mat))}


@pytest.mark.parametrize("w", [8, 32])
@pytest.mark.parametrize("k", [1, 8])
def test_sell_compiles_for_v5e(sds, w, k):
    chunk = (w, 128)
    assert chunk in _sell_chunk_shapes()
    # a chunk is a whole number of f32 (8, 128) tiles: no HBM tile padding
    assert chunk[0] % 8 == 0 and chunk[1] % 128 == 0
    t = SELL_SLOTS // (w * 128)
    segs = row_aligned_segments(np.arange(t + 1), n_arrays=1)
    assert len(segs) == 1
    lowered = sell_spmm.lower(sds((t,) + chunk, jnp.float32),
                              sds((t,) + chunk, jnp.int32),
                              sds((t,), jnp.int32),
                              sds((SELL_N, k), jnp.float32),
                              num_slices=t, kb=k, segments=segs)
    assert _compiled_kernels(lowered) == 1


@pytest.mark.parametrize("k", [1, 8])
def test_ell_compiles_for_v5e_in_seconds(sds, k):
    x = sds((M,), jnp.float32) if k == 1 else sds((M, k), jnp.float32)
    fn = ops._ell_matvec if k == 1 else ops._ell_matmul
    t0 = time.perf_counter()
    fn.lower(sds((ELL_K, M), jnp.int32), sds((ELL_K, M), jnp.float32),
             x).compile()
    assert time.perf_counter() - t0 < 30


@pytest.mark.parametrize("k", [1, 8])
def test_dia_compiles_for_v5e(sds, k):
    x = sds((M,), jnp.float32) if k == 1 else sds((M, k), jnp.float32)
    compiled = ops._dia_apply.lower(
        sds((len(HPCG_OFFSETS) * M,), jnp.float32), x,
        offsets=HPCG_OFFSETS, m=M).compile()
    if k == 1:
        text = compiled.as_text()
        assert text[text.index("\nENTRY"):].count(" fusion(") == 1
    # no copy of the 121 MB of values: temp is a few vectors at most
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * M * k * 4


def test_cg_over_dia_takes_the_values_as_an_argument(sds):
    """The natural-order CG set: the values reach the loop as an argument
    (a 121 MB constant would be compiled into the program) and no copy
    of them is made."""
    from repro.core.measure.cg import cg_solve

    op = ops.DeviceDIA.tree_unflatten(
        (M, M, HPCG_OFFSETS),
        (sds((len(HPCG_OFFSETS) * M,), jnp.float32),))
    compiled = cg_solve.lower(op, sds((M,), jnp.float32), max_iter=50,
                              tol=0.0).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= len(HPCG_OFFSETS) * M * 4
    assert mem.temp_size_in_bytes < 8 * M * 4
