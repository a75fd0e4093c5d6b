"""Fused all-pairs-distance + running top-k kNN Pallas kernel.

The BASELINE.json north star names this kernel explicitly: "a kNN-graph +
LOF outlier scorer as a batched all-pairs-distance + top-k Pallas kernel".
The reference project has no kNN at all (its outlier rule is a community
size threshold, ``Graphframes.py:135-136``); this is the upgrade path.

Design (TPU-first):

- 2-D sequential grid ``(row_tiles, col_tiles)``. Each step computes one
  ``[TM, TC]`` block of squared distances with a single MXU matmul
  (``rows @ cols.T``) and immediately folds it into a per-row running
  top-k held in VMEM scratch — the ``[N, N]`` distance matrix never
  exists in HBM, so the working set is ``O(TM * (TC + k))``.
- The fold is k rounds of min-extraction over the ``[TM, k + TC]``
  concatenation (VPU work comparable to the matmul's MXU work at
  k ≈ 16-64, TC = 256-512). ``lax.top_k`` is avoided: it has no TPU
  Pallas lowering, and extraction yields ascending order for free.
- Scratch persists across the column (innermost, "arbitrary") grid
  dimension; results are flushed to the output refs on the last column
  step. Row tiles are independent ("parallel").
- Self-matches and padding columns are masked to +inf before the fold.

The XLA implementation in :mod:`graphmine_tpu.ops.knn` is the oracle;
``tests/test_pallas.py`` checks exact index agreement on tie-free inputs
in interpreter mode (CPU) and the dispatcher picks this kernel on TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_BIG = float("inf")


def _knn_kernel(rows_ref, cols_ref, out_d_ref, out_i_ref, best_d, best_i,
                *, k: int, n: int, tm: int, tc: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_d[:] = jnp.full_like(best_d, _BIG)
        best_i[:] = jnp.full_like(best_i, -1)

    rows = rows_ref[:]                                   # [TM, F]
    cols = cols_ref[:]                                   # [TC, F]
    # d2[a, b] = |r_a|^2 - 2 r_a . c_b + |c_b|^2 — the matmul is the MXU op.
    # precision=HIGHEST: match the XLA oracle's true-f32 products — the
    # MXU's default bf16 rounding diverged ~1e-2 from CPU (r4 audit).
    cross = jax.lax.dot_general(
        rows, cols,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                                    # [TM, TC]
    row_sq = jnp.sum(rows * rows, axis=1, keepdims=True)
    col_sq = jnp.sum(cols * cols, axis=1)[None, :]
    d2 = jnp.maximum(row_sq - 2.0 * cross + col_sq, 0.0)

    row_ids = i * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tc), 0)
    col_ids = j * tc + jax.lax.broadcasted_iota(jnp.int32, (tm, tc), 1)
    invalid = (row_ids == col_ids) | (col_ids >= n) | (row_ids >= n)
    d2 = jnp.where(invalid, _BIG, d2)

    # Fold the tile into the running top-k: k rounds of min-extraction over
    # the [TM, k + TC] concat. Ascending output order falls out of the
    # extraction order; ties break toward the candidate buffer's leftmost
    # column, i.e. toward the smallest global column id, matching the
    # ascending-index tie order of lax.top_k over -d2 in the XLA oracle.
    cat_d = jnp.concatenate([best_d[:], d2], axis=1)      # [TM, k + TC]
    cat_i = jnp.concatenate([best_i[:], col_ids], axis=1)
    width = k + tc
    lane = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 1)

    new_d = []
    new_i = []
    for _ in range(k):
        m = jnp.min(cat_d, axis=1, keepdims=True)               # [TM, 1]
        first = jnp.min(jnp.where(cat_d == m, lane, width), axis=1, keepdims=True)
        hit = lane == first                                      # one per row
        chosen_i = jnp.sum(jnp.where(hit, cat_i, 0), axis=1, keepdims=True)
        new_d.append(m)
        new_i.append(chosen_i)
        cat_d = jnp.where(hit, _BIG, cat_d)
    best_d[:] = jnp.concatenate(new_d, axis=1)
    best_i[:] = jnp.concatenate(new_i, axis=1)

    @pl.when(j == nj - 1)
    def _flush():
        out_d_ref[:] = best_d[:]
        out_i_ref[:] = best_i[:]


@functools.partial(
    jax.jit, static_argnames=("k", "row_tile", "col_tile", "interpret")
)
def knn_pallas(points: jax.Array, k: int, row_tile: int = 128,
               col_tile: int = 512, interpret: bool = False):
    """k nearest neighbors (squared Euclidean, self excluded), fused on TPU.

    Same contract as :func:`graphmine_tpu.ops.knn.knn`: returns
    ``(dists, idx)`` of shape ``[N, k]``, ascending by distance.
    """
    n, f = points.shape
    if k >= n:
        raise ValueError(f"k={k} must be < number of points {n}")
    if k > 128:
        raise ValueError("knn_pallas supports k <= 128")

    # Pad rows to the tile grid and features to the 128-lane layout; padding
    # rows/columns are masked inside the kernel, zero-padded features are
    # distance-neutral. n must pad to a common multiple of both tile sizes —
    # the grid divides by each independently.
    tile_lcm = math.lcm(row_tile, col_tile)
    n_pad = -(-n // tile_lcm) * tile_lcm
    f_pad = max(-(-f // 128) * 128, 128)
    pts = jnp.pad(points.astype(jnp.float32), ((0, n_pad - n), (0, f_pad - f)))

    grid = (n_pad // row_tile, n_pad // col_tile)
    kernel = functools.partial(
        _knn_kernel, k=k, n=n, tm=row_tile, tc=col_tile
    )
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, f_pad), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((col_tile, f_pad), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((row_tile, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((row_tile, k), jnp.float32),
            pltpu.VMEM((row_tile, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pts, pts)
    return out_d[:n], out_i[:n]
