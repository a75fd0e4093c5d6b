"""Segment reductions beyond sum/max — notably *segment mode*.

The one true compute kernel of the reference pipeline is GraphX's Pregel LPA
superstep (``Graphframes.py:81``): each vertex adopts the most frequent label
among its incoming messages. "Most frequent per segment" has no native XLA
segment op; this module implements it with static shapes and pure int32
arithmetic (TPU-friendly, no x64):

  sort (segment, value) pairs  →  run-length rank via a max-scan  →
  segment_max of ranks (max multiplicity)  →  segment_min over the
  max-multiplicity candidates (deterministic smallest-value tie-break).

O(M log M) compute, O(M) memory, fully jit-able, no data-dependent shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_INT32_MAX = jnp.iinfo(jnp.int32).max


def segment_mode(
    segment_ids: jax.Array,
    values: jax.Array,
    num_segments: int,
    indices_are_sorted: bool = False,
    weights: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Most frequent ``value`` per segment; ties break toward the smallest.

    Out-of-range segment ids (e.g. ``num_segments`` used as a padding
    sentinel) are dropped. Empty segments yield ``(INT32_MAX, 0)``.

    ``weights``: optional non-negative per-element weights — the winner
    becomes the value with the largest weight *sum* per segment
    (unweighted = all-ones weights; the weighted LPA semantics).

    Returns ``(mode, count)`` with shapes ``[num_segments]``: the winning
    value and its multiplicity (weight sum, float32, when weighted).

    Note on parity: GraphX's tie-break is implementation-defined (hash-map
    iteration order), so golden comparisons against GraphFrames must compare
    community *partitions*, not raw label values (see SURVEY §6).
    """
    del indices_are_sorted  # the lexicographic sort below handles both cases
    with jax.named_scope("segment_mode"):
        segment_ids = segment_ids.astype(jnp.int32)
        values = values.astype(jnp.int32)
        if weights is not None:
            return _segment_mode_weighted(
                segment_ids, values, weights.astype(jnp.float32), num_segments
            )
        with jax.named_scope("sort"):
            seg_s, val_s = lax.sort((segment_ids, values), num_keys=2)
        with jax.named_scope("run_reduce"):
            m = seg_s.shape[0]
            pos = jnp.arange(m, dtype=jnp.int32)
            new_run = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), (seg_s[1:] != seg_s[:-1]) | (val_s[1:] != val_s[:-1])]
            )
            # Index of each element's run start, via max-scan of start positions.
            # lax.cummax, not associative_scan: the generic scan unrolls into log(M)
            # irregular slice/concat stages that take minutes of TPU compile time at
            # M ~ 10^7; cummax lowers to XLA's native cumulative op (~9x faster
            # compile, same result).
            run_start = lax.cummax(jnp.where(new_run, pos, -1))
            rank = pos - run_start  # 0-based multiplicity-1 within the run
            best_rank = jax.ops.segment_max(
                rank, seg_s, num_segments=num_segments, indices_are_sorted=True
            )
            # Candidates: elements sitting at the maximal rank of their segment
            # (the last element of every maximal-multiplicity run).
            is_cand = rank == best_rank[jnp.clip(seg_s, 0, num_segments - 1)]
            is_cand &= seg_s < num_segments
            cand_val = jnp.where(is_cand, val_s, _INT32_MAX)
            mode = jax.ops.segment_min(
                cand_val, seg_s, num_segments=num_segments, indices_are_sorted=True
            )
            count = jnp.maximum(best_rank + 1, 0)
            return mode, count


def _segment_mode_weighted(segment_ids, values, weights, num_segments):
    """Weighted variant: argmax of per-(segment, value) weight sums, ties
    toward the smallest value. Same sort machinery; the run multiplicity
    becomes the run's weight sum, accumulated *per run* with segment_sum —
    never as differences of a global cumsum, whose float32 quantization at
    M >~ 2^24 elements would corrupt small sums (measured)."""
    with jax.named_scope("sort"):
        seg_s, val_s, w_s = lax.sort((segment_ids, values, weights), num_keys=2)
    with jax.named_scope("run_reduce"):
        m = seg_s.shape[0]
        new_run = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), (seg_s[1:] != seg_s[:-1]) | (val_s[1:] != val_s[:-1])]
        )
        run_id = jnp.cumsum(new_run.astype(jnp.int32)) - 1
        run_total = jax.ops.segment_sum(
            w_s, run_id, num_segments=m, indices_are_sorted=True
        )[run_id]
        best_w = jax.ops.segment_max(
            jnp.where(seg_s < num_segments, run_total, -jnp.inf),
            seg_s, num_segments=num_segments, indices_are_sorted=True,
        )
        # every element of a winning run is a candidate (same value per run)
        is_cand = run_total == best_w[jnp.clip(seg_s, 0, num_segments - 1)]
        is_cand &= seg_s < num_segments
        cand_val = jnp.where(is_cand, val_s, _INT32_MAX)
        mode = jax.ops.segment_min(
            cand_val, seg_s, num_segments=num_segments, indices_are_sorted=True
        )
        return mode, jnp.maximum(best_w, 0.0)
