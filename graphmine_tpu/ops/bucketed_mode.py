"""Degree-bucketed dense segment-mode — the fast path of the LPA superstep.

The sort-based :func:`graphmine_tpu.ops.segment.segment_mode` pays one
global O(M log M) two-key sort per superstep — at 10^7+ messages the sort
dominates LPA wall-clock. This module exploits two static facts about the
message CSR (``graph.msg_ptr`` — built once on host, ``container.py``):

1. each vertex's messages are a *contiguous* slice, and
2. the slice lengths (degrees) are known at trace time.

So vertices are **bucketed by degree class** and each bucket's messages
are gathered into a dense ``[n_b, w_b]`` matrix whose row-wise mode is
computed with the cheapest method for its width. Measured on TPU v5e, the
superstep is **gather-latency-bound** (~125M gathered elements/s; the mode
arithmetic is ~10x cheaper), so the design minimizes *gathered slots*:

- width classes step by 1.10x (r4; exact widths through degree 20),
  capping row padding at 10% — the r1-r3 1.5x ladder allowed 33%, and
  tightening it moved the gather-bound chip rate +15% on real v5e;
- degree 1 and 2 get exact sentinel-free widths (copy / elementwise-min —
  a two-message mode is ``min``: equal -> that label, tie -> smallest);
- widths <= 32 use an O(w^2) pairwise-equality count (pure VPU compare+add,
  no sort compile), wider buckets the bitonic row sort + run-length scan;
- mega-hubs (degree > 2048) skip dense rows entirely: their neighbor
  labels scatter-add into a per-hub histogram over the label space and
  ``argmax`` picks the mode (first-max = smallest label, matching the
  tie rule) — for as many hubs as ``_HIST_BUDGET // V`` admits (4 at 2^24
  vertices, none on a mesh). The other rows past 2048 are dense rows on
  the same 1.10x ladder, continued at its own step (``_extend_widths``).

Power-law skew (SURVEY §7 hard part 3) is exactly what this absorbs: the
million degree<=8 vertices ride in narrow rows while a degree-100K hub
becomes one histogram pass. The plan (bucket membership + padded gather
indices) is host-precomputed from the static CSR once per graph and reused
across all supersteps and runs — the same amortization the message CSR
itself gets.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import Graph

_SENTINEL = jnp.iinfo(jnp.int32).max

# 1.10x-step width ladder (r4): padding <= 10% per row. The r1-r3 1.5x
# ladder capped padding at 33% and measured 2.374 gathered slots/edge on
# the bench graph; at 1.10x that drops to ~2.08, and since the superstep
# is gather-bound the chip rate moved 54.2 -> 62.6M edges/s/chip on real
# v5e (+15%, ladder experiment r4; 1.08x gained only ~1% more while the
# host plan build kept growing — the kernel is AT the ~130M slots/s
# measured gather roofline from here). Degrees 1-20 get exact widths
# (zero padding where most power-law vertices live). Degrees beyond the
# ladder go to the histogram path (fused plans only) as far as its budget
# admits; every other row rides the ladder continued at the same step
# (``_extend_widths``).
_WIDTHS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18,
           19, 20, 22, 24, 26, 28, 30, 33, 36, 39, 42, 46, 50, 55, 60, 66,
           72, 79, 86, 94, 103, 113, 124, 136, 149, 163, 179, 196, 215,
           236, 259, 284, 312, 343, 377, 414, 455, 500, 550, 605, 665,
           731, 804, 884, 972, 1069, 1175, 1292, 1421, 1563, 1719, 1890,
           2048)
_PAIRWISE_MAX_W = 32      # <=32: O(w^2) pairwise mode; >32: row sort
_HIST_MIN_DEG = 2048      # fused plans: degree above this -> histogram mode
_HIST_BUDGET = 1 << 26    # max total int32 entries across all histograms


def _extend_widths(max_deg: int) -> np.ndarray:
    """The width ladder, continued past its last entry at the ladder's own
    1.10x step (next width ``ceil(1.10 * last)``) until it covers
    ``max_deg``: padding <= 10% holds for every row a plan builds, however
    long. Rows past 2048 are not few where the histogram budget admits few
    hubs (``_HIST_BUDGET // V``: 4 at 2^24 vertices, none on a mesh): on a
    Kronecker graph at scale 24 they hold half the plan's slots."""
    ws = list(_WIDTHS)
    while ws[-1] < max_deg:
        ws.append(int(np.ceil(1.10 * ws[-1])))
    return np.asarray(ws, dtype=np.int64)


@partial(jax.jit, static_argnames=("w", "fill"))
def _gather_rows_device(send, starts, degs, w: int, fill: int):
    """Device-side [n, w] bucket-matrix construction — same output as
    :func:`_class_rows` with ``values=send``, but the big gather runs on
    the accelerator against the already-resident ``[M]`` sender array, so
    the host never materializes (or transfers) the padded matrices."""
    offs = jnp.arange(w, dtype=jnp.int32)[None, :]
    idx = starts[:, None] + offs
    valid = offs < degs[:, None]
    safe = jnp.minimum(idx, send.shape[0] - 1)
    return jnp.where(valid, send[safe].astype(jnp.int32), fill)


def _class_rows(ptr, deg, eligible, classes, c, w, values, fill, num_values,
                out_dtype=np.int32, weight_values=None):
    """Rows and padded [n, w] gather matrix for one width class (host).

    The single source of truth for bucket-row construction, shared by
    :meth:`BucketedModePlan.from_ptr` and the sharded plan builder
    (``parallel/sharded.py``) so the two stay semantically identical.
    ``values=None`` emits message *indices* (non-fused plans); otherwise
    ``values`` is gathered (fused plans: sender ids). Padding slots get
    ``fill``. ``weight_values``: optional per-message weights gathered
    through the SAME idx/valid in the same pass (padding 0) — returns a
    third float32 matrix, avoiding a second full construction.
    """
    rows = np.nonzero((classes == c) & eligible)[0]
    offs = np.arange(w, dtype=np.int64)[None, :]
    idx = ptr[rows][:, None] + offs
    valid = offs < deg[rows][:, None]
    safe = np.minimum(idx, max(num_values - 1, 0))
    if values is None:
        mat = np.where(valid, idx, fill)
    else:
        mat = np.where(valid, values[safe], fill)
    if weight_values is None:
        return rows, mat.astype(out_dtype)
    wmat = np.where(valid, weight_values[safe], 0.0).astype(np.float32)
    return rows, mat.astype(out_dtype), wmat


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BucketedModePlan:
    """Static gather plan: per degree-class vertex ids + message indices.

    ``vertex_ids[b]``: int32 ``[n_b]`` — vertices in bucket ``b``.
    ``msg_idx[b]``: int32 ``[n_b, w_b]`` — indices into the message array,
    padded with ``num_messages`` (gathers a sentinel label slot). ``None``
    on fused plans (``send_idx`` replaces it; halves plan HBM).
    ``send_idx[b]``: optional int32 ``[n_b, w_b]`` — the *sender vertex id*
    behind each slot (padding = ``num_vertices``). When present, the LPA
    superstep gathers straight from the label vector — one fused gather
    instead of materializing the [M] message array and re-gathering it.
    """

    vertex_ids: tuple
    msg_idx: tuple | None
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    num_messages: int = dataclasses.field(metadata=dict(static=True))
    send_idx: tuple | None = None
    # Histogram path (fused plans, degree > _HIST_MIN_DEG): exact (unpadded)
    # sender ids of all hub messages, the owning hub's row offset (row * V)
    # per message, and the hub vertex ids. None when no hub qualifies.
    hist_vertex_ids: jax.Array | None = None
    hist_send: jax.Array | None = None
    hist_row_offset: jax.Array | None = None
    # Weighted-mode payload (built when the graph carries msg_weight):
    # per-class float32 [n_b, w_b] weights aligned slot-for-slot with
    # send_idx/msg_idx (padding = 0), plus the hub messages' weights.
    weight_mat: tuple | None = None
    hist_weight: jax.Array | None = None
    # Slot index by sender (fused plans; added by with_slot_index for the
    # carried-rows scan of ops/lpa.py, never by the builders below): with
    # the classes' [n_b, w_b] rows laid end to end as one flat int32[S]
    # buffer, sender s's label sits in the flat slots
    # out_slot[out_ptr[s]:out_ptr[s + 1]]. A message a histogram hub
    # receives has no slot and maps to S.
    out_ptr: jax.Array | None = None
    out_slot: jax.Array | None = None

    @classmethod
    def from_graph(cls, graph: Graph, with_send: bool = False) -> "BucketedModePlan":
        """Build from a device-resident graph. Note: fetches ``msg_ptr``
        (and ``msg_send`` when ``with_send``) to host; when the original
        edge arrays are still on host, prefer :meth:`from_edges` (no device
        round-trip, fused-gather plan included)."""
        send = np.asarray(graph.msg_send) if with_send else None
        w = None if graph.msg_weight is None else np.asarray(graph.msg_weight)
        return cls.from_ptr(
            np.asarray(graph.msg_ptr), graph.num_vertices, send,
            weights_sorted=w,
        )

    @classmethod
    def from_edges(
        cls, src, dst, num_vertices: int, symmetric: bool = True
    ) -> "BucketedModePlan":
        """Host-pure construction from endpoint arrays — same CSR layout as
        :func:`graphmine_tpu.graph.container.build_graph` (messages grouped
        by receiver, stable order). Includes the fused-gather ``send_idx``
        plan."""
        from graphmine_tpu.graph.container import _message_csr

        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        if src.shape != dst.shape or src.ndim != 1:
            raise ValueError("src/dst must be equal-length 1-D arrays")
        ptr, _, send_sorted, _ = _message_csr(src, dst, num_vertices, symmetric)
        return cls.from_ptr(ptr, num_vertices, send_sorted)

    @classmethod
    def from_ptr(
        cls, ptr: np.ndarray, num_vertices: int,
        send_sorted: np.ndarray | None = None,
        send_device: "jax.Array | None" = None,
        weights_sorted: np.ndarray | None = None,
    ) -> "BucketedModePlan":
        """``send_device``: the device-resident ``[M]`` sender array (e.g.
        ``graph.msg_send``). When given, bucket matrices and hub histogram
        inputs are built on the accelerator — only ``[n_b]`` row starts and
        degrees cross the host boundary instead of the ~2.5E padded plan
        entries. Bit-identical to the host path.

        ``weights_sorted``: optional float [M] per-message weights in the
        same CSR order; builds the weighted-mode payload (host path only).
        """
        if weights_sorted is not None and send_device is not None:
            raise ValueError(
                "weighted plans are host-built; pass send_sorted, not "
                "send_device"
            )
        ptr = np.asarray(ptr).astype(np.int64)
        deg = ptr[1:] - ptr[:-1]
        m = int(ptr[-1])
        if m >= np.iinfo(np.int32).max:
            raise ValueError("message count exceeds int32; shard the build")

        # Mega-hubs -> histogram path (fused plans only: it needs messages
        # to be labels in [0, V)). Budget-capped so the [n_hist, V] count
        # matrix stays bounded; overflow hubs fall back to sort rows.
        hist_mask = np.zeros(len(deg), dtype=bool)
        if send_sorted is not None and num_vertices > 0:
            allowed = max(_HIST_BUDGET // max(num_vertices, 1), 0)
            cand = np.nonzero(deg > _HIST_MIN_DEG)[0]
            if len(cand) > allowed:
                cand = cand[np.argsort(deg[cand], kind="stable")[::-1][:allowed]]
            hist_mask[cand] = True

        widths = _extend_widths(int(deg[~hist_mask].max(initial=1)))
        classes = np.searchsorted(widths, np.maximum(deg, 1))
        vertex_ids, msg_idx, send_idx, weight_mat = [], [], [], []
        bucketed = (deg > 0) & ~hist_mask
        for c in np.unique(classes[bucketed]):
            # Fused plans carry only sender-id matrices — msg_idx would
            # double plan HBM and never be read.
            if send_device is not None and send_sorted is not None:
                rows = np.nonzero((classes == c) & bucketed)[0]
                mat = _gather_rows_device(
                    send_device,
                    jnp.asarray(ptr[rows].astype(np.int32)),
                    jnp.asarray(deg[rows].astype(np.int32)),
                    int(widths[c]), num_vertices,
                )
                ids = rows
            elif weights_sorted is not None:
                ids, mat, wmat = _class_rows(
                    ptr, deg, bucketed, classes, c, int(widths[c]),
                    send_sorted, num_vertices if send_sorted is not None else m, m,
                    weight_values=np.asarray(weights_sorted, np.float32),
                )
                mat = jnp.asarray(mat)
                weight_mat.append(jnp.asarray(wmat))
            else:
                ids, mat = _class_rows(
                    ptr, deg, bucketed, classes, c, int(widths[c]),
                    send_sorted, num_vertices if send_sorted is not None else m, m,
                )
                mat = jnp.asarray(mat)
            vertex_ids.append(jnp.asarray(ids.astype(np.int32)))
            (msg_idx if send_sorted is None else send_idx).append(mat)

        hist_vertex_ids = hist_send = hist_row_offset = hist_weight = None
        if hist_mask.any():
            hubs = np.nonzero(hist_mask)[0]
            rows = np.repeat(np.arange(len(hubs), dtype=np.int64), deg[hubs])
            assert len(hubs) * num_vertices < np.iinfo(np.int32).max
            hist_vertex_ids = jnp.asarray(hubs.astype(np.int32))
            if send_device is not None:
                # Hub messages are contiguous CSR spans — device slices, no
                # host gather or transfer of the hub message payload.
                hist_send = jnp.concatenate(
                    [send_device[int(ptr[h]):int(ptr[h + 1])] for h in hubs]
                ).astype(jnp.int32)
            else:
                pos = np.concatenate(
                    [np.arange(ptr[h], ptr[h + 1], dtype=np.int64) for h in hubs]
                )
                hist_send = jnp.asarray(send_sorted[pos].astype(np.int32))
                if weights_sorted is not None:
                    hist_weight = jnp.asarray(
                        np.asarray(weights_sorted, np.float32)[pos]
                    )
            hist_row_offset = jnp.asarray((rows * num_vertices).astype(np.int32))

        return cls(
            vertex_ids=tuple(vertex_ids),
            msg_idx=tuple(msg_idx) if send_sorted is None else None,
            num_vertices=num_vertices,
            num_messages=m,
            send_idx=tuple(send_idx) if send_sorted is not None else None,
            hist_vertex_ids=hist_vertex_ids,
            hist_send=hist_send,
            hist_row_offset=hist_row_offset,
            weight_mat=tuple(weight_mat) if weights_sorted is not None else None,
            hist_weight=hist_weight,
        )


def build_graph_and_plan(
    src, dst, num_vertices: int | None = None, symmetric: bool = True,
    use_native: bool = True, edge_weights=None,
):
    """Build the :class:`Graph` and its fused plan from ONE message-CSR
    pass — the pipeline's single-device fast path. Calling
    :func:`~graphmine_tpu.graph.container.build_graph` and
    :meth:`BucketedModePlan.from_edges` separately runs the counting sort
    twice over the same edges; this shares it. ``edge_weights`` builds a
    weighted graph plus the plan's weight payload in the same pass."""
    from graphmine_tpu.graph.container import (
        _graph_from_csr,
        _message_csr,
        _prepare_edges,
        _prepare_weights,
    )

    src, dst, num_vertices = _prepare_edges(src, dst, num_vertices)
    w = _prepare_weights(edge_weights, src)
    ptr, recv, send, w_sorted = _message_csr(
        src, dst, num_vertices, symmetric, use_native, weights=w
    )
    graph = _graph_from_csr(
        src, dst, ptr, recv, send, num_vertices, symmetric, msg_weight=w_sorted
    )
    # Host plan build by default. A device-side variant exists
    # (from_ptr(send_device=graph.msg_send)) that avoids shipping the
    # ~2.5E padded plan entries over the host boundary, but it costs one
    # XLA compile per width class whose shapes change with every graph —
    # measured a wash warm and far slower cold on the current setup; see
    # docs/DESIGN.md ("Plan construction placement").
    return graph, BucketedModePlan.from_ptr(
        ptr, num_vertices, send, weights_sorted=w_sorted
    )


def _with_sentinel(values: jax.Array) -> jax.Array:
    """``values`` as int32 with the sentinel appended: what a padding
    index (``len(values)``) gathers."""
    return jnp.concatenate(
        [values.astype(jnp.int32), jnp.full((1,), _SENTINEL, jnp.int32)]
    )


def _rowwise_mode(lbl: jax.Array) -> jax.Array:
    """Mode of each row of a ``[n, w]`` int32 matrix; sentinel entries
    ignored; ties break toward the smallest value. Rows must contain at
    least one non-sentinel entry."""
    return _sorted_rows_mode(jnp.sort(lbl, axis=1))


def _sorted_rows_mode(s: jax.Array) -> jax.Array:
    """:func:`_rowwise_mode` of rows already sorted ascending: the longest
    run of each row, the first of the longest."""
    w = s.shape[1]
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    new_run = jnp.concatenate(
        [jnp.ones((s.shape[0], 1), jnp.bool_), s[:, 1:] != s[:, :-1]], axis=1
    )
    run_start = lax.cummax(jnp.where(new_run, pos, -1), axis=1)
    rank = pos - run_start
    rank = jnp.where(s == _SENTINEL, -1, rank)
    best = rank.max(axis=1)
    cand = jnp.where(rank == best[:, None], s, _SENTINEL)
    return cand.min(axis=1)


def _rowwise_mode_pairwise(lbl: jax.Array) -> jax.Array:
    """Same contract as :func:`_rowwise_mode` via O(w^2) pairwise-equality
    counting — pure compare+add on the VPU, no sort network to compile.
    Faster to compile and comparable to run for narrow rows."""
    valid = lbl != _SENTINEL
    eq = (lbl[:, :, None] == lbl[:, None, :]) & valid[:, None, :]
    counts = jnp.where(valid, jnp.sum(eq, axis=2, dtype=jnp.int32), 0)
    best = counts.max(axis=1)
    cand = jnp.where(counts == best[:, None], lbl, _SENTINEL)
    return cand.min(axis=1)


def _bucket_mode(mat: jax.Array) -> jax.Array:
    """Row-wise mode with the cheapest method for the bucket width.

    Width 1 is the value itself; width 2 is ``min`` (rows are exact by
    construction: the w=2 class holds only degree-2 vertices — equal
    labels -> that label, distinct -> tie -> smallest); narrow rows use
    pairwise counting, wide rows the bitonic sort + run-length scan."""
    w = mat.shape[1]
    if w == 1:
        return mat[:, 0]
    if w == 2:
        return jnp.min(mat, axis=1)
    if w <= _PAIRWISE_MAX_W:
        return _rowwise_mode_pairwise(mat)
    return _rowwise_mode(mat)


def _segmented_row_cumsum(new_run: jax.Array, vals: jax.Array) -> jax.Array:
    """Inclusive per-run cumulative sum along axis 1, reset where
    ``new_run`` is set — an UNROLLED Hillis-Steele segmented scan
    (log2(w) steps of static pad/slice + add/select).

    Replaces ``lax.associative_scan`` with the same segmented-⊕ operator:
    the generic scan's recursive odd/even splitting took the r4 weighted
    chip tier past its 900 s child timeout on real TPU — minutes of
    Mosaic compile PER width class (the same pathology
    ``segment.py:segment_mode`` documents for 1-D scans, where the fix is
    ``lax.cummax``; no native segmented-sum cumulative op exists, hence
    the manual unroll here). Numerics match the scan: every within-run
    prefix is a sum of that run's elements only — never differences of a
    row-wide cumsum, whose float32 ulp at wide rows would misrank labels.
    """
    flag = new_run
    val = vals
    d = 1
    w = vals.shape[1]
    while d < w:
        # combine x[p-d] into x[p]; identity (False, 0) pads the left edge
        a_f = jnp.pad(flag[:, :-d], ((0, 0), (d, 0)), constant_values=False)
        a_v = jnp.pad(val[:, :-d], ((0, 0), (d, 0)))
        val = jnp.where(flag, val, a_v + val)
        flag = flag | a_f
        d *= 2
    return val


def _rowwise_wmode(lbl: jax.Array, wgt: jax.Array) -> jax.Array:
    """Weighted mode of each ``[n, w]`` row: argmax of per-label weight
    sums, ties toward the smallest label. Sentinel slots carry weight 0
    and are excluded. Weights must be non-negative (LPA weights are): a
    run's within-run cumulative sums then never exceed its total, so the
    global max of the scan is always attained at a run end.

    Per-run sums come from a SEGMENTED scan (reset at run boundaries),
    not differences of a row-wide cumsum: at wide rows the row prefix
    reaches magnitudes where float32 ulp exceeds small weight gaps, and
    total-as-difference misranks labels (the same corruption
    ``segment.py:_segment_mode_weighted`` documents and avoids)."""
    # One multi-operand sort carries the weights through the sort network
    # itself — no argsort + per-slot gathers (gathers are the measured
    # bottleneck on TPU, docs/DESIGN.md).
    s, ws = lax.sort(
        (lbl, jnp.where(lbl == _SENTINEL, 0.0, wgt)), dimension=1, num_keys=1
    )
    new_run = jnp.concatenate(
        [jnp.ones((s.shape[0], 1), jnp.bool_), s[:, 1:] != s[:, :-1]], axis=1
    )
    score = _segmented_row_cumsum(new_run, ws)
    score = jnp.where(s == _SENTINEL, -1.0, score)
    best = score.max(axis=1)
    cand = jnp.where(score == best[:, None], s, _SENTINEL)
    return cand.min(axis=1)


def _rowwise_wmode_pairwise(lbl: jax.Array, wgt: jax.Array) -> jax.Array:
    """Same contract as :func:`_rowwise_wmode` via O(w^2) pairwise-equality
    weight sums — no sort network for narrow rows."""
    valid = lbl != _SENTINEL
    wz = jnp.where(valid, wgt, 0.0)
    eq = (lbl[:, :, None] == lbl[:, None, :]) & valid[:, None, :]
    scores = jnp.where(valid, jnp.sum(eq * wz[:, None, :], axis=2), -1.0)
    best = scores.max(axis=1)
    cand = jnp.where(scores == best[:, None], lbl, _SENTINEL)
    return cand.min(axis=1)


def _bucket_wmode(mat: jax.Array, wmat: jax.Array) -> jax.Array:
    """Weighted :func:`_bucket_mode`: cheapest method per bucket width."""
    w = mat.shape[1]
    if w == 1:
        return mat[:, 0]
    if w == 2:
        # degree-2 rows are exact: equal labels -> that label; else the
        # heavier label wins, equal weights tie toward the smaller label.
        l0, l1 = mat[:, 0], mat[:, 1]
        w0, w1 = wmat[:, 0], wmat[:, 1]
        pick0 = (w0 > w1) | ((w0 == w1) & (l0 <= l1))
        return jnp.where(l0 == l1, l0, jnp.where(pick0, l0, l1))
    if w <= _PAIRWISE_MAX_W:
        return _rowwise_wmode_pairwise(mat, wmat)
    return _rowwise_wmode(mat, wmat)


def bucketed_mode(plan: BucketedModePlan, messages: jax.Array, fallback: jax.Array,
                  weights: str | None = "plan"):
    """Per-vertex mode of ``messages`` under the plan's CSR layout.

    ``messages``: int32 ``[M]`` in message-CSR order (``labels[msg_send]``).
    ``fallback``: int32 ``[V]`` — value for vertices with no messages
    (LPA: keep the old label). Returns int32 ``[V]``.

    ``weights="plan"`` (default): when the plan carries a weight payload
    (built from a weighted graph), the mode is the argmax of per-value
    weight sums — weighted-LPA semantics. Pass ``weights=None`` to force
    the plain unweighted mode for generic reductions over a weighted
    graph's plan.
    """
    if weights not in ("plan", None):
        raise ValueError("weights must be 'plan' or None")
    if plan.msg_idx is None:
        raise ValueError(
            "this plan is fused (send_idx only) — use lpa_superstep_bucketed, "
            "or build with from_graph/from_ptr for generic message reduction"
        )
    if messages.shape[0] != plan.num_messages or fallback.shape[0] != plan.num_vertices:
        raise ValueError(
            f"plan built for M={plan.num_messages}, V={plan.num_vertices} but got "
            f"M={messages.shape[0]}, V={fallback.shape[0]} — plan/graph mismatch"
        )
    msgs_pad = _with_sentinel(messages)
    wmats = (
        plan.weight_mat
        if weights == "plan" and plan.weight_mat is not None
        else (None,) * len(plan.vertex_ids)
    )
    return _row_modes(
        msgs_pad, fallback.astype(jnp.int32), plan.vertex_ids, plan.msg_idx,
        wmats,
    )


def _row_modes(values_pad, out, vertex_ids, row_idx, wmats):
    """The reduce of every degree class: gather each class's dense rows
    from ``values_pad``, take the row-wise mode, write it to the class's
    vertices in ``out``."""
    for ids, idx, wmat in zip(vertex_ids, row_idx, wmats):
        width = f"w{idx.shape[1]}"
        with jax.named_scope("row_gather"), jax.named_scope(width):
            mat = values_pad[idx]
        out = _reduce_rows(out, ids, mat, wmat)
    return out


def _reduce_rows(out, ids, mat, wmat):
    """One class's reduce: the row-wise mode of its dense rows ``mat``,
    written to the class's vertices ``ids`` in ``out``."""
    width = f"w{mat.shape[1]}"
    with jax.named_scope("row_mode"), jax.named_scope(width):
        mode = _bucket_mode(mat) if wmat is None else _bucket_wmode(mat, wmat)
    with jax.named_scope("write_back"):
        return out.at[ids].set(mode, unique_indices=True, mode="drop")


def lpa_superstep_bucketed(
    labels: jax.Array, graph: Graph, plan: BucketedModePlan
) -> jax.Array:
    """One LPA superstep via the bucketed plan — semantics identical to
    :func:`graphmine_tpu.ops.lpa.lpa_superstep` (asserted by tests).

    With a fused plan (``send_idx`` present, e.g. from
    :meth:`BucketedModePlan.from_edges`) the [M] message array is never
    materialized: each bucket gathers sender labels directly — one gather
    instead of two, saving an [M]-sized HBM round trip per superstep.

    Weighted graphs are first-class (r2; was sort-path-only): the plan
    carries slot-aligned weight matrices and the row modes become argmax
    of per-label weight sums (ties toward the smallest label, matching
    ``segment_mode(weights=...)``)."""
    check_plan_fits(labels, graph, plan)
    if plan.send_idx is not None:
        with jax.named_scope("lpa_bucketed"):
            return _lpa_superstep_fused(labels, plan)
    with jax.named_scope("lpa_bucketed"):
        with jax.named_scope("msg_gather"):
            msg = labels[graph.msg_send]
        return bucketed_mode(plan, msg, labels)


def check_plan_fits(labels: jax.Array, graph: Graph, plan: BucketedModePlan):
    """Raise unless ``plan`` was built for this graph's shapes and weights
    (a fused plan reads nothing of the graph, so nothing else would)."""
    if graph.msg_weight is not None and plan.weight_mat is None:
        raise ValueError(
            "graph carries msg_weight but the plan has no weight payload; "
            "build it with build_graph_and_plan(edge_weights=...), "
            "BucketedModePlan.from_graph, or from_ptr(weights_sorted=...)"
        )
    if plan.send_idx is not None and (
        labels.shape[0] != plan.num_vertices
        or graph.num_messages != plan.num_messages
    ):
        raise ValueError(
            f"plan built for V={plan.num_vertices}, M={plan.num_messages} "
            f"but got V={labels.shape[0]}, M={graph.num_messages} — "
            "plan/graph mismatch"
        )


def _lpa_superstep_fused(labels: jax.Array, plan: BucketedModePlan):
    """The fused superstep body: every degree class gathers its senders'
    labels straight from the padded label vector."""
    lbl_pad = _with_sentinel(labels)
    wmats = plan.weight_mat or (None,) * len(plan.vertex_ids)
    out = _row_modes(
        lbl_pad, labels.astype(jnp.int32), plan.vertex_ids, plan.send_idx,
        wmats,
    )
    return _hist_modes(labels, out, plan)


def _hist_modes(labels: jax.Array, out: jax.Array, plan: BucketedModePlan):
    """The histogram hubs' modes of ``labels``, written over ``out``."""
    if plan.hist_vertex_ids is not None:
        # Mega-hub mode: per-hub label histogram + argmax. Exact slot
        # count (no padding), no wide sort; argmax's first-max rule is
        # the smallest-label tie-break. Weighted: the histogram
        # accumulates weights instead of counts.
        with jax.named_scope("hist"):
            n_hist = plan.hist_vertex_ids.shape[0]
            neigh = labels[plan.hist_send].astype(jnp.int32)
            flat = plan.hist_row_offset + neigh
            if plan.hist_weight is None:
                hist = jnp.zeros((n_hist * plan.num_vertices,), jnp.int32)
                hist = hist.at[flat].add(1, mode="drop")
            else:
                # Weights may legally all be 0 for a hub (validation only
                # requires >= 0); an all-zero histogram row would argmax to
                # label 0 — possibly never received. Start every slot at
                # -inf, raise *received* slots to 0.0 with a scatter-max,
                # then accumulate: unreceived labels stay -inf and ties
                # resolve to the smallest received label, matching
                # segment_mode and the row-wise weighted paths
                # (cross-path one-answer invariant), with no second buffer.
                hist = jnp.full((n_hist * plan.num_vertices,), -jnp.inf,
                                jnp.float32)
                hist = hist.at[flat].max(0.0, mode="drop")
                hist = hist.at[flat].add(plan.hist_weight, mode="drop")
            counts = hist.reshape(n_hist, plan.num_vertices)
            modes = jnp.argmax(counts, axis=1).astype(jnp.int32)
        with jax.named_scope("write_back"):
            out = out.at[plan.hist_vertex_ids].set(
                modes, unique_indices=True, mode="drop"
            )
    return out


# The span of a hub's messages one float32 partial sum runs over. A running
# float32 sum of n like terms drifts by about sqrt(n) x 6e-8 of its value:
# 6e-5 at the 10^6 neighbours of graph500-24's first hub, against the 1e-4
# LDBC Graphalytics validates PageRank to. Summed in chunks the longest
# chain is this long (2e-6) and the chunks' partials reduce as a row.
_HUB_SUM_CHUNK = 1024


def row_sums(values: jax.Array, plan: BucketedModePlan) -> jax.Array:
    """``[V]`` float32: for every vertex the sum of ``values[sender]`` over
    the messages it receives, on a fused plan: the sum twin of the row
    modes (:func:`_row_modes`) and the row min
    (:func:`~graphmine_tpu.ops.cc.cc_superstep_bucketed`), and the first
    reduce of the plan that is not idempotent. Every message is one slot
    of one class or one entry of ``hist_send``, so each is counted once;
    a padding slot names index ``V`` and reads the 0.0 appended to
    ``values``, which adds nothing; a vertex that receives nothing is in
    no class and stays 0.0.

    The histogram hubs (degree > ``_HIST_MIN_DEG``) sum their exact
    message spans in chunks of :data:`_HUB_SUM_CHUNK`: one sorted
    ``segment_sum`` into a partial per (hub, chunk), then the partials
    summed as one dense row a hub."""
    if plan.send_idx is None:
        raise ValueError(
            "row_sums needs a fused plan (send_idx); build it with "
            "build_graph_and_plan or BucketedModePlan.from_edges"
        )
    v = plan.num_vertices
    pad = jnp.concatenate(
        [values.astype(jnp.float32), jnp.zeros((1,), jnp.float32)]
    )
    out = jnp.zeros((v,), jnp.float32)
    for ids, sidx in zip(plan.vertex_ids, plan.send_idx):
        width = f"w{sidx.shape[1]}"
        with jax.named_scope("row_gather"), jax.named_scope(width):
            mat = pad[sidx]
        with jax.named_scope("row_sum"), jax.named_scope(width):
            total = jnp.sum(mat, axis=1)
        with jax.named_scope("write_back"):
            out = out.at[ids].set(total, unique_indices=True, mode="drop")
    if plan.hist_vertex_ids is not None:
        with jax.named_scope("hub_sum"):
            n_hist = plan.hist_vertex_ids.shape[0]
            count = plan.hist_send.shape[0]
            chunks = -(-count // _HUB_SUM_CHUNK)
            hub = plan.hist_row_offset // jnp.int32(v)
            chunk = jnp.arange(count, dtype=jnp.int32) // _HUB_SUM_CHUNK
            partial_sums = jax.ops.segment_sum(
                pad[plan.hist_send], hub * chunks + chunk,
                num_segments=n_hist * chunks, indices_are_sorted=True,
            )
            total = jnp.sum(partial_sums.reshape(n_hist, chunks), axis=1)
        with jax.named_scope("write_back"):
            out = out.at[plan.hist_vertex_ids].set(
                total, unique_indices=True, mode="drop"
            )
    return out


# ---- carried rows: the gathered message rows as state across supersteps ----
#
# A class's gathered rows depend on the labels only through the senders
# behind their slots, so a scan that keeps the rows (ops/lpa.py) has to
# read again only what the senders whose label changed wrote. The rows of
# all classes live end to end in one flat int32[S] buffer; padding slots
# hold the sentinel from the first full gather on and are never written
# again. Measured on a TPU v5e the row gather is bound by issue per index
# (116-151 M slots/s whatever the table, PERF.md §7.4), so the lever is the
# count of indices and not the bytes.


def row_slots(plan: BucketedModePlan) -> int:
    """S: the padded slots of the plan's dense rows (fused plans)."""
    return sum(idx.shape[0] * idx.shape[1] for idx in plan.send_idx)


def _positions_by_key(keys: np.ndarray, num_keys: int):
    """``(ptr int64 [num_keys + 1], out int32)``: positions of ``keys``
    grouped by key, ascending inside a key; keys outside ``[0, num_keys)``
    name nothing. The native counting sort when the library is there, else
    a NumPy stable argsort (the same layout, tested)."""
    from graphmine_tpu.io import native

    out = native.positions_by_key(keys, num_keys)
    if out is not None:
        return out
    pos = np.nonzero((keys >= 0) & (keys < num_keys))[0]
    kept = keys[pos]
    ptr = np.zeros(num_keys + 1, np.int64)
    np.cumsum(np.bincount(kept, minlength=num_keys), out=ptr[1:])
    return ptr, pos[np.argsort(kept, kind="stable")].astype(np.int32)


def with_slot_index(plan: BucketedModePlan) -> BucketedModePlan:
    """The fused ``plan`` with its slot index by sender (``out_ptr``,
    ``out_slot``): the transpose of ``send_idx``, from one stable counting
    sort of the senders behind the padded slots. It reads the plan and
    nothing of the graph, so it is as right for ``symmetric=False`` and
    weighted plans (weights are slot-aligned and never move). A plan with
    no dense rows, or with more slots than an int32 counts, comes back as
    it is: there is nothing to carry, or no index to carry it by."""
    if plan.send_idx is None:
        raise ValueError("the slot index needs a fused plan (send_idx)")
    s = row_slots(plan)
    if s == 0 or s >= np.iinfo(np.int32).max:
        return plan
    keys = [np.asarray(idx).reshape(-1) for idx in plan.send_idx]
    if plan.hist_send is not None:
        keys.append(np.asarray(plan.hist_send))  # positions from S on
    ptr, slot = _positions_by_key(np.concatenate(keys), plan.num_vertices)
    np.minimum(slot, s, out=slot)  # a hub's message has no slot: S
    return dataclasses.replace(
        plan, out_ptr=jnp.asarray(ptr.astype(np.int32)),
        out_slot=jnp.asarray(slot),
    )


def _gather_classes(rows: jax.Array, values: jax.Array, plan: BucketedModePlan):
    """:func:`gather_rows` under its caller's scope: every class's rows
    gathered anew from ``values``, one class after the other, into the
    flat buffer ``rows``."""
    pad = _with_sentinel(values)
    off = 0
    for idx in plan.send_idx:
        width = f"w{idx.shape[1]}"
        with jax.named_scope("row_gather"), jax.named_scope(width):
            rows = lax.dynamic_update_slice(rows, pad[idx].reshape(-1), (off,))
        off += idx.shape[0] * idx.shape[1]
    return rows


def gather_rows(rows: jax.Array, labels: jax.Array, plan: BucketedModePlan):
    """Every class's rows gathered anew from ``labels`` into the flat
    buffer ``rows``: what :func:`_row_modes` gathers, kept."""
    with jax.named_scope("lpa_bucketed"):
        return _gather_classes(rows, labels, plan)


def _compact_spans(send: jax.Array, out_ptr: jax.Array, out_deg: jax.Array, *carried):
    """``(key, start, count, *carried)``, each ``[V]``: the vertices of the
    mask ``send`` in front, ascending, by one sort that carries each one's
    span of ``out_slot`` (where it starts, how many messages it sends) and
    what else rides with the key; behind them the key is ``V`` and the
    count 0."""
    v = send.shape[0]
    key = jnp.where(send, jnp.arange(v, dtype=jnp.int32), v)
    return lax.sort(
        (key, out_ptr[:-1], jnp.where(send, out_deg, 0), *carried), num_keys=1
    )


def _expand_spans(start: jax.Array, count: jax.Array, cap: int):
    """``(place, source, spread, end)``: the compacted spans laid end to
    end over ``cap`` places. Place ``p`` reads ``out_slot[source[p]]``;
    ``spread(x)`` gives each place its span's entry of the per-sender
    ``x``, by one scattered difference at the span's start and a
    ``cumsum``; the places from ``end[-1]`` on belong to no span."""
    end = jnp.cumsum(count)
    first = end - count  # a span's first place; the last ends at K
    at = jnp.where(count > 0, first, cap)  # past the senders: dropped

    def spread(per_sender):
        step = jnp.diff(per_sender, prepend=jnp.zeros((1,), jnp.int32))
        return jnp.cumsum(
            jnp.zeros((cap,), jnp.int32).at[at].add(step, mode="drop")
        )

    place = jnp.arange(cap, dtype=jnp.int32)
    # place p of a span that starts at `first` reads out_slot[p + skip]
    return place, place + spread(start - first), spread, end


def _span_slots(place, source, end, out_slot: jax.Array, s: int):
    """The flat slot behind each place; past the last span lie other
    senders' slots: those places name ``s``, which is none."""
    m = out_slot.shape[0]
    return jnp.where(place < end[-1], out_slot[jnp.clip(source, 0, m - 1)], s)


def _rewrite_rows_and_slots(
    rows: jax.Array, labels: jax.Array, changed: jax.Array,
    plan: BucketedModePlan, cap: int,
):
    """``(rows, slot)``: :func:`rewrite_rows`, and the ``cap`` flat slots
    it wrote to; a place past the last changed sender's span names slot
    ``S``, which is none."""
    v, s = plan.num_vertices, rows.shape[0]
    senders = min(cap, v)
    out_deg = plan.out_ptr[1:] - plan.out_ptr[:-1]
    with jax.named_scope("delta"):
        with jax.named_scope("compact"):
            _, start, count, label = (
                x[:senders] for x in _compact_spans(
                    changed & (out_deg > 0), plan.out_ptr, out_deg,
                    labels.astype(jnp.int32),
                )
            )
        with jax.named_scope("expand"):
            place, source, spread, end = _expand_spans(start, count, cap)
            value = spread(label)
        with jax.named_scope("scatter"):
            slot = _span_slots(place, source, end, plan.out_slot, s)
            return rows.at[slot].set(value, mode="drop"), slot


def rewrite_rows(
    rows: jax.Array, labels: jax.Array, changed: jax.Array,
    plan: BucketedModePlan, cap: int,
):
    """The slots of ``rows`` behind the ``changed`` senders rewritten with
    their ``labels``; every other slot stays. ``cap`` (static) bounds the
    messages the changed senders send, the caller's promise: the rows then
    equal :func:`gather_rows`'s slot for slot.

    Two per-index passes over ``cap`` (read ``out_slot``, scatter the
    label) instead of one over every slot of the graph. The changed
    senders are compacted by one sort that carries their ``out_ptr`` span
    and label with the key (at most ``min(cap, V)`` of them: each sends a
    message); the spans are laid end to end, and each span's offset into
    ``out_slot`` and its sender's label are spread over the span by one
    scattered difference at the span's start and a ``cumsum``, so nothing
    is looked up per slot but the slot itself. Measured on a TPU v5e at
    V = 2^22 (PERF.md §6, PR 32): the sort 12-16 ms whatever ``cap``, a
    read of ``out_slot`` 23 ns and a scatter into the rows 8 ns a place
    of ``cap``."""
    return _rewrite_rows_and_slots(rows, labels, changed, plan, cap)[0]


# ---- dirty rows: the reduce of a sparse superstep (ISSUE 43) ----
#
# labels_t[v] is the mode of rows_{t-1}[v], and a rewrite brings rows_t up
# to labels_t slot for slot, touching a row only where a sender's label
# moved. A row with no rewritten slot is the row it was, so its mode is the
# label its vertex already holds: only the DIRTY rows, those that hold a
# rewritten slot, can move. Exact, no tolerance. In the quiet tail of a
# Kronecker job the dirty rows are a fifth of a per cent of the rows and a
# twentieth to a tenth of the slots (graph500-22: 3,942 of 2.4 M rows,
# 4.9 % of S; graph500-24: 20,809 of 8.9 M, 9.3 %: the hubs' rows, touched
# by leaves that flicker; _proof/dirty_rows_replay.py), so the reduce runs
# over them alone.
#
# A row of the plan has a number: the classes' rows counted end to end, as
# their slots lie in the flat buffer.


def _class_tables(plan: BucketedModePlan):
    """``(slot offsets [C + 1], row offsets [C + 1], widths [C])`` of the
    plan's classes as they lie in the flat rows (host ints)."""
    n = np.asarray([idx.shape[0] for idx in plan.send_idx], np.int64)
    w = np.asarray([idx.shape[1] for idx in plan.send_idx], np.int64)
    zero = np.zeros(1, np.int64)
    return (
        np.concatenate([zero, np.cumsum(n * w)]),
        np.concatenate([zero, np.cumsum(n)]), w,
    )


def _pick(ge: jax.Array, table) -> jax.Array:
    """``table[sum(ge, axis=1)]`` for ``ge[i, c] = x[i] >= bound[c + 1]``
    over ascending bounds, as a telescoped sum of the table's steps: a
    lookup in a table of a few dozen entries with no gather."""
    table = np.asarray(table, np.int64)
    step = jnp.asarray(np.diff(table), jnp.int32)
    return jnp.int32(table[0]) + jnp.sum(
        jnp.where(ge, step[None, :], 0), axis=1, dtype=jnp.int32
    )


def _rows_of_slots(slot: jax.Array, plan: BucketedModePlan) -> jax.Array:
    """The number of the row that holds each flat ``slot``, by arithmetic:
    the slot's class by comparing it to the few dozen class offsets, then
    ``(slot - class offset) // width``. No slot (``S``) is past every
    class: offset ``S``, width 1, the plan's count of rows."""
    offs, rowoffs, widths = _class_tables(plan)
    ge = slot[:, None] >= jnp.asarray(offs[1:], jnp.int32)[None, :]
    return _pick(ge, rowoffs) + lax.div(
        slot - _pick(ge, offs), _pick(ge, np.append(widths, 1))
    )


def rewrite_rows_marked(
    rows: jax.Array, labels: jax.Array, changed: jax.Array,
    plan: BucketedModePlan, cap: int,
):
    """``(rows, dirty)``: :func:`rewrite_rows`, and the numbers of the rows
    it wrote to, ascending and each once, ``int32 [min(cap, rows of the
    plan)]`` padded with the plan's count of rows: what
    :func:`lpa_modes_from_dirty_rows` reduces. A place's row comes from
    its slot by arithmetic (the slot's class by comparing it to the few
    dozen class offsets, then ``(slot - class offset) // width``), and two
    ``cap``-long sorts leave each row once; a message a histogram hub
    receives has no slot and marks no row."""
    rows, slot = _rewrite_rows_and_slots(rows, labels, changed, plan, cap)
    total = sum(idx.shape[0] for idx in plan.send_idx)
    with jax.named_scope("delta"), jax.named_scope("mark"):
        row = lax.sort(_rows_of_slots(slot, plan))
        again = jnp.concatenate([jnp.zeros((1,), jnp.bool_), row[1:] == row[:-1]])
        dirty = lax.sort(jnp.where(again, total, row))[: min(cap, total)]
    return rows, dirty


_DIRTY_GROUP_ROWS = 8      # rows a trip of the dirty reduce takes: the sublanes
# Narrow rows: as many rows a trip as fill this many slots, by one gather (64
# rows at the coarse width 32, 32 at 64, 16 at 128). Three quarters of a quiet
# tail's dirty rows are that narrow (graph500-24: 15,841 of 20,809 at width 32,
# graph500-22: 3,131 of 3,942), and a trip's cost is its couple of dozen small
# operations, not its slots. Measured on a v5e against 8 rows a trip by
# `dynamic_slice` (_proof/dirty_trip_forms.py, PERF.md §6, PR 43): the width-32
# loop 12.2 ms in 248 trips against 34.7 ms in 1,981 (graph500-24), 1.9 against
# 6.3 ms (graph500-22): 12 % and 7.5 % of a sparse superstep. At 64 the two
# forms tie; at 128 the slices are 0.5 ms a superstep ahead (1.3 against 1.8).
_DIRTY_GATHER_SLOTS = 2048


def _dirty_groups(widths) -> list:
    """``[(W, first class, last class + 1)]``: the plan's classes, in
    order, merged onto a coarse ladder of widths for the dirty reduce
    (every pairwise class onto ``_PAIRWISE_MAX_W``, a sorted class onto
    the next power of two). One loop and one sort network a coarse width,
    not one a class: a network is code, code is device memory, and the
    padding costs little where a twentieth of the rows run."""
    groups = []
    for c, w in enumerate(int(w) for w in widths):
        coarse = max(_PAIRWISE_MAX_W, 1 << (w - 1).bit_length())
        if groups and groups[-1][0] == coarse:
            groups[-1] = (coarse, groups[-1][1], c + 1)
        else:
            groups.append((coarse, c, c + 1))
    return groups


def lpa_modes_from_dirty_rows(
    rows: jax.Array, labels: jax.Array, dirty: jax.Array,
    plan: BucketedModePlan,
):
    """``(new labels, dirty rows, dirty slots)``: the superstep's reduce
    over carried rows that a rewrite has just brought up to ``labels``,
    run over the ``dirty`` rows alone (:func:`rewrite_rows_marked`'s list);
    every other vertex keeps its label, which is what
    :func:`lpa_modes_from_rows` would give it. The histogram hubs run as
    they do there.

    The list is ascending, so the dirty rows of a group of neighbouring
    classes (:func:`_dirty_groups`) are one span of it. A loop a group,
    its trip count the span's length over the rows a trip takes, so work
    follows the dirty rows and a group without one costs a loop that does
    not run. A trip cuts its rows out of the flat buffer at the group's
    coarse width (a wide row is ``w`` contiguous int32: one
    ``dynamic_slice``, clamped to the buffer; narrow rows by one gather),
    blanks what lies outside the row to the sentinel (a mode does not
    read the order of a row), reduces them as the full reduce does
    (pairwise count or row sort, ties to the smallest) and writes the
    labels back. The rows are never viewed whole. Unweighted plans only:
    a weighted plan's weights are a matrix a class, which no coarse width
    spans, and its job keeps the full reduce."""
    if plan.weight_mat is not None:
        raise ValueError("the dirty reduce takes no weighted plan")
    v, s = plan.num_vertices, rows.shape[0]
    offs, rowoffs, widths = _class_tables(plan)
    out = labels.astype(jnp.int32)
    with jax.named_scope("lpa_bucketed"), jax.named_scope("dirty_rows"):
        # the span of the list each class's dirty rows take
        span = jnp.searchsorted(dirty, jnp.asarray(rowoffs, jnp.int32)).astype(jnp.int32)
        count = span[1:] - span[:-1]
        dirty_rows = span[-1]
        dirty_slots = jnp.sum(count * jnp.asarray(widths, jnp.int32), dtype=jnp.int32)
        for coarse, c0, c1 in _dirty_groups(widths):
            cut = min(coarse, s)
            gathered = coarse * _DIRTY_GROUP_ROWS < _DIRTY_GATHER_SLOTS
            g = _DIRTY_GATHER_SLOTS // coarse if gathered else _DIRTY_GROUP_ROWS
            lo, hi = span[c0], span[c1]
            ids = jnp.concatenate(plan.vertex_ids[c0:c1])
            bounds = jnp.asarray(rowoffs[c0 + 1:c1], jnp.int32)
            lane = jnp.arange(cut, dtype=jnp.int32)
            width = f"w{coarse}"

            def trip(i, out):  # traced here, inside this turn of the loop
                at = lo + i * g + jnp.arange(g, dtype=jnp.int32)
                row = dirty[jnp.minimum(at, hi - 1)]  # past the span: the last again
                ge = row[:, None] >= bounds[None, :]
                span_of = _pick(ge, widths[c0:c1])  # each row's own width
                start = _pick(ge, offs[c0:c1]) + (row - _pick(ge, rowoffs[c0:c1])) * span_of
                if gathered:
                    inside = lane[None, :] < span_of[:, None]
                    # past the row's end its last slot is read again, and blanked
                    reach = jnp.minimum(lane[None, :], span_of[:, None] - 1)
                    mat = jnp.where(inside, rows[start[:, None] + reach], _SENTINEL)
                else:
                    cuts = []
                    for k in range(g):
                        first = jnp.minimum(start[k], s - cut)
                        place = first + lane
                        cuts.append(jnp.where(
                            (place >= start[k]) & (place < start[k] + span_of[k]),
                            lax.dynamic_slice(rows, (first,), (cut,)), _SENTINEL,
                        ))
                    mat = jnp.stack(cuts)
                with jax.named_scope(width):
                    # one key and no payload: the order of equal labels is
                    # nobody's, so the sort carries no iota to keep it
                    mode = (
                        _rowwise_mode_pairwise(mat) if coarse <= _PAIRWISE_MAX_W
                        else _sorted_rows_mode(
                            lax.sort(mat, dimension=1, is_stable=False)
                        )
                    )
                vertex = jnp.where(at < hi, ids[row - jnp.int32(rowoffs[c0])], v)
                return out.at[vertex].set(mode, mode="drop")

            out = lax.fori_loop(0, (hi - lo + g - 1) // g, trip, out)
    with jax.named_scope("lpa_bucketed"):
        return _hist_modes(labels, out, plan), dirty_rows, dirty_slots


def lpa_modes_from_rows(
    rows: jax.Array, labels: jax.Array, plan: BucketedModePlan
) -> jax.Array:
    """The superstep's reduce over carried rows: the new labels
    :func:`lpa_superstep_bucketed` would give from ``labels`` when ``rows``
    hold those labels' gathered rows."""
    wmats = plan.weight_mat or (None,) * len(plan.vertex_ids)
    out, off = labels.astype(jnp.int32), 0
    with jax.named_scope("lpa_bucketed"):
        for ids, idx, wmat in zip(plan.vertex_ids, plan.send_idx, wmats):
            out = _reduce_rows(out, ids, _class_of_rows(rows, off, idx.shape), wmat)
            off += idx.shape[0] * idx.shape[1]
        return _hist_modes(labels, out, plan)


def _class_of_rows(rows: jax.Array, off: int, shape: tuple) -> jax.Array:
    """The ``[n, w]`` rows of the class that starts at slot ``off`` of the
    flat carried ``rows``."""
    (n, w), s = shape, rows.shape[0]
    mat = rows[off:off + n * w]
    if w > 1 and off % w == 0 and s % w == 0:
        # Where the class starts at a multiple of its width in rows whose
        # length is one too, the chip's compiler cuts the class out of a
        # [S / w, w] view of ALL the rows, which it tiles to 128 lanes:
        # 42 x the rows at w = 3 (92.6 GB for graph500-24's plan; PERF.md
        # §6, PR 42). The barrier keeps the slice a slice; every other
        # class's text is as it was.
        mat = lax.optimization_barrier(mat)
    return mat.reshape(shape)


# ---- BFS levels: the rows hold depths, and the reduce is a min (ISSUE 49) ----
#
# A depth is an int32 a slot as a label is, an unreached vertex's the
# sentinel, which is also what a padding slot holds: a row's min is the
# nearest neighbour's depth whatever the padding, and one more (saturating,
# so that "unreached" stays unreached) is the vertex's own unless it already
# has a smaller one. The rows are the carried-rows job's (:func:`gather_rows`
# in full, :func:`rewrite_rows` behind the vertices a level reached); the
# histogram hubs have no rows and take a ``segment_min`` over their senders'
# depths, as ``ops/cc.py:cc_superstep_bucketed`` does.


def _one_past(near: jax.Array) -> jax.Array:
    """The depth one past ``near``, saturating: past "unreached" lies
    "unreached"."""
    return jnp.where(near == _SENTINEL, _SENTINEL, near + 1)


def _relax_class(out: jax.Array, ids: jax.Array, mat: jax.Array) -> jax.Array:
    """``out`` with each of ``ids`` at most one past the least of its row."""
    width = f"w{mat.shape[1]}"
    with jax.named_scope("row_min"), jax.named_scope(width):
        reach = _one_past(jnp.min(mat, axis=1))
    with jax.named_scope("write_back"):
        return out.at[ids].min(reach, unique_indices=True, mode="drop")


def _relax_hubs(depth: jax.Array, out: jax.Array, plan: BucketedModePlan):
    """The histogram hubs' relaxation of ``depth``, written over ``out``."""
    if plan.hist_vertex_ids is None:
        return out
    with jax.named_scope("hubs"):
        near = jax.ops.segment_min(
            depth[plan.hist_send],
            plan.hist_row_offset // jnp.int32(plan.num_vertices),
            num_segments=plan.hist_vertex_ids.shape[0], indices_are_sorted=True,
        )
        reach = _one_past(near)
    with jax.named_scope("write_back"):
        return out.at[plan.hist_vertex_ids].min(
            reach, unique_indices=True, mode="drop"
        )


def gather_depth_rows(rows: jax.Array, depth: jax.Array, plan: BucketedModePlan):
    """:func:`gather_rows` for the BFS job: the same gathers, named as its
    level's."""
    with jax.named_scope("bfs_level"):
        return _gather_classes(rows, depth, plan)


def rewrite_depth_rows(
    rows: jax.Array, depth: jax.Array, reached: jax.Array,
    plan: BucketedModePlan, cap: int,
):
    """:func:`rewrite_rows` for the BFS job: the slots behind the vertices
    the last level ``reached`` take their depth."""
    with jax.named_scope("bfs_level"), jax.named_scope("rewrite"):
        return rewrite_rows(rows, depth, reached, plan, cap)


def bfs_level_from_rows(
    rows: jax.Array, depth: jax.Array, plan: BucketedModePlan
) -> jax.Array:
    """One BFS level over carried rows that hold every neighbour's
    ``depth``: ``min(own, row min + 1)`` a vertex, the new depths."""
    out, off = depth.astype(jnp.int32), 0
    with jax.named_scope("bfs_level"):
        for ids, idx in zip(plan.vertex_ids, plan.send_idx):
            out = _relax_class(out, ids, _class_of_rows(rows, off, idx.shape))
            off += idx.shape[0] * idx.shape[1]
        return _relax_hubs(depth, out, plan)


def bfs_level_bucketed(depth: jax.Array, plan: BucketedModePlan) -> jax.Array:
    """One BFS level at full width and with nothing kept: every class's
    rows gathered from ``depth`` and reduced as :func:`bfs_level_from_rows`
    reduces them, the same depths bit for bit."""
    pad = _with_sentinel(depth)
    out = depth.astype(jnp.int32)
    with jax.named_scope("bfs_level"):
        for ids, idx in zip(plan.vertex_ids, plan.send_idx):
            width = f"w{idx.shape[1]}"
            with jax.named_scope("row_gather"), jax.named_scope(width):
                mat = pad[idx]
            out = _relax_class(out, ids, mat)
        return _relax_hubs(depth, out, plan)


# ---- the bottom-up level (ISSUE 50, ISSUE 53) ----
#
# Late in a search the frontier's messages land almost all in the rows of
# vertices that already have their depth, and the question a level answers
# is the other one: which vertex still unreached has a reached neighbour?
# The graph's message CSR is sorted by receiver, so the neighbours of u are
# msg_send[msg_ptr[u]:msg_ptr[u + 1]] with no slot, row or class between
# (Beamer's direction-optimising search, read off the graph itself). The
# unreached vertices' spans are laid end to end as a rewrite lays the
# changed senders', each place reads its neighbour and that neighbour's
# depth, and the rows are neither read nor written: after such a level they
# are stale.


def compact_unreached(depth: jax.Array, msg_ptr: jax.Array):
    """``(owner, start, count)``, each ``int32 [V]``: the vertices without a
    depth that receive a message, ascending, in front, each with its span
    of the message CSR (``msg_send[start:start + count]``, its neighbours);
    behind them ``owner`` is ``V`` and ``count`` 0. The one V-long sort of a
    bottom-up level."""
    in_deg = msg_ptr[1:] - msg_ptr[:-1]
    with jax.named_scope("bfs_level"), jax.named_scope("bottom_up"):
        with jax.named_scope("compact"):
            return _compact_spans((depth == _SENTINEL) & (in_deg > 0), msg_ptr, in_deg)


# The spans a trip of the bottom-up loop cuts out of the compacted lists, as a
# share of its places. A span's offset and vertex reach the span's places by a
# scatter at the span's start, and a scatter costs an issue an index, taken or
# dropped: cutting as many spans as a trip has places, the two spreads were 14 ns
# a place of graph500-24's level 4, at a quarter 3.8 (PERF.md §6, PR 53). A chunk
# holds chunk / (mean degree of the unreached) spans; where those are more than
# a quarter of its places (a hundred thousand vertices of degree under 4 in a
# row) the trip stops at its last span's end and the next starts there.
_BOTTOM_UP_SPAN_SHARE = 4

# The places between neighbouring issues of a trip's read of `msg_send`: read
# in the order of the places, neighbouring issues of the gather fall in one span,
# a few bytes apart, and the chip serves them one after the other: 26.3 ns a
# place on graph500-24 against 14.8 with the issues far apart (PERF.md §6, PR 53).
_BOTTOM_UP_ISSUE_STRIDE = 1024


def bfs_level_bottom_up(
    depth: jax.Array, owner: jax.Array, start: jax.Array, count: jax.Array,
    msg_send: jax.Array, chunk: int,
):
    """``(new depths, trips)`` of one BFS level that asks the unreached
    vertices (:func:`compact_unreached`'s lists) for a reached neighbour:
    ``min(own, least neighbour's depth + 1)`` for each of them, the depths
    :func:`bfs_level_from_rows` gives bit for bit in a search whose reached
    vertices keep their depths.

    The spans lie end to end over U places, U the edges of the unreached
    vertices, read on the device: one loop, ``chunk`` places a trip
    (static), about ``ceil(U / chunk)`` trips (``trips`` says how many), so
    a level costs what U costs and holds ``chunk``-long temporaries
    whatever U. A trip starts at the first place not yet looked at, in the
    span its predecessor stopped in, and cuts the next ``chunk //
    _BOTTOM_UP_SPAN_SHARE`` spans out of the lists; it takes a chunk of
    places, or fewer where those spans end sooner. It spreads each span's
    offset into ``msg_send`` and its vertex over the span's places by a
    scattered difference and a ``cumsum`` (:func:`_expand_spans`'s way, a
    chunk at a time), reads the neighbour and the neighbour's depth as the
    level found it, and takes the least into the vertex by a scatter-min.
    Places and vertices ascend together, and the scatters say so: neither
    sorts its indices. A histogram hub's id stands in ``msg_send`` like any
    other."""
    v, m = depth.shape[0], msg_send.shape[0]
    spans = max(1, min(chunk // _BOTTOM_UP_SPAN_SHARE, v))
    stride = math.gcd(chunk, _BOTTOM_UP_ISSUE_STRIDE)  # 1: in the order of the places
    with jax.named_scope("bfs_level"), jax.named_scope("bottom_up"):
        with jax.named_scope("expand"):
            end = jnp.cumsum(count)
            first = end - count
            # place p of a span reads msg_send[p + start - first]
            lists = (first, end, start - first, owner)
            u = end[-1]

        def trip(state):
            base, lo, trips, out = state  # `lo` is the span that holds place `base`
            with jax.named_scope("expand"):
                cut = jnp.minimum(lo, v - spans)  # the lists' last spans: `lo` among them
                a, b, skip, vertex = (
                    lax.dynamic_slice(x, (cut,), (spans,)) for x in lists
                )
                stop = jnp.minimum(base + chunk, b[-1])
                # the spans with a place here are neighbours in the cut; those
                # in front of them add nothing at place 0, so `at` ascends
                here = (b > jnp.maximum(a, base)) & (a < stop)
                at = jnp.where(b <= base, 0, jnp.where(here, jnp.maximum(a - base, 0), chunk))
                after = jnp.concatenate([jnp.zeros((1,), jnp.bool_), here[:-1]])

                def spread(per_span):
                    last = jnp.concatenate([jnp.zeros((1,), jnp.int32), per_span[:-1]])
                    step = jnp.where(here, per_span - jnp.where(after, last, 0), 0)
                    return jnp.cumsum(
                        jnp.zeros((chunk,), jnp.int32).at[at].add(
                            step, indices_are_sorted=True, mode="drop"
                        )
                    )

                place = base + jnp.arange(chunk, dtype=jnp.int32)
                source = jnp.clip(place + spread(skip), 0, m - 1)
                whose = jnp.where(place < stop, spread(vertex), v)  # past the stop: nobody's
            with jax.named_scope("neighbours"):
                # issued `stride` places apart, and put back in place
                apart = source.reshape(-1, stride).T.reshape(-1)
                near = depth[msg_send[apart]].reshape(stride, -1).T.reshape(-1)
            with jax.named_scope("write_back"):
                out = out.at[whose].min(
                    _one_past(near), indices_are_sorted=True, mode="drop"
                )
            # the spans looked at to their end lie behind the next trip
            return stop, cut + jnp.sum(b <= stop, dtype=jnp.int32), trips + 1, out

        zero = jnp.int32(0)
        _, _, trips, out = lax.while_loop(
            lambda state: state[0] < u, trip, (zero, zero, zero, depth)
        )
        return out, trips
