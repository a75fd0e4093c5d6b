"""Propagation-blocking superstep engine: destination-binned message tiles.

Every superstep family (LPA / CC / PageRank) is **random-gather bound**:
the r4 width-ladder work drove the fused bucketed kernel to the measured
~130M gathered-slots/s roofline (the r5 ``roofline`` bench tier;
``ops/bucketed_mode.py`` header), so further chip-rate gains require
changing the *memory-access pattern*, not the arithmetic. This module
implements propagation blocking (PAPERS.md: arXiv 2011.08451 "Optimizing
Graph Processing and Preprocessing with Hardware Assisted Propagation
Blocking"; arXiv 1608.01362 "Making Caches Work for Graph Analytics") as
a third plan family next to the sort path and the degree-bucketed plan:

1. **Host plan** (:class:`BlockedPlan`, built once per graph like the
   message CSR itself): destination vertices are grouped into contiguous
   **bins** sized so one bin's message tile fits on-chip (VMEM is ~16 MB
   per core — ``/opt/skills/guides/pallas_guide.md``; the default
   ``DEFAULT_TILE_SLOTS`` int32 tile is 1 MiB). Bin boundaries snap to
   vertex boundaries so no vertex's messages straddle two tiles, and the
   CSR (already destination-sorted) makes each bin's messages one
   contiguous slice.

2. **Bin phase** (per superstep, on device): stream the per-vertex values
   once in *sender-major* order — ``values[src_sorted]`` with monotone
   non-decreasing indices, a sequential pass over the value vector
   instead of a random walk over it — and scatter each message into its
   host-precomputed slot of the destination-binned tile. The scatter's
   active window at any point of the stream is one insertion frontier per
   bin (the propagation-blocking locality argument; the ``blocking``
   bench tier measures the resulting binned-pass slots/s against the
   random-gather slots/s on the same message volume).

3. **Reduce phase**: each destination's messages are a contiguous run
   *inside its bin's tile*, so the reduce reuses the bucketed-mode width
   ladder within the bin — dense ``[n, w]`` rows gathered with
   **tile-local** indices (bounded by the tile size, not V) and resolved
   by the existing row-mode / row-min / row-sum machinery
   (:func:`~graphmine_tpu.ops.bucketed_mode._bucket_mode` et al.), so the
   r4 padding wins stack with the layout change rather than compete.

Row reductions are order-independent within a row (the row mode sorts or
pairwise-counts; min and the weighted argmax are commutative with the
same smallest-label tie-break), so blocked LPA/CC supersteps are
**bit-identical** to the sort-based ``segment_mode`` oracle — pinned by
``tests/test_blocking.py`` across power-law / ring / self-loop /
isolated-vertex / duplicate-edge graphs, fused and sharded.

Unlike the fused bucketed plan there is no mega-hub histogram path: a
hub's messages stay contiguous in its (oversized) bin tile and ride a
wide sort row on the 1.5x-extended ladder — the blocked layout is also
the gate to bigger-than-HBM graphs, since bins stream tile-by-tile
instead of materializing one global gather.

Measured on a TPU v5e (PR 26; the policy comment below has the numbers):
the bet did not pay — both blocked gathers ran at 0.4x the rate of the
one bucketed gather they replace, a superstep took 5.4x as long, and
``plan="auto"`` resolves no graph to this family. It runs on request.
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.ops.bucketed_mode import (
    _SENTINEL,
    _extend_widths,
    _row_modes,
)

# ---- plan-family crossover policy (single owner) ---------------------------
# Measured provenance:
#   * bucketed beats sort from ~2^16 messages (r1 measurement, the
#     threshold label_propagation has shipped since; plan build amortizes
#     past there).
#   * blocked has NO auto path on one device (PR 26). Its premise — a
#     gather from a value table past on-chip capacity costs several times
#     a tile-local one — was measured on a TPU v5e at V = 2^22,
#     M = 128.3 M (benchmark cell `cdlp-g500-22`; PERF.md §6, PR 26) and
#     did not hold. Device seconds per LPA superstep: blocked 6.134 =
#     bin_gather 2.40 (monotone indices into the 16 MB label vector,
#     53 M elements/s) + bin_scatter 1.13 (lowered through a sort) +
#     row_gather 2.50 (tile-local indices into the ~0.5 GB tile, the
#     same rate); bucketed 1.139 = row_gather 1.02 (139 M padded slots
#     straight from the same label vector, 136 M/s) + row_mode, hist and
#     write_back 0.10. One bucketed job is 0.186x a blocked one. The
#     family stays reachable as `sort` is — requested="blocked", a
#     BlockedPlan passed as plan=, GRAPHMINE_SUPERSTEP_FAMILY=blocked,
#     the sharded paths' build_blocked_plan=True — until ROADMAP D2
#     deletes it.
BUCKETED_MIN_MESSAGES = 1 << 16

# On a mesh of >= 2 devices `auto` is the same per-class bucket rows, built
# per vertex-range shard, with the labels replicated and exchanged by one
# tiled all_gather a superstep (`parallel/sharded.py:_lpa_shard_body_bucketed`).
# Read off four TPU v5e chips on the graphalytics-g500-22 draw (V = 2^22,
# M = 128.3 M, 10 supersteps, labels equal across the three and to the
# reference; PERF.md §6, PR 27): bucket rows + all_gather 2.748 s a job,
# sharded_2d 12.397 s (4.5x), the ring schedule 33.291 s (12.1x). Of the
# 2.748 s, 2.483 s a chip is lpa_sharded/row_gather (36.5 M padded slots a
# shard a superstep, 147 M slots/s) and 0.0014 s lpa_sharded/exchange: the
# 12.6 MB a chip receives per superstep is 0.05 % of the job, so a schedule
# that shrinks the exchange (2D ships 5.0 MB) has nothing to win, and the
# 2D family pays for it with blocked's stream + tile + rows (69.4 M padded
# slots a shard, a 20.9 s plan build against 0.9 s).
# `sharded_2d` (labels sharded, per-peer boundary `ppermute`s, then blocked's
# stream + tile + rows) keeps no auto path: reachable by
# requested="sharded_2d" / GRAPHMINE_SUPERSTEP_FAMILY=sharded_2d until ROADMAP
# D2, as `blocked` is. The ring schedule (`parallel/ring.py`) is the planner's
# memory rung for label vectors that do not fit replicated, not a speed choice.

#: One bin's message-tile budget (int32 slots). 2^18 slots = 1 MiB —
#: small against the ~16 MB/core VMEM so the tile, its row matrices and
#: the reduce transients co-reside on chip (docs/DESIGN.md "Propagation-
#: blocking binned layout").
DEFAULT_TILE_SLOTS = 1 << 18

FAMILIES = ("blocked", "bucketed", "sort", "sharded_2d")


def crossover_thresholds() -> dict:
    """The ACTIVE family-crossover constants, env overrides applied — the
    numbers that decide every ``plan="auto"`` resolution. One owner for
    both the selection itself (:func:`select_superstep_family`) and the
    provenance records (``impl_selected`` carries this dict, so a policy
    flip is explainable from the JSONL alone — ISSUE 12 satellite)."""
    return {"bucketed_min_messages": BUCKETED_MIN_MESSAGES}


def select_superstep_family(
    num_vertices: int, num_messages: int, requested: str = "auto",
    weighted: bool = False, num_devices: int = 1,
) -> tuple[str, str]:
    """Resolve the superstep plan family — THE single policy owner behind
    ``plan="auto"`` in ``ops/lpa.py`` / ``ops/cc.py`` / ``ops/pagerank.py``
    and ``pipeline/planner.plan_superstep``.

    Returns ``(family, reason)`` with ``family`` in :data:`FAMILIES`.
    ``requested`` forces a family (still validated) and the
    ``GRAPHMINE_SUPERSTEP_FAMILY`` env var forces it process-wide: the
    only two ways to ``blocked`` here, which ``auto`` never resolves
    (the policy comment above has the measurement). ``weighted`` is
    accepted for signature stability: every family carries the
    slot-aligned weight payload, so weights never change the selection
    (the weighted contract is enforced at superstep time — see
    :func:`lpa_superstep_blocked`).

    ``num_devices``: on a mesh of >= 2 devices ``auto`` is ``bucketed``
    at every size — the per-shard bucket rows with replicated labels and
    one ``all_gather`` a superstep (the policy comment above has the
    four-chip reading). ``sharded_2d`` (labels sharded, per-peer boundary
    ``ppermute``) is resolved only on request, and only there: an
    explicit ``requested="sharded_2d"`` on fewer than 2 devices is a loud
    error, while the process-wide env override simply does not apply
    there (it targets the sharded paths; raising would break the fused
    ops under a global override).
    """
    del weighted
    d = int(num_devices)
    if requested != "auto":
        if requested not in FAMILIES:
            raise ValueError(
                f"unknown superstep family {requested!r}; expected one of "
                f"{FAMILIES} or 'auto'"
            )
        if requested == "sharded_2d" and d < 2:
            raise ValueError(
                "superstep family 'sharded_2d' needs a >= 2-device mesh "
                f"(num_devices={d}); its neighbor-only exchange has no "
                "single-device meaning — use 'auto' there"
            )
        return requested, f"requested {requested!r}"
    env = os.environ.get("GRAPHMINE_SUPERSTEP_FAMILY")
    if env and not (env == "sharded_2d" and d < 2):
        if env not in FAMILIES:
            raise ValueError(
                f"GRAPHMINE_SUPERSTEP_FAMILY={env!r} is not one of {FAMILIES}"
            )
        return env, f"GRAPHMINE_SUPERSTEP_FAMILY={env} (env override)"
    if d >= 2:
        return "bucketed", (
            f"D={d}: per-shard degree-bucketed rows, labels replicated, one "
            "all_gather a superstep (four v5e chips at M=128.3 M: 2.75 s a "
            "job against 12.40 s sharded_2d and 33.29 s ring, the exchange "
            "0.05 % of it; PERF.md PR 27)"
        )
    if num_messages >= BUCKETED_MIN_MESSAGES:
        return "bucketed", (
            f"M={num_messages} >= {BUCKETED_MIN_MESSAGES}: degree-bucketed "
            "dense rows amortize the host plan build (r1 crossover)"
        )
    return "sort", (
        f"M={num_messages} < {BUCKETED_MIN_MESSAGES}: sort-based "
        "segment_mode superstep (plan build would dominate)"
    )


# ---- host plan construction ------------------------------------------------


def _bin_bounds(ptr: np.ndarray, tile_slots: int) -> np.ndarray:
    """Destination-bin vertex boundaries (int64 ``[n_bins + 1]``): greedy
    contiguous vertex ranges of at most ``tile_slots`` messages each,
    snapped to vertex boundaries. A vertex whose own degree exceeds the
    budget gets a dedicated (oversized) bin — its tile is then the max
    over bins, but its messages stay one contiguous run."""
    v = len(ptr) - 1
    bounds = [0]
    while bounds[-1] < v:
        start = bounds[-1]
        end = int(np.searchsorted(ptr, ptr[start] + tile_slots, side="right")) - 1
        bounds.append(min(max(end, start + 1), v))
    return np.asarray(bounds, dtype=np.int64)


def _blocked_layout(
    ptr: np.ndarray,
    send: np.ndarray,
    tile_slots: int,
    widths: np.ndarray | None = None,
    tile_width: int | None = None,
    weights: np.ndarray | None = None,
):
    """Host core of the blocked layout, shared by the single-device
    builder and the per-shard stacked builder (``parallel/sharded.py``).

    ``ptr``/``send``/``weights``: the (local) message CSR. ``widths``: a
    shared width ladder (the sharded builder passes one ladder for all
    shards; ``None`` extends the default ladder to this CSR's max
    degree). ``tile_width``: force the per-bin tile width Tb (the sharded
    builder passes the max across shards so SPMD shapes stay uniform).

    Returns ``(src_sorted, scatter_pos, bounds, tb, rows)`` where
    ``rows`` maps width-class index ``c`` -> ``(vertex_rows, idx_mat,
    weight_mat | None)``: per-destination dense rows whose ``idx_mat``
    entries are *tile slots* (``-1`` marks padding — the caller rewrites
    it to its tile's sentinel slot).
    """
    ptr = np.asarray(ptr, dtype=np.int64)
    deg = ptr[1:] - ptr[:-1]
    m = int(ptr[-1])
    bounds = _bin_bounds(ptr, tile_slots)
    n_bins = len(bounds) - 1
    bin_msg_start = ptr[bounds[:-1]]                     # [n_bins]
    bin_sizes = ptr[bounds[1:]] - bin_msg_start
    tb = int(bin_sizes.max(initial=1))
    tb = -(-tb // 8) * 8
    if tile_width is not None:
        if tile_width < tb:
            raise ValueError(
                f"tile_width {tile_width} below this CSR's max bin size {tb}"
            )
        tb = tile_width

    # Tile slot of every CSR message position: bin-major, CSR order
    # within the bin (so each destination's messages stay contiguous).
    pos = np.arange(m, dtype=np.int64)
    bin_of = np.searchsorted(bin_msg_start, pos, side="right") - 1
    slot_of_csr = bin_of * tb + (pos - bin_msg_start[bin_of])

    # Sender-major stream order (stable: equal senders keep CSR order so
    # the layout is deterministic). The phase-1 gather indices
    # (src_sorted) are monotone non-decreasing by construction.
    order = np.argsort(send[:m], kind="stable")
    src_sorted = send[:m][order].astype(np.int32)
    scatter_pos = slot_of_csr[order].astype(np.int32)

    if widths is None:
        widths = _extend_widths(int(deg.max(initial=1)))
    classes = np.searchsorted(widths, np.maximum(deg, 1))
    eligible = deg > 0
    row_start = np.zeros(len(deg), dtype=np.int64)
    row_start[eligible] = slot_of_csr[ptr[:-1][eligible]]
    w_arr = None if weights is None else np.asarray(weights, np.float32)

    rows = {}
    for c in np.unique(classes[eligible]):
        w = int(widths[c])
        vr = np.nonzero((classes == c) & eligible)[0]
        offs = np.arange(w, dtype=np.int64)[None, :]
        valid = offs < deg[vr][:, None]
        idx = np.where(valid, row_start[vr][:, None] + offs, -1)
        wmat = None
        if w_arr is not None:
            cidx = np.minimum(ptr[vr][:, None] + offs, max(m - 1, 0))
            wmat = np.where(valid, w_arr[cidx], 0.0).astype(np.float32)
        rows[int(c)] = (vr, idx, wmat)
    return src_sorted, scatter_pos, bounds, tb, rows


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BlockedPlan:
    """Static propagation-blocking plan for one graph's message CSR.

    ``src_sorted``: int32 ``[M]`` — sender vertex ids in sender-major
    order (monotone; the phase-1 sequential value pass).
    ``scatter_pos``: int32 ``[M]`` — each streamed message's slot in the
    destination-binned tile (bin-major; CSR order within a bin).
    ``row_idx[c]``: int32 ``[n_c, w_c]`` — per-destination dense rows of
    *tile slots* on the shared width ladder (padding = the tile's
    reserved sentinel slot). ``row_vertex[c]``: int32 ``[n_c]`` — the
    owning destination vertex ids. ``weight_mat[c]``: optional float32
    ``[n_c, w_c]`` slot-aligned message weights (padding 0) — present iff
    built from a weighted CSR.
    """

    src_sorted: jax.Array
    scatter_pos: jax.Array
    row_idx: tuple
    row_vertex: tuple
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    num_messages: int = dataclasses.field(metadata=dict(static=True))
    num_bins: int = dataclasses.field(metadata=dict(static=True))
    tile_slots: int = dataclasses.field(metadata=dict(static=True))
    tile_alloc: int = dataclasses.field(metadata=dict(static=True))
    weight_mat: tuple | None = None

    @property
    def num_width_classes(self) -> int:
        return len(self.row_idx)

    @property
    def padded_row_slots(self) -> int:
        """Total reduce-phase gather slots (incl. row padding)."""
        return int(sum(int(r.shape[0]) * int(r.shape[1]) for r in self.row_idx))

    @classmethod
    def from_graph(cls, graph: Graph, tile_slots: int | None = None) -> "BlockedPlan":
        """Build from a (device- or host-resident) graph; fetches
        ``msg_ptr``/``msg_send`` (and ``msg_weight``) to host once —
        the same amortization the message CSR itself gets."""
        w = None if graph.msg_weight is None else np.asarray(graph.msg_weight)
        return cls.from_ptr(
            np.asarray(graph.msg_ptr), graph.num_vertices,
            np.asarray(graph.msg_send), weights_sorted=w,
            tile_slots=tile_slots,
        )

    @classmethod
    def from_ptr(
        cls,
        ptr: np.ndarray,
        num_vertices: int,
        send_sorted: np.ndarray,
        weights_sorted: np.ndarray | None = None,
        tile_slots: int | None = None,
    ) -> "BlockedPlan":
        """Host-pure construction from the message CSR (``ptr`` int
        ``[V+1]``, ``send_sorted`` int32 ``[M]`` in CSR order,
        ``weights_sorted`` optional float ``[M]``)."""
        if tile_slots is None:
            tile_slots = int(
                os.environ.get("GRAPHMINE_BLOCKED_TILE_SLOTS", DEFAULT_TILE_SLOTS)
            )
        if tile_slots < 1:
            raise ValueError("tile_slots must be >= 1")
        ptr = np.asarray(ptr, dtype=np.int64)
        m = int(ptr[-1]) if len(ptr) else 0
        if m >= np.iinfo(np.int32).max:
            raise ValueError("message count exceeds int32; shard the build")
        send_sorted = np.asarray(send_sorted, dtype=np.int32)
        if m == 0:
            return cls(
                src_sorted=jnp.zeros((0,), jnp.int32),
                scatter_pos=jnp.zeros((0,), jnp.int32),
                row_idx=(), row_vertex=(),
                num_vertices=num_vertices, num_messages=0,
                num_bins=0, tile_slots=tile_slots, tile_alloc=1,
                weight_mat=None if weights_sorted is None else (),
            )
        src_sorted, scatter_pos, bounds, tb, rows = _blocked_layout(
            ptr, send_sorted, tile_slots, weights=weights_sorted,
        )
        n_bins = len(bounds) - 1
        # One reserved slot past the bins: never scattered to, stays at
        # the reduce's fill value — the target of every row padding slot
        # (bins padded short of Tb would also work, but a FULL final bin
        # leaves no guaranteed-unwritten slot).
        tile_alloc = n_bins * tb + 1
        sentinel_slot = tile_alloc - 1
        row_idx, row_vertex, weight_mat = [], [], []
        for c in sorted(rows):
            vr, idx, wmat = rows[c]
            row_vertex.append(jnp.asarray(vr.astype(np.int32)))
            row_idx.append(
                jnp.asarray(
                    np.where(idx < 0, sentinel_slot, idx).astype(np.int32)
                )
            )
            if wmat is not None:
                weight_mat.append(jnp.asarray(wmat))
        return cls(
            src_sorted=jnp.asarray(src_sorted),
            scatter_pos=jnp.asarray(scatter_pos),
            row_idx=tuple(row_idx),
            row_vertex=tuple(row_vertex),
            num_vertices=num_vertices,
            num_messages=m,
            num_bins=n_bins,
            tile_slots=tb,
            tile_alloc=tile_alloc,
            weight_mat=tuple(weight_mat) if weights_sorted is not None else None,
        )


def build_graph_and_blocked_plan(
    src, dst, num_vertices: int | None = None, symmetric: bool = True,
    use_native: bool = True, edge_weights=None, tile_slots: int | None = None,
):
    """Build the :class:`Graph` and its :class:`BlockedPlan` from ONE
    message-CSR pass — the blocked twin of
    :func:`~graphmine_tpu.ops.bucketed_mode.build_graph_and_plan` (the
    driver's single-device fast path when the planner resolves the
    ``blocked`` family)."""
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
    plan = BlockedPlan.from_ptr(
        ptr, num_vertices, send, weights_sorted=w_sorted, tile_slots=tile_slots
    )
    return graph, plan


# ---- device supersteps -----------------------------------------------------


def _blocked_tile(plan: BlockedPlan, values_pad: jax.Array, fill) -> jax.Array:
    """The two blocked passes: phase 1 streams ``values_pad`` in
    sender-major order (monotone gather indices), phase 2 scatters each
    message into its destination bin's tile slot. Unwritten slots (bin
    padding + the reserved sentinel slot) keep ``fill``, which the reduce
    rows rely on (mode/min sentinel, sum identity 0)."""
    with jax.named_scope("bin_gather"):
        vals = values_pad[plan.src_sorted]
    with jax.named_scope("bin_scatter"):
        tile = jnp.full((plan.tile_alloc,), fill, values_pad.dtype)
        return tile.at[plan.scatter_pos].set(vals, unique_indices=True)


def _check_plan(plan: BlockedPlan, labels: jax.Array, graph: Graph | None):
    if labels.shape[0] != plan.num_vertices or (
        graph is not None and graph.num_messages != plan.num_messages
    ):
        raise ValueError(
            f"plan built for V={plan.num_vertices}, M={plan.num_messages} "
            f"but got V={labels.shape[0]}"
            + (f", M={graph.num_messages}" if graph is not None else "")
            + " — plan/graph mismatch"
        )


def lpa_superstep_blocked(
    labels: jax.Array, graph: Graph, plan: BlockedPlan
) -> jax.Array:
    """One LPA superstep via the blocked plan — semantics identical to
    :func:`graphmine_tpu.ops.lpa.lpa_superstep` (bit-identical labels,
    pinned by ``tests/test_blocking.py``).

    Weighted graphs are first-class: the plan's slot-aligned
    ``weight_mat`` switches the row modes to the per-label weight-sum
    argmax. A weighted graph with a weight-less plan **refuses loudly**
    (the serving layer's contract for weighted snapshots,
    ``serve/delta.py``) — silently dropping weights would change weighted
    LPA's semantics; rebuild via :meth:`BlockedPlan.from_graph` or route
    to the sort/bucketed path."""
    if graph.msg_weight is not None and plan.weight_mat is None:
        raise ValueError(
            "graph carries msg_weight but the blocked plan has no weight "
            "payload; build it with BlockedPlan.from_graph / "
            "build_graph_and_blocked_plan(edge_weights=...), or pass "
            "plan=None / a weighted bucketed plan — weights are never "
            "silently dropped"
        )
    _check_plan(plan, labels, graph)
    with jax.named_scope("lpa_blocked"):
        lbl_pad = jnp.concatenate(
            [labels.astype(jnp.int32), jnp.full((1,), _SENTINEL, jnp.int32)]
        )
        tile = _blocked_tile(plan, lbl_pad, _SENTINEL)
        wmats = plan.weight_mat or (None,) * len(plan.row_idx)
        return _row_modes(
            tile, labels.astype(jnp.int32), plan.row_vertex, plan.row_idx,
            wmats,
        )


def cc_superstep_blocked(labels: jax.Array, plan: BlockedPlan) -> jax.Array:
    """One CC superstep on the blocked plan — the min-reduce twin of
    :func:`lpa_superstep_blocked`, step-for-step identical to
    :func:`graphmine_tpu.ops.cc.cc_superstep` (min over own + incoming
    labels, then pointer jump); padding slots carry the int32-max
    sentinel, which never wins a min."""
    _check_plan(plan, labels, None)
    with jax.named_scope("cc_blocked"):
        lbl_pad = jnp.concatenate(
            [labels.astype(jnp.int32), jnp.full((1,), _SENTINEL, jnp.int32)]
        )
        tile = _blocked_tile(plan, lbl_pad, _SENTINEL)
        new = labels.astype(jnp.int32)
        for ids, ridx in zip(plan.row_vertex, plan.row_idx):
            width = f"w{ridx.shape[1]}"
            with jax.named_scope("row_gather"), jax.named_scope(width):
                mat = tile[ridx]
            with jax.named_scope("row_min"), jax.named_scope(width):
                row_min = jnp.min(mat, axis=1)
            with jax.named_scope("write_back"):
                new = new.at[ids].min(
                    row_min, unique_indices=True, mode="drop"
                )
        with jax.named_scope("pointer_jump"):
            return jnp.minimum(new, new[new]).astype(jnp.int32)


def blocked_inflow(plan: BlockedPlan, contrib: jax.Array) -> jax.Array:
    """Per-destination sum of ``contrib[sender]`` over the blocked layout
    — the PageRank inflow (``segment_sum`` twin; float sums reassociate
    across the row layout, so parity is to float tolerance, not bits).
    ``contrib``: float ``[V]`` per-vertex outgoing contribution."""
    if contrib.shape[0] != plan.num_vertices:
        raise ValueError(
            f"plan built for V={plan.num_vertices} but contrib has "
            f"V={contrib.shape[0]} — plan/graph mismatch"
        )
    c_pad = jnp.concatenate([contrib, jnp.zeros((1,), contrib.dtype)])
    tile = _blocked_tile(plan, c_pad, jnp.zeros((), contrib.dtype))
    inflow = jnp.zeros((plan.num_vertices,), contrib.dtype)
    for ids, ridx in zip(plan.row_vertex, plan.row_idx):
        inflow = inflow.at[ids].set(
            jnp.sum(tile[ridx], axis=1), unique_indices=True, mode="drop"
        )
    return inflow


# ---- plan-build observability ----------------------------------------------


def plan_build_stats(plan, num_edges: int) -> dict:
    """The ``plan_build`` record payload for either plan family (see
    ``obs/schema.py``): bins/width classes and the padded gather slots
    per edge — the number the width-ladder work optimizes and the blocked
    layout re-balances (docs/DESIGN.md)."""
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    e = max(int(num_edges), 1)
    if isinstance(plan, BlockedPlan):
        # tile pass (M slots) + reduce rows
        slots = plan.num_messages + plan.padded_row_slots
        return {
            "family": "blocked",
            "bins": plan.num_bins,
            "width_classes": plan.num_width_classes,
            "tile_slots": plan.tile_slots,
            "padded_slots_per_edge": round(slots / e, 3),
        }
    if isinstance(plan, BucketedModePlan):
        mats = plan.send_idx if plan.send_idx is not None else plan.msg_idx
        slots = sum(int(m.shape[0]) * int(m.shape[1]) for m in mats or ())
        if plan.hist_send is not None:
            slots += int(plan.hist_send.shape[0])
        return {
            "family": "bucketed",
            "bins": 0,
            "width_classes": len(plan.vertex_ids),
            "padded_slots_per_edge": round(slots / e, 3),
        }
    raise TypeError(f"unknown plan type {type(plan).__name__}")


def emit_plan_records(
    sink, op: str, plan, reason: str, seconds: float, cached: bool,
    num_edges: int, num_messages: int, num_vertices: int | None = None,
) -> None:
    """Emit the ``impl_selected`` + ``plan_build`` provenance pair for one
    auto-plan resolution (no-op without a sink). ``plan=None`` (sort
    family) emits only ``impl_selected`` — there is no plan to build.

    Both records carry the decision's full evidence (ISSUE 12): the
    active crossover ``thresholds`` (:func:`crossover_thresholds`) and
    the analytical ``cost`` sub-record
    (:func:`graphmine_tpu.obs.costmodel.superstep_cost` — exact padded
    slots when a plan exists), so every auto-policy flip ships the
    numbers that justified it."""
    if sink is None:
        return
    from graphmine_tpu.obs.costmodel import superstep_cost

    family = "sort" if plan is None else plan_build_stats(plan, num_edges)["family"]
    v = (
        num_vertices if num_vertices is not None
        else getattr(plan, "num_vertices", 0)
    )
    cost = superstep_cost(
        op, family, v, num_messages, num_edges, plan=plan
    )
    sink.emit(
        "impl_selected", op=op, impl=family, n=num_messages, reason=reason,
        thresholds=crossover_thresholds(), cost=cost.record(),
    )
    if plan is None:
        return
    stats = plan_build_stats(plan, num_edges)
    sink.emit(
        "plan_build", op=op, seconds=round(seconds, 6), cached=cached,
        cost=cost.record(), **stats,
    )


def timed_plan_build(build) -> tuple:
    """``(plan, seconds)`` for one host plan build."""
    t0 = time.perf_counter()
    plan = build()
    return plan, time.perf_counter() - t0
