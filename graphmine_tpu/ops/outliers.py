"""Outlier detection, parity path: recursive LPA + bottom-decile threshold.

This is the capability the reference *intended* but left as dead code
(``Graphframes.py:121-137``): for every community, re-run label propagation
on its induced subgraph, then flag sub-communities in the bottom decile by
size as outliers.

TPU-native design: instead of a host loop building a GraphFrame per
community (the dead spec), one **masked global LPA** computes every
community's recursive LPA simultaneously — cross-community messages are
retargeted to a drop sentinel, so propagation happens strictly inside each
community's induced subgraph. O(E) per superstep, zero host loops, no
dynamic shapes.

The decile rule follows the dead spec (``Graphframes.py:135-136``):
sub-communities sorted by size descending, threshold element at index
``-len//10``; communities with fewer than 10 sub-communities produce no
outliers (the reference's ``-int(len/10)`` would index element 0 there —
a bug we do not copy).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.obs.spans import stage_span
from graphmine_tpu.ops.bucketed_mode import (
    _SENTINEL,
    BucketedModePlan,
    lpa_superstep_bucketed,
)
from graphmine_tpu.ops.segment import segment_mode


def masked_label_propagation(
    graph: Graph, communities: jax.Array, max_iter: int = 5, plan=None
) -> jax.Array:
    """LPA restricted to intra-community edges, for all communities at once.

    Equivalent to running ``labelPropagation(maxIter)`` independently on
    every community's induced subgraph (the dead spec at
    ``Graphframes.py:122-126``), because labels can only flow along
    messages whose endpoints share a community.

    ``plan``: the graph's fused :class:`BucketedModePlan`, where the
    caller still holds one (the pipeline does, from its LPA chapter):
    the supersteps then reduce through the plan's degree-class rows,
    masked once, instead of a gather and a sort over the messages. The
    labels are the same bit for bit; ``None`` is the sort-based reference.
    """
    return _masked_lpa(graph, communities, max_iter, plan)[0]


def _masked_lpa(graph: Graph, communities, max_iter: int, plan):
    """Labels and the count of slots the mask left, from the family the
    inputs select: ``bucketed`` when a plan is there, ``sort`` when not."""
    communities = jnp.asarray(communities, jnp.int32)
    if plan is None:
        return _masked_lpa_sort(graph, communities, max_iter=max_iter)
    if plan.send_idx is None:
        raise ValueError(
            "the masked pass reads sender ids: it needs a fused plan "
            "(build_graph_and_plan, from_edges, from_graph(with_send=True))"
        )
    return _masked_lpa_bucketed(graph, plan, communities, max_iter)


@partial(jax.jit, static_argnames=("max_iter",))
def _masked_lpa_sort(graph: Graph, communities: jax.Array, max_iter: int):
    """The reference family: mask, gather and sort the messages."""
    v = graph.num_vertices
    with jax.named_scope("masked_lpa"), jax.named_scope("mask"):
        keep = communities[graph.msg_send] == communities[graph.msg_recv]
        recv = jnp.where(keep, graph.msg_recv, v)  # v = drop sentinel
        deg = jax.ops.segment_sum(
            keep.astype(jnp.int32), graph.msg_recv, num_segments=v
        )
    labels0 = jnp.arange(v, dtype=jnp.int32)

    def step(labels, _):
        with jax.named_scope("masked_lpa"):
            with jax.named_scope("msg_gather"):
                msg = labels[graph.msg_send]
            mode, _ = segment_mode(recv, msg, num_segments=v)
            with jax.named_scope("write_back"):
                new = jnp.where(deg > 0, mode, labels).astype(jnp.int32)
        return new, None

    labels, _ = lax.scan(step, labels0, None, length=max_iter)
    return labels, deg.sum(dtype=jnp.int32)


_lpa_superstep = jax.jit(lpa_superstep_bucketed)


def _masked_lpa_bucketed(graph: Graph, plan: BucketedModePlan, communities, max_iter):
    """The plan family. The community mask goes onto the plan's index
    rows once; the supersteps are then the LPA chapter's own compiled
    superstep over the masked plan, unweighted whatever graph and plan
    carry. Calling that program, not a scan of a second one over the same
    rows, is what keeps the pass out of device memory: one superstep over
    the pipeline cell's 65 degree classes is 80 MB of code on the chip,
    and a scan of it with the mask in one program was 149 MB, resident
    while the LOF chapter sets the job's peak (PERF.md, PR 30)."""
    rows, hist_offset, hub_alive, kept = _mask_plan_rows(plan, communities)
    masked = dataclasses.replace(
        plan, send_idx=rows, hist_row_offset=hist_offset,
        weight_mat=None, hist_weight=None,
    )
    if graph.msg_weight is not None:
        graph = dataclasses.replace(graph, msg_weight=None)
    labels = jnp.arange(plan.num_vertices, dtype=jnp.int32)
    for _ in range(max_iter):
        # refuses a plan of another graph, as it does for the LPA chapter
        new = _lpa_superstep(labels, graph, masked)
        labels = _keep_unreached(new, labels, plan.hist_vertex_ids, hub_alive)
    return labels, kept


@jax.jit
def _mask_plan_rows(plan: BucketedModePlan, communities: jax.Array):
    """The plan's index rows under the community mask: a cross-community
    slot points at the sentinel slot of the padded label vector, where row
    padding already points, and every width's reduce ignores it. A masked
    hub message gets the row offset past the last histogram row, so its
    flat index is out of range and dropped. Also which hubs keep a message
    at all, and how many slots do."""
    v = plan.num_vertices
    with jax.named_scope("masked_lpa"), jax.named_scope("mask"):
        comm_pad = jnp.concatenate([communities, jnp.zeros((1,), jnp.int32)])
        rows, kept = [], jnp.int32(0)
        for ids, idx in zip(plan.vertex_ids, plan.send_idx):
            keep = (idx < v) & (comm_pad[idx] == communities[ids][:, None])
            rows.append(jnp.where(keep, idx, v))
            kept += keep.sum(dtype=jnp.int32)
        hubs = plan.hist_vertex_ids
        if hubs is None:
            return tuple(rows), None, None, kept
        n_hist = hubs.shape[0]
        row = plan.hist_row_offset // v
        keep = communities[plan.hist_send] == communities[hubs][row]
        hist_offset = jnp.where(keep, plan.hist_row_offset, n_hist * v)
        hub_alive = jnp.zeros((n_hist,), jnp.bool_).at[row].max(keep)
        return tuple(rows), hist_offset, hub_alive, kept + keep.sum(dtype=jnp.int32)


@jax.jit
def _keep_unreached(new, labels, hubs, hub_alive):
    """A vertex with no surviving message keeps its label. Its row reduced
    to the sentinel, in every width's form; a hub's empty histogram gave
    label 0, which plain LPA never sees and the masked pass can."""
    with jax.named_scope("masked_lpa"), jax.named_scope("write_back"):
        new = jnp.where(new == _SENTINEL, labels, new)
        if hubs is not None:
            new = new.at[hubs].set(
                jnp.where(hub_alive, new[hubs], labels[hubs]),
                unique_indices=True, mode="drop",
            )
    return new


@dataclass(frozen=True)
class OutlierReport:
    """Result of the recursive-LPA outlier pass (host-side arrays)."""

    sub_labels: np.ndarray        # int32 [V] sub-community of each vertex
    outlier_vertices: np.ndarray  # bool [V] vertex is in an outlier sub-community
    sub_sizes: np.ndarray         # int32 [S] size of each distinct sub-community
    sub_parents: np.ndarray       # int32 [S] parent community of each sub-community
    thresholds: dict              # parent community -> bottom-decile size threshold


def recursive_lpa_outliers(
    graph: Graph, communities: jax.Array, max_iter: int = 5,
    decile: float = 0.1, sink=None, plan=None,
) -> OutlierReport:
    """Parity outlier detector (dead spec, ``Graphframes.py:121-137``).

    Device side: one masked LPA over the whole graph. Host side: the
    per-parent decile thresholds over the (tiny) sub-community size table.
    ``sink``: optional MetricsSink; the two sides are then the stage
    spans ``masked_lpa`` (to the fetched labels) and ``decile_report``.
    ``plan``: the graph's fused plan, as :func:`masked_label_propagation`
    takes it; the ``masked_lpa`` span says which family ran and how many
    of its slots the mask left.
    """
    with stage_span(sink, "masked_lpa") as stage:
        sub, kept = _masked_lpa(graph, communities, max_iter, plan)
        sub = np.asarray(sub)
        stage.note(
            family="sort" if plan is None else "bucketed",
            padded_slots=_padded_slots(graph, plan),
            kept_slots=int(kept),
        )
    with stage_span(sink, "decile_report") as stage:
        report = _decile_report(sub, np.asarray(communities), decile)
        stage.note(sub_communities=len(report.sub_sizes))
    return report


def _padded_slots(graph: Graph, plan) -> int:
    """Slots one masked superstep reads: the messages themselves on the
    sort family, the plan's padded rows and hub messages on the other."""
    if plan is None:
        return graph.num_messages
    hubs = 0 if plan.hist_send is None else plan.hist_send.shape[0]
    return sum(idx.size for idx in plan.send_idx) + hubs


def recursive_lpa_outliers_sharded(
    graph: Graph,
    communities,
    mesh,
    max_iter: int = 5,
    decile: float = 0.1,
    schedule: str = "replicated",
) -> OutlierReport:
    """Scale-out recursive-LPA outlier pass (dead spec,
    ``Graphframes.py:121-137``) for graphs that do not fit one device.

    Equivalence: masked LPA retargets every cross-community message to a
    drop sentinel, so it equals PLAIN LPA over the graph whose edge set is
    filtered to intra-community edges — ``segment_mode`` is value-sorted
    with a smallest-value tie-break (order-independent), and both keep a
    vertex's own label when it has no surviving messages. That filtered
    graph is built HOST-side (NumPy, O(E)) from the host-resident arrays
    of a scale-out :class:`Graph`, then partitioned over the mesh and run
    through the distributed LPA schedules — so the reference's specified
    outlier capability survives at exactly the scale where the
    device-resident masked pass cannot (VERDICT r3 item 2).

    ``schedule``: ``"replicated"`` (full label vector per device, one
    all_gather per superstep) or ``"ring"`` (labels stay sharded, chunks
    rotate over ICI) — pass the planner-resolved schedule of the main run.
    The filtered graph is a subgraph of the one the planner already
    budgeted, partitioned with the plain sort-body CSR (no bucket plan):
    strictly less device memory than the main LPA under the same schedule.

    The recursive pass is unweighted regardless of ``graph.msg_weight``
    (parity with :func:`masked_label_propagation`, whose mode is a count).
    """
    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    if schedule not in ("replicated", "ring"):
        raise ValueError(
            f"unknown schedule {schedule!r}; expected 'replicated' or "
            "'ring' (the planner's distributed schedules — a 'single' "
            "plan should use recursive_lpa_outliers)"
        )
    comm = np.asarray(communities)
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    keep = comm[src] == comm[dst]
    intra = build_graph(
        src[keep], dst[keep], num_vertices=graph.num_vertices,
        symmetric=graph.symmetric, to_device=False,
    )
    sg = shard_graph_arrays(partition_graph(intra, mesh=mesh), mesh)
    if schedule == "ring":
        from graphmine_tpu.parallel.ring import ring_label_propagation

        sub = ring_label_propagation(sg, mesh, max_iter=max_iter)
    else:
        sub = sharded_label_propagation(sg, mesh, max_iter=max_iter)
    return _decile_report(np.asarray(sub), comm, decile)


def _decile_report(sub: np.ndarray, comm: np.ndarray, decile: float) -> OutlierReport:
    """Host-side bottom-decile thresholding over the sub-community size
    table (``Graphframes.py:135-136`` semantics); shared by the
    single-device masked pass and the scale-out sharded pass.

    Vectorized grouped decile (r5): the original per-parent Python loop
    was O(parents x sub-communities) — the sharded bench tier measured it
    at 220-300 s on the chip-tier graph (~10^5 parent communities), while
    the device LPA it post-processes takes ~3 s. One (parent, size)
    lexsort + per-group threshold gather does the same decile in
    O(S log S); semantics are unchanged (the threshold is the cut-th
    smallest size within the parent, ties all flagged — pinned by the
    outlier tests).
    """
    sub_ids, inverse, sizes = np.unique(sub, return_inverse=True, return_counts=True)
    parents = comm[sub_ids]  # sub-community label = a member vertex id

    outlier_sub = np.zeros(len(sub_ids), dtype=bool)
    thresholds: dict[int, int] = {}
    if len(sub_ids):
        order = np.lexsort((sizes, parents))  # group by parent, sizes asc
        p_sorted = parents[order]
        s_sorted = sizes[order]
        uniq_p, starts, counts = np.unique(
            p_sorted, return_index=True, return_counts=True
        )
        cuts = (counts * decile).astype(np.int64)
        has_decile = cuts > 0  # fewer than 1/decile sub-communities: skip
        thr = s_sorted[starts[has_decile] + cuts[has_decile] - 1]
        thresholds = dict(zip(
            uniq_p[has_decile].astype(int).tolist(),
            thr.astype(int).tolist(),
        ))
        # per-sorted-row parent group id -> its threshold (or -1: nothing
        # can be <= -1, so no-decile groups flag nothing)
        thr_full = np.full(len(uniq_p), -1, dtype=np.int64)
        thr_full[has_decile] = thr
        group_of_row = np.repeat(np.arange(len(uniq_p)), counts)
        out_sorted = s_sorted <= thr_full[group_of_row]
        outlier_sub[order] = out_sorted

    return OutlierReport(
        sub_labels=sub.astype(np.int32),
        outlier_vertices=outlier_sub[inverse],
        sub_sizes=sizes.astype(np.int32),
        sub_parents=parents.astype(np.int32),
        thresholds=thresholds,
    )
