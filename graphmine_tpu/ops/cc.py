"""Connected components via iterated min-label propagation.

The reference never calls ``connectedComponents`` but BASELINE.json names it
as a required capability (GraphFrames exposes it on the object built at
``Graphframes.py:78``). Semantics: *weakly* connected components of the
directed edge list — messages flow both directions, every vertex ends with
the smallest vertex id reachable from it.

Two device-side accelerations over naive propagation:
- each step takes ``min(own, neighbor mins)`` (monotone, so safe);
- **pointer jumping** (``labels = labels[labels]``) after each propagation
  halves the remaining depth, giving O(log V) convergence on long chains —
  the classic PRAM trick, a good fit for XLA's static-shape while_loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import Graph

# Slots of the per-superstep changed-label counts the fixpoint loop carries.
# Pointer jumping keeps the passes far under this (5 on graph500-22); a
# longer run keeps overwriting the last slot.
_CHANGED_SLOTS = 64


def cc_superstep(labels: jax.Array, graph: Graph) -> jax.Array:
    with jax.named_scope("cc_sort"):
        with jax.named_scope("msg_gather"):
            msg = labels[graph.msg_send]
        with jax.named_scope("segment_min"):
            neigh_min = jax.ops.segment_min(
                msg, graph.msg_recv, num_segments=graph.num_vertices,
                indices_are_sorted=True,
            )
            new = jnp.minimum(labels, neigh_min)
        # Pointer jumping: follow the current representative one hop.
        with jax.named_scope("pointer_jump"):
            return jnp.minimum(new, new[new]).astype(jnp.int32)


def cc_superstep_bucketed(labels: jax.Array, plan) -> jax.Array:
    """One CC superstep on the fused degree-bucket plan — the min-reduce
    twin of :func:`~graphmine_tpu.ops.bucketed_mode.lpa_superstep_bucketed`
    (r5). Per-step function identical to :func:`cc_superstep` (min over
    own + incoming labels, then pointer jump), so the two paths agree
    bit-for-bit every superstep (tested).

    Why: the r5 cc bench tier measured the segment_min superstep at
    21.9M edges/s/chip — 2.5x off the gather roofline — because the
    sorted-segment reduction over the [M] message array dominates. The
    plan's dense [n_b, w_b] rows turn that into row-wise ``min`` (pure
    VPU) after the same gather the LPA kernel already amortized; padding
    slots gather the int32-max sentinel, which never wins a min. Mega-hub
    rows ride an exact segment_min over their (row-grouped) message
    spans instead of dense rows, mirroring the histogram path's shape
    policy. Requires a FUSED plan (``send_idx`` present, e.g. from
    :func:`~graphmine_tpu.ops.bucketed_mode.build_graph_and_plan`).
    """
    if plan.send_idx is None:
        raise ValueError(
            "cc_superstep_bucketed needs a fused plan (send_idx); build "
            "it with build_graph_and_plan or BucketedModePlan.from_edges"
        )
    sentinel = jnp.iinfo(jnp.int32).max
    with jax.named_scope("cc_bucketed"):
        lbl_pad = jnp.concatenate(
            [labels.astype(jnp.int32), jnp.full((1,), sentinel, jnp.int32)]
        )
        new = labels.astype(jnp.int32)
        for ids, sidx in zip(plan.vertex_ids, plan.send_idx):
            width = f"w{sidx.shape[1]}"
            with jax.named_scope("row_gather"), jax.named_scope(width):
                mat = lbl_pad[sidx]
            with jax.named_scope("row_min"), jax.named_scope(width):
                row_min = jnp.min(mat, axis=1)
            with jax.named_scope("write_back"):
                new = new.at[ids].min(
                    row_min, unique_indices=True, mode="drop"
                )
        if plan.hist_vertex_ids is not None:
            with jax.named_scope("hist"):
                n_hist = plan.hist_vertex_ids.shape[0]
                rows = plan.hist_row_offset // jnp.int32(plan.num_vertices)
                hub_min = jax.ops.segment_min(
                    labels[plan.hist_send].astype(jnp.int32), rows,
                    num_segments=n_hist, indices_are_sorted=True,
                )
            with jax.named_scope("write_back"):
                new = new.at[plan.hist_vertex_ids].min(
                    hub_min, unique_indices=True, mode="drop"
                )
        with jax.named_scope("pointer_jump"):
            return jnp.minimum(new, new[new]).astype(jnp.int32)


def connected_components(
    graph: Graph, max_iter: int = 0, return_iterations: bool = False,
    plan="auto", sink=None,
):
    """Weakly-connected component labels ``[V]`` (smallest member vertex id).

    Runs to fixpoint inside a ``lax.while_loop`` (bounded by ``max_iter``
    when nonzero). Returns int32 labels; distinct count on the bundled data
    must equal the measured golden of 34 WCCs (BASELINE.md).

    ``return_iterations`` additionally returns the supersteps-to-fixpoint
    count (int32 scalar, includes the final no-change confirming pass) —
    the ``fixpoint`` record's ``supersteps`` (VERDICT r4 item 2).

    ``plan``: a fused :class:`BucketedModePlan` (r5) — supersteps run
    :func:`cc_superstep_bucketed` instead of the segment_min path
    (identical labels every step, tested; measured 2.57x on the
    100M-edge cc bench tier in the r5 capture). The default ``"auto"``
    resolves the family through
    :func:`~graphmine_tpu.ops.superstep_policy.select_superstep_family`
    (the single crossover-policy owner; same per-graph plan cache as
    :func:`~graphmine_tpu.ops.lpa.label_propagation`); ``None`` forces
    the segment_min path. Callers that built the graph with
    ``build_graph_and_plan`` can pass their plan directly. ``sink``:
    optional MetricsSink — auto
    resolutions emit ``impl_selected`` + ``plan_build`` provenance
    records (see ``label_propagation``), and every call one ``fixpoint``
    record: the supersteps it took and how many labels each one moved
    (the last entry is the confirming pass's 0 unless ``max_iter`` cut
    the run short), and one ``program_memory`` record: what the loop's
    executable takes of the chip (``label_propagation``'s).
    """
    if isinstance(plan, str) and plan == "auto":
        from graphmine_tpu.ops.lpa import _cached_auto_plan
        from graphmine_tpu.ops.superstep_policy import (
            emit_plan_records,
            select_superstep_family,
        )

        plan = None
        if not isinstance(graph.msg_ptr, jax.core.Tracer):
            family, reason = select_superstep_family(
                graph.num_vertices, graph.num_messages
            )
            seconds, cached = 0.0, False
            if family == "bucketed":
                plan, seconds, cached = _cached_auto_plan(graph)
            emit_plan_records(
                sink, "cc_superstep", plan, reason, seconds, cached,
                graph.num_edges, graph.num_messages,
                num_vertices=graph.num_vertices,
            )
    if plan is not None and plan.send_idx is None:
        plan = None  # non-fused plan: no label-gather indices to min over
    if sink is not None and not isinstance(graph.msg_ptr, jax.core.Tracer):
        # Achieved-vs-model attribution (ISSUE 12): run the fixpoint with
        # the iteration counter on (so the window size is the REAL
        # supersteps-to-fixpoint, not the bound), wall-time it, and judge
        # it against the analytical cost model.
        from graphmine_tpu.obs.costmodel import (
            emit_superstep_timing,
            superstep_cost,
            timed_fixpoint,
        )

        from graphmine_tpu.ops.superstep_policy import (
            emit_program_memory,
            noting,
            plan_anchor,
            program_log,
        )

        programs = program_log(sink, plan_anchor(graph, plan))
        loop = noting(programs, "loop", _connected_components, max_iter=max_iter)
        (labels, iters, changed), secs, cold = timed_fixpoint(
            lambda: loop(graph, max_iter, True, plan),
        )
        iters = int(iters)
        # weighted=False explicitly: CC's min ignores the weight payload
        # even when the shared auto plan carries one.
        cost = superstep_cost(
            "cc_superstep", "sort" if plan is None else "auto",
            graph.num_vertices, graph.num_messages, graph.num_edges,
            plan=plan, weighted=False,
        )
        emit_superstep_timing(
            sink, "cc_superstep", cost, iters, iters, secs,
            graph.num_edges, variant="fused", cold_compile=cold,
        )
        sink.emit(
            "fixpoint", op="cc_superstep", supersteps=iters,
            changed=np.asarray(changed)[:iters].tolist(),
            num_vertices=graph.num_vertices, family=cost.family,
        )
        emit_program_memory(sink, "cc_superstep", programs)
        if return_iterations:
            return labels, iters
        return labels
    out = _connected_components(graph, max_iter, return_iterations, plan)
    return out[:2] if return_iterations else out  # the counts are the sink's


@partial(jax.jit, static_argnames=("max_iter", "return_iterations"))
def _connected_components(
    graph: Graph, max_iter: int = 0, return_iterations: bool = False,
    plan=None,
):
    limit = max_iter if max_iter > 0 else graph.num_vertices + 2

    def cond(state):
        labels, prev_changed, it, _ = state
        with jax.named_scope("superstep"), jax.named_scope("converged"):
            return (prev_changed > 0) & (it < limit)

    def body(state):
        labels, _, it, per_step = state
        if plan is None:
            new = cc_superstep(labels, graph)
        else:
            new = cc_superstep_bucketed(labels, plan)
        with jax.named_scope("superstep"), jax.named_scope("changed_count"):
            changed = jnp.sum(new != labels, dtype=jnp.int32)
            per_step = per_step.at[jnp.minimum(it, _CHANGED_SLOTS - 1)].set(
                changed
            )
        return new, changed, it + 1, per_step

    labels0 = jnp.arange(graph.num_vertices, dtype=jnp.int32)
    labels, _, iters, per_step = lax.while_loop(
        cond, body,
        (labels0, jnp.int32(1), jnp.int32(0),
         jnp.zeros((_CHANGED_SLOTS,), jnp.int32)),
    )
    if return_iterations:
        return labels, iters, per_step
    return labels
