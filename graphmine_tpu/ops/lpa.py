"""Label propagation — the TPU-native core of the pipeline.

Reproduces the semantics of ``GraphFrame.labelPropagation(maxIter=5)`` as
invoked at ``Graphframes.py:81`` (GraphX Pregel LPA):

- initial label of every vertex = its own id;
- synchronous supersteps: each vertex adopts the **mode of its neighbors'
  labels**, messages flowing along both directions of every directed edge,
  duplicate edges counted with multiplicity (``Graphframes.py:70-74``);
- exactly ``max_iter`` supersteps, no convergence test;
- isolated vertices keep their label;
- tie-break: deterministic smallest-label (GraphX's is implementation-
  defined, so cross-engine validation compares partitions, not ids).

The superstep is one gather + one segment-mode over the precomputed message
CSR — no shuffle, no driver round-trips. The stateless supersteps run as a
single ``lax.scan`` XLA program; over a fused plan whose rows the device has
room for, the supersteps step from the host and keep their gathered rows
(:func:`_carried_rows_job`; on a mesh a shard's rows a chip,
``parallel/sharded.py:carried_label_propagation``).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.ops.segment import segment_mode


def lpa_superstep(labels: jax.Array, graph: Graph) -> jax.Array:
    """One synchronous LPA superstep: gather → segment-mode → select.

    On a weighted graph (``build_graph(edge_weights=...)``) the mode is
    the label with the largest incoming *weight sum* (ties toward the
    smallest label) — classic weighted LPA; unweighted is the all-ones
    special case."""
    with jax.named_scope("lpa_sort"):
        with jax.named_scope("msg_gather"):
            msg = labels[graph.msg_send]
        mode, _ = segment_mode(
            graph.msg_recv, msg, num_segments=graph.num_vertices,
            indices_are_sorted=True, weights=graph.msg_weight,
        )
        with jax.named_scope("write_back"):
            deg = graph.degrees()
            return jnp.where(deg > 0, mode, labels).astype(jnp.int32)


def label_propagation(
    graph: Graph,
    max_iter: int = 5,
    init_labels: jax.Array | None = None,
    return_history: bool = False,
    plan="auto",
    sink=None,
    mesh=None,
):
    """Run ``max_iter`` LPA supersteps; returns int32 labels ``[V]``.

    With ``return_history=True`` also returns the per-iteration count of
    vertices whose label changed (the structured observability signal the
    reference lacked — SURVEY §5 metrics).

    ``plan``: a
    :class:`~graphmine_tpu.ops.bucketed_mode.BucketedModePlan` (the
    degree-bucketed dense mode kernel, ~3x the sort superstep at 10^7
    messages) — identical labels to the sort superstep, tested. The
    default ``"auto"`` resolves the family through
    :func:`~graphmine_tpu.ops.superstep_policy.select_superstep_family`
    (the single crossover-policy owner: ``bucketed`` or ``sort``) and
    builds the plan from the graph (cached per graph). Auto stays on the
    sort path when
    custom ``init_labels`` are given (the fused plan's
    histogram/sentinel machinery assumes labels in ``[0, V)`` — the
    default ``arange`` initialization guarantees that, arbitrary labels
    don't) or under an enclosing jit trace, where host plan construction
    is impossible. Pass ``None`` to force the sort-based superstep.

    With a fused plan (``"auto"``'s, or one with ``send_idx``) the
    supersteps run as :func:`_carried_rows_job`, stepped from the host:
    the gathered message rows live in one device buffer across supersteps,
    and a superstep that follows few changed labels rewrites only the
    slots behind their senders, through a slot index built once per plan
    (:func:`_cached_slot_index`). Each superstep is two programs, the
    rows' update (a full gather or a rung's rewrite, the rows donated to
    it and so updated in place) and the row modes; the host reads one
    count between them to pick the next update, and ``max_iter`` is the
    length of its loop (a job of another length compiles nothing). Labels
    are bit-identical to the stateless supersteps'; nothing selects it but
    what each superstep counts, and what the device has free: the rows
    and the index go on the device only if :func:`~graphmine_tpu.ops.
    superstep_policy.admit_carried_rows` finds room for them, once per
    plan, before the index is built; otherwise, and under a caller's
    trace, where the host cannot step, the stateless bucketed scan runs
    (``impl_selected`` says ``scan`` and ``scan_reason``).

    ``sink``: optional MetricsSink — each auto resolution emits an
    ``impl_selected`` record, and each plan materialization a
    ``plan_build`` record (family, build seconds with the slot index's,
    width classes, padded slots/edge), so host plan cost is visible in
    obs_report instead of hiding inside first-call latency, and a
    ``device_residency`` record (the bytes the device holds for this
    graph's supersteps, by array group, beside its limit); a job over
    a fused plan's rows one ``superstep_delta`` record (per superstep: the
    branch taken, the labels moved, the messages their vertices send, and
    its seconds on the host's clock, read where the host fetches the
    count; all ``full`` and no seconds where the rows were not admitted),
    and every job one ``program_memory`` record for each compiled program
    it ran: what the executable takes of the chip (code, temporaries,
    arguments), beside the admission's count of its temporaries where it
    has one (:func:`~graphmine_tpu.ops.superstep_policy.
    emit_program_memory`; asked once a plan, copied after).

    On one device the graph may be host-resident too
    (``build_graph(..., to_device=False)``): the fused plan is built from
    the host arrays and placed alone, and the job's programs, which read
    the plan and nothing of the graph, are handed none of the graph's
    arrays (``device_residency`` then says ``graph_bytes: 0``); the same
    entry, the same programs, equal labels (tested on the CPU; no chip run
    has taken it at a size that fills the chip). The admission sizes the
    device alone: the host compiles each program of the job alone, the
    largest in about the stateless scan's memory (19.7 against 20 GB for
    graph500-24's plan; PERF.md §6, PR 36).

    ``mesh``: a ``jax.sharding.Mesh`` runs the job across its devices
    (``None`` is the one-device path above, byte for byte). The graph
    may — and past one chip's memory must — be host-resident
    (``build_graph(..., to_device=False)``): it is partitioned into
    vertex-range shards once per (graph, mesh, family), each shard placed
    straight on its device; no device ever holds the whole edge list or
    message CSR. The supersteps then run as the carried-rows job on the
    mesh (:func:`~graphmine_tpu.parallel.sharded.
    carried_label_propagation`): each chip keeps its shard's gathered rows
    in one donated buffer, the host steps a ``gather`` or a ``rewrite``
    and then a ``modes`` program a superstep, and the rung is picked by
    the largest shard's K, which the host reads from its own replica. The
    rows and a slot index a shard go on the chips only if
    ``admit_carried_rows`` finds room on the fullest one, asked once per
    (graph, mesh) before the index is built; otherwise, under a caller's
    trace, on a mesh that spans processes and on the ``sort`` family all
    ``max_iter`` supersteps run as one compiled program
    (:func:`~graphmine_tpu.parallel.sharded.sharded_label_propagation`),
    the same labels bit for bit. ``plan`` is then ``"auto"`` or a family
    name, and the family comes from
    ``select_superstep_family(..., num_devices=D)``. ``sink`` receives
    ``impl_selected`` (with ``scan`` and ``scan_reason``), ``partition``,
    ``plan_build`` (its seconds with the index's), a ``device_residency``
    record of what ONE chip holds, one ``exchange`` record per call (bytes
    a chip receives per superstep, messages and padded slots per shard)
    and, from the carried job, ``superstep_delta`` (with ``shards``; K,
    the rungs and ``num_messages`` are the largest shard's).
    ``return_history`` is the one-device path's.
    """
    if mesh is not None:
        if return_history:
            raise ValueError("return_history is not available with mesh=")
        return _mesh_label_propagation(
            graph, mesh, max_iter, init_labels, plan, sink
        )
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan
    from graphmine_tpu.ops.superstep_policy import (
        emit_device_residency,
        emit_plan_records,
        emit_program_memory,
        noting,
        plan_anchor,
        program_log,
        reckoned_temp_bytes,
        select_superstep_family,
    )

    if isinstance(plan, str) and plan == "auto":
        plan = None
        if init_labels is None and not isinstance(graph.msg_ptr, jax.core.Tracer):
            family, reason = select_superstep_family(
                graph.num_vertices, graph.num_messages
            )
            seconds, cached, scan = 0.0, False, None
            if family == "bucketed":
                # Weighted graphs ride the fast path too (r2): the plan
                # carries the slot-aligned weight payload.
                plan, seconds, cached = _cached_auto_plan(graph)
                plan, index_seconds, scan = _cached_slot_index(plan)
                seconds += index_seconds
            emit_plan_records(
                sink, "lpa_superstep", plan, reason, seconds, cached,
                graph.num_edges, graph.num_messages,
                num_vertices=graph.num_vertices, scan=scan,
            )
            if plan is not None:
                emit_device_residency(sink, "lpa_superstep", graph, plan, scan)
    elif plan is not None and not isinstance(plan, BucketedModePlan):
        raise ValueError(
            f"plan must be 'auto', None or a BucketedModePlan; got {plan!r}"
        )
    elif plan is not None and plan.send_idx:
        plan, _, _ = _cached_slot_index(plan)
    if (
        isinstance(plan, BucketedModePlan)
        and plan.hist_vertex_ids is not None
        and init_labels is not None
        and not isinstance(init_labels, jax.core.Tracer)
    ):
        # The fused histogram path scatter-adds labels as indices in
        # [0, V); out-of-range labels would silently drop and argmax an
        # all-zero histogram to label 0. Check while still concrete.
        import numpy as _np

        il = _np.asarray(init_labels)
        if len(il) and (il.min() < 0 or il.max() >= plan.num_vertices):
            raise ValueError(
                "fused plans with a histogram path need init_labels in "
                f"[0, {plan.num_vertices}); got range "
                f"[{int(il.min())}, {int(il.max())}] — pass plan=None for "
                "arbitrary label values"
            )
    carried = (
        plan is not None and plan.out_slot is not None and not _under_a_trace()
    )
    job = _carried_rows_job if carried else _label_propagation
    if sink is not None and not isinstance(graph.msg_ptr, jax.core.Tracer):
        # Achieved-vs-model attribution (ISSUE 12): wall-time the whole
        # job as one window of max_iter supersteps and judge it
        # against the analytical cost model — one superstep_timing record
        # per call, zero extra device syncs beyond the result fetch the
        # caller was about to pay anyway.
        from graphmine_tpu.obs.costmodel import (
            emit_superstep_timing,
            superstep_cost,
            timed_fixpoint,
        )

        programs = program_log(
            sink, plan_anchor(graph, plan),
            partial(reckoned_temp_bytes, plan) if carried else None,
        )
        if carried:
            timed = {"clock": time.perf_counter, "programs": programs}
        else:
            job, timed = noting(programs, "scan", job, max_iter=max_iter), {}
        (labels, per_step), secs, cold = timed_fixpoint(
            lambda: job(graph, max_iter, init_labels, plan, **timed),
        )
        cost = superstep_cost(
            "lpa_superstep",
            "sort" if plan is None else "auto",
            graph.num_vertices, graph.num_messages, graph.num_edges,
            plan=plan, weighted=graph.msg_weight is not None,
        )
        emit_superstep_timing(
            sink, "lpa_superstep", cost, max_iter, max_iter, secs,
            graph.num_edges, variant="fused", cold_compile=cold,
        )
        if plan is not None and plan.send_idx:
            _emit_superstep_delta(
                sink, per_step, plan.num_messages,
                _plan_rows_and_slots(plan.send_idx),
            )
        emit_program_memory(sink, "lpa_superstep", programs)
    else:
        labels, per_step = job(graph, max_iter, init_labels, plan)
    if return_history:
        return labels, jnp.asarray(per_step["changed_vertices"], jnp.int32)
    return labels


def _emit_superstep_delta(
    sink, per_step: dict, num_messages: int, full: tuple,
    shards: int | None = None,
) -> None:
    """The ``superstep_delta`` record of one job over a fused plan's dense
    rows, from the job's per-superstep counts (the host's own by now; the
    stateless scan's come back with its labels). The stateless scan, which
    runs where the rows were not admitted to the device, has no ``branch``
    to report: every one of its supersteps is a full gather, and the
    record says that. ``full`` is the plan's ``(rows, slots)``: what
    ``dirty_rows`` and ``dirty_slots`` say of a superstep whose reduce ran
    over every row (``reduce: "full"``; every superstep of the stateless
    scan and of the mesh job). On a mesh (``shards``) ``num_messages`` and
    ``changed_messages`` are the largest shard's: what the rungs are cut
    from and what picks one for every shard; ``full`` is one shard's."""
    import numpy as np

    from graphmine_tpu.ops.superstep_policy import delta_rungs

    changed = np.asarray(per_step["changed_vertices"]).tolist()
    if "branch" in per_step:
        names = [*delta_rungs(num_messages), "full"]
        branch = [names[b] for b in per_step["branch"]]
        messages = per_step["changed_messages"]
        rungs = names[:-1]
    else:
        branch, messages, rungs = ["full"] * len(changed), [], []
    nothing = [None] * len(changed)
    dirty = [
        [whole if got is None else got for got in per_step.get(key, nothing)]
        for key, whole in zip(("dirty_rows", "dirty_slots"), full)
    ]
    sink.emit(
        "superstep_delta", op="lpa_superstep", changed_vertices=changed,
        changed_messages=messages, branch=branch, rungs=rungs,
        num_messages=num_messages,
        reduce=per_step.get("reduce", ["full"] * len(changed)),
        dirty_rows=dirty[0], dirty_slots=dirty[1],
        seconds=[round(s, 6) for s in per_step.get("seconds", ())],
        **({} if shards is None else {"shards": shards}),
    )


def _plan_rows_and_slots(send_idx) -> tuple:
    """``(rows, slots)`` of a plan's dense rows, from its matrices' shapes
    (a shard's, from the stacked ``[D, n, w]`` matrices of a mesh plan)."""
    shapes = [idx.shape[-2:] for idx in send_idx]
    return sum(n for n, _ in shapes), sum(n * w for n, w in shapes)


def _under_a_trace() -> bool:
    """A caller's ``jit`` (or other transform) is tracing: no value is
    concrete, so the host cannot read a count between two programs."""
    return not jax.core.trace_ctx.is_top_level()


# the admission's answer where no value is concrete and the host reads no K
_NO_HOST_STEPS = ("plain", "under a trace the supersteps cannot step from the host")

_auto_plan_cache: dict = {}


def _cached_auto_plan(graph: Graph):
    """The graph's auto (bucketed) plan, cached so repeated calls pay the
    host build (device->host fetch of msg_ptr/msg_send + NumPy layout)
    once. Keyed by the identity of the graph's msg_ptr array; a weakref
    finalizer evicts the entry when that array is collected. Returns
    ``(plan, build_seconds, cached)`` — the ``plan_build`` record's raw
    material (seconds is 0.0 on a cache hit)."""
    import weakref

    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan
    from graphmine_tpu.ops.superstep_policy import timed_plan_build

    key = id(graph.msg_ptr)
    hit = _auto_plan_cache.get(key)
    if hit is not None and hit[0]() is graph.msg_ptr:
        return hit[1], 0.0, True
    plan, seconds = timed_plan_build(
        lambda: BucketedModePlan.from_graph(graph, with_send=True)
    )
    ref = weakref.ref(
        graph.msg_ptr, lambda _, k=key: _auto_plan_cache.pop(k, None)
    )
    _auto_plan_cache[key] = (ref, plan)
    return plan, seconds, False


_slot_index_cache: dict = {}


def _cached_slot_index(plan, reduce: str = "mode"):
    """``(plan, build seconds, (scan, reason))``: the fused ``plan`` as the
    job will run it. Once per plan (as the plan is paid once per graph)
    :func:`~graphmine_tpu.ops.superstep_policy.admit_carried_rows` says
    whether the carried rows and their index fit the device beside what
    it holds; only then is the index of the carried-rows job built
    (:func:`~graphmine_tpu.ops.bucketed_mode.with_slot_index`), and the
    plan comes back with it. The question is a job's own (``reduce``:
    ``"mode"`` for LPA, ``"min"`` for the BFS job of ``ops/paths.py``,
    whose programs hold other temporaries), asked once per plan and job;
    the index is one, built for the first job admitted and shared. Under ``plain`` the plan comes back as it
    is, and runs the stateless bucketed scan; under a caller's trace too,
    with no question asked and nothing kept. The answer and the index
    are kept (0.0 seconds on a hit), keyed by the identity of the plan's
    first row matrix; a weakref finalizer evicts the entry with it. The
    index stays out of the plan the cache of :func:`_cached_auto_plan`
    holds: ``connected_components`` shares that plan and reads no index."""
    import dataclasses
    import weakref

    from graphmine_tpu.ops.bucketed_mode import with_slot_index
    from graphmine_tpu.ops.superstep_policy import (
        admit_carried_rows,
        device_memory_stats,
        timed_plan_build,
    )

    if _under_a_trace():
        return plan, 0.0, _NO_HOST_STEPS
    if plan.out_slot is not None:
        return plan, 0.0, ("carried", "the plan came with its slot index")
    if not plan.send_idx:
        return plan, 0.0, ("plain", "no dense rows to carry")
    anchor = plan.send_idx[0]
    key = id(anchor)
    hit = _slot_index_cache.get(key)
    seconds = 0.0
    if hit is None or hit[0]() is not anchor:
        # the index alone: a cached plan would keep its own anchor alive
        hit = (
            weakref.ref(anchor, lambda _, k=key: _slot_index_cache.pop(k, None)),
            {"index": None, "scan": {}},
        )
        _slot_index_cache[key] = hit
    kept = hit[1]
    if reduce not in kept["scan"]:
        stats = device_memory_stats(plan)
        if stats and kept["index"] is not None:
            # another job's index is in use already: it is not asked for twice
            held = sum(int(x.nbytes) for x in kept["index"])
            stats = dict(stats, bytes_in_use=int(stats.get("bytes_in_use", 0)) - held)
        kept["scan"][reduce] = admit_carried_rows(plan, stats, reduce=reduce)
    scan = kept["scan"][reduce]
    if scan[0] == "carried":
        if kept["index"] is None:
            indexed, seconds = timed_plan_build(lambda: with_slot_index(plan))
            kept["index"] = (indexed.out_ptr, indexed.out_slot)
        if kept["index"][1] is not None:
            out_ptr, out_slot = kept["index"]
            plan = dataclasses.replace(plan, out_ptr=out_ptr, out_slot=out_slot)
    return plan, seconds, scan


_mesh_partition_cache: dict = {}


def _mesh_label_propagation(graph, mesh, max_iter, init_labels, plan, sink):
    """The mesh half of :func:`label_propagation`: resolve the family,
    partition and place the graph (cached, with the shards' slot index
    where the chips have room for the carried rows), emit the provenance
    records, then step the carried-rows job from the host
    (:func:`~graphmine_tpu.parallel.sharded.carried_label_propagation`) or,
    where there is no index or the host cannot step (a caller's trace),
    run the one compiled program."""
    from graphmine_tpu.ops.superstep_policy import (
        crossover_thresholds,
        emit_program_memory,
        emit_shard_residency,
        noting,
        program_log,
        reckoned_temp_bytes,
        select_superstep_family,
    )
    from graphmine_tpu.parallel.sharded import (
        _sharded_lpa_jit,
        carried_label_propagation,
        shard_messages,
        shard_plan_shapes,
    )

    if not isinstance(plan, str):
        raise ValueError(
            "with mesh=, plan is 'auto' or a superstep family name; got "
            f"{plan!r}"
        )
    family, reason = select_superstep_family(
        graph.num_vertices, graph.num_messages, requested=plan,
        num_devices=mesh.size,
    )
    sg, stats, cached = _cached_mesh_partition(graph, mesh, family)
    scan = stats["scan"]
    if scan[0] == "carried" and _under_a_trace():
        scan = _NO_HOST_STEPS
    if sink is not None:
        cost = stats["cost"]
        sink.emit(
            "impl_selected", op="lpa_superstep", impl=family,
            n=graph.num_messages, reason=reason, devices=mesh.size,
            thresholds=crossover_thresholds(), cost=cost,
            scan=scan[0], scan_reason=scan[1],
        )
        sink.emit(
            "partition", shards=mesh.size, family=family, cached=cached,
            schedule="replicated",
            seconds=0.0 if cached else round(stats["partition_seconds"], 6),
        )
        if family != "sort":
            sink.emit(
                "plan_build", op="lpa_superstep", family=family, cached=cached,
                seconds=0.0 if cached else round(
                    stats["plan_seconds"] + stats["index_seconds"], 6
                ),
                index_seconds=0.0 if cached else round(stats["index_seconds"], 6),
                width_classes=stats["width_classes"],
                padded_slots_per_edge=round(
                    stats["padded_slots_per_shard"] * mesh.size
                    / max(graph.num_edges, 1), 3,
                ),
                cost=cost,
            )
            emit_shard_residency(sink, "lpa_superstep", sg, mesh, scan)
        sink.emit("exchange", op="lpa_superstep", family=family, **stats["exchange"])
    # what the partition's cache keys on stays with the graph; the placed
    # shards are this (graph, mesh, family)'s own
    anchor = jax.tree.leaves(sg)[0]
    if scan[0] == "carried":
        programs = program_log(
            sink, anchor, shards=sg.num_shards,
            reckoned=lambda: reckoned_temp_bytes(
                shard_plan_shapes(sg, shard_messages(sg)), shards=sg.num_shards
            ),
        )
        labels, per_step = carried_label_propagation(
            sg, mesh, max_iter, init_labels,
            clock=time.perf_counter if sink is not None else None,
            programs=programs,
        )
        if sink is not None:
            _emit_superstep_delta(
                sink, per_step, shard_messages(sg),
                _plan_rows_and_slots(sg.bucket_send), shards=sg.num_shards,
            )
            emit_program_memory(sink, "lpa_superstep", programs)
        return labels
    if sg.out_slot is not None:  # the one program is handed what it reads
        import dataclasses

        sg = dataclasses.replace(sg, out_ptr=None, out_slot=None)
    # sharded_label_propagation's one program, no tripwire and no telemetry
    programs = program_log(sink, anchor, shards=sg.num_shards)
    labels = noting(programs, "scan", _sharded_lpa_jit, max_iter=max_iter)(
        sg, mesh, max_iter, init_labels, 0, False
    )
    emit_program_memory(sink, "lpa_superstep", programs)
    return labels


def _cached_mesh_partition(graph: Graph, mesh, family: str):
    """``(sharded graph on the mesh, stats, cached)`` per (graph, mesh,
    family): the host partition, its plan, the placement and the shards'
    slot index are paid once, as :func:`_cached_auto_plan` and
    :func:`_cached_slot_index` pay a plan and its index once. Keyed by the
    identity of the graph's ``msg_ptr`` (host or device array); a weakref
    finalizer evicts the entry with it. Only the placed shards are kept:
    the host copies go as soon as they are on the devices.

    Once the plan is placed, :func:`~graphmine_tpu.ops.superstep_policy.
    admit_carried_rows` is asked, of the fullest chip of the mesh, whether
    a shard's carried rows and slot index go on it beside what it holds;
    only then is the index built, from the host partition before it is let
    go (:func:`~graphmine_tpu.parallel.sharded.with_shard_slot_index`), and
    placed. ``stats["scan"]`` keeps the answer and its arithmetic,
    ``stats["index_seconds"]`` the build and the placement. The ``sort``
    family keeps no rows; a mesh that spans processes is not asked (the
    host steps the job by reading K from its own replica, and cannot step
    another host's chips); under a caller's trace nothing is asked and
    nothing kept, as on one chip."""
    import dataclasses
    import weakref

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from graphmine_tpu.obs.costmodel import sharded_superstep_cost
    from graphmine_tpu.ops.superstep_policy import (
        admit_carried_rows,
        mesh_memory_stats,
    )
    from graphmine_tpu.parallel.sharded import (
        _shard_message_offsets,
        _vertex_axes,
        partition_graph,
        shard_graph_arrays,
        shard_plan_shapes,
        with_shard_slot_index,
    )

    traced = _under_a_trace()
    key = id(graph.msg_ptr)
    hit = _mesh_partition_cache.get(key)
    if hit is None or hit[0]() is not graph.msg_ptr:
        ref = weakref.ref(
            graph.msg_ptr, lambda _, k=key: _mesh_partition_cache.pop(k, None)
        )
        hit = (ref, {})
        if not traced:
            _mesh_partition_cache[key] = hit
    placed = hit[1]
    where = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names, family)
    if where in placed:
        return (*placed[where], True)
    lpa_only = family != "sort"
    timings: dict = {}
    t0 = time.perf_counter()
    host = partition_graph(
        graph, mesh=mesh, lpa_only=lpa_only, timings=timings,
        build_bucket_plan=family == "bucketed",
    )
    sg = shard_graph_arrays(host, mesh, lpa_only=lpa_only)
    jax.block_until_ready(sg)  # the transfer is set-up's, not the first job's
    seconds = time.perf_counter() - t0
    counts = np.diff(_shard_message_offsets(
        np.asarray(graph.msg_ptr), sg.num_shards, sg.chunk_size
    ))
    t0 = time.perf_counter()
    if family != "bucketed":
        scan = ("plain", f"the {family} family keeps no gathered rows")
    elif traced:
        scan = _NO_HOST_STEPS
    elif jax.process_count() > 1:
        scan = ("plain", "the mesh spans processes: one host cannot step "
                         "another's chips by reading K from its own replica")
    else:
        scan = admit_carried_rows(
            shard_plan_shapes(sg, int(counts.max(initial=0))),
            mesh_memory_stats(mesh), shards=sg.num_shards,
        )
    if scan[0] == "carried":
        indexed = with_shard_slot_index(host, counts)
        if indexed.out_slot is None:
            scan = ("plain", "no slot index: a shard's slots are none, or "
                             "more than an int32 counts")
        else:
            spec = NamedSharding(mesh, PartitionSpec(_vertex_axes(mesh)))
            sg = dataclasses.replace(
                sg, out_ptr=jax.device_put(indexed.out_ptr, spec),
                out_slot=jax.device_put(indexed.out_slot, spec),
            )
            jax.block_until_ready((sg.out_ptr, sg.out_slot))
        del indexed
    del host
    index_seconds = time.perf_counter() - t0
    # shapes only: the padded slots a shard streams and the bytes a chip
    # receives per superstep have one owner, the cost model
    cost = sharded_superstep_cost(
        "lpa_superstep", sg, graph.num_edges, num_messages=graph.num_messages
    )
    stats = {
        "partition_seconds": seconds - timings["plan_seconds"],
        "plan_seconds": timings["plan_seconds"],
        "index_seconds": index_seconds,
        "scan": scan,
        "width_classes": len(sg.bucket_send),
        "padded_slots_per_shard": cost.padded_slots,
        "cost": cost.record(),
        "exchange": {
            "shards": sg.num_shards,
            "bytes_per_superstep": cost.exchange_bytes,
            "messages_per_shard_max": int(counts.max()),
            "messages_per_shard_mean": float(counts.mean()),
            "padded_slots_per_shard": cost.padded_slots,
        },
    }
    placed[where] = (sg, stats)
    return sg, stats, False


@partial(jax.jit, static_argnames=("max_iter",))
def _label_propagation(
    graph: Graph,
    max_iter: int = 5,
    init_labels: jax.Array | None = None,
    plan=None,
):
    """``(labels, per_step)``: all ``max_iter`` stateless supersteps as one
    ``lax.scan``: the sort family (``plan=None``), a plan without a slot
    index, and every plan under a caller's trace. ``per_step`` holds
    ``changed_vertices``, ``int32[max_iter]``. A plan with its slot index
    outside a trace runs :func:`_carried_rows_job` instead."""
    labels = (
        jnp.arange(graph.num_vertices, dtype=jnp.int32)
        if init_labels is None
        else init_labels.astype(jnp.int32)
    )
    if plan is None:
        superstep = lambda lbl: lpa_superstep(lbl, graph)
    else:
        from graphmine_tpu.ops.bucketed_mode import lpa_superstep_bucketed

        superstep = lambda lbl: lpa_superstep_bucketed(lbl, graph, plan)

    def step(labels, _):
        new = superstep(labels)
        with jax.named_scope("superstep"), jax.named_scope("changed_count"):
            changed = jnp.sum(new != labels, dtype=jnp.int32)
        return new, changed

    labels, changed = lax.scan(step, labels, None, length=max_iter)
    return labels, {"changed_vertices": changed}


# The carried-rows job's three kinds of program (ISSUE 36). The rows are a
# donated argument of the two that update them, so the chip's compiler
# writes each class's gather and the rewrite's scatter into the buffer it
# was handed: no copy of the rows, no temporary of their size (held by
# tests/test_chip_compile.py). The plan is an argument of each: closed
# over, its arrays would be constants of the program.


@partial(jax.jit, static_argnames=("slots",))
def _blank_rows(slots: int):
    """The job's rows before the first superstep's full gather."""
    return jnp.zeros((slots,), jnp.int32)


@partial(jax.jit, donate_argnums=0)
def _gather_program(rows, labels, plan):
    from graphmine_tpu.ops.bucketed_mode import gather_rows

    return gather_rows(rows, labels, plan)


@partial(jax.jit, static_argnames=("cap", "marked"), donate_argnums=0)
def _rewrite_program(rows, labels, changed, plan, cap: int, marked: bool = False):
    """The rows rewritten in place; ``marked``, ``(rows, dirty)``: beside
    them the rows the rewrite wrote to, for :func:`_dirty_modes_program`."""
    from graphmine_tpu.ops.bucketed_mode import rewrite_rows, rewrite_rows_marked

    if marked:
        return rewrite_rows_marked(rows, labels, changed, plan, cap)
    return rewrite_rows(rows, labels, changed, plan, cap)


def _changed_and_k(new, labels, plan):
    """``(changed, K, count)`` of one superstep: K the messages the changed
    vertices send, which picks the next superstep's update, and count the
    changed vertices."""
    with jax.named_scope("superstep"), jax.named_scope("changed_count"):
        changed = new != labels
        out_deg = plan.out_ptr[1:] - plan.out_ptr[:-1]
        k = jnp.sum(jnp.where(changed, out_deg, 0), dtype=jnp.int32)
        count = jnp.sum(changed, dtype=jnp.int32)
    return changed, k, count


@jax.jit
def _modes_program(rows, labels, plan):
    """``(new labels, changed, K, count)`` of one superstep over ``rows``:
    K the messages the changed vertices send, which picks the next
    superstep's update, and count the changed vertices."""
    from graphmine_tpu.ops.bucketed_mode import lpa_modes_from_rows

    new = lpa_modes_from_rows(rows, labels, plan)
    return (new, *_changed_and_k(new, labels, plan))


@jax.jit
def _dirty_modes_program(rows, labels, dirty, plan):
    """:func:`_modes_program` after a marked rewrite, over the ``dirty``
    rows alone: ``(new labels, changed, K, count, dirty rows, dirty
    slots)``, the first four its results bit for bit."""
    from graphmine_tpu.ops.bucketed_mode import lpa_modes_from_dirty_rows

    new, dirty_rows, dirty_slots = lpa_modes_from_dirty_rows(
        rows, labels, dirty, plan
    )
    return (new, *_changed_and_k(new, labels, plan), dirty_rows, dirty_slots)


def _carried_rows_job(
    graph: Graph, max_iter: int, init_labels, plan, clock=None, programs=None
):
    """``(labels, per_step)`` of ``max_iter`` supersteps over a fused plan
    with its slot index, stepped from the host
    (:func:`~graphmine_tpu.ops.superstep_policy.step_carried_rows`): the
    gathered rows live in one buffer across supersteps, and a superstep
    reads again only what changed.

    Each superstep first brings the rows up to the labels it starts from,
    by the update its predecessor's count picks: K, the messages sent by
    the vertices whose label changed. K above every rung of
    :func:`~graphmine_tpu.ops.superstep_policy.delta_rungs` gathers every
    class anew (:func:`_gather_program`; the first superstep always);
    K <= a rung rewrites that many slots through the index
    (:func:`_rewrite_program`, compiled when a job first takes the rung).
    Then :func:`_modes_program` runs the row modes, the histogram hubs and
    the write back over the rows, as in ``lpa_superstep_bucketed``: the
    labels are its labels bit for bit. The host waits once a superstep,
    for K; ``max_iter`` is the length of its loop and no program's
    argument. ``per_step`` holds ``changed_vertices``,
    ``changed_messages`` and ``branch`` (the rung's place, or
    ``len(rungs)`` for a full gather), one a superstep; with a ``clock``
    (the caller's, where a sink wants them) also ``seconds``. ``programs``
    (the caller's too, :class:`~graphmine_tpu.ops.superstep_policy.
    ProgramLog`) notes each program the job runs."""
    from graphmine_tpu.ops.bucketed_mode import check_plan_fits, row_slots
    from graphmine_tpu.ops.superstep_policy import (
        delta_rungs,
        noting,
        step_carried_rows,
    )

    labels = (
        jnp.arange(graph.num_vertices, dtype=jnp.int32)
        if init_labels is None
        else jnp.asarray(init_labels).astype(jnp.int32)
    )
    check_plan_fits(labels, graph, plan)
    slots = row_slots(plan)
    blank = noting(programs, "blank_rows", _blank_rows, slots=slots)
    gather = noting(programs, "gather", _gather_program)
    rewrite = noting(programs, "rewrite", _rewrite_program)
    modes = noting(programs, "modes", _modes_program)
    dirty_modes = noting(programs, "dirty_modes", _dirty_modes_program)
    return step_carried_rows(
        max_iter, delta_rungs(plan.num_messages), plan.num_messages + 1,
        blank(slots), labels,
        gather=lambda rows, labels: gather(rows, labels, plan),
        rewrite=lambda rows, labels, changed, cap, marked=False: rewrite(
            rows, labels, changed, plan, cap=cap, marked=marked
        ),
        modes=lambda rows, labels: modes(rows, labels, plan),
        # a weighted plan's weights are a matrix a class: its job keeps the
        # full reduce (bucketed_mode.lpa_modes_from_dirty_rows)
        dirty_modes=None if plan.weight_mat is not None else (
            lambda rows, labels, dirty: dirty_modes(rows, labels, dirty, plan)
        ),
        clock=clock,
    )


def num_communities(labels: jax.Array) -> jax.Array:
    """Distinct-label count (the reference's headline print, ``Graphframes.py:85``)."""
    v = labels.shape[0]
    present = jnp.zeros((v,), jnp.int32).at[labels].set(1, mode="drop")
    return present.sum()


def canonicalize(labels: jax.Array) -> jax.Array:
    """Relabel communities to dense ids ordered by first member vertex.

    Makes partitions comparable across engines/tie-breaks (SURVEY §6:
    validate partitions, not raw label values).
    """
    v = labels.shape[0]
    first_member = jnp.full((v,), v, jnp.int32).at[labels].min(jnp.arange(v, dtype=jnp.int32))
    rep = first_member[labels]  # representative = smallest vertex id in community
    order = jnp.unique(rep, size=v, fill_value=v)
    dense = jnp.searchsorted(order, rep)
    return dense.astype(jnp.int32)
