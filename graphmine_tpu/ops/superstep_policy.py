"""Superstep family policy: which plan a LPA / CC / PageRank superstep runs.

Two families. ``bucketed`` (:class:`~graphmine_tpu.ops.bucketed_mode.
BucketedModePlan`: per-degree-class dense rows gathered straight from the
label vector) is the fast path, fused and per shard on a mesh. ``sort``
(the ``segment_mode`` / ``segment_min`` / ``segment_sum`` superstep over
the message CSR, no plan) is the reference every test holds ``bucketed``
to, the path for graphs too small to repay a plan build, and the planner's
degrade rung.

This module owns the choice (:func:`select_superstep_family`) and the
``impl_selected`` / ``plan_build`` records that explain it
(:func:`emit_plan_records`); ``ops/lpa.py``, ``ops/cc.py``,
``ops/pagerank.py``, ``pipeline/planner.py`` and ``pipeline/driver.py``
read both from here. Beside it, for the one-chip LPA job over a fused
plan: whether the gathered rows and their slot index go on the device
(:func:`admit_carried_rows`) and the ``device_residency`` record that
says what the device then holds (:func:`emit_device_residency`); for the
PageRank job over the same plan, which carries nothing, what its one
iteration's program takes beside the plan (:func:`stepped_residency`).
For every job that steps or runs compiled programs under a sink: what each
program takes of the chip by its own executable's account
(:class:`ProgramLog`, :func:`emit_program_memory`).
"""

from __future__ import annotations

import time
import weakref

from graphmine_tpu.obs.costmodel import _bucketed_padded_slots, superstep_cost
from graphmine_tpu.obs.memmodel import (
    FAMILY_DEGRADE,
    carried_job_transients,
    carried_rows_inventory,
    row_sum_transients,
    superstep_footprint,
)

# bucketed beats sort from ~2^16 messages (r1 measurement, the threshold
# label_propagation has shipped since; the plan build amortizes past there).
#
# Two more families were built, measured on a TPU v5e and deleted (PR 29;
# docs/DESIGN.md "Tried, measured, deleted"):
#   * `blocked` (propagation blocking: sender-major stream -> destination-
#     binned tile -> tile-local rows), PR 26, V = 2^22, M = 128.3 M: device
#     seconds per LPA superstep 6.134 (bin_gather 2.40 + bin_scatter 1.13 +
#     row_gather 2.50) against bucketed's 1.139 (row_gather 1.02 + the rest
#     0.10). A gather from a value table past on-chip capacity did not cost
#     several times a tile-local one, so two gathers and a scatter lost to one.
#   * the 2D partition (labels sharded, per-peer boundary `ppermute`, then
#     blocked's passes), PR 27, four chips, same graph: 12.397 s a job against
#     2.748 s for per-shard bucket rows + one tiled `all_gather` a superstep,
#     whose exchange is 0.05 % of the job: nothing for a thinner exchange to win.
# The ring schedule (`parallel/ring.py`, 33.291 s there) is the planner's
# memory rung for label vectors that do not fit replicated, not a speed choice.
BUCKETED_MIN_MESSAGES = 1 << 16

# the families, fast to lean: the keys of the one degrade order
FAMILIES = tuple(FAMILY_DEGRADE)

# Carried rows (PR 32): the one-chip LPA job keeps the gathered rows and,
# when the senders whose label changed send K <= a rung messages, rewrites
# K slots (padded to the rung: a static shape) instead of gathering all S.
# A rung is M over a divisor; K above the last rung takes the full gather.
# Measured on a TPU v5e, V = 2^22, M = 128.3 M, S = 137.8 M (PERF.md §6,
# PR 32): the full gather 0.970 s a superstep; a rewrite 0.012 s for the
# sort that compacts the changed senders whatever the rung, 0.03-0.09 s to
# lay their spans out, then 21.2 ns a place of the rung to read `out_slot`
# (fusion s32[21384447] 0.454 s) and 8.3 ns to scatter the label, so a rung
# of R costs about 0.10 s + 29.5 ns x R and meets the full gather at
# R = 29.5 M = M / 4.35. The top rung stands at three quarters of that
# crossover (M / 6: 0.73 s), so the worst K under it still repays the
# switch; the rungs below step by 16, and the lowest two cost 0.02 and
# 0.05 s, under the 0.13 s of row modes and write back that every
# superstep pays either way.
DELTA_RUNG_DIVISORS = (4096, 256, 16, 6)

# The dirty reduce (PR 43): after a rewrite on a rung at or under this place
# of `delta_rungs` the one-chip job reduces only the rows the rewrite wrote
# to (`ops/bucketed_mode.py:lpa_modes_from_dirty_rows`); above it, and after
# a full gather, every row (`lpa_modes_from_rows`). The lowest rung, M/4096,
# and no higher, by a superstep's seconds on a TPU v5e on both sides of it
# (PERF.md §5-6, PR 43; `_proof/dirty_place.py`, one process a graph):
#   * after M/4096 (the quiet tail: 0.2 % of the rows dirty, 5 % of the
#     slots on graph500-22 and 9 % on graph500-24): 0.1350 -> 0.0589 s at
#     scale 22, 0.5187 -> 0.1896 s at scale 24;
#   * after M/256 (4-7 % of the rows, 36-53 % of the slots): 0.1543 ->
#     0.3152 s at scale 22, 0.6101 -> 1.7825 s at scale 24. A trip of the
#     dirty reduce takes 8 rows in ~20 us whatever their width, and the rows
#     a full reduce sweeps at 2 ns a row are narrow ones; by sort cost 54-72 %
#     of the plan is dirty there anyway.
DIRTY_REDUCE_TOP_PLACE = 0


# The places a trip of the BFS job's bottom-up loop takes
# (ops/bucketed_mode.bfs_level_bottom_up). A level costs its trips, so U rounded
# up to this. Measured on graph500-24 (PERF.md §6, PR 53): 39.3-39.5 ns a place
# at 2^19 and at 2^20, 35.1 at 2^22; one trip, which is what each of a search's
# last levels pays for a few thousand edges, 0.034 s at 2^19, 0.064 at 2^20,
# 0.17 at 2^22. At 2^19 the level of 68.2 M edges takes 131 trips.
BOTTOM_UP_CHUNK = 1 << 19


def bottom_up_chunk(num_messages: int) -> int:
    """A trip's places on a graph of ``num_messages``: no more than it has."""
    return max(1, min(BOTTOM_UP_CHUNK, int(num_messages)))


def delta_rungs(num_messages: int) -> tuple:
    """The rungs of the carried-rows job, ascending: the static caps on
    the messages a sparse superstep rewrites (none on a graph too small
    to have one: every superstep then gathers in full)."""
    return tuple(sorted(
        {num_messages // d for d in DELTA_RUNG_DIVISORS} - {0}
    ))


def step_carried_rows(
    max_iter: int, rungs: tuple, over: int, rows, labels,
    gather, rewrite, modes, dirty_modes=None, clock=None,
    until_quiet: bool = False,
):
    """``(labels, per_step)`` of ``max_iter`` supersteps of a carried-rows
    job, stepped from the host: the loop of ``ops/lpa.py:_carried_rows_job``,
    of its mesh form (``parallel/sharded.py:carried_label_propagation``)
    and of the BFS job where its rows were not admitted
    (``ops/paths.py:_full_width_job``: no rung, nothing carried), which hand
    it their programs. Each superstep first brings ``rows`` up
    to the labels it starts from by the update its predecessor's K picks
    (K above every rung: ``gather(rows, labels)``; K <= a rung:
    ``rewrite(rows, labels, changed, rung)``), then ``modes(rows, labels)``
    gives ``(new labels, changed, K, count)``. ``over`` is the K that picks
    the first superstep's update: the rows start blank, so it is a K above
    every rung and the first superstep a full gather.
    The stop: ``max_iter`` supersteps, or with ``until_quiet`` the first
    that moves nothing (its count is the one the host fetches anyway),
    whichever comes first. With ``dirty_modes`` (the
    one-chip job's), a rewrite on a rung at or under
    :data:`DIRTY_REDUCE_TOP_PLACE` is asked for the rows it wrote to
    (``rewrite(..., marked=True)`` gives ``(rows, dirty)``) and
    ``dirty_modes(rows, labels, dirty)`` reduces those alone: the same
    four results, then the count of dirty rows and of their slots. A row
    that was not rewritten keeps its mode, so the labels are the full
    reduce's bit for bit; after a full gather, and on the rungs above,
    ``modes`` runs. The host waits once a superstep, for K and the
    counts; ``max_iter`` is the length of this loop and no program's
    argument. ``per_step`` holds ``changed_vertices``,
    ``changed_messages`` (K), ``branch`` (the rung's place, or
    ``len(rungs)`` for a full gather), ``reduce`` (``"full"`` or
    ``"dirty"``) and ``dirty_rows`` / ``dirty_slots`` (``None`` where the
    reduce was full), one a superstep; with a ``clock`` also ``seconds``,
    the clock's reading after each fetch of K less the reading before it:
    a superstep's seconds on the host's clock, at the wait the job has.
    The BFS job over carried rows steps itself
    (``ops/paths.py:_frontier_job``): its levels choose between this
    loop's updates and one that reads no row."""
    import jax

    k, changed = over, None
    count, sent, branch, dirty = [], [], [], []
    marks = [clock()] if clock else []
    for _ in range(max_iter):
        place = sum(k > rung for rung in rungs)
        branch.append(place)
        if place == len(rungs):
            rows, touched = gather(rows, labels), None
        elif dirty_modes is not None and place <= DIRTY_REDUCE_TOP_PLACE:
            rows, touched = rewrite(rows, labels, changed, rungs[place], marked=True)
        else:
            rows, touched = rewrite(rows, labels, changed, rungs[place]), None
        if touched is None:
            labels, changed, *counts = modes(rows, labels)
        else:
            labels, changed, *counts = dirty_modes(rows, labels, touched)
        k, moved, *of_dirty = (int(x) for x in jax.device_get(counts))  # the one wait
        sent.append(k)
        count.append(moved)
        dirty.append(tuple(of_dirty) or None)
        if clock:
            marks.append(clock())
        if until_quiet and moved == 0:
            break
    per_step = {
        "changed_vertices": count, "changed_messages": sent, "branch": branch,
        "reduce": ["full" if d is None else "dirty" for d in dirty],
        "dirty_rows": [d and d[0] for d in dirty],
        "dirty_slots": [d and d[1] for d in dirty],
    }
    if clock:
        per_step["seconds"] = [b - a for a, b in zip(marks, marks[1:])]
    return labels, per_step


_INT32_MAX = (1 << 31) - 1


def device_memory_stats(plan) -> dict | None:
    """The allocator's statistics of the device that holds ``plan``'s rows
    (a host-side query, no sync); ``None`` where the backend keeps none
    (the CPU) or the rows sit on no one device."""
    placed = plan.send_idx[0].devices() if plan.send_idx else ()
    return next(iter(placed)).memory_stats() if len(placed) == 1 else None


def mesh_memory_stats(mesh) -> dict | None:
    """The allocator's statistics of the FULLEST device of ``mesh``: the
    one with the least ``bytes_limit`` less ``bytes_in_use`` (every chip
    runs the one SPMD program, so the fullest decides for all); ``None``
    where a device keeps none (the CPU)."""
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if not all(s and s.get("bytes_limit") for s in stats):
        return None
    return min(
        stats, key=lambda s: int(s["bytes_limit"]) - int(s.get("bytes_in_use", 0))
    )


def _admission_sizes(plan, shards: int, reduce: str) -> dict:
    """What the admission sizes the job's programs at: the rewrite at the
    top rung; the BFS job's bottom-up level at its loop's chunk."""
    rungs = delta_rungs(int(plan.num_messages))
    return dict(
        top_rung=max(rungs, default=0), shards=shards, reduce=reduce,
        bottom_up_chunk=bottom_up_chunk(plan.num_messages) if reduce == "min" else 0,
    )


def reckoned_temp_bytes(plan, shards: int = 1, reduce: str = "mode") -> dict:
    """``{(program, cap): bytes}``: what :func:`admit_carried_rows` counted
    for each program's temporaries, under the names the job gives its
    programs (the BFS job's row min is its ``level``) and, for the
    rewrite, which is reckoned at the top rung alone, that rung's ``cap``;
    ``None`` for a program that has no rung. The hubs' histograms, which
    the admission's sum holds as a term of its own and the compiler as
    temporaries of the two programs that reduce the hubs, are counted with
    those two, so that both sides count the same bytes. A program the
    admission counts nothing for has no entry. The ``reckoned_temp_bytes``
    of the ``program_memory`` records (:class:`ProgramLog`)."""
    sized = _admission_sizes(plan, shards, reduce)
    caps = {"rewrite": sized["top_rung"]}
    hubs = carried_rows_inventory(plan, **sized)["hub_histograms"]
    return {
        ("level" if name == "row_min" else name, caps.get(name)):
            held + (hubs if name in ("modes", "dirty_modes") else 0)
        for name, held in carried_job_transients(plan, **sized).items() if held
    }


def admit_carried_rows(
    plan, stats: dict | None, shards: int = 1, reduce: str = "mode"
) -> tuple[str, str]:
    """``("carried" | "plain", reason)`` for the LPA job over the fused
    ``plan``: do the carried rows and the slot index go on the device
    beside what it already holds? On a mesh (``shards`` > 1) ``plan`` is
    ONE shard's, by shapes (``parallel/sharded.shard_plan_shapes``: its
    vertices the padded vertex space every chip holds the labels of, its
    messages the largest shard's) and ``stats`` the fullest chip's
    (:func:`mesh_memory_stats`), asked once per (graph, mesh); the terms
    are the same, a chip each. Taken once per plan, on the host,
    before the index is built, from the plan's shapes
    (:func:`~graphmine_tpu.obs.memmodel.carried_rows_inventory`: the rows,
    held once, the index, the labels, the hubs' histograms and the
    temporaries of the job's largest program as the chip tiles them: the
    full gather, the row modes or the rewrite at the top rung of
    :func:`delta_rungs`; the ``reason`` names which) against ``stats``
    (:func:`device_memory_stats`): ``bytes_limit`` less ``bytes_in_use``,
    the graph and the plan being in use already. ``plain`` is the
    stateless bucketed scan, the same labels bit for bit at a full gather
    every superstep. A device that reports no limit admits ``carried``.
    ``reduce="min"`` asks for the BFS job over the same rows and index
    (``ops/paths.py``): its own programs (the gather, the row min, the top
    rung's rewrite, the bottom-up level at its loop's chunk) and no
    histogram; ``plain`` is then one compiled full-width level stepped from
    the host.

    The DEVICE's memory alone is sized. The host's is not: each program of
    the job compiles alone, and for graph500-24's plan (607.6 M slots, 58
    classes) the largest, the row modes, took 19.7 GB of host memory, as
    the stateless program's 20 GB (PERF.md §6, PR 36); a job admitted here
    whose compile does not fit the host still ends there, minutes later.
    Every ``reason`` says so."""
    sized = _admission_sizes(plan, shards, reduce)
    need = carried_rows_inventory(plan, **sized)
    by_program = carried_job_transients(plan, **sized)
    largest = max(by_program, key=by_program.get)
    slots = need["carried_rows"] // 4
    if slots == 0 or slots >= _INT32_MAX:
        return "plain", (
            f"{slots} padded slots: nothing to carry, or more than an "
            "int32 slot index counts"
        )
    total = sum(need.values())
    said = (
        (f"a shard of {shards}, on the fullest chip: " if shards > 1 else "")
        + f"rows, held once, {need['carried_rows']} B + slot index "
        f"{need['slot_index']} B + {'depths' if reduce == 'min' else 'labels'} "
        f"and changed mask {need['labels'] + need['changed_mask']} B + hub "
        f"histograms {need['hub_histograms']} B + the largest program's other "
        f"temporaries ({largest}; " + ", ".join(
            f"{name} {held} B" for name, held in by_program.items()
        ) + f") {need['gather_transient']} B = {total} B"
    )
    unsized = " (device memory alone: the host's compile memory is not sized)"
    limit = (stats or {}).get("bytes_limit")
    if not limit:
        return "carried", said + "; the device reports no limit" + unsized
    free = int(limit) - int(stats.get("bytes_in_use", 0))
    said += f" against {free} B free of {int(limit)} B"
    if total <= free:
        return "carried", said + unsized
    return "plain", (
        said + ": a full gather every superstep, nothing kept" + unsized
    )


def stepped_residency(plan) -> tuple[str, str]:
    """``("plain", reason)`` for the PageRank job over the fused ``plan``
    (``ops/pagerank.py``, the message reading): the ``scan`` and
    ``reason`` of its ``device_residency`` record. Nothing is carried and
    nothing is chosen: every rank moves in every iteration, so every
    iteration gathers every row anew (``plain``, the stateless scan's
    word), no slot index is built, and one compiled iteration is stepped
    from the host. The ``reason`` answers "does that program fit beside
    this graph": its temporaries from the plan's shapes
    (:func:`~graphmine_tpu.obs.memmodel.row_sum_transients`) against the
    device's ``bytes_limit`` less ``bytes_in_use``, the graph and the plan
    being in use already. A program that does not fit is still handed to
    the device, which then says so itself: there is no leaner path to
    take."""
    need = row_sum_transients(plan)
    said = (
        "no rows carried, no slot index: every iteration gathers in full; "
        "one compiled iteration stepped from the host, temporaries "
        f"{need} B"
    )
    stats = device_memory_stats(plan) or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return "plain", said + "; the device reports no limit"
    free = int(limit) - int(stats.get("bytes_in_use", 0))
    fits = "fits" if need <= free else "DOES NOT FIT"
    return "plain", said + f" against {free} B free of {int(limit)} B: {fits}"


def crossover_thresholds() -> dict:
    """The family-crossover constants that decide every ``plan="auto"``
    resolution. One owner for the selection (:func:`select_superstep_family`)
    and its provenance (``impl_selected`` carries this dict, so a policy
    flip is explainable from the JSONL alone)."""
    return {"bucketed_min_messages": BUCKETED_MIN_MESSAGES}


def select_superstep_family(
    num_vertices: int, num_messages: int, requested: str = "auto",
    num_devices: int = 1,
) -> tuple[str, str]:
    """Resolve the superstep plan family: ``(family, reason)`` with
    ``family`` in :data:`FAMILIES`.

    ``requested`` forces a family (validated). ``auto`` is ``bucketed`` on
    a mesh of >= 2 devices at every size (per-shard bucket rows, labels
    replicated, one ``all_gather`` a superstep) and on one device from
    :data:`BUCKETED_MIN_MESSAGES` messages; below that it is ``sort``.
    The crossover is in messages alone; ``num_vertices`` decides nothing.
    """
    d = int(num_devices)
    if requested != "auto":
        if requested not in FAMILIES:
            raise ValueError(
                f"unknown superstep family {requested!r}; expected one of "
                f"{FAMILIES} or 'auto'"
            )
        return requested, f"requested {requested!r}"
    if d >= 2:
        return "bucketed", (
            f"D={d}: per-shard degree-bucketed rows, labels replicated, one "
            "all_gather a superstep (four v5e chips at M=128.3 M: 2.75 s a "
            "job, the exchange 0.05 % of it; PERF.md PR 27)"
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


# ---- plan-build observability ----------------------------------------------


def plan_build_stats(plan, num_edges: int) -> dict:
    """The ``plan_build`` record payload (see ``obs/schema.py``): width
    classes and the padded gather slots per edge, the number the
    width-ladder work optimizes, and the plan's shape by how its rows
    reduce: ``padded_slots_per_message`` (1.17 on a Kronecker draw, 1.03
    on a uniform one), the vertices whose row is reduced by the copy, the
    min or the pairwise count (``rows_pairwise``: classes up to
    ``_PAIRWISE_MAX_W`` wide), by the row sort (``rows_sorted``) and by a
    histogram (``rows_hist``), and the widest class (``max_width``).
    ``bins`` is always 0 (the record's key from when a binned family
    existed; its readers select on it)."""
    from graphmine_tpu.ops.bucketed_mode import _PAIRWISE_MAX_W

    slots = _bucketed_padded_slots(plan)
    mats = plan.send_idx if plan.send_idx is not None else plan.msg_idx
    shapes = [(int(m.shape[0]), int(m.shape[1])) for m in mats or ()]
    hubs = plan.hist_vertex_ids
    return {
        "family": "bucketed",
        "bins": 0,
        "width_classes": len(plan.vertex_ids),
        "padded_slots_per_edge": round(slots / max(int(num_edges), 1), 3),
        "padded_slots_per_message": round(
            slots / max(int(plan.num_messages), 1), 4
        ),
        "rows_pairwise": sum(n for n, w in shapes if w <= _PAIRWISE_MAX_W),
        "rows_sorted": sum(n for n, w in shapes if w > _PAIRWISE_MAX_W),
        "rows_hist": 0 if hubs is None else int(hubs.shape[0]),
        "max_width": max((w for _, w in shapes), default=0),
    }


def emit_plan_records(
    sink, op: str, plan, reason: str, seconds: float, cached: bool,
    num_edges: int, num_messages: int, num_vertices: int | None = None,
    scan: tuple[str, str] | None = None,
) -> None:
    """Emit the ``impl_selected`` + ``plan_build`` provenance pair for one
    auto-plan resolution (no-op without a sink). ``plan=None`` (sort
    family) emits only ``impl_selected``: there is no plan to build.
    ``scan`` is :func:`admit_carried_rows`'s answer, where the caller
    asked: ``impl_selected`` then says ``scan`` and ``scan_reason``.

    Both records carry the decision's evidence: the active crossover
    ``thresholds`` (:func:`crossover_thresholds`) and the analytical
    ``cost`` sub-record (:func:`graphmine_tpu.obs.costmodel.superstep_cost`,
    exact padded slots when a plan exists)."""
    if sink is None:
        return
    family = "sort" if plan is None else "bucketed"
    v = (
        num_vertices if num_vertices is not None
        else getattr(plan, "num_vertices", 0)
    )
    cost = superstep_cost(
        op, family, v, num_messages, num_edges, plan=plan
    )
    admitted = {} if scan is None else {"scan": scan[0], "scan_reason": scan[1]}
    sink.emit(
        "impl_selected", op=op, impl=family, n=num_messages, reason=reason,
        thresholds=crossover_thresholds(), cost=cost.record(), **admitted,
    )
    if plan is None:
        return
    sink.emit(
        "plan_build", op=op, seconds=round(seconds, 6), cached=cached,
        cost=cost.record(), **plan_build_stats(plan, num_edges),
    )


def emit_device_residency(
    sink, op: str, graph, plan, scan: tuple[str, str],
) -> None:
    """The ``device_residency`` record of one plan materialisation (see
    ``obs/schema.py``): what the device holds for this graph's supersteps,
    by array group, from the arrays' own ``nbytes`` and, for the job's
    rows and labels, ``superstep_footprint``'s terms; beside the device's
    ``bytes_limit`` (:func:`device_memory_stats`, asked here). No-op
    without a sink. ``plan`` is the plan the job runs, with its slot
    index when ``scan`` says ``carried``."""
    if sink is None:
        return
    import dataclasses

    import jax

    def on_device(tree, fields) -> int:
        return sum(
            int(x.nbytes) for name in fields
            for x in jax.tree.leaves(getattr(tree, name))
            if isinstance(x, jax.Array)
        )

    index = ("out_ptr", "out_slot")
    names = lambda tree: [f.name for f in dataclasses.fields(tree)]
    inv = superstep_footprint(
        op, "bucketed", plan.num_vertices, plan.num_messages, plan=plan
    ).inventory
    stats = device_memory_stats(plan) or {}
    sink.emit(
        "device_residency", op=op, scan=scan[0], reason=scan[1],
        bytes_limit=stats.get("bytes_limit"),
        bytes_in_use=stats.get("bytes_in_use"),
        graph_bytes=on_device(graph, names(graph)),
        plan_bytes=on_device(plan, set(names(plan)) - set(index)),
        rows_bytes=inv.get("carried_rows", 0),
        slot_index_bytes=on_device(plan, index),
        labels_bytes=inv["labels"],
    )


def emit_shard_residency(
    sink, op: str, sg, mesh, scan: tuple[str, str],
) -> None:
    """The ``device_residency`` record of the mesh entry: what ONE chip
    holds for this graph's supersteps, by array group, from the stacked
    arrays' per-shard shapes (every shard's are the same), beside the
    fullest chip's ``bytes_limit`` and ``bytes_in_use``
    (:func:`mesh_memory_stats`, asked here); ``shards`` says it is a
    chip's share. The graph stays on the host (``graph_bytes: 0``); the
    labels are the padded vector, replicated, in and out. No-op without a
    sink. ``sg`` is the placed partition, with its slot index when
    ``scan`` says ``carried``."""
    if sink is None:
        return
    import jax

    def per_chip(*trees) -> int:
        return sum(
            int(x.nbytes) // sg.num_shards
            for tree in trees for x in jax.tree.leaves(tree)
        )

    carried = scan[0] == "carried"
    stats = mesh_memory_stats(mesh) or {}
    sink.emit(
        "device_residency", op=op, scan=scan[0], reason=scan[1],
        shards=sg.num_shards,
        bytes_limit=stats.get("bytes_limit"),
        bytes_in_use=stats.get("bytes_in_use"),
        graph_bytes=per_chip(sg.msg_recv_local, sg.msg_send, sg.degrees,
                             sg.msg_weight),
        plan_bytes=per_chip(sg.bucket_send, sg.bucket_target, sg.bucket_weight),
        rows_bytes=per_chip(sg.bucket_send) if carried else 0,
        slot_index_bytes=per_chip(sg.out_ptr, sg.out_slot),
        labels_bytes=2 * 4 * sg.padded_vertices,
    )


# ---- what a job's programs take of the chip ---------------------------------

# What the executables said, once a (plan, program): kept under the identity
# of the array that anchors the plan (as `ops/lpa.py:_cached_auto_plan` and
# its kin key theirs) and let go with it.
_program_memory: dict = {}

_EXECUTABLE_SIZES = {
    "code_bytes": "generated_code_size_in_bytes",
    "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
}


def _ask_executable(fn, args, statics) -> dict:
    """The sizes the executable of the call ``fn(*args, **statics)`` states
    of itself (``memory_analysis()``), on a mesh ONE chip's. Asked after
    the call, of its very arguments (a donated one is gone by then and
    serves: only its shape and placement are read), the lowering and the
    executable are the ones the call left in jit's caches: nothing is
    compiled or loaded again (held by ``tests/test_program_memory.py``)."""
    said = fn.lower(*args, **statics).compile().memory_analysis()
    return {
        field: None if said is None else int(getattr(said, name))
        for field, name in _EXECUTABLE_SIZES.items()
    }


class ProgramLog:
    """The compiled programs one job under a sink ran, each with what its
    executable takes of the chip: the ``program_memory`` records'
    material. A job wraps the programs it hands out with :func:`noting`;
    the first call of a program in the job looks its sizes up in what is
    kept with ``anchor`` (the array whose identity keys the plan's cache
    entry; the graph's ``msg_ptr`` where there is no plan) and asks the
    executable only where nothing is kept yet, so a later job on the same
    plan copies its records (``cached: True``) and asks nothing.
    ``reckoned``, called here, gives the admission's count by program
    (:func:`reckoned_temp_bytes`'s dict) where it counted this job's;
    ``shards`` is the mesh's size."""

    def __init__(self, anchor, reckoned=None, shards: int | None = None):
        key = id(anchor)
        hit = _program_memory.get(key)
        if hit is None or hit[0]() is not anchor:
            hit = (
                weakref.ref(anchor, lambda _, k=key: _program_memory.pop(k, None)),
                {},
            )
            _program_memory[key] = hit
        self.kept = hit[1]
        self.reckoned = reckoned() if reckoned else {}
        self.shards = shards
        self.ran: dict = {}
        self.seconds = 0.0

    def note(self, name: str, fn, args, statics: dict, said: dict) -> None:
        t0 = time.perf_counter()
        fields = {**said, **statics}
        key = (fn, name, *sorted(fields.items()))
        if key not in self.ran:
            sizes = self.kept.get(key)
            cached = sizes is not None
            if not cached:
                sizes = self.kept[key] = _ask_executable(fn, args, statics)
            counted = self.reckoned.get((name, fields.get("cap")))
            self.ran[key] = dict(
                program=name, **fields, **sizes, cached=cached,
                **({} if counted is None else {"reckoned_temp_bytes": counted}),
            )
        self.seconds += time.perf_counter() - t0


def plan_anchor(graph, plan):
    """The array a :class:`ProgramLog` keeps its answers with: the plan's
    first (every plan of one graph's cache entry shares it, with or
    without its slot index), the graph's ``msg_ptr`` on the ``sort``
    family, which has no plan."""
    return plan.vertex_ids[0] if plan is not None and plan.vertex_ids else graph.msg_ptr


def program_log(sink, anchor, reckoned=None, shards=None) -> ProgramLog | None:
    """A job's :class:`ProgramLog`, or ``None`` without a sink and under a
    caller's trace (no executable to ask): the job then calls its programs
    bare and nothing is asked or kept."""
    import jax

    if sink is None or not jax.core.trace_ctx.is_top_level():
        return None
    return ProgramLog(anchor, reckoned, shards)


def noting(programs: ProgramLog | None, name: str, fn, **said):
    """``fn`` itself without a log; under one, ``fn`` followed by the
    log's note of the call under the job's word for the program
    (``name``). The call's keyword arguments (a program's static ones:
    ``cap``, ``w``) and ``said`` (a static argument the call passes by
    position) tell one program of that name from another and go into its
    record; arrays are passed by position. A stand-in that is no jitted
    function (a test's, in a program's place) has no executable to ask and
    is not noted."""
    if programs is None or not hasattr(fn, "lower"):
        return fn

    def call(*args, **statics):
        out = fn(*args, **statics)
        programs.note(name, fn, args, statics, said)
        return out

    return call


def emit_program_memory(sink, op: str, programs: ProgramLog | None) -> None:
    """One ``program_memory`` record (see ``obs/schema.py``) for each
    program the job behind ``programs`` ran, in the order it first ran
    them; the last one says ``asked_s``, the seconds the job spent in the
    log and here by this module's own clock. No-op without a sink."""
    if sink is None or programs is None:
        return
    t0 = time.perf_counter()
    mesh = {} if programs.shards is None else {"shards": programs.shards}
    records = list(programs.ran.values())
    for i, fields in enumerate(records, 1):
        last = {} if i < len(records) else {
            "asked_s": round(programs.seconds + time.perf_counter() - t0, 6)
        }
        sink.emit("program_memory", op=op, **fields, **mesh, **last)


def timed_plan_build(build) -> tuple:
    """``(plan, seconds)`` for one host plan build."""
    t0 = time.perf_counter()
    plan = build()
    return plan, time.perf_counter() - t0
