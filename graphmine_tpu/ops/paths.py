"""BFS distances and landmark shortest paths.

Engine-surface parity with GraphFrames' ``bfs`` / ``shortestPaths`` (the
object built at ``Graphframes.py:78`` exposes both; the reference script
never calls them). TPU design: distances are dense int32 vectors; one
superstep relaxes every edge with a gather + ``segment_min`` — Bellman-Ford
over unit weights, which for BFS converges in diameter supersteps inside a
single ``lax.while_loop``. That loop is full width in every pass. On an
undirected graph past the policy's crossover :func:`bfs_distances` follows
the frontier instead (ISSUE 49): the carried-rows job of ``ops/lpa.py``
with a min for its reduce, stepped from the host to the last level, and
turned bottom-up once the unreached vertices' edges cost less to look at,
in the graph's own message CSR, than the frontier's messages cost to write
(ISSUE 50, ISSUE 53).

Direction conventions:
- ``direction="out"``: follow edge direction (src -> dst), GraphFrames'
  default for bfs.
- ``direction="both"``: treat edges as undirected (uses the symmetric
  message CSR).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import Graph

UNREACHABLE = jnp.iinfo(jnp.int32).max


def _edges(graph: Graph, direction: str):
    if direction == "out":
        return graph.src, graph.dst
    if direction == "both":
        if not graph.symmetric:
            raise ValueError(
                "direction='both' needs a graph built with symmetric=True "
                "(the message CSR of an asymmetric graph only carries the "
                "forward direction)"
            )
        return graph.msg_send, graph.msg_recv
    raise ValueError(f"direction must be 'out' or 'both', got {direction!r}")


def bfs_distances(
    graph: Graph, sources: jax.Array, direction: str = "out", max_depth: int = 0,
    plan="auto", sink=None, return_levels: bool = False,
):
    """Hop distance from the nearest of ``sources`` to every vertex.

    Returns int32 ``[V]``; unreachable vertices get ``UNREACHABLE``
    (int32 max). ``sources`` is an int array of vertex ids. The search
    runs until a level reaches nothing, or ``max_depth`` levels where that
    is not 0. ``return_levels`` additionally returns the supersteps it
    took, the last, which reaches nothing, among them.

    ``plan``: which path runs; all give the same depths bit for bit.
    ``"auto"`` on a symmetric graph with ``direction="both"`` resolves the
    family through :func:`~graphmine_tpu.ops.superstep_policy.
    select_superstep_family`, as ``connected_components`` does (the same
    plan, cached per graph). On ``bucketed`` the search follows its
    frontier over the carried rows (:func:`_frontier_job`): the plan's rows
    hold every neighbour's depth, a level is ``min(own, row min + 1)``,
    and only the vertices a level reached write their depth into their
    neighbours' rows, through the slot index, at the rung the messages
    they send fit under (:func:`~graphmine_tpu.ops.superstep_policy.
    delta_rungs`; a level that sends more than the top rung gathers every
    row anew). The search turns bottom-up (:func:`_next_update`) once the
    edges of the vertices still unreached cost less to look at than the
    top-down update does, by what a place of each costs on the chip: those
    vertices then read their neighbours out of the graph's message CSR,
    which is sorted by receiver (``depth[msg_send[msg_ptr[u]:msg_ptr[u +
    1]]]``, no slot, row or class between;
    :func:`~graphmine_tpu.ops.bucketed_mode.bfs_level_bottom_up`), in one
    program that loops over fixed chunks of places as many times as those
    edges take: 68 M places at the loud level of a Kronecker graph where
    the frontier would have every slot gathered anew, a few thousand late
    in a search. Such a level
    reads and writes no row, so the rows are stale after it: from then on
    the one top-down update is the full gather, and the search stays
    turned unless that is the cheaper. The host reads three counts a level
    and stops at the first that reached nothing. The rows and the index go on the device
    only where :func:`~graphmine_tpu.ops.superstep_policy.
    admit_carried_rows` finds room for this job's programs; otherwise one
    compiled full-width level (gather every class, row min, write back) is
    stepped from the host (:func:`_full_width_job`). On ``sort``, for
    ``direction="out"``, on a plan that is not fused, with ``plan=None``
    and under a caller's trace the ``lax.while_loop`` over the message
    arrays runs (small graphs, ``bfs_parents``, ``shortest_paths``). A
    fused :class:`~graphmine_tpu.ops.bucketed_mode.BucketedModePlan` may
    be passed in ``"auto"``'s place.

    ``sink``: optional MetricsSink. An auto resolution emits
    ``impl_selected`` (with ``scan`` and ``scan_reason``), ``plan_build``
    and ``device_residency`` as ``label_propagation`` does; a job over the
    plan's rows one ``superstep_delta`` record (``op: bfs_level``; a level:
    its direction, the branch taken, the places a bottom-up level looked
    at, the vertices reached, the messages they send, the edges of the
    vertices still unreached, its seconds at the host's one wait) and
    every host-stepped job one ``fixpoint`` record (the supersteps and the vertices each reached);
    every job one ``program_memory`` record for each compiled program it ran
    (what the executable takes of the chip; ``label_propagation``'s).
    """
    from graphmine_tpu.ops.lpa import _under_a_trace

    sources = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
    traced = _under_a_trace() or isinstance(graph.msg_ptr, jax.core.Tracer)
    scan = None
    if direction != "both" or not graph.symmetric or traced:
        plan = None
    if isinstance(plan, str):
        if plan != "auto":
            raise ValueError(f"plan must be 'auto', None or a fused plan; got {plan!r}")
        plan, scan = _auto_plan(graph, sink)
    elif plan is not None and plan.send_idx:
        from graphmine_tpu.ops.lpa import _cached_slot_index

        plan, _, scan = _cached_slot_index(plan, reduce="min")
    from graphmine_tpu.ops.superstep_policy import (
        emit_program_memory,
        noting,
        plan_anchor,
        program_log,
        reckoned_temp_bytes,
    )

    if plan is None or not plan.send_idx:
        programs = program_log(sink, graph.msg_ptr)
        dist, levels = noting(
            programs, "loop", _bfs_loop, direction=direction, max_depth=max_depth
        )(graph, sources, direction, max_depth)
    else:
        limit = max_depth if max_depth > 0 else graph.num_vertices + 1
        clock = time.perf_counter if sink is not None else None
        if plan.out_slot is not None:
            programs = program_log(
                sink, plan_anchor(graph, plan),
                partial(reckoned_temp_bytes, plan, reduce="min"),
            )
            dist, per_step = _frontier_job(
                graph, sources, limit, plan, clock, programs
            )
        else:
            programs = program_log(sink, plan_anchor(graph, plan))
            dist, per_step = _full_width_job(
                graph, sources, limit, plan, clock, programs
            )
        levels = len(per_step["changed_vertices"])
        _emit_job_records(sink, graph, plan, per_step)
    emit_program_memory(sink, "bfs_level", programs)
    return (dist, levels) if return_levels else dist


def _auto_plan(graph: Graph, sink):
    """``(plan, scan)`` of an auto resolution, with its records: the graph's
    cached bucketed plan with its slot index where the BFS job's rows are
    admitted, or ``(None, None)`` on the ``sort`` family."""
    from graphmine_tpu.ops.lpa import _cached_auto_plan, _cached_slot_index
    from graphmine_tpu.ops.superstep_policy import (
        emit_device_residency,
        emit_plan_records,
        select_superstep_family,
    )

    family, reason = select_superstep_family(graph.num_vertices, graph.num_messages)
    plan, seconds, cached, scan = None, 0.0, False, None
    if family == "bucketed":
        plan, seconds, cached = _cached_auto_plan(graph)
        plan, index_seconds, scan = _cached_slot_index(plan, reduce="min")
        seconds += index_seconds
    emit_plan_records(
        sink, "bfs_level", plan, reason, seconds, cached, graph.num_edges,
        graph.num_messages, num_vertices=graph.num_vertices, scan=scan,
    )
    if plan is not None:
        emit_device_residency(sink, "bfs_level", graph, plan, scan)
    return plan, scan


def _emit_job_records(sink, graph: Graph, plan, per_step: dict) -> None:
    """The ``superstep_delta`` and ``fixpoint`` records of one host-stepped
    job (no-op without a sink): a level's ``branch`` is ``"fill"`` for a
    first level that found the rows as the fill left them and wrote the
    sources' slots alone, the rung K fits under, ``"full"``, or on a
    bottom-up level the ``places`` its loop ran over; a bottom-up level
    reduces no row (``reduce: "none"``); the full-width job has no rows, no
    K, no U and no rung, and every level of it is ``"full"``."""
    if sink is None:
        return
    from graphmine_tpu.ops.lpa import _plan_rows_and_slots
    from graphmine_tpu.ops.superstep_policy import delta_rungs

    reached = per_step["changed_vertices"]
    rows, slots = _plan_rows_and_slots(plan.send_idx)
    turned = [d == "bottom_up" for d in per_step.get("direction", reached)]
    if "branch" in per_step:
        rungs = list(delta_rungs(plan.num_messages))
        branch = list(per_step["branch"])
        if branch and branch[0] != "full" and not turned[0]:
            branch[0] = "fill"
        more = {
            name: per_step[name] for name in
            ("direction", "unreached_messages", "places", "source_messages")
        }
        sent = per_step["changed_messages"]
    else:
        rungs, branch, sent, more = [], ["full"] * len(reached), [], {}
    sink.emit(
        "superstep_delta", op="bfs_level", changed_vertices=reached,
        changed_messages=sent, branch=branch, rungs=rungs,
        num_messages=plan.num_messages,
        reduce=["none" if t else "full" for t in turned],
        dirty_rows=[0 if t else rows for t in turned],
        dirty_slots=[0 if t else slots for t in turned],
        seconds=[round(s, 6) for s in per_step.get("seconds", ())], **more,
    )
    sink.emit(
        "fixpoint", op="bfs_level", supersteps=len(reached), changed=reached,
        num_vertices=graph.num_vertices, family="bucketed",
    )


# The BFS job's programs. The rows are the donated argument of the three
# that write them, as in ``ops/lpa.py``: filled, gathered and rewritten in
# place (held by tests/test_chip_compile.py). The plan is an argument of
# each: closed over, its arrays would be constants of the program.


@partial(jax.jit, static_argnames=("slots", "num_vertices"))
def _start_program(sources, out_ptr, slots: int, num_vertices: int):
    """``(rows, depth, reached, K, U)`` before the first level: the rows as
    a gather of all-unreached depths would leave them (a fill, and no gather
    of S slots), the sources at depth 0, the messages they send and the
    edges of every other vertex."""
    rows = jnp.full((slots,), UNREACHABLE, jnp.int32)
    depth = _unreached_but(sources, num_vertices)
    reached = depth == 0
    k, _, u = _level_counts(depth, reached, out_ptr)
    return rows, depth, reached, k, u


def _unreached_but(sources, num_vertices: int):
    """The depths before the first level: 0 at the sources."""
    return jnp.full((num_vertices,), UNREACHABLE, jnp.int32).at[sources].set(0)


def _level_counts(depth, reached, out_ptr):
    """``(K, count, U)``, what the host reads once a level: K the messages
    the ``reached`` vertices send and U the edges of the vertices still
    without a ``depth``, which between them pick the next level's update
    (:func:`_next_update`), and the count of the reached."""
    with jax.named_scope("bfs_level"), jax.named_scope("changed_count"):
        out_deg = out_ptr[1:] - out_ptr[:-1]
        k = jnp.sum(jnp.where(reached, out_deg, 0), dtype=jnp.int32)
        u = jnp.sum(jnp.where(depth == UNREACHABLE, out_deg, 0), dtype=jnp.int32)
        return k, jnp.sum(reached, dtype=jnp.int32), u


@partial(jax.jit, donate_argnums=0)
def _gather_program(rows, depth, plan):
    from graphmine_tpu.ops.bucketed_mode import gather_depth_rows

    return gather_depth_rows(rows, depth, plan)


@partial(jax.jit, static_argnames=("cap",), donate_argnums=0)
def _rewrite_program(rows, depth, reached, plan, cap: int):
    from graphmine_tpu.ops.bucketed_mode import rewrite_depth_rows

    return rewrite_depth_rows(rows, depth, reached, plan, cap)


@jax.jit
def _level_program(rows, depth, plan):
    """``(new depths, reached, K, count, U)`` of one level over ``rows``."""
    from graphmine_tpu.ops.bucketed_mode import bfs_level_from_rows

    new = bfs_level_from_rows(rows, depth, plan)
    reached = new != depth
    return (new, reached, *_level_counts(new, reached, plan.out_ptr))


@jax.jit
def _unreached_program(depth, msg_ptr):
    """The unreached vertices' spans of the message CSR, compacted: the one
    V-long sort of a bottom-up level, in a program of its own (a minute and
    more a compile at 2^24 vertices; the level's loop compiles in seconds)."""
    from graphmine_tpu.ops.bucketed_mode import compact_unreached

    return compact_unreached(depth, msg_ptr)


@partial(jax.jit, static_argnames=("chunk",))
def _bottom_up_program(depth, owner, start, count, msg_send, out_ptr, chunk: int):
    """``(new depths, reached, K, count, U, trips)`` of one bottom-up level
    over the unreached vertices' spans, ``chunk`` places a trip of its loop
    and as many trips as their edges take; no sort, no row."""
    from graphmine_tpu.ops.bucketed_mode import bfs_level_bottom_up

    new, trips = bfs_level_bottom_up(depth, owner, start, count, msg_send, chunk)
    reached = new != depth
    return (new, reached, *_level_counts(new, reached, out_ptr), trips)


@jax.jit
def _full_level_program(depth, plan):
    """``(new depths, count)`` of one level at full width, nothing kept."""
    from graphmine_tpu.ops.bucketed_mode import bfs_level_bucketed

    new = bfs_level_bucketed(depth, plan)
    with jax.named_scope("bfs_level"), jax.named_scope("changed_count"):
        return new, jnp.sum(new != depth, dtype=jnp.int32)


# What a place of each update costs on a TPU v5e, in nanoseconds: what the
# direction rule weighs. Measured on graph500-24 (V = 2^24, M = 520.8 M), each
# by the PERF.md section named; the ratios decide, and they hold from scale 22
# up (a gather and a rewrite's place are bound by issue per index, §7.4).
_GATHERED_SLOT_NS = 7.4     # the full gather: 542.5 M slots in 4.03 s (§5, PR 50)
_REWRITTEN_PLACE_NS = 43.0  # a rewrite, a place of its RUNG: 37-48 (§6, PR 49)
_BOTTOM_UP_PLACE_NS = 39.4  # a bottom-up place of the loop's trips: 39.3-39.5 (§6, PR 53)


def _next_update(k: int, places: int, rungs: tuple, slots: int, stale: bool) -> tuple:
    """``(place, bottom_up)``: the cheaper update of the next level, by what
    a place of each costs. Top-down brings the rows up to date behind the K
    messages the last level's vertices send and takes the row min: a rewrite
    of the rung K fits under (``place`` in ``rungs``), which costs the
    RUNG's places, or above the top rung the gather of all ``slots``
    (``place == len(rungs)``); rows a bottom-up level left ``stale`` can
    only be gathered anew. Bottom-up looks at ``places``, the edges of the
    vertices still unreached as the level's loop runs over them (U rounded
    up to its trips). It is taken where it is the cheaper."""
    full = len(rungs)
    place = full if stale else sum(k > rung for rung in rungs)
    top_down = (
        slots * _GATHERED_SLOT_NS if place == full
        else rungs[place] * _REWRITTEN_PLACE_NS
    )
    return place, places * _BOTTOM_UP_PLACE_NS < top_down


def _frontier_job(graph: Graph, sources, limit: int, plan, clock=None, programs=None):
    """``(depths, per_step)`` of a search that follows its frontier over a
    fused plan with its slot index, stepped from the host: the rows start as
    a fill, and every level takes the update :func:`_next_update` picks from
    the two counts its predecessor left. Top-down: the rows are brought up
    to date by what the predecessor reached (a rung's rewrite, the first
    level's of the sources' slots; a full gather above the top rung) and
    :func:`_level_program` takes the row min. Bottom-up (ISSUE 50, 53),
    once the unreached vertices' edges cost less to look at than the
    frontier's messages to write: :func:`_unreached_program` compacts those
    vertices' spans of the graph's message CSR and
    :func:`_bottom_up_program` reads their neighbours' depths there; the
    rows are not touched and are stale from then on (the job lets them go:
    a fifth of what it holds on the device), so the search stays turned
    unless a full gather is the cheaper (U never grows). The host waits
    once a level, for the three counts. It stops at the first level that
    reaches nothing, or after ``limit``.

    ``per_step``, one entry a level: ``changed_vertices``,
    ``changed_messages`` (K), ``unreached_messages`` (U), ``direction``,
    ``branch`` (the rung K fits under or ``"full"``; on a bottom-up level
    its ``places``), ``places`` (what a bottom-up level's loop ran over, its
    trips' chunks: the U before it rounded up; 0 on a top-down level), with
    a ``clock`` ``seconds``; and ``source_messages``, the K that picked the
    first level's rung. ``programs`` (the caller's ``ProgramLog``) notes
    each program run."""
    from graphmine_tpu.ops.bucketed_mode import check_plan_fits, row_slots
    from graphmine_tpu.ops.superstep_policy import bottom_up_chunk, delta_rungs, noting

    start = noting(programs, "start", _start_program)
    unreached = noting(programs, "unreached", _unreached_program)
    bottom_up_level = noting(programs, "bottom_up", _bottom_up_program)
    gather = noting(programs, "gather", _gather_program)
    rewrite = noting(programs, "rewrite", _rewrite_program)
    level = noting(programs, "level", _level_program)
    rungs = delta_rungs(plan.num_messages)
    slots = row_slots(plan)
    chunk = bottom_up_chunk(plan.num_messages)
    rows, depth, reached, *counts = start(
        sources, plan.out_ptr, slots=slots, num_vertices=plan.num_vertices
    )
    check_plan_fits(depth, graph, plan)
    # a fetch a job: the sources' out-degree picks the first rung
    k, u = (int(x) for x in jax.device_get(counts))
    source_messages = k
    names = ("changed_vertices", "changed_messages", "unreached_messages",
             "direction", "branch", "places")
    per_step = {name: [] for name in names}
    marks = [clock()] if clock else []
    for _ in range(limit):
        place, bottom_up = _next_update(
            k, -(-u // chunk) * chunk, rungs, slots, stale=rows is None
        )
        if bottom_up:
            rows = None  # stale from here on: the device has their room back
            spans = unreached(depth, graph.msg_ptr)
            depth, reached, *counts = bottom_up_level(
                depth, *spans, graph.msg_send, plan.out_ptr, chunk=chunk
            )
        else:
            if rows is None:  # the gather writes every slot: any rows will do
                rows = jnp.empty((slots,), jnp.int32)
            if place == len(rungs):
                rows = gather(rows, depth, plan)
            else:
                rows = rewrite(rows, depth, reached, plan, cap=rungs[place])
            depth, reached, *counts = level(rows, depth, plan)
        # the one wait; a bottom-up level also says the trips its loop took
        k, moved, u, *trips = (int(x) for x in jax.device_get(counts))
        places = sum(trips) * chunk
        said = (
            moved, k, u, "bottom_up" if bottom_up else "top_down",
            places if bottom_up else [*rungs, "full"][place], places,
        )
        for name, value in zip(names, said):
            per_step[name].append(value)
        if clock:
            marks.append(clock())
        if moved == 0:
            break
    if clock:
        per_step["seconds"] = [b - a for a, b in zip(marks, marks[1:])]
    return depth, dict(per_step, source_messages=source_messages)


def _full_width_job(graph: Graph, sources, limit: int, plan, clock=None, programs=None):
    """``(depths, per_step)`` where the rows were not admitted: one compiled
    full-width level stepped from the host until it reaches nothing (never
    a ``while_loop`` over the plan's classes, whose temporaries the chip's
    compiler holds all at once; PERF.md §7.5). The loop is still the one
    stepping loop, with no rung to take and no rows to bring up to date;
    ``per_step`` holds ``changed_vertices`` and, with a ``clock``,
    ``seconds``."""
    from graphmine_tpu.ops.bucketed_mode import check_plan_fits
    from graphmine_tpu.ops.superstep_policy import noting, step_carried_rows

    depth = _unreached_but(sources, plan.num_vertices)
    check_plan_fits(depth, graph, plan)
    full_level = noting(programs, "full_level", _full_level_program)

    def level(rows, depth):
        new, count = full_level(depth, plan)
        return new, None, 0, count  # no K: there is no update to pick

    depth, per_step = step_carried_rows(
        limit, (), 0, None, depth, gather=lambda rows, depth: rows, rewrite=None,
        modes=level, clock=clock, until_quiet=True,
    )
    return depth, {
        k: per_step[k] for k in ("changed_vertices", "seconds") if k in per_step
    }


@partial(jax.jit, static_argnames=("direction", "max_depth"))
def _bfs_loop(
    graph: Graph, sources: jax.Array, direction: str = "out", max_depth: int = 0
):
    """``(depths, supersteps)``: every level relaxes every edge, inside one
    ``lax.while_loop``."""
    v = graph.num_vertices
    send, recv = _edges(graph, direction)
    limit = max_depth if max_depth > 0 else v + 1
    dist0 = jnp.full((v,), UNREACHABLE, jnp.int32).at[sources].set(0)

    def step(state):
        dist, _, it = state
        # saturating +1 so UNREACHABLE does not wrap
        msg = jnp.where(dist[send] == UNREACHABLE, UNREACHABLE, dist[send] + 1)
        relaxed = jax.ops.segment_min(msg, recv, num_segments=v)
        new = jnp.minimum(dist, relaxed)
        changed = jnp.sum(new != dist, dtype=jnp.int32)
        return new, changed, it + 1

    def cond(state):
        _, changed, it = state
        return (changed > 0) & (it < limit)

    dist, _, levels = lax.while_loop(cond, step, (dist0, jnp.int32(1), jnp.int32(0)))
    return dist, levels


def shortest_paths(graph: Graph, landmarks, direction: str = "out",
                   landmark_batch: int = 16) -> jax.Array:
    """Distance to each landmark, shape ``[V, L]`` (GraphFrames
    ``shortestPaths`` semantics: distance FROM each vertex TO the landmark
    following edge direction).

    Landmarks run ``landmark_batch`` at a time in one vectorized
    Bellman-Ford (every relaxation handles the whole lane block —
    per-superstep message buffer is ``[M, B]`` int32, so lower ``B`` on
    huge graphs); tiles are processed sequentially via ``lax.map``.
    """
    landmarks = jnp.atleast_1d(jnp.asarray(landmarks, jnp.int32))
    num = int(landmarks.shape[0])
    b = max(1, min(landmark_batch, num))
    # distance v -> landmark along src->dst == distance landmark -> v along
    # reversed edges; for "both" the graph is symmetric already.
    if direction == "out":
        send, recv = graph.dst, graph.src
    else:
        send, recv = _edges(graph, direction)
    pad = (-num) % b
    tiles = jnp.concatenate(
        [landmarks, jnp.zeros(pad, jnp.int32)]
    ).reshape(-1, b)
    per = partial(_bfs_tile, send=send, recv=recv, v=graph.num_vertices)
    out = lax.map(per, tiles)  # [T, V, B]
    return jnp.moveaxis(out, 0, 1).reshape(graph.num_vertices, -1)[:, :num]


def _bfs_tile(sources: jax.Array, *, send, recv, v: int) -> jax.Array:
    """Per-source BFS distances for one lane block: ``[V, B]``."""
    b = sources.shape[0]
    dist0 = jnp.full((v, b), UNREACHABLE, jnp.int32)
    dist0 = dist0.at[sources, jnp.arange(b)].min(0)

    def step(state):
        dist, _, it = state
        msg = jnp.where(dist[send] == UNREACHABLE, UNREACHABLE, dist[send] + 1)
        relaxed = jax.ops.segment_min(msg, recv, num_segments=v)
        new = jnp.minimum(dist, relaxed)
        changed = jnp.sum(new != dist, dtype=jnp.int32)
        return new, changed, it + 1

    def cond(state):
        _, changed, it = state
        return (changed > 0) & (it < v + 1)

    dist, _, _ = lax.while_loop(cond, step, (dist0, jnp.int32(1), jnp.int32(0)))
    return dist


def weighted_shortest_paths(
    graph: Graph,
    sources: jax.Array,
    weights: jax.Array,
    direction: str = "out",
    max_iter: int = 0,
) -> jax.Array:
    """Weighted distance from the nearest of ``sources`` to every vertex —
    Bellman-Ford over the same gather + ``segment_min`` superstep as BFS
    (no priority queue: data-parallel relaxation converges in
    longest-shortest-path-hops iterations, the TPU-friendly trade).

    ``weights``: non-negative float ``[E]`` aligned with ``graph.src`` /
    ``graph.dst`` (for ``direction="both"`` each edge's weight applies in
    both directions). Returns float32 ``[V]`` with ``inf`` for unreachable
    vertices. Negative weights converge too (bounded by ``max_iter``,
    default V), but negative *cycles* are not detected.
    """
    # NaN weights would poison distances AND defeat the convergence check
    # (NaN != NaN keeps `changed` nonzero for the full V iterations) — same
    # host-side guard build_graph(edge_weights=...) applies; skipped only
    # when tracing (weights produced inside a caller's jit).
    if not isinstance(weights, jax.core.Tracer):
        w_host = np.asarray(weights)
        if np.isnan(w_host).any():
            raise ValueError("weights must not contain NaN")
    return _weighted_shortest_paths_jit(graph, sources, weights, direction,
                                        max_iter)


@partial(jax.jit, static_argnames=("direction", "max_iter"))
def _weighted_shortest_paths_jit(
    graph: Graph,
    sources: jax.Array,
    weights: jax.Array,
    direction: str = "out",
    max_iter: int = 0,
) -> jax.Array:
    v = graph.num_vertices
    w = jnp.asarray(weights, jnp.float32)
    if direction == "out":
        send, recv = graph.src, graph.dst
    elif direction == "both":
        # weights align with the edge list, not the sorted message CSR, so
        # build the two directions straight from src/dst
        send = jnp.concatenate([graph.src, graph.dst])
        recv = jnp.concatenate([graph.dst, graph.src])
        w = jnp.concatenate([w, w])
    else:
        raise ValueError(f"direction must be 'out' or 'both', got {direction!r}")
    dist0 = jnp.full((v,), jnp.inf, jnp.float32).at[sources].set(0.0)
    limit = max_iter if max_iter > 0 else v

    def step(state):
        dist, _, it = state
        relaxed = jax.ops.segment_min(dist[send] + w, recv, num_segments=v)
        new = jnp.minimum(dist, relaxed)
        changed = jnp.sum(new != dist, dtype=jnp.int32)
        return new, changed, it + 1

    def cond(state):
        _, changed, it = state
        return (changed > 0) & (it < limit)

    dist, _, _ = lax.while_loop(cond, step, (dist0, jnp.int32(1), jnp.int32(0)))
    return dist


@partial(jax.jit, static_argnames=("direction", "max_depth"))
def bfs_parents(
    graph: Graph, sources: jax.Array, direction: str = "out", max_depth: int = 0
) -> tuple[jax.Array, jax.Array]:
    """BFS distances plus parent pointers for path reconstruction.

    Returns ``(dist, parent)``, both int32 ``[V]``. ``parent[v]`` is the
    smallest-id predecessor of ``v`` on some shortest path from ``sources``
    (-1 for sources and unreachable vertices). Parents are recovered in one
    extra relaxation pass after the distance fixpoint — keeps the hot loop
    identical to :func:`bfs_distances`.
    """
    v = graph.num_vertices
    send, recv = _edges(graph, direction)
    dist = bfs_distances(graph, sources, direction=direction, max_depth=max_depth)
    on_sp = (dist[send] != UNREACHABLE) & (dist[recv] == dist[send] + 1)
    cand = jnp.where(on_sp, send, UNREACHABLE)
    parent = jax.ops.segment_min(cand, recv, num_segments=v)
    parent = jnp.where((parent == UNREACHABLE) | (dist == 0), -1, parent)
    return dist, parent.astype(jnp.int32)


def bfs(
    graph: Graph,
    from_vertices,
    to_vertices,
    direction: str = "out",
    max_path_length: int = 10,
):
    """Shortest paths from a source set to a target set.

    Semantics of ``GraphFrame.bfs(fromExpr, toExpr, maxPathLength)`` (the
    object at ``Graphframes.py:78`` exposes it): breadth-first search stops
    at the first depth where any target is reached; one shortest path per
    target at that depth is returned. Instead of SQL expressions the
    endpoint sets are vertex-id arrays — build them with any host-side
    predicate over vertex properties.

    Returns a list of int32 NumPy paths ``[source, ..., target]``, empty if
    no target is within ``max_path_length`` hops. The distance/parent sweep
    is one compiled kernel; only the final pointer walk (path-length steps)
    runs on host.
    """
    import numpy as np

    from_vertices = jnp.atleast_1d(jnp.asarray(from_vertices, jnp.int32))
    to_np = np.atleast_1d(np.asarray(to_vertices, np.int64))
    dist, parent = bfs_parents(
        graph, from_vertices, direction=direction, max_depth=max_path_length
    )
    dist, parent = np.asarray(dist), np.asarray(parent)
    if to_np.size == 0:
        return []
    tdist = dist[to_np]
    reach = tdist != int(UNREACHABLE)
    if not reach.any():
        return []
    best = int(tdist[reach].min())
    paths = []
    for t in to_np[reach & (tdist == best)]:
        path = [int(t)]
        while parent[path[-1]] >= 0:
            path.append(int(parent[path[-1]]))
        paths.append(np.asarray(path[::-1], dtype=np.int32))
    return paths
