"""PageRank as a power iteration.

The reference never calls PageRank, but it is part of the engine surface
its GraphFrame object exposes (the same object built at
``Graphframes.py:78`` also provides ``pageRank``); SURVEY §2.2 scopes the
framework to that engine surface. TPU design: rank is a dense float32
vector; one iteration is a gather of the senders' contributions and a sum
at the receivers — the same message machinery as LPA with sum instead of
mode.

Two readings of the graph (:func:`pagerank`): the edges as drawn (one
``segment_sum`` over ``graph.src`` / ``graph.dst``, the classic directed
formulation, GraphFrames/GraphX up to their scaling convention), and the
graph's message CSR, which on an undirected (``symmetric=True``) graph is
PageRank as LDBC Graphalytics defines it there, and runs on either
superstep family: the float row sums of the shared bucketed plan
(``ops/bucketed_mode.row_sums``) or a ``segment_sum`` over the messages.

Semantics: damping ``alpha``, uniform teleport (or a personalized reset
distribution), dangling-vertex mass redistributed via the teleport vector,
ranks summing to 1; stopped by a tolerance on the L1 delta or, with
``tol=None``, after exactly ``max_iter`` iterations.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import numpy as np
from jax import lax
from jax.lax import pcast

from graphmine_tpu.graph.container import Graph


def pagerank(
    graph: Graph,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float | None = 1e-6,
    reset: jax.Array | None = None,
    weights: jax.Array | None = None,
    plan="auto",
    sink=None,
    directed: bool = True,
) -> jax.Array:
    """PageRank vector ``[V]`` (float32, sums to 1).

    Two readings of the graph. ``directed=True`` (the default; what
    ``frames.py`` and ``compat.py`` call) ranks the edge list as drawn:
    rank flows from ``graph.src`` to ``graph.dst``, a vertex splits its
    rank over its out-edges, one ``segment_sum`` over the edges whatever
    ``plan`` says. ``directed=False`` takes the inflow over the graph's
    MESSAGE CSR: a vertex splits its rank over the messages it sends. On a
    ``build_graph(..., symmetric=True)`` graph every edge carries a
    message each way, so this is PageRank of the undirected graph as LDBC
    Graphalytics defines it (``N_in = N_out =`` the neighbours,
    ``|N_out|`` the degree); on a ``symmetric=False`` graph the messages
    are the edges and the two readings agree.

    ``tol``: converges when the L1 delta drops below it, bounded by
    ``max_iter`` (checked inside the while_loop, no host sync per
    iteration; where the host steps the iterations, below, it reads the
    delta once an iteration). ``tol=None`` runs exactly ``max_iter``
    iterations, computes no delta and stops on nothing else, as
    Graphalytics states a count.

    ``reset``: optional personalization distribution (normalized
    internally); ``None`` = uniform teleport. The rank of a vertex that
    sends nothing (no out-edge; on the message reading an isolated vertex
    too) is spread over ``reset`` in every iteration. ``weights``:
    optional non-negative per-edge weights ``[E]`` (aligned with
    ``graph.src``), the directed reading's alone: each vertex splits its
    rank across out-edges in proportion to weight (NetworkX
    weighted-pagerank semantics; vertices whose out-weight sums to 0 are
    treated as dangling). The message reading is unweighted (a graph's
    ``msg_weight`` is not read) and raises on ``weights``.

    ``plan`` picks the message reading's inflow, one path per family as
    in :func:`~graphmine_tpu.ops.cc.connected_components`: ``"auto"``
    resolves the family through :func:`~graphmine_tpu.ops.
    superstep_policy.select_superstep_family`; ``bucketed`` sums the
    float rows of the graph's cached plan
    (:func:`~graphmine_tpu.ops.bucketed_mode.row_sums` over
    ``_cached_auto_plan(graph)``: the plan ``label_propagation`` and
    ``connected_components`` built, no second build, no slot index, no
    carried rows: every rank moves in every iteration, so every iteration
    gathers in full); ``sort`` and ``None`` take one ``segment_sum`` over
    the messages; a fused :class:`~graphmine_tpu.ops.bucketed_mode.
    BucketedModePlan` is summed as given. The ``sort`` family runs all
    ``max_iter`` iterations as one program; the ``bucketed`` family steps
    one compiled iteration from the host (:func:`_stepped_pagerank`: inside
    a loop the chip's compiler holds every class's rows at once; under a
    caller's trace, where the host cannot step, it is one program all the
    same). ``max_iter`` compiles nothing anew on either.

    ``sink``: optional MetricsSink. Every call emits one
    ``superstep_timing`` record (``op: pagerank_inflow``, the real
    iteration count); an auto resolution of the message reading also
    ``impl_selected`` and, on the bucketed family, ``plan_build`` and
    ``device_residency`` (``slot_index_bytes`` and ``rows_bytes`` 0,
    ``scan: plain``, the reckoned bytes of an iteration's program in
    ``reason``).
    """
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    auto = isinstance(plan, str) and plan == "auto"
    if not (auto or plan is None or isinstance(plan, BucketedModePlan)):
        raise ValueError(
            f"plan must be 'auto', None or a BucketedModePlan; got {plan!r}"
        )
    from graphmine_tpu.obs.memmodel import row_sum_transients
    from graphmine_tpu.ops.superstep_policy import (
        emit_program_memory,
        noting,
        plan_anchor,
        program_log,
    )

    traced = isinstance(graph.msg_ptr, jax.core.Tracer)
    if directed:
        plan = None
        programs = program_log(sink, graph.msg_ptr)
        run = lambda: noting(programs, "loop", _pagerank, max_iter=max_iter)(
            graph, alpha, max_iter, tol, reset, weights
        )
    else:
        if weights is not None:
            raise ValueError(
                "weights= is the directed reading's; the message reading "
                "(directed=False) is unweighted"
            )
        if auto:
            plan = None if traced else _auto_inflow_plan(graph, sink)
        if plan is not None and plan.send_idx is None:
            plan = None  # non-fused plan: no sender indices to sum over
        # the one program the admission's model counts: the stepped iteration
        reckoned = None if plan is None else (
            lambda: {("iteration", None): row_sum_transients(plan)}
        )
        programs = program_log(sink, plan_anchor(graph, plan), reckoned)
        run = lambda: _pagerank_messages(
            graph, plan, alpha, max_iter, tol, reset, programs
        )
    if sink is None or traced:
        return run()[0]
    # Achieved-vs-model attribution (ISSUE 12): the program returns its
    # iteration count, so the window is the REAL iterations run, judged
    # against the analytical model of the inflow's family.
    from graphmine_tpu.obs.costmodel import (
        emit_superstep_timing,
        superstep_cost,
        timed_fixpoint,
    )

    (pr, iters), secs, cold = timed_fixpoint(run)
    iters = max(int(iters), 1)
    cost = superstep_cost(
        "pagerank_inflow", "sort",
        graph.num_vertices, graph.num_messages, graph.num_edges,
        plan=plan, weighted=weights is not None,
    )
    emit_superstep_timing(
        sink, "pagerank_inflow", cost, iters, iters, secs,
        graph.num_edges, variant="fused", cold_compile=cold,
    )
    emit_program_memory(sink, "pagerank_inflow", programs)
    return pr


def _auto_inflow_plan(graph: Graph, sink):
    """``plan="auto"`` of the message reading, resolved as
    ``connected_components`` resolves it: the family from the one policy
    owner, the graph's cached plan on ``bucketed`` (``None`` on ``sort``),
    and the provenance records."""
    from graphmine_tpu.ops.lpa import _cached_auto_plan
    from graphmine_tpu.ops.superstep_policy import (
        emit_device_residency,
        emit_plan_records,
        select_superstep_family,
        stepped_residency,
    )

    family, reason = select_superstep_family(
        graph.num_vertices, graph.num_messages
    )
    plan, seconds, cached = None, 0.0, False
    if family == "bucketed":
        plan, seconds, cached = _cached_auto_plan(graph)
    emit_plan_records(
        sink, "pagerank_inflow", plan, reason, seconds, cached,
        graph.num_edges, graph.num_messages,
        num_vertices=graph.num_vertices,
    )
    if plan is not None and sink is not None:
        emit_device_residency(
            sink, "pagerank_inflow", graph, plan, stepped_residency(plan)
        )
    return plan


def _next_ranks(pr, inflow, dangling, reset_v, alpha):
    """One iteration's update: the inflow, the mass of the vertices that
    send nothing spread over ``reset_v``, and the teleport."""
    with jax.named_scope("dangling_mass"):
        dangling_mass = jnp.sum(jnp.where(dangling, pr, 0.0))
    with jax.named_scope("rank_update"):
        return alpha * (inflow + dangling_mass * reset_v) + (1.0 - alpha) * reset_v


def _power_iteration(inflow_of, dangling, reset_v, alpha, max_iter, tol):
    """``(ranks, iterations)`` of the power iteration from the uniform
    start, as one ``while_loop``: ``max_iter`` iterations, fewer where a
    ``tol`` is given and the L1 delta falls under it."""
    v = reset_v.shape[0]

    def step(state):
        pr, _, it = state
        new = _next_ranks(pr, inflow_of(pr), dangling, reset_v, alpha)
        delta = jnp.float32(1.0) if tol is None else jnp.abs(new - pr).sum()
        return new, delta, it + 1

    def cond(state):
        _, delta, it = state
        return (it < max_iter) if tol is None else (delta > tol) & (it < max_iter)

    pr0 = jnp.full((v,), 1.0 / v, jnp.float32)
    pr, _, it = lax.while_loop(cond, step, (pr0, jnp.float32(1.0), jnp.int32(0)))
    # iterations ride along so the sink path can report the REAL window
    # (the public wrapper discards them for plain callers)
    return pr, it


def _reset_vector(reset, v: int):
    if reset is None:
        return jnp.full((v,), 1.0 / v, jnp.float32)
    r = jnp.maximum(reset.astype(jnp.float32), 0.0)
    return r / jnp.maximum(r.sum(), 1e-12)


@partial(jax.jit, static_argnames=("max_iter",))
def _pagerank(
    graph: Graph,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float | None = 1e-6,
    reset: jax.Array | None = None,
    weights: jax.Array | None = None,
) -> jax.Array:
    """The directed reading: rank flows along the edges as drawn."""
    v = graph.num_vertices
    src, dst = graph.src, graph.dst
    if weights is None:
        out_w = jax.ops.segment_sum(
            jnp.ones_like(src, jnp.float32), src, num_segments=v
        )
        edge_frac = None
    else:
        w = jnp.maximum(weights.astype(jnp.float32), 0.0)
        out_w = jax.ops.segment_sum(w, src, num_segments=v)
        edge_frac = w / jnp.maximum(out_w[src], 1e-30)
    inv_out = jnp.where(out_w > 0, 1.0 / jnp.maximum(out_w, 1e-30), 0.0).astype(
        jnp.float32
    )

    def inflow_of(pr):
        if edge_frac is None:
            return jax.ops.segment_sum((pr * inv_out)[src], dst, num_segments=v)
        return jax.ops.segment_sum(pr[src] * edge_frac, dst, num_segments=v)

    return _power_iteration(
        inflow_of, out_w <= 0, _reset_vector(reset, v), alpha, max_iter, tol
    )


def _inverse_sent(graph: Graph):
    """``[V]`` float32: one over the messages a vertex sends, 0.0 for a
    vertex that sends none (the message reading's dangling vertices)."""
    if graph.symmetric:
        # every message has its twin the other way: sent = received
        sent = jnp.asarray(graph.degrees())
    else:
        sent = jnp.zeros((graph.num_vertices,), jnp.int32).at[graph.msg_send].add(1)
    return jnp.where(
        sent > 0, 1.0 / jnp.maximum(sent, 1).astype(jnp.float32), 0.0
    )


def _bucketed_inflow(pr, inv_out, plan):
    from graphmine_tpu.ops.bucketed_mode import row_sums

    with jax.named_scope("pagerank_bucketed"):
        return row_sums(pr * inv_out, plan)


@jax.jit
def _pagerank_messages_jit(graph, plan, alpha, max_iter, tol, reset):
    """Every iteration of the message reading in ONE program: the ``sort``
    family, and the ``bucketed`` one under a caller's trace."""
    v = graph.num_vertices
    inv_out = _inverse_sent(graph)

    if plan is None:
        def inflow_of(pr):
            with jax.named_scope("pagerank_sort"):
                with jax.named_scope("msg_gather"):
                    msg = (pr * inv_out)[graph.msg_send]
                with jax.named_scope("segment_sum"):
                    return jax.ops.segment_sum(
                        msg, graph.msg_recv, num_segments=v,
                        indices_are_sorted=True,
                    )
    else:
        inflow_of = lambda pr: _bucketed_inflow(pr, inv_out, plan)

    return _power_iteration(
        inflow_of, inv_out == 0, _reset_vector(reset, v), alpha, max_iter, tol
    )


# The bucketed inflow's one program, stepped from the host. The plan is an
# argument (closed over, its arrays would be constants of the program); the
# ranks are donated, so an iteration writes where it read.
@partial(jax.jit, static_argnames=("with_delta",), donate_argnums=0)
def _bucketed_iteration(pr, inv_out, reset_v, plan, alpha, with_delta: bool):
    new = _next_ranks(
        pr, _bucketed_inflow(pr, inv_out, plan), inv_out == 0, reset_v, alpha
    )
    return new, (jnp.abs(new - pr).sum() if with_delta else None)


@jax.jit
def _stepped_start(graph, reset):
    """``(uniform ranks, inverse out-degree, teleport vector)``: what the
    stepped job holds beside the plan, made by one small program."""
    v = graph.num_vertices
    return (
        jnp.full((v,), 1.0 / v, jnp.float32), _inverse_sent(graph),
        _reset_vector(reset, v),
    )


def _stepped_pagerank(graph, plan, alpha, max_iter, tol, reset, programs=None):
    """``(ranks, iterations)`` over a fused plan, one compiled iteration
    (:func:`_bucketed_iteration`) stepped from the host, as
    ``ops/lpa.py:_carried_rows_job`` steps its supersteps, and for the
    same reason: inside a ``while_loop`` the chip's compiler keeps every
    class's gathered rows and indices at once (5.26 GB of temporaries at
    graph500-24's shapes, 11.0 GB at GAP Urand's, which does not fit
    beside the graph), alone the classes take turns and the program holds
    its largest class (PERF.md §6, PR 41). With a stated count the host
    reads nothing between iterations: ten dispatches, one wait. With a
    ``tol`` it reads the delta once an iteration. ``max_iter`` is the
    length of this loop and no program's argument. ``programs`` (the
    caller's ``ProgramLog``) notes the two programs."""
    from graphmine_tpu.ops.superstep_policy import noting

    iteration = noting(programs, "iteration", _bucketed_iteration)
    pr, inv_out, reset_v = noting(programs, "start", _stepped_start)(graph, reset)
    iterations = 0
    while iterations < max_iter:
        pr, delta = iteration(
            pr, inv_out, reset_v, plan, alpha, with_delta=tol is not None
        )
        iterations += 1
        if tol is not None and float(delta) <= tol:  # the one wait
            break
    return pr, iterations


def _pagerank_messages(graph, plan, alpha, max_iter, tol, reset, programs=None):
    """The message reading: ``(ranks, iterations)`` on the family ``plan``
    names (``None``: ``sort``); ``programs`` notes the programs it runs."""
    from graphmine_tpu.ops.superstep_policy import noting

    if plan is None:
        return noting(programs, "loop", _pagerank_messages_jit)(
            graph, None, alpha, max_iter, tol, reset
        )
    if (
        plan.num_vertices != graph.num_vertices
        or plan.num_messages != graph.num_messages
    ):
        raise ValueError(
            f"plan built for V={plan.num_vertices}, M={plan.num_messages} "
            f"but got V={graph.num_vertices}, M={graph.num_messages}: "
            "plan/graph mismatch"
        )
    if not jax.core.trace_ctx.is_top_level():  # a caller's jit: no host steps
        return _pagerank_messages_jit(graph, plan, alpha, max_iter, tol, reset)
    return _stepped_pagerank(graph, plan, alpha, max_iter, tol, reset, programs)


def _validate_sources(sources, v: int) -> np.ndarray:
    """Shared source-id coercion/validation for the single-device and
    source-sharded (parallel/ppr.py) PPR entry points."""
    sources = np.asarray(sources, dtype=np.int32)
    if sources.size and (sources.min() < 0 or sources.max() >= v):
        bad = sources[(sources < 0) | (sources >= v)]
        raise ValueError(f"source ids {bad.tolist()} out of range [0, {v})")
    return sources


@partial(jax.jit, static_argnames=("v", "max_iter", "varying_axes"))
def _batched_ppr(src, dst, v, sources, alpha, max_iter, tol,
                 varying_axes=None):
    """``varying_axes``: set when called inside ``shard_map`` with sharded
    ``sources`` (parallel/ppr.py) — the loop carry must then be marked
    device-varying up front so its type matches the varying loop output."""
    s = sources.shape[0]
    out_deg = jax.ops.segment_sum(jnp.ones_like(src), src, num_segments=v)
    inv_out = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1), 0.0).astype(
        jnp.float32
    )
    dangling = out_deg == 0
    # One-hot teleport distributions, one column per source: [V, S].
    reset = jnp.zeros((v, s), jnp.float32).at[sources, jnp.arange(s)].set(1.0)

    def step(state):
        pr, _, it = state
        contrib = pr * inv_out[:, None]
        inflow = jax.ops.segment_sum(contrib[src], dst, num_segments=v)
        dangling_mass = jnp.sum(jnp.where(dangling[:, None], pr, 0.0), axis=0)
        new = alpha * (inflow + dangling_mass[None, :] * reset) + (1.0 - alpha) * reset
        delta = jnp.abs(new - pr).sum(axis=0).max()
        if varying_axes:
            # Couple the stopping rule across the mesh: every column chunk
            # iterates until the globally slowest column converges —
            # exactly the single-device batch's max-over-all-columns rule,
            # so the sharded result matches it to float noise.
            delta = lax.pmax(delta, varying_axes)
        return new, delta, it + 1

    def cond(state):
        _, delta, it = state
        return (delta > tol) & (it < max_iter)

    pr0 = jnp.full((v, s), 1.0 / v, jnp.float32)
    if varying_axes:
        # pr varies per device; delta stays replicated (the pmax in step
        # produces the same coupled value everywhere).
        pr0 = pcast(pr0, varying_axes, to="varying")
    pr, _, _ = lax.while_loop(cond, step, (pr0, jnp.float32(1.0), jnp.int32(0)))
    return pr


def parallel_personalized_pagerank(
    graph: Graph,
    sources,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float = 1e-6,
) -> jax.Array:
    """Personalized PageRank from many sources at once — GraphFrames'
    ``parallelPersonalizedPageRank`` (part of the GraphFrame capability
    surface, SURVEY §2.2).

    Returns ``[V, S]``: column ``j`` is the PPR vector teleporting to
    ``sources[j]``. One batched power iteration over the whole [V, S] rank
    matrix — the per-edge gather/segment-sum is shared across sources, so S
    sources cost barely more HBM traffic than one (vs GraphX, which runs a
    vector program per source over the same Pregel machinery).
    """
    sources = _validate_sources(sources, graph.num_vertices)
    if sources.size == 0:
        return jnp.zeros((graph.num_vertices, 0), jnp.float32)
    return _batched_ppr(
        graph.src, graph.dst, graph.num_vertices, jnp.asarray(sources), alpha,
        max_iter, tol,
    )
