"""PageRank as a jit-compiled power iteration.

The reference never calls PageRank, but it is part of the engine surface
its GraphFrame object exposes (the same object built at
``Graphframes.py:78`` also provides ``pageRank``); SURVEY §2.2 scopes the
framework to that engine surface. TPU design: rank is a dense float32
vector; one iteration is a gather along edge sources + ``segment_sum`` at
destinations — the same message machinery as LPA with sum instead of mode.

Semantics match the classic formulation (and GraphFrames/GraphX up to
their scaling convention): damping ``alpha``, uniform teleport (or a
personalized reset distribution), dangling-vertex mass redistributed via
the teleport vector, ranks summing to 1.
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
    tol: float = 1e-6,
    reset: jax.Array | None = None,
    weights: jax.Array | None = None,
    plan="auto",
    sink=None,
) -> jax.Array:
    """PageRank vector ``[V]`` (float32, sums to 1).

    ``reset``: optional personalization distribution (normalized
    internally); ``None`` = uniform teleport. ``weights``: optional
    non-negative per-edge weights ``[E]`` (aligned with ``graph.src``) —
    each vertex splits its rank across out-edges in proportion to weight
    (NetworkX weighted-pagerank semantics; vertices whose out-weight sums
    to 0 are treated as dangling). Converges when the L1 delta drops
    below ``tol`` (checked inside the while_loop — no host sync per
    iteration), bounded by ``max_iter``.

    ``plan``: ``"auto"`` or ``None``, both the ``segment_sum`` inflow at
    every size (PageRank has no bucketed inflow; the argument is the
    superstep ops' common one). ``sink``: optional MetricsSink for the
    ``superstep_timing`` record.
    """
    if plan is not None and not (isinstance(plan, str) and plan == "auto"):
        raise ValueError(f"plan must be 'auto' or None; got {plan!r}")
    if sink is not None and not isinstance(graph.msg_ptr, jax.core.Tracer):
        # Achieved-vs-model attribution (ISSUE 12): _pagerank returns its
        # while_loop iteration count, so the window is the REAL
        # supersteps-to-tolerance; judged against the analytical model
        # (segment_sum inflow ≈ the sort gather).
        from graphmine_tpu.obs.costmodel import (
            emit_superstep_timing,
            superstep_cost,
            timed_fixpoint,
        )

        (pr, iters), secs, cold = timed_fixpoint(
            lambda: _pagerank(graph, alpha, max_iter, tol, reset, weights),
        )
        iters = max(int(iters), 1)
        cost = superstep_cost(
            "pagerank_inflow", "sort",
            graph.num_vertices, graph.num_messages, graph.num_edges,
            weighted=weights is not None,
        )
        emit_superstep_timing(
            sink, "pagerank_inflow", cost, iters, iters, secs,
            graph.num_edges, variant="fused", cold_compile=cold,
        )
        return pr
    pr, _ = _pagerank(graph, alpha, max_iter, tol, reset, weights)
    return pr


@partial(jax.jit, static_argnames=("max_iter",))
def _pagerank(
    graph: Graph,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float = 1e-6,
    reset: jax.Array | None = None,
    weights: jax.Array | None = None,
) -> jax.Array:
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
    dangling = out_w <= 0
    if reset is None:
        reset_v = jnp.full((v,), 1.0 / v, jnp.float32)
    else:
        r = jnp.maximum(reset.astype(jnp.float32), 0.0)
        reset_v = r / jnp.maximum(r.sum(), 1e-12)

    def step(state):
        pr, _, it = state
        if edge_frac is None:
            inflow = jax.ops.segment_sum((pr * inv_out)[src], dst, num_segments=v)
        else:
            inflow = jax.ops.segment_sum(pr[src] * edge_frac, dst, num_segments=v)
        dangling_mass = jnp.sum(jnp.where(dangling, pr, 0.0))
        new = alpha * (inflow + dangling_mass * reset_v) + (1.0 - alpha) * reset_v
        delta = jnp.abs(new - pr).sum()
        return new, delta, it + 1

    def cond(state):
        _, delta, it = state
        return (delta > tol) & (it < max_iter)

    pr0 = jnp.full((v,), 1.0 / v, jnp.float32)
    pr, _, it = lax.while_loop(cond, step, (pr0, jnp.float32(1.0), jnp.int32(0)))
    # iterations ride along so the sink path can report the REAL window
    # (the public wrapper discards them for plain callers)
    return pr, it


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
