"""HITS and closeness centrality — engine-surface extensions.

The reference never computes centrality beyond degree, but its GraphFrame
object is the one-stop analysis surface (``Graphframes.py:78``); these round
out that surface for NetworkX migrants (the reference's ``Overview:8`` names
NetworkX as a tool considered). TPU design: both are dense-vector
power/frontier iterations on the same gather + ``segment_sum`` machinery as
PageRank/BFS — no new memory shapes, jit-compiled, static shapes.

Semantics match NetworkX (``nx.hits``, ``nx.closeness_centrality``) and are
oracle-tested against it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.ops.paths import shortest_paths


@partial(jax.jit, static_argnames=("max_iter",))
def hits(
    graph: Graph, max_iter: int = 100, tol: float = 1e-8
) -> tuple[jax.Array, jax.Array]:
    """HITS hub and authority scores ``([V], [V])``, NetworkX semantics.

    One iteration: ``a = Aᵀh`` (authorities gather hub mass along in-edges),
    ``h = Aa`` (hubs gather authority mass along out-edges), each normalized
    by its max; converges when the L1 hub delta drops below ``tol`` (checked
    in the ``while_loop`` — no host sync), bounded by ``max_iter``. Final
    vectors are sum-normalized (``nx.hits(normalized=True)``).

    Use a ``symmetric=False`` graph (directed edges); on a symmetric graph
    hubs equal authorities (eigenvector centrality up to normalization).
    """
    v = graph.num_vertices
    src, dst = graph.src, graph.dst
    h0 = jnp.full(v, 1.0 / v, dtype=jnp.float32)

    def step(state):
        h, _, err, i = state
        a = jax.ops.segment_sum(h[src], dst, num_segments=v)
        h_new = jax.ops.segment_sum(a[dst], src, num_segments=v)
        h_new = h_new / jnp.maximum(h_new.max(), 1e-30)
        a = a / jnp.maximum(a.max(), 1e-30)
        err = jnp.abs(h_new - h).sum()
        return h_new, a, err, i + 1

    def cond(state):
        _, _, err, i = state
        return (err >= tol) & (i < max_iter)

    h, a, _, _ = lax.while_loop(
        cond, step, (h0, jnp.zeros(v, jnp.float32), jnp.inf, jnp.array(0))
    )
    h = h / jnp.maximum(h.sum(), 1e-30)
    a = a / jnp.maximum(a.sum(), 1e-30)
    return h, a


@partial(jax.jit, static_argnames=("max_iter",))
def eigenvector_centrality(
    graph: Graph, max_iter: int = 100, tol: float = 1e-6
) -> jax.Array:
    """Eigenvector centrality ``[V]`` — power iteration on ``Aᵀx`` (each
    vertex accumulates its in-neighbors' scores), L2-normalized with an
    L1 convergence test scaled by V, matching ``nx.eigenvector_centrality``.
    Use a symmetric graph for the undirected notion."""
    v = graph.num_vertices
    src, dst = (
        (graph.msg_send, graph.msg_recv) if graph.symmetric
        else (graph.src, graph.dst)
    )
    x0 = jnp.full(v, 1.0 / v, jnp.float32)

    def step(state):
        x, _, it = state
        nxt = x + jax.ops.segment_sum(x[src], dst, num_segments=v)
        norm = jnp.sqrt(jnp.sum(nxt * nxt))
        nxt = nxt / jnp.maximum(norm, 1e-30)
        err = jnp.abs(nxt - x).sum()
        return nxt, err, it + 1

    def cond(state):
        _, err, it = state
        return (err >= v * tol) & (it < max_iter)

    x, _, _ = lax.while_loop(cond, step, (x0, jnp.inf, jnp.array(0)))
    return x


@partial(jax.jit, static_argnames=("max_iter", "normalized"))
def katz_centrality(
    graph: Graph,
    alpha: float = 0.1,
    beta: float = 1.0,
    max_iter: int = 1000,
    tol: float = 1e-6,
    normalized: bool = True,
) -> jax.Array:
    """Katz centrality ``[V]``: fixpoint of ``x = alpha·Aᵀx + beta``
    (NetworkX semantics, including the final L2 normalization). ``alpha``
    must be below ``1/λ_max`` to converge."""
    v = graph.num_vertices
    src, dst = (
        (graph.msg_send, graph.msg_recv) if graph.symmetric
        else (graph.src, graph.dst)
    )
    x0 = jnp.zeros(v, jnp.float32)

    def step(state):
        x, _, it = state
        nxt = alpha * jax.ops.segment_sum(x[src], dst, num_segments=v) + beta
        err = jnp.abs(nxt - x).sum()
        return nxt, err, it + 1

    def cond(state):
        _, err, it = state
        return (err >= v * tol) & (it < max_iter)

    x, _, _ = lax.while_loop(cond, step, (x0, jnp.inf, jnp.array(0)))
    if normalized:
        x = x / jnp.maximum(jnp.sqrt(jnp.sum(x * x)), 1e-30)
    return x


def betweenness_centrality(
    graph: Graph,
    sources=None,
    normalized: bool = True,
    directed: bool | None = None,
    source_batch: int = 8,
    mesh=None,
) -> jax.Array:
    """Betweenness centrality ``[V]`` (float32) via Brandes' algorithm as
    data-parallel level sweeps — no priority queues or per-node stacks:
    one BFS forward pass accumulates shortest-path counts per level, one
    backward pass accumulates pair dependencies per level, both as
    gather + ``segment_sum`` supersteps batched ``source_batch`` sources
    at a time (the same lane-block recipe as ``shortest_paths``).

    ``sources=None`` runs every vertex (exact, NetworkX-oracle tested);
    an id array runs the standard sampled estimator scaled by ``V/k``.
    Parallel edges count as distinct shortest paths (multigraph
    semantics, the engine's multiplicity convention — dedupe the edge
    list first for simple-graph parity).

    ``mesh``: optional ``jax.sharding.Mesh`` — sources are sharded across
    the mesh (graph replicated per device) and partial accumulators meet
    in one ``psum``; equivalent to the single-device result up to float32
    summation order (per-device partials reduce in a different order).
    ``directed`` defaults to ``not graph.symmetric``; undirected scores
    are halved (each unordered pair is counted from both endpoints) and
    ``normalized`` applies NetworkX's ``1/((V-1)(V-2))`` (×2 undirected).
    """
    v = graph.num_vertices
    if directed is None:
        directed = not graph.symmetric
    if directed:
        send, recv = graph.src, graph.dst
    else:
        send = jnp.concatenate([graph.src, graph.dst])
        recv = jnp.concatenate([graph.dst, graph.src])
    if sources is None:
        src_ids = jnp.arange(v, dtype=jnp.int32)
    else:
        src_ids = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
    k = int(src_ids.shape[0])
    b = max(1, min(source_batch, k))
    n_dev = 1 if mesh is None else int(np.prod(mesh.devices.shape))
    pad = (-k) % (b * n_dev)  # every device gets whole tiles
    tiles = jnp.concatenate([src_ids, jnp.zeros(pad, jnp.int32)]).reshape(-1, b)
    # padded lanes recompute source 0; mask their contribution out
    lane_valid = (jnp.arange(k + pad) < k).reshape(-1, b)

    def tile_scan(tiles_, valid_):
        def tile(acc, args):
            srcs, valid = args
            # scan with a running [V] sum — a stacked [tiles, V] result
            # would be O(V^2 / b) for exact betweenness
            return acc + _brandes_tile(srcs, valid, send=send, recv=recv, v=v), None

        acc, _ = lax.scan(tile, jnp.zeros(v, jnp.float32), (tiles_, valid_))
        return acc

    if mesh is None:
        bc = tile_scan(tiles, lane_valid)
    else:
        # Source-parallel: the graph is replicated, the source tiles are
        # sharded across every mesh axis, partial accumulators meet in one
        # psum over ICI — embarrassingly parallel Brandes.
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        axes = tuple(mesh.axis_names)

        def per_device(tiles_, valid_):
            return jax.lax.psum(tile_scan(tiles_, valid_), axis_name=axes)

        bc = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axes), P(axes)), out_specs=P(),
            # while_loop carries mix sharded-derived and replicated values;
            # varying-axis checking can't track that through the fixpoint
            check_vma=False,
        )(tiles, lane_valid)
    if not directed:
        bc = bc / 2.0
    if sources is not None and k and k < v:
        bc = bc * (v / k)  # sampled-source estimator rescale
    if normalized and v > 2:
        scale = 1.0 / ((v - 1) * (v - 2))
        if not directed:
            scale *= 2.0
        bc = bc * scale
    return bc


def _brandes_tile(srcs, valid, *, send, recv, v: int) -> jax.Array:
    """Dependency accumulation for one lane block of sources: ``[V]``.

    Both segment sums flatten the lane axis into the segment ids
    (``vertex * b + lane``) instead of segment-summing a ``[M, b]``
    operand over its leading axis — the 2-D form chained across
    supersteps miscompiles to zeros on the TPU backend this was built
    against (single steps are fine; verified minimal repro), and the
    flat form is equivalent.
    """
    b = srcs.shape[0]
    lanes = jnp.arange(b, dtype=jnp.int32)
    unreach = jnp.int32(v + 1)
    seg_recv = (recv[:, None] * b + lanes[None, :]).ravel()
    seg_send = (send[:, None] * b + lanes[None, :]).ravel()
    dist = jnp.full((v, b), unreach, jnp.int32)
    dist = dist.at[srcs, lanes].min(0)
    sigma = jnp.zeros((v, b), jnp.float32).at[srcs, lanes].add(1.0)

    def fwd(state):
        dist, sigma, it, _ = state
        on_level = dist[send] == it
        msg = jnp.where(on_level, sigma[send], 0.0)
        contrib = jax.ops.segment_sum(
            msg.ravel(), seg_recv, num_segments=v * b
        ).reshape(v, b)
        newly = (dist == unreach) & (contrib > 0)
        dist = jnp.where(newly, it + 1, dist)
        sigma = jnp.where(newly, contrib, sigma)
        return dist, sigma, it + 1, jnp.sum(newly, dtype=jnp.int32)

    def fwd_cond(state):
        _, _, it, progressed = state
        return (progressed > 0) & (it < v)

    dist, sigma, depth, _ = lax.while_loop(
        fwd_cond, fwd, (dist, sigma, jnp.int32(0), jnp.int32(1))
    )

    def bwd(state):
        delta, it = state
        # edges u->w on shortest paths with dist[w] == it+1 push
        # sigma[u]/sigma[w] * (1 + delta[w]) back to u at level it
        on_sp = (dist[send] == it) & (dist[recv] == it + 1)
        ratio = sigma[send] / jnp.maximum(sigma[recv], 1.0)
        msg = jnp.where(on_sp, ratio * (1.0 + delta[recv]), 0.0)
        back = jax.ops.segment_sum(
            msg.ravel(), seg_send, num_segments=v * b
        ).reshape(v, b)
        delta = jnp.where(dist == it, back, delta)
        return delta, it - 1

    def bwd_cond(state):
        _, it = state
        return it >= 0

    delta, _ = lax.while_loop(
        bwd_cond, bwd, (jnp.zeros((v, b), jnp.float32), depth - 1)
    )
    # sources don't count their own dependency; padded lanes contribute 0
    delta = delta.at[srcs, lanes].set(0.0)
    return jnp.where(valid[None, :], delta, 0.0).sum(axis=1)


def closeness_centrality(
    graph: Graph, vertices=None, wf_improved: bool = True
) -> jax.Array:
    """Closeness centrality for ``vertices`` (default: all), ``[L]`` float32.

    NetworkX semantics: for vertex ``u`` with ``r`` vertices able to reach
    it and total incoming distance ``s``: ``(r-1)/s``, scaled by
    ``(r-1)/(V-1)`` when ``wf_improved`` (the Wasserman–Faust correction
    NetworkX applies by default). Isolated vertices score 0. A symmetric
    graph gives the undirected notion; a ``symmetric=False`` graph gives
    directed closeness over incoming paths — exactly
    ``nx.closeness_centrality(DiGraph)``.

    Cost: landmarks run through batched multi-source BFS tiles
    (``shortest_paths``), ``[V, L]`` result memory. Exact closeness for
    every vertex means ``L = V``; on large graphs pass a landmark sample
    instead (the standard approximation) and keep ``L`` bounded.
    """
    v = graph.num_vertices
    idx = (
        jnp.arange(v, dtype=jnp.int32)
        if vertices is None
        else jnp.atleast_1d(jnp.asarray(vertices, jnp.int32))
    )
    # [V, L]: symmetric graphs walk the undirected message CSR; directed
    # graphs follow edge direction toward the target (incoming distance)
    direction = "both" if graph.symmetric else "out"
    dist = shortest_paths(graph, idx, direction=direction)
    unreach = jnp.iinfo(jnp.int32).max
    reach = dist < unreach
    total = jnp.where(reach, dist, 0).astype(jnp.float32).sum(axis=0)
    r = reach.sum(axis=0).astype(jnp.float32)  # includes the vertex itself
    c = jnp.where(total > 0, (r - 1.0) / jnp.maximum(total, 1.0), 0.0)
    if wf_improved:
        c = c * (r - 1.0) / max(v - 1, 1)
    return c
