"""Source-sharded personalized PageRank — query-axis data parallelism.

:func:`graphmine_tpu.ops.pagerank.parallel_personalized_pagerank` runs one
batched ``[V, S]`` power iteration; every source column shares the per-edge
gather/segment-sum. The natural multi-chip axis for that program is the
SOURCE dimension (every source needs every edge, so the graph replicates —
for vertex-axis memory scaling use ``sharded_pagerank``/``ring_pagerank``):
each device owns ``ceil(S/D)`` teleport columns and runs the identical
power iteration on its slice, with zero cross-device traffic until the
final column concatenation. This is the framework's query-DP pattern — the
Spark-"partitioned DataFrame ops" analog for analysis queries rather than
graph state (SURVEY §2.3).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.parallel.mesh import VERTEX_AXIS, cached_jit_shard_map


def _ppr_chunk(src, dst, sources, alpha, tol, *, v, max_iter):
    from graphmine_tpu.ops.pagerank import _batched_ppr

    return _batched_ppr(
        src, dst, v, sources, alpha, max_iter, tol,
        varying_axes=(VERTEX_AXIS,),
    )


def _compiled_body(mesh, v: int, chunk: int, max_iter: int):
    """One compiled program per (mesh, V, source-chunk, max_iter);
    alpha/tol ride as traced scalars so parameter sweeps reuse it."""
    return cached_jit_shard_map(
        ("ppr", mesh, v, chunk, max_iter),
        lambda: shard_map(
            partial(_ppr_chunk, v=v, max_iter=max_iter),
            mesh=mesh,
            # the mesh's one axis shards the SOURCE dimension here
            in_specs=(P(), P(), P(VERTEX_AXIS), P(), P()),
            out_specs=P(None, VERTEX_AXIS),
        ),
    )


def sharded_personalized_pagerank(
    graph: Graph,
    sources,
    mesh,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float = 1e-6,
) -> jax.Array:
    """``parallel_personalized_pagerank`` with sources sharded over the
    mesh. Returns ``[V, S]`` (columns sharded); parity with the
    single-device op is asserted by the virtual-mesh tests.

    Convergence matches the single-device batch exactly: the per-chunk
    ``while_loop`` delta is ``pmax``-coupled across the mesh, so every
    column iterates until the globally slowest column meets ``tol`` —
    the same max-over-all-columns stopping rule as the batch, making the
    two paths comparable at float-noise tolerance.
    """
    from graphmine_tpu.ops.pagerank import _validate_sources

    v, d = graph.num_vertices, mesh.size
    sources = _validate_sources(sources, v)
    if sources.size == 0:
        return jnp.zeros((v, 0), jnp.float32)
    s = len(sources)
    chunk = -(-s // d)
    # Padding columns recompute a valid source; sliced away below.
    padded = np.full(d * chunk, sources[0], np.int32)
    padded[:s] = sources
    out = _compiled_body(mesh, v, chunk, max_iter)(
        graph.src, graph.dst, jnp.asarray(padded),
        jnp.float32(alpha), jnp.float32(tol),
    )
    return out[:, :s]
