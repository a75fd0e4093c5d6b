"""Per-vertex structural features for the kNN/LOF outlier scorer.

The north-star upgrade over the reference's size-threshold heuristic
(BASELINE.json: "kNN-graph + LOF outlier scorer"): each vertex gets a small
dense feature vector derived from graph structure, and outliers are scored
geometrically. Cost: mostly O(E) segment ops, plus two O(M log M) device
argsorts (distinct neighbor communities) and one host-side oriented-CSR
triangle pass (clustering coefficient — forward a warm triangle cache via
``triangles_cache`` to skip it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from graphmine_tpu.graph.container import Graph
from graphmine_tpu.obs.spans import stage_span
from graphmine_tpu.ops.census import community_sizes


def vertex_features(
    graph: Graph, communities: jax.Array, triangles_cache=None,
    include_clustering: bool | str = True, simple_edges=None, sink=None,
) -> jax.Array:
    """Feature matrix ``[V, 8]`` (float32):

    log1p(out-degree), log1p(in-degree), log1p(message degree),
    log1p(community size), log1p(mean neighbor degree), the
    **same-community neighbor fraction** — the share of a vertex's
    messages arriving from its own community — plus
    log1p(**distinct neighbor communities**) and the local
    **clustering coefficient**.

    Same-frac/distinct-communities are the direct signature of a
    community-bridging outlier (edges scattered uniformly across the
    graph land in many foreign communities), and random bridges close
    almost no triangles, so the clustering coefficient separates them
    from organically embedded hubs — raw degree cannot under a power-law
    degree distribution: legitimate hubs out-degree injected anomalies by
    orders of magnitude. Measured on the r-series AUROC harness (before
    the chip records; not in the ledger): r1 CPU-class 0.89–0.91 with the first six
    features, 0.91–0.93 with all eight; r4 real-TPU capture (after the
    true-f32 distance fix, which alone moved the headline from 0.92 to
    0.99) 0.9905 with all eight. Degree-ish features are log-scaled to tame the power
    law (max degree 1,223 at 4.6K vertices on the bundled data — SURVEY
    §7 hard part 3); fractions are already in [0, 1].
    """
    # clustering_coefficient plans on the host (ops/triangles.py:_lcc_plan),
    # so it runs outside jit; everything else is one compiled program.
    # ``triangles_cache``: a prior ops.triangles._triangles result (e.g.
    # GraphFrame._triangle_cache()) to skip the host pass.
    # ``include_clustering`` mirrors the host twin: True = the exact
    # counts; ``"sampled"`` = the wedge-count-independent estimator (r5:
    # the exact counts then listed ~28 B a wedge on the host, which
    # OOM-killed a 25M-edge mega-hub run at 130 GB; they list none since
    # PR 46, and the driver still probes ``oriented_wedge_count`` and
    # passes "sampled" past its budget);
    # False zeros the column (the measured-weaker host-7 configuration).
    # ``sink``: optional MetricsSink; the triangle pass and the compiled
    # feature program are then stage spans (``triangles_host``,
    # ``triangles_device``, ``features_device``).
    if isinstance(include_clustering, np.bool_):
        include_clustering = bool(include_clustering)
    if include_clustering == "sampled":
        from graphmine_tpu.ops.triangles import sampled_clustering_coefficient

        clust = jnp.asarray(sampled_clustering_coefficient(
            graph, simple_edges=simple_edges
        ))
    elif include_clustering is True:
        from graphmine_tpu.ops.triangles import clustering_coefficient

        clust = clustering_coefficient(
            graph, _cached=triangles_cache, simple_edges=simple_edges,
            sink=sink,
        )
    elif include_clustering is False:
        clust = jnp.zeros((graph.num_vertices,), jnp.float32)
    else:
        raise ValueError(
            f"include_clustering must be True, False or 'sampled' "
            f"(got {include_clustering!r})"
        )
    with stage_span(sink, "features_device") as stage:
        return stage.sync(_vertex_features_jit(graph, communities, clust))


@partial(jax.jit, static_argnames=())
def _vertex_features_jit(
    graph: Graph, communities: jax.Array, clust: jax.Array
) -> jax.Array:
    v = graph.num_vertices
    with jax.named_scope("features"):
        with jax.named_scope("degrees"):
            ones_e = jnp.ones_like(graph.src)
            out_deg = jax.ops.segment_sum(ones_e, graph.src, num_segments=v)
            in_deg = jax.ops.segment_sum(ones_e, graph.dst, num_segments=v)
            msg_deg = graph.degrees()
            comm_size = community_sizes(communities)[communities]
        with jax.named_scope("neighbor_stats"):
            neigh_deg_sum = jax.ops.segment_sum(
                msg_deg[graph.msg_send], graph.msg_recv, num_segments=v,
                indices_are_sorted=True,
            )
            mean_neigh_deg = neigh_deg_sum / jnp.maximum(msg_deg, 1)
            same = (
                communities[graph.msg_send] == communities[graph.msg_recv]
            ).astype(jnp.int32)
            same_cnt = jax.ops.segment_sum(
                same, graph.msg_recv, num_segments=v, indices_are_sorted=True
            )
            same_frac = same_cnt / jnp.maximum(msg_deg, 1)
        with jax.named_scope("distinct_communities"):
            distinct = _distinct_neighbor_communities(graph, communities, v)
        with jax.named_scope("stack"):
            feats = jnp.log1p(
                jnp.stack(
                    [out_deg, in_deg, msg_deg, comm_size, mean_neigh_deg,
                     distinct.astype(jnp.float32)], axis=1
                ).astype(jnp.float32)
            )
            return jnp.concatenate(
                [feats, same_frac[:, None].astype(jnp.float32),
                 clust[:, None].astype(jnp.float32)], axis=1
            )


def _distinct_neighbor_communities(
    graph: Graph, communities: jax.Array, v: int
) -> jax.Array:
    """Per-vertex count of distinct communities among message senders.

    Messages are ordered by (receiver, sender community) with two stable
    argsorts — no 64-bit composite key, so it stays int32-safe at any V —
    then run boundaries are segment-summed per receiver."""
    c = communities[graph.msg_send]
    o1 = jnp.argsort(c, stable=True)
    o2 = jnp.argsort(graph.msg_recv[o1], stable=True)
    perm = o1[o2]
    rc, cs = graph.msg_recv[perm], c[perm]
    new_run = jnp.concatenate(
        [jnp.ones(1, dtype=bool), (rc[1:] != rc[:-1]) | (cs[1:] != cs[:-1])]
    )
    return jax.ops.segment_sum(new_run.astype(jnp.int32), rc, num_segments=v)


def vertex_features_host(
    graph: Graph, communities, include_clustering: bool | str = True,
    clustering_samples: int = 64, clustering_seed: int = 0,
):
    """NumPy twin of :func:`vertex_features` for HOST graphs
    (``build_graph(to_device=False)``, r3 scale-out mode): the O(E)/O(M)
    feature columns compute with bincounts and one int64 unique — no
    device transfer of the edge arrays.

    ``include_clustering`` selects the 8th column:

    * ``True`` — the exact counts (``ops/triangles.py``); matches
      :func:`vertex_features` within float32 rounding (tested; host
      accumulation is float64).
    * ``"sampled"`` (r4, the scale-out default) — the wedge-sampled
      estimator (:func:`~graphmine_tpu.ops.triangles.
      sampled_clustering_coefficient`, per-vertex stderr
      ``<= 1/(2*sqrt(clustering_samples))``), whose cost is independent
      of the wedge count — so the full 8-feature set survives where
      the graph does not fit one device (the exact counts run on one).
    * ``False`` — zero the column (7 informative features). The
      r-series AUROC harness scored the 7-feature and sampled-8 configs
      next to the exact-8 headline (VERDICT r3 item 5; before the chip
      records, not in the ledger). r4 real-TPU capture (65K vertices,
      64 injected anomalies, k=128, after the true-f32 distance fix):
      exact-8 **0.9905**, host-7 **0.9940**, sampled-8 **0.9887** — all
      three configs within ~0.005 of each other at this scale.
    """
    v = graph.num_vertices
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    recv = np.asarray(graph.msg_recv)
    send = np.asarray(graph.msg_send)
    comm = np.asarray(communities)

    out_deg = np.bincount(src, minlength=v).astype(np.float64)
    in_deg = np.bincount(dst, minlength=v).astype(np.float64)
    msg_deg = np.diff(np.asarray(graph.msg_ptr).astype(np.int64)).astype(
        np.float64
    )
    comm_size = np.bincount(comm, minlength=v).astype(np.float64)[comm]
    neigh_deg_sum = np.bincount(recv, weights=msg_deg[send], minlength=v)
    mean_neigh_deg = neigh_deg_sum / np.maximum(msg_deg, 1.0)
    same = comm[send] == comm[recv]
    same_cnt = np.bincount(recv[same], minlength=v).astype(np.float64)
    same_frac = same_cnt / np.maximum(msg_deg, 1.0)
    # distinct neighbor communities: unique (receiver, sender-community)
    # pairs via one int64 composite key (V <= 2^31 so recv * V + comm
    # stays within int64)
    key = recv.astype(np.int64) * v + comm[send].astype(np.int64)
    uniq = np.unique(key)
    distinct = np.bincount((uniq // v).astype(np.int64), minlength=v).astype(
        np.float64
    )
    # Normalize bool-likes first (ADVICE r4): callers threading flags out
    # of numpy/config arrays pass np.True_/np.False_, which the identity
    # checks below would bounce to the typo ValueError.
    if isinstance(include_clustering, np.bool_):
        include_clustering = bool(include_clustering)
    if include_clustering == "sampled":
        from graphmine_tpu.ops.triangles import sampled_clustering_coefficient

        clust = sampled_clustering_coefficient(
            graph, samples=clustering_samples, seed=clustering_seed
        ).astype(np.float64)
    elif include_clustering is True:
        from graphmine_tpu.ops.triangles import clustering_coefficient

        clust = np.asarray(clustering_coefficient(graph), np.float64)
    elif include_clustering is False:
        clust = np.zeros(v, np.float64)
    else:
        # a typo like "sample" must not silently run the exact wedge
        # pipeline — the path documented as infeasible at exactly the
        # scale this twin exists for
        raise ValueError(
            f"include_clustering must be True, False or 'sampled' "
            f"(got {include_clustering!r})"
        )
    feats = np.log1p(
        np.stack(
            [out_deg, in_deg, msg_deg, comm_size, mean_neigh_deg, distinct],
            axis=1,
        )
    ).astype(np.float32)
    return np.concatenate(
        [feats, same_frac[:, None].astype(np.float32),
         clust[:, None].astype(np.float32)], axis=1,
    )


def standardize(feats: jax.Array) -> jax.Array:
    """Zero-mean unit-variance columns (guarding constant features)."""
    mu = feats.mean(axis=0, keepdims=True)
    sd = feats.std(axis=0, keepdims=True)
    return (feats - mu) / jnp.maximum(sd, 1e-6)
