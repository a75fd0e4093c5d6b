"""graphmine_tpu — a TPU-native massive-graph-mining framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of the
reference PySpark project (community detection + outlier detection over
massive graphs, ``/root/reference/CommunityDetection/Graphframes.py``):

- L0  ingestion: parquet / edge-list readers, null filtering, dense int32
      string factorization (replaces the sha1[:8] NodeHash scheme of
      ``Graphframes.py:57-58`` — no birthday collisions, device-friendly).
- L1  mesh runtime: ``jax.sharding.Mesh`` + ``shard_map`` over ICI; XLA
      collectives (psum / all_gather / ppermute) are the comms backend
      (replaces Spark shuffle + py4j). See :mod:`graphmine_tpu.parallel`.
- L2  sharded graph container: vertex-range-sharded message CSR + vertex
      property arrays (replaces Spark DataFrames / GraphFrames).
- L3  graph ops: label propagation (``Graphframes.py:81``), connected
      components, degrees, community census (replaces the O(C*V*E) driver
      loops of ``Graphframes.py:100-118``), induced subgraphs, kNN + LOF
      outlier scoring (the intended capability of ``Graphframes.py:121-137``).
- L4  pipeline driver with a plugin boundary (backend=jax|graphframes).
      See :mod:`graphmine_tpu.pipeline`.

Exports are **lazy** (PEP 562): ``graphmine_tpu.X`` imports X's defining
module on first access. This keeps the package importable on hosts with
no jax at all — the observability plane (``graphmine_tpu.obs``, used by
the stdlib-only fleet tools ``tools/obs_report.py`` /
``tools/trace_stitch.py`` / ``tools/schema_lint.py``) must load on a
bare triage machine, and an eager ``from .graph.container import ...``
here would drag the whole device stack in with it.
"""

__version__ = "0.1.0"

# export name -> (defining module, attribute). None attribute = the
# module itself. find_motifs aliases ops.motifs.find.
_EXPORTS = {
    "Graph": ("graphmine_tpu.graph.container", "Graph"),
    "build_graph": ("graphmine_tpu.graph.container", "build_graph"),
    "GraphFrame": ("graphmine_tpu.frames", "GraphFrame"),
    "load_parquet_edges": ("graphmine_tpu.io.edges", "load_parquet_edges"),
    "load_edge_list": ("graphmine_tpu.io.edges", "load_edge_list"),
    "label_propagation": ("graphmine_tpu.ops.lpa", "label_propagation"),
    "make_mesh": ("graphmine_tpu.parallel.mesh", "make_mesh"),
    "connected_components": ("graphmine_tpu.ops.cc", "connected_components"),
    "leiden": ("graphmine_tpu.ops.louvain", "leiden"),
    "louvain": ("graphmine_tpu.ops.louvain", "louvain"),
    "modularity": ("graphmine_tpu.ops.modularity", "modularity"),
    "pagerank": ("graphmine_tpu.ops.pagerank", "pagerank"),
    "parallel_personalized_pagerank": (
        "graphmine_tpu.ops.pagerank", "parallel_personalized_pagerank"
    ),
    "degrees": ("graphmine_tpu.ops.degrees", "degrees"),
    "in_degrees": ("graphmine_tpu.ops.degrees", "in_degrees"),
    "out_degrees": ("graphmine_tpu.ops.degrees", "out_degrees"),
    "out_weights": ("graphmine_tpu.ops.degrees", "out_weights"),
    "bfs": ("graphmine_tpu.ops.paths", "bfs"),
    "bfs_distances": ("graphmine_tpu.ops.paths", "bfs_distances"),
    "bfs_parents": ("graphmine_tpu.ops.paths", "bfs_parents"),
    "shortest_paths": ("graphmine_tpu.ops.paths", "shortest_paths"),
    "weighted_shortest_paths": (
        "graphmine_tpu.ops.paths", "weighted_shortest_paths"
    ),
    "adjusted_rand_index": (
        "graphmine_tpu.ops.cluster_metrics", "adjusted_rand_index"
    ),
    "normalized_mutual_info": (
        "graphmine_tpu.ops.cluster_metrics", "normalized_mutual_info"
    ),
    "strongly_connected_components": (
        "graphmine_tpu.ops.scc", "strongly_connected_components"
    ),
    "aggregate_messages": (
        "graphmine_tpu.ops.aggregate", "aggregate_messages"
    ),
    "pregel": ("graphmine_tpu.ops.aggregate", "pregel"),
    "find_motifs": ("graphmine_tpu.ops.motifs", "find"),
    "StreamingLOF": ("graphmine_tpu.ops.streaming_lof", "StreamingLOF"),
    "fit_lof": ("graphmine_tpu.ops.streaming_lof", "fit_lof"),
    "score_lof": ("graphmine_tpu.ops.streaming_lof", "score_lof"),
    "standardize": ("graphmine_tpu.ops.features", "standardize"),
    "vertex_features": ("graphmine_tpu.ops.features", "vertex_features"),
    "vertex_features_host": (
        "graphmine_tpu.ops.features", "vertex_features_host"
    ),
    "ivf_knn": ("graphmine_tpu.ops.ann", "ivf_knn"),
    "kmeans": ("graphmine_tpu.ops.ann", "kmeans"),
    "knn": ("graphmine_tpu.ops.knn", "knn"),
    "lof_scores": ("graphmine_tpu.ops.lof", "lof_scores"),
    "select_lof_impl": ("graphmine_tpu.ops.lof", "select_lof_impl"),
    "masked_label_propagation": (
        "graphmine_tpu.ops.outliers", "masked_label_propagation"
    ),
    "recursive_lpa_outliers": (
        "graphmine_tpu.ops.outliers", "recursive_lpa_outliers"
    ),
    "recursive_lpa_outliers_sharded": (
        "graphmine_tpu.ops.outliers", "recursive_lpa_outliers_sharded"
    ),
    "triangle_count": ("graphmine_tpu.ops.triangles", "triangle_count"),
    "clustering_coefficient": (
        "graphmine_tpu.ops.triangles", "clustering_coefficient"
    ),
    "sampled_clustering_coefficient": (
        "graphmine_tpu.ops.triangles", "sampled_clustering_coefficient"
    ),
    "core_numbers": ("graphmine_tpu.ops.kcore", "core_numbers"),
    "greedy_color": ("graphmine_tpu.ops.mis", "greedy_color"),
    "maximal_independent_set": (
        "graphmine_tpu.ops.mis", "maximal_independent_set"
    ),
    "link_prediction": ("graphmine_tpu.ops.linkpred", "link_prediction"),
    "k_truss": ("graphmine_tpu.ops.ktruss", "k_truss"),
    "spectral_embedding": (
        "graphmine_tpu.ops.embedding", "spectral_embedding"
    ),
    "degree_assortativity": (
        "graphmine_tpu.ops.stats", "degree_assortativity"
    ),
    "density": ("graphmine_tpu.ops.stats", "density"),
    "diameter": ("graphmine_tpu.ops.stats", "diameter"),
    "reciprocity": ("graphmine_tpu.ops.stats", "reciprocity"),
    "betweenness_centrality": (
        "graphmine_tpu.ops.centrality", "betweenness_centrality"
    ),
    "closeness_centrality": (
        "graphmine_tpu.ops.centrality", "closeness_centrality"
    ),
    "eigenvector_centrality": (
        "graphmine_tpu.ops.centrality", "eigenvector_centrality"
    ),
    "hits": ("graphmine_tpu.ops.centrality", "hits"),
    "katz_centrality": ("graphmine_tpu.ops.centrality", "katz_centrality"),
    "datasets": ("graphmine_tpu.datasets", None),
    "Table": ("graphmine_tpu.table", "Table"),
    "read_parquet": ("graphmine_tpu.table", "read_parquet"),
    "svd_plus_plus": ("graphmine_tpu.ops.svdpp", "svd_plus_plus"),
    "svdpp_predict": ("graphmine_tpu.ops.svdpp", "svdpp_predict"),
    "from_networkx": ("graphmine_tpu.interop", "from_networkx"),
    "graph_from_networkx": (
        "graphmine_tpu.interop", "graph_from_networkx"
    ),
    "to_networkx": ("graphmine_tpu.interop", "to_networkx"),
    "graphx_label_propagation": (
        "graphmine_tpu.oracle", "graphx_label_propagation"
    ),
    "select_superstep_family": (
        "graphmine_tpu.ops.superstep_policy", "select_superstep_family"
    ),
    "obs": ("graphmine_tpu.obs", None),
    "CostEstimate": ("graphmine_tpu.obs.costmodel", "CostEstimate"),
    "superstep_cost": ("graphmine_tpu.obs.costmodel", "superstep_cost"),
    "sharded_superstep_cost": (
        "graphmine_tpu.obs.costmodel", "sharded_superstep_cost"
    ),
    "lof_cost": ("graphmine_tpu.obs.costmodel", "lof_cost"),
    "rooflines": ("graphmine_tpu.obs.costmodel", "rooflines"),
    # memory plane (ISSUE 14) — the HBM footprint twins of the cost rows
    "MemEstimate": ("graphmine_tpu.obs.memmodel", "MemEstimate"),
    "superstep_footprint": (
        "graphmine_tpu.obs.memmodel", "superstep_footprint"
    ),
    "sharded_superstep_footprint": (
        "graphmine_tpu.obs.memmodel", "sharded_superstep_footprint"
    ),
    "lof_footprint": ("graphmine_tpu.obs.memmodel", "lof_footprint"),
    "schedule_footprint": (
        "graphmine_tpu.obs.memmodel", "schedule_footprint"
    ),
    "crossover_thresholds": (
        "graphmine_tpu.ops.superstep_policy", "crossover_thresholds"
    ),
    "LofPlan": ("graphmine_tpu.pipeline.planner", "LofPlan"),
    "PlanError": ("graphmine_tpu.pipeline.planner", "PlanError"),
    "RunPlan": ("graphmine_tpu.pipeline.planner", "RunPlan"),
    "SuperstepPlan": ("graphmine_tpu.pipeline.planner", "SuperstepPlan"),
    "plan_lof": ("graphmine_tpu.pipeline.planner", "plan_lof"),
    "plan_run": ("graphmine_tpu.pipeline.planner", "plan_run"),
    "plan_superstep": ("graphmine_tpu.pipeline.planner", "plan_superstep"),
}

__all__ = ["__version__", *sorted(_EXPORTS)]


def __getattr__(name: str):
    """PEP 562 lazy export: import the defining module on first access
    and cache the attribute on the package, so the second access is a
    plain dict hit."""
    try:
        module_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    module = importlib.import_module(module_name)
    value = module if attr is None else getattr(module, attr)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
