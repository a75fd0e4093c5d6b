from graphmine_tpu.ops.segment import segment_mode
from graphmine_tpu.ops.aggregate import aggregate_messages, pregel
from graphmine_tpu.ops.lpa import label_propagation, lpa_superstep
from graphmine_tpu.ops.cc import connected_components
from graphmine_tpu.ops.scc import strongly_connected_components
from graphmine_tpu.ops.paths import bfs, bfs_parents
from graphmine_tpu.ops.motifs import find, parse_pattern
from graphmine_tpu.ops.streaming_lof import StreamingLOF, fit_lof, score_lof
from graphmine_tpu.ops.louvain import leiden, louvain
from graphmine_tpu.ops.modularity import modularity
from graphmine_tpu.ops.bucketed_mode import BucketedModePlan, bucketed_mode, lpa_superstep_bucketed
from graphmine_tpu.ops.superstep_policy import select_superstep_family
from graphmine_tpu.ops.pagerank import pagerank, parallel_personalized_pagerank
from graphmine_tpu.ops.svdpp import SVDPlusPlusModel, svd_plus_plus, svdpp_predict
from graphmine_tpu.ops.degrees import degrees, in_degrees, out_degrees
from graphmine_tpu.ops.paths import bfs_distances, shortest_paths, weighted_shortest_paths
from graphmine_tpu.ops.cluster_metrics import adjusted_rand_index, normalized_mutual_info
from graphmine_tpu.ops.triangles import triangle_count, clustering_coefficient
from graphmine_tpu.ops.kcore import core_numbers
from graphmine_tpu.ops.mis import greedy_color, maximal_independent_set
from graphmine_tpu.ops.linkpred import link_prediction
from graphmine_tpu.ops.ktruss import k_truss
from graphmine_tpu.ops.embedding import spectral_embedding
from graphmine_tpu.ops.stats import degree_assortativity, density, diameter, reciprocity
from graphmine_tpu.ops.centrality import (
    betweenness_centrality,
    closeness_centrality,
    eigenvector_centrality,
    hits,
    katz_centrality,
)

__all__ = ["degree_assortativity", "density", "diameter", "reciprocity", "spectral_embedding", "k_truss", "link_prediction", "maximal_independent_set", "greedy_color", "hits", "closeness_centrality", "betweenness_centrality",
           "eigenvector_centrality", "katz_centrality",
           "weighted_shortest_paths",
           "adjusted_rand_index", "normalized_mutual_info","segment_mode", "BucketedModePlan", "bucketed_mode", "lpa_superstep_bucketed",
           "select_superstep_family", "aggregate_messages", "pregel", "find", "parse_pattern", "StreamingLOF", "fit_lof", "score_lof", "label_propagation", "lpa_superstep", "connected_components", "strongly_connected_components", "louvain", "leiden", "modularity", "pagerank", "parallel_personalized_pagerank", "svd_plus_plus", "svdpp_predict", "SVDPlusPlusModel", "degrees", "in_degrees", "out_degrees", "bfs", "bfs_parents", "bfs_distances", "shortest_paths", "triangle_count", "clustering_coefficient", "core_numbers"]
