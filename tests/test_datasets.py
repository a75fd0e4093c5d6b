"""R-MAT generator + scale ladder + anomaly-injection AUROC harness."""

import numpy as np
import pytest

from graphmine_tpu.datasets import (
    LADDER,
    inject_structural_anomalies,
    load,
    planted_anomaly_graph,
    rmat,
)


def test_rmat_shapes_and_ranges():
    src, dst = rmat(10, edge_factor=8, seed=3)
    v, e = 1 << 10, 8 << 10
    assert src.shape == dst.shape == (e,)
    assert src.dtype == dst.dtype == np.int32
    assert src.min() >= 0 and src.max() < v
    assert dst.min() >= 0 and dst.max() < v


def test_rmat_power_law_skew():
    # skewed quadrants must concentrate degree far beyond a uniform graph
    src, _ = rmat(12, edge_factor=16, seed=0)
    deg = np.bincount(src, minlength=1 << 12)
    uniform_max = 16 * 3  # ~Poisson(16) tail bound
    assert deg.max() > 4 * uniform_max
    # uniform quadrants ~ Erdos-Renyi: no such hub
    usrc, _ = rmat(12, edge_factor=16, a=0.25, b=0.25, c=0.25, seed=0)
    udeg = np.bincount(usrc, minlength=1 << 12)
    assert udeg.max() < deg.max() / 3


def test_rmat_determinism_and_dedup():
    a = rmat(8, 4, seed=7)
    b = rmat(8, 4, seed=7)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    ds, dd = rmat(8, 4, seed=7, dedup=True)
    pairs = set(zip(ds.tolist(), dd.tolist()))
    assert len(pairs) == len(ds) <= len(a[0])


def test_ladder_load_synthetic():
    et = load("ego-facebook", data_dir="/nonexistent", max_scale=10)
    assert et.num_edges > 0 and et.num_vertices <= 1 << 10
    with pytest.raises(KeyError):
        load("not-a-rung")
    assert set(LADDER) == {
        "ego-facebook", "com-amazon", "com-livejournal", "twitter-2010"
    }


def test_anomaly_injection_auroc_end_to_end():
    """The BASELINE.json second metric: LOF AUROC on injected outliers."""
    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.ops.features import standardize, vertex_features
    from graphmine_tpu.ops.lof import auroc, lof_scores
    from graphmine_tpu.ops.lpa import label_propagation

    src, dst = rmat(10, edge_factor=12, seed=1)
    v = 1 << 10
    src, dst, truth = inject_structural_anomalies(
        src, dst, v, num_anomalies=12, edges_per_anomaly=40, seed=2
    )
    g = build_graph(src, dst, num_vertices=v)
    labels = label_propagation(g, max_iter=5)
    feats = standardize(vertex_features(g, labels))
    scores = np.asarray(lof_scores(feats, k=15))
    assert auroc(scores, truth) > 0.8


def test_planted_anomaly_graph_contract():
    v, e = 4096, 120_000
    src, dst, mask, comm = planted_anomaly_graph(v, e, seed=7)
    assert src.dtype == dst.dtype == np.int32
    assert len(src) == len(dst) >= e  # anomaly edges appended
    assert src.min() >= 0 and src.max() < v
    assert dst.min() >= 0 and dst.max() < v
    assert mask.shape == (v,) and mask.dtype == bool and mask.sum() >= 32
    assert comm.shape == (v,) and comm.max() >= 7
    # deterministic in the seed
    src2, dst2, mask2, _ = planted_anomaly_graph(v, e, seed=7)
    np.testing.assert_array_equal(src, src2)
    np.testing.assert_array_equal(mask, mask2)


def test_planted_anomaly_graph_detects_end_to_end():
    """The e2e dataset's reason to exist (VERDICT r5 weak 1): every timed
    detection chapter produces NONZERO output on it — a long-tailed LPA
    census, populated recursive deciles with flagged vertices, and LOF
    separating the injected anomalies — at CI scale.
    """
    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.ops.lof import auroc, lof_scores
    from graphmine_tpu.ops.features import standardize, vertex_features
    from graphmine_tpu.ops.lpa import label_propagation, num_communities
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers

    v, e = 4096, 200_000
    src, dst, truth, _ = planted_anomaly_graph(v, e, seed=9)
    g = build_graph(src, dst, num_vertices=v)
    labels = label_propagation(g, max_iter=5)
    assert int(num_communities(labels)) > 100  # long-tailed, not 3 giants
    rep = recursive_lpa_outliers(g, labels)
    assert int(rep.outlier_vertices.sum()) > 0
    assert len(rep.thresholds) >= 10  # >= 10 parents with populated deciles
    feats = standardize(vertex_features(g, labels))
    lof = np.asarray(lof_scores(feats, k=128))
    assert int((lof > 1.5).sum()) > 0
    assert auroc(lof, truth) > 0.9
