"""Census, recursive-LPA outliers (parity path) and kNN/LOF (north-star path)."""

import numpy as np
import jax.numpy as jnp
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.census import census_table, community_sizes, intra_community_edge_mask
from graphmine_tpu.ops.knn import knn
from graphmine_tpu.ops.lof import auroc, lof_scores
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.ops.outliers import masked_label_propagation, recursive_lpa_outliers


def test_community_sizes_and_census(bundled_graph):
    labels = label_propagation(bundled_graph, max_iter=5)
    present, sizes, edges = census_table(labels, bundled_graph)
    assert sizes.sum() == bundled_graph.num_vertices
    assert len(present) == len(np.unique(np.asarray(labels)))
    # BASELINE.md: top community sizes around 288, 240, 220 (tie-break dependent)
    assert 150 <= sizes.max() <= 600


def test_intra_mask_matches_numpy(rng):
    src = rng.integers(0, 30, 100)
    dst = rng.integers(0, 30, 100)
    g = build_graph(src, dst, num_vertices=30)
    labels = label_propagation(g, max_iter=3)
    mask = np.asarray(intra_community_edge_mask(labels, g))
    l = np.asarray(labels)
    np.testing.assert_array_equal(mask, l[src] == l[dst])


def _fused_plan(g):
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    return BucketedModePlan.from_graph(g, with_send=True)


_FAMILIES = pytest.mark.parametrize(
    "plan_of", [lambda g: None, _fused_plan], ids=["sort", "bucketed"]
)


@_FAMILIES
def test_masked_lpa_stays_within_communities(rng, plan_of):
    src = rng.integers(0, 60, 300)
    dst = rng.integers(0, 60, 300)
    g = build_graph(src, dst, num_vertices=60)
    comm = label_propagation(g, max_iter=3)
    sub = np.asarray(
        masked_label_propagation(g, comm, max_iter=5, plan=plan_of(g))
    )
    comm_np = np.asarray(comm)
    # every sub-community is contained in exactly one parent community
    for s in np.unique(sub):
        members = np.flatnonzero(sub == s)
        assert len(np.unique(comm_np[members])) == 1


@_FAMILIES
def test_masked_lpa_equals_per_community_lpa(plan_of):
    # Two disjoint triangles: masking with the 2-community partition must give
    # the same result as running LPA on each triangle separately.
    src = np.array([0, 1, 2, 3, 4, 5])
    dst = np.array([1, 2, 0, 4, 5, 3])
    g = build_graph(src, dst)
    comm = jnp.array([0, 0, 0, 1, 1, 1], jnp.int32)
    sub = np.asarray(
        masked_label_propagation(g, comm, max_iter=4, plan=plan_of(g))
    )
    ga = build_graph([0, 1, 2], [1, 2, 0])
    sub_a = np.asarray(label_propagation(ga, max_iter=4))
    assert (sub[:3] == sub_a).all()


def _both_families(g, comm, max_iter=5, plan=None):
    """The two families' reports on one graph, equal field for field; the
    plan family's is handed back."""
    ref = recursive_lpa_outliers(g, comm, max_iter=max_iter)
    got = recursive_lpa_outliers(
        g, comm, max_iter=max_iter, plan=plan or _fused_plan(g))
    assert got.sub_labels.tobytes() == ref.sub_labels.tobytes()
    np.testing.assert_array_equal(got.outlier_vertices, ref.outlier_vertices)
    np.testing.assert_array_equal(got.sub_sizes, ref.sub_sizes)
    np.testing.assert_array_equal(got.sub_parents, ref.sub_parents)
    assert got.thresholds == ref.thresholds
    return got


@pytest.mark.parametrize("masked", ["all", "all_but_one"])
@pytest.mark.parametrize("degree", [1, 2, 7, 42])  # the four row forms
def test_masked_lpa_plan_row_with_no_surviving_message_keeps_its_label(
    rng, degree, masked,
):
    """Vertex 0 sits in a community of its own with ``degree`` neighbours
    (width 1 copies the slot, width 2 is ``min``, 7 counts pairwise, 42
    sorts), all in the other community or all but one: plain LPA never
    reduces a row of sentinels, the masked pass does."""
    v = 120
    src = rng.integers(1, v, 500)
    dst = rng.integers(1, v, 500)
    own = np.arange(1, degree + 1)
    g = build_graph(
        np.concatenate([src, np.zeros(degree, np.int64)]),
        np.concatenate([dst, own]), num_vertices=v,
    )
    comm = np.ones(v, np.int32)
    comm[0] = 0
    if masked == "all_but_one":
        comm[degree] = 0
    plan = _fused_plan(g)
    (row_class,) = [i for i, ids in enumerate(plan.vertex_ids) if 0 in np.asarray(ids)]
    assert plan.send_idx[row_class].shape[1] == degree
    report = _both_families(g, jnp.asarray(comm))
    if masked == "all":
        assert report.sub_labels[0] == 0


@pytest.mark.parametrize("hub_community", ["alone", "half", "everyone"])
def test_masked_lpa_plan_hub_histogram_respects_the_mask(rng, hub_community):
    """A hub above the histogram threshold whose neighbours are all in
    other communities (an empty histogram row: it keeps its label), half
    in its own, or all in its own (nothing masked)."""
    from graphmine_tpu.ops.bucketed_mode import _HIST_MIN_DEG

    v = _HIST_MIN_DEG + 600
    spokes = np.arange(1, _HIST_MIN_DEG + 200)
    src = np.concatenate([np.zeros(len(spokes), np.int64), rng.integers(1, v, 6000)])
    dst = np.concatenate([spokes, rng.integers(1, v, 6000)])
    g = build_graph(src, dst, num_vertices=v)
    plan = _fused_plan(g)
    assert np.asarray(plan.hist_vertex_ids).tolist() == [0]
    comm = (np.arange(v) % 3 + 1).astype(np.int32)
    if hub_community == "alone":
        comm[0] = 0
    elif hub_community == "everyone":
        comm[:] = 1
    report = _both_families(g, jnp.asarray(comm))
    if hub_community == "alone":
        assert report.sub_labels[0] == 0


def test_masked_lpa_plan_ignores_the_plan_s_weights(rng):
    """The plan of a weighted graph carries ``weight_mat``; the recursive
    pass is a count on both families."""
    src = rng.integers(0, 200, 1200).astype(np.int32)
    dst = rng.integers(0, 200, 1200).astype(np.int32)
    w = (rng.integers(1, 16, 1200) / 4.0).astype(np.float32)
    g = build_graph(src, dst, num_vertices=200, edge_weights=w)
    assert _fused_plan(g).weight_mat is not None
    comm = label_propagation(g, max_iter=3)
    got = _both_families(g, comm, max_iter=4)
    plain = build_graph(src, dst, num_vertices=200)
    want = masked_label_propagation(plain, comm, max_iter=4)
    assert got.sub_labels.tobytes() == np.asarray(want).tobytes()


def test_masked_lpa_plan_on_a_graph_with_communities():
    """Twelve planted blocks with 15 % of the edges across them, the LPA
    chapter's own labels as the mask: the shape of the pipeline's call."""
    from graphmine_tpu.ops.bucketed_mode import build_graph_and_plan

    rng = np.random.default_rng(11)
    v, e, block = 1536, 24000, 128
    src = rng.integers(0, v, e)
    near = (src // block) * block + rng.integers(0, block, e)
    dst = np.where(rng.random(e) < 0.85, near, rng.integers(0, v, e))
    g, plan = build_graph_and_plan(src, dst, num_vertices=v)
    comm = label_propagation(g, max_iter=5, plan=plan)
    assert len(_both_families(g, comm, plan=plan).sub_sizes) < v


def test_masked_lpa_refuses_a_plan_of_another_graph_or_without_senders(rng):
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    g = build_graph(rng.integers(0, 30, 90), rng.integers(0, 30, 90), num_vertices=30)
    other = build_graph(rng.integers(0, 30, 80), rng.integers(0, 30, 80), num_vertices=30)
    comm = jnp.zeros(30, jnp.int32)
    with pytest.raises(ValueError, match="plan/graph mismatch"):
        masked_label_propagation(g, comm, plan=_fused_plan(other))
    with pytest.raises(ValueError, match="fused plan"):
        masked_label_propagation(g, comm, plan=BucketedModePlan.from_graph(g))


def test_recursive_outliers_bundled(bundled_graph):
    comm = label_propagation(bundled_graph, max_iter=5)
    report = recursive_lpa_outliers(bundled_graph, comm)
    assert report.sub_sizes.sum() == bundled_graph.num_vertices
    # outlier sub-communities must be small ones
    if report.outlier_vertices.any():
        flagged = np.unique(report.sub_labels[report.outlier_vertices])
        sub_index = {s: i for i, s in enumerate(np.unique(report.sub_labels))}
        for s in flagged:
            parent = report.sub_parents[sub_index[s]]
            thr = report.thresholds[int(parent)]
            assert report.sub_sizes[sub_index[s]] <= thr


def test_recursive_outliers_sharded_matches_masked(bundled_graph):
    """The scale-out composition (host intra-community edge filter →
    distributed LPA → shared decile) reproduces the single-device masked
    pass bit-for-bit on both distributed schedules (VERDICT r3 item 2)."""
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers_sharded
    from graphmine_tpu.parallel.mesh import make_mesh

    comm = label_propagation(bundled_graph, max_iter=5)
    ref = recursive_lpa_outliers(bundled_graph, comm)
    mesh = make_mesh(8)
    for schedule in ("replicated", "ring"):
        got = recursive_lpa_outliers_sharded(
            bundled_graph, comm, mesh, schedule=schedule
        )
        np.testing.assert_array_equal(ref.sub_labels, got.sub_labels)
        np.testing.assert_array_equal(ref.outlier_vertices, got.outlier_vertices)
        np.testing.assert_array_equal(ref.sub_sizes, got.sub_sizes)
        np.testing.assert_array_equal(ref.sub_parents, got.sub_parents)
        assert ref.thresholds == got.thresholds


def test_recursive_outliers_sharded_ignores_weights_like_masked(rng):
    """The recursive pass is unweighted by definition (parity with
    masked_label_propagation, whose mode is a count) — on a WEIGHTED
    graph the sharded composition must still match the masked pass
    bit-for-bit, i.e. neither may let msg_weight leak into the
    sub-community LPA."""
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers_sharded
    from graphmine_tpu.parallel.mesh import make_mesh

    src = rng.integers(0, 200, 1200).astype(np.int32)
    dst = rng.integers(0, 200, 1200).astype(np.int32)
    w = (rng.integers(1, 16, 1200) / 4.0).astype(np.float32)
    g = build_graph(src, dst, num_vertices=200, edge_weights=w)
    comm = label_propagation(g, max_iter=3)
    ref = recursive_lpa_outliers(g, comm, max_iter=4)
    got = recursive_lpa_outliers_sharded(
        g, comm, make_mesh(8), max_iter=4, schedule="ring"
    )
    np.testing.assert_array_equal(ref.sub_labels, got.sub_labels)
    np.testing.assert_array_equal(ref.outlier_vertices, got.outlier_vertices)
    assert ref.thresholds == got.thresholds


def test_recursive_outliers_sharded_all_cross_community():
    """Degenerate mask: every edge crosses communities, so the filtered
    graph is empty and every vertex is its own sub-community — on the
    distributed path too (empty-message partition)."""
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers_sharded
    from graphmine_tpu.parallel.mesh import make_mesh

    # bipartite edges, communities = the two sides
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([4, 5, 6, 7], np.int32)
    g = build_graph(src, dst, num_vertices=8)
    comm = jnp.array([0, 0, 0, 0, 1, 1, 1, 1], jnp.int32)
    ref = recursive_lpa_outliers(g, comm)
    got = recursive_lpa_outliers_sharded(g, comm, make_mesh(8))
    np.testing.assert_array_equal(ref.sub_labels, got.sub_labels)
    np.testing.assert_array_equal(got.sub_labels, np.arange(8, dtype=np.int32))
    assert not got.outlier_vertices.any()


def test_knn_matches_sklearn(rng):
    from sklearn.neighbors import NearestNeighbors

    x = rng.normal(size=(300, 5)).astype(np.float32)
    d, i = knn(jnp.asarray(x), k=7, row_tile=64)
    sk = NearestNeighbors(n_neighbors=7).fit(x)
    sk_d, sk_i = sk.kneighbors(None)  # None: exclude each point itself
    np.testing.assert_allclose(np.sqrt(np.asarray(d)), sk_d, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i), sk_i)


def test_lof_matches_sklearn(rng):
    from sklearn.neighbors import LocalOutlierFactor

    x = rng.normal(size=(400, 4)).astype(np.float32)
    x[:10] += 6.0  # inject a clear outlier cluster
    ours = np.asarray(lof_scores(jnp.asarray(x), k=15, row_tile=128))
    sk = LocalOutlierFactor(n_neighbors=15)
    sk.fit(x)
    theirs = -sk.negative_outlier_factor_
    np.testing.assert_allclose(ours, theirs, rtol=1e-3, atol=1e-3)


def test_lof_auroc_on_injected_anomalies(rng):
    x = rng.normal(size=(500, 5)).astype(np.float32)
    y = np.zeros(500, dtype=bool)
    y[:25] = True
    x[:25] += rng.normal(scale=5.0, size=(25, 5))
    scores = np.asarray(lof_scores(jnp.asarray(x), k=20, row_tile=128))
    assert auroc(scores, y) > 0.95


def test_auroc_sanity():
    assert auroc([0.1, 0.2, 0.9, 0.8], [False, False, True, True]) == 1.0
    assert auroc([0.9, 0.8, 0.1, 0.2], [False, False, True, True]) == 0.0
