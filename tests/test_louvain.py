"""Louvain + modularity tests: hand-checked fixtures, a networkx oracle,
determinism, and partition-quality comparison against LPA (SURVEY §7.7)."""

import functools

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.louvain import louvain
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.ops.modularity import modularity


def _two_cliques_bridge():
    """Two K4s joined by one edge. Optimal partition = the cliques,
    Q = 2 * (12/26 - (13/26)^2) = 0.42307..."""
    edges = []
    for base in (0, 4):
        for i in range(4):
            for j in range(i + 1, 4):
                edges.append((base + i, base + j))
    edges.append((0, 4))
    src, dst = np.array(edges, np.int32).T
    return build_graph(src, dst, num_vertices=8)


def test_modularity_two_cliques():
    g = _two_cliques_bridge()
    labels = np.array([0, 0, 0, 0, 1, 1, 1, 1], np.int32)
    q = float(modularity(labels, g))
    assert abs(q - (24 / 26 - 0.5)) < 1e-6
    # all-singletons partition has known Q too: -sum((k_i/2m)^2)
    singles = np.arange(8, dtype=np.int32)
    deg = np.asarray(g.degrees())
    want = -np.sum((deg / 26) ** 2)
    assert abs(float(modularity(singles, g)) - want) < 1e-6


def test_modularity_matches_networkx(rng):
    nx = pytest.importorskip("networkx")
    gnx = nx.gnm_random_graph(60, 180, seed=3)
    edges = np.array(gnx.edges(), np.int32)
    g = build_graph(edges[:, 0], edges[:, 1], num_vertices=60)
    labels = rng.integers(0, 5, 60).astype(np.int32)
    comms = [set(np.flatnonzero(labels == c)) for c in range(5)]
    comms = [c for c in comms if c]
    want = nx.algorithms.community.modularity(gnx, comms)
    assert abs(float(modularity(labels, g)) - want) < 1e-5


def test_louvain_two_cliques():
    g = _two_cliques_bridge()
    labels, q = louvain(g)
    labels = np.asarray(labels)
    assert len(set(labels[:4])) == 1 and len(set(labels[4:])) == 1
    assert labels[0] != labels[4]
    assert abs(q - (24 / 26 - 0.5)) < 1e-6


def test_louvain_ring_of_cliques():
    """8 K5s in a ring: every clique must land inside one community and
    Q must be near the known optimum (~0.72 for merged-pair solutions,
    ~0.7578 for the clique partition)."""
    edges = []
    s, r = 5, 8
    for c in range(r):
        base = c * s
        for i in range(s):
            for j in range(i + 1, s):
                edges.append((base + i, base + j))
        edges.append((base, ((c + 1) % r) * s))
    src, dst = np.array(edges, np.int32).T
    g = build_graph(src, dst, num_vertices=s * r)
    labels, q = louvain(g)
    labels = np.asarray(labels)
    for c in range(r):
        assert len(set(labels[c * s:(c + 1) * s])) == 1, f"clique {c} split"
    assert q > 0.70


def test_louvain_beats_lpa_on_bundled(bundled_graph):
    lpa_q = float(modularity(label_propagation(bundled_graph, max_iter=5), bundled_graph))
    _, louvain_q = louvain(bundled_graph)
    assert louvain_q > lpa_q
    assert louvain_q > 0.3  # real community structure in the web graph


def test_louvain_same_parity_singletons_merge():
    """Regression: two adjacent same-parity singletons must merge, not swap
    labels forever (the synchronous-move swap cycle; broken by the
    singleton-ordering rule)."""
    g = build_graph([0], [2], num_vertices=3)
    labels, q = louvain(g)
    labels = np.asarray(labels)
    assert labels[0] == labels[2]
    assert abs(q - 0.0) < 1e-6  # one edge, one community: Q = 1/2m*2m... = 0

    # an even-id-only path: 0-2-4-6; all moves are even->even
    g2 = build_graph([0, 2, 4], [2, 4, 6], num_vertices=7)
    l2, q2 = louvain(g2)
    l2 = np.asarray(l2)
    assert len({l2[0], l2[2], l2[4], l2[6]}) <= 2  # path communities merge
    assert q2 > 0.0


def test_louvain_deterministic():
    g = _two_cliques_bridge()
    l1, q1 = louvain(g)
    l2, q2 = louvain(g)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert q1 == q2


def test_leiden_dominates_louvain_and_splits_disconnected():
    """Leiden's refinement: modularity within a fraction of a percent of
    Louvain's, and communities Louvain leaves internally disconnected are
    split (the R-MAT cases produce ~10 such communities under Louvain —
    the connectivity property is the hard guarantee here)."""
    import networkx as nx

    from graphmine_tpu.datasets import rmat, sbm
    from graphmine_tpu.ops.louvain import leiden, louvain

    def disconnected_count(labels, src, dst, v):
        G = nx.Graph()
        G.add_nodes_from(range(v))
        G.add_edges_from((int(a), int(b)) for a, b in zip(src, dst) if a != b)
        labels = np.asarray(labels)
        bad = 0
        for lab in np.unique(labels):
            mem = np.flatnonzero(labels == lab)
            if len(mem) > 1 and not nx.is_connected(G.subgraph(mem.tolist())):
                bad += 1
        return bad

    cases = []
    s, d, blocks = sbm([150] * 4, 0.06, 0.004, seed=2)
    cases.append((s, d, len(blocks)))
    for seed in (3, 7):
        s, d = rmat(10, 8, seed=seed)
        cases.append((s, d, 1 << 10))

    for src, dst, v in cases:
        g = build_graph(src, dst, num_vertices=v)
        _, ql = louvain(g)
        labels, qe = leiden(g)
        assert qe >= ql - 0.005  # comparable modularity
        assert disconnected_count(labels, src, dst, v) == 0


def test_leiden_recovers_planted_blocks():
    from graphmine_tpu.datasets import sbm
    from graphmine_tpu.ops.cluster_metrics import adjusted_rand_index
    from graphmine_tpu.ops.louvain import leiden

    src, dst, blocks = sbm([120] * 5, 0.08, 0.003, seed=9)
    g = build_graph(src, dst, num_vertices=len(blocks))
    labels, q = leiden(g)
    assert adjusted_rand_index(np.asarray(labels), blocks) > 0.95
    assert q > 0.5


# The SBM at the detectability margin: 50 blocks of 400, in-block degree
# ~11 against out-block degree ~16, right above the recovery threshold
# (p_in 0.026 collapses to ARI 0.54, 0.03 saturates at 0.98). The planted
# blocks above have a 20x ratio every method recovers; here the best of
# the three methods sits mid-band and can move either way.
_MARGIN_SIZES, _MARGIN_P_IN, _MARGIN_P_OUT = [400] * 50, 0.028, 0.0008
_MARGIN_SEEDS = (3, 4, 5)


@functools.cache
def _margin_sbm_best_ari(seed: int) -> float:
    from graphmine_tpu.datasets import sbm
    from graphmine_tpu.ops.cluster_metrics import adjusted_rand_index
    from graphmine_tpu.ops.louvain import leiden

    src, dst, truth = sbm(_MARGIN_SIZES, _MARGIN_P_IN, _MARGIN_P_OUT, seed=seed)
    g = build_graph(src, dst, num_vertices=len(truth))
    return max(
        float(adjusted_rand_index(np.asarray(labels), truth))
        for labels in (
            label_propagation(g, max_iter=5), louvain(g)[0], leiden(g)[0]
        )
    )


@pytest.mark.parametrize("seed", _MARGIN_SEEDS)
def test_margin_sbm_ari_band(seed):
    # measured 0.81-0.94 over seeds 3, 4, 5, 11 on the CPU
    assert 0.7 < _margin_sbm_best_ari(seed) < 0.97


def test_margin_sbm_ari_spread():
    values = [_margin_sbm_best_ari(seed) for seed in _MARGIN_SEEDS]
    assert max(values) - min(values) < 0.15, values
