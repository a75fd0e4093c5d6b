"""Connected components vs goldens (34 WCCs, giant 4,440 — BASELINE.md) and a
networkx union-find oracle on random graphs.
"""

import networkx as nx
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph, graph_from_edge_table
from graphmine_tpu.ops.cc import connected_components


def test_bundled_wcc_golden(bundled_edges, bundled_graph):
    labels = np.asarray(connected_components(bundled_graph))
    _, counts = np.unique(labels, return_counts=True)
    assert len(counts) == 34
    assert counts.max() == 4440


def test_cc_matches_networkx_oracle(rng):
    for trial in range(5):
        v = int(rng.integers(10, 200))
        e = int(rng.integers(5, 400))
        src = rng.integers(0, v, e)
        dst = rng.integers(0, v, e)
        g = build_graph(src, dst, num_vertices=v)
        labels = np.asarray(connected_components(g))
        nxg = nx.Graph()
        nxg.add_nodes_from(range(v))
        nxg.add_edges_from(zip(src.tolist(), dst.tolist()))
        for comp in nx.connected_components(nxg):
            comp = sorted(comp)
            assert len(set(labels[comp].tolist())) == 1
            assert labels[comp[0]] == comp[0]  # label = smallest member


def test_long_chain_converges():
    # Pointer jumping keeps iterations ~log(V) rather than V; correctness check.
    v = 500
    src = np.arange(v - 1)
    dst = np.arange(1, v)
    g = build_graph(src, dst, num_vertices=v)
    labels = np.asarray(connected_components(g))
    assert (labels == 0).all()


def test_bucketed_cc_matches_segment_path(rng):
    """r5: the bucketed-min CC superstep (cc_superstep_bucketed) is the
    min-reduce twin of the fused LPA kernel — labels must match the
    segment_min path BIT-FOR-BIT every superstep, across random graphs
    and a >2048-degree mega-hub (the histogram-path shape class), and
    the fixpoint runs must agree in labels AND iteration counts."""
    import jax.numpy as jnp

    from graphmine_tpu.ops.bucketed_mode import build_graph_and_plan
    from graphmine_tpu.ops.cc import cc_superstep, cc_superstep_bucketed

    def check(src, dst, v):
        g, plan = build_graph_and_plan(src, dst, num_vertices=v)
        labels = jnp.arange(v, dtype=jnp.int32)
        for _ in range(4):  # per-superstep equality, not just fixpoint
            want = cc_superstep(labels, g)
            got = cc_superstep_bucketed(labels, plan)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            labels = want
        want, it_w = connected_components(g, return_iterations=True)
        got, it_g = connected_components(g, return_iterations=True, plan=plan)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(it_g) == int(it_w)

    for v, e in ((97, 400), (500, 3000), (64, 80)):
        check(rng.integers(0, v, e).astype(np.int32),
              rng.integers(0, v, e).astype(np.int32), v)
    # mega-hub star + a disjoint path: hist path plus multiple components
    n = 2600
    src = np.concatenate([np.zeros(n, np.int32),
                          np.arange(n + 1, n + 4, dtype=np.int32)])
    dst = np.concatenate([np.arange(1, n + 1, dtype=np.int32),
                          np.arange(n + 2, n + 5, dtype=np.int32)])
    check(src, dst, n + 5)


def test_cc_auto_plan_policy(rng):
    """r5: plan="auto" reuses LPA's cached fused plan above the message
    threshold and must agree with the forced segment path; tiny graphs
    stay on segment_min (no plan build)."""
    from graphmine_tpu.ops import lpa as lpa_mod

    v, e = 300, 40_000  # 80K messages > the 1<<16 auto threshold
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    g = build_graph(src, dst, num_vertices=v)
    auto = np.asarray(connected_components(g))
    seg = np.asarray(connected_components(g, plan=None))
    np.testing.assert_array_equal(auto, seg)
    # the auto path populated the shared LPA plan cache for this graph
    assert any(
        ref() is g.msg_ptr for ref, _ in lpa_mod._auto_plan_cache.values()
    )


# -- the fixpoint record (PR 31): what each superstep moved -------------------


def _fixpoint_graph():
    rng = np.random.default_rng(31)
    v = 600  # a random part, a chain that takes a few passes, isolated vertices
    src = np.concatenate([rng.integers(0, 300, 900), np.arange(300, 499)])
    dst = np.concatenate([rng.integers(0, 300, 900), np.arange(301, 500)])
    return build_graph(src, dst, num_vertices=v), src, dst, v


def _plan_of(family, g):
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    return None if family == "sort" else BucketedModePlan.from_graph(g, with_send=True)


@pytest.mark.parametrize("family", ["sort", "bucketed"])
def test_fixpoint_record_counts_what_each_superstep_moved(family):
    from graphmine_tpu.obs.schema import validate_records
    from graphmine_tpu.pipeline.metrics import MetricsSink

    g, src, dst, v = _fixpoint_graph()
    sink = MetricsSink()
    labels, iters = connected_components(
        g, return_iterations=True, plan=_plan_of(family, g), sink=sink)
    (rec,) = [r for r in sink.records if r["phase"] == "fixpoint"]  # once a call
    assert validate_records(sink.records) == []
    assert rec["op"] == "cc_superstep" and rec["family"] == family
    assert rec["num_vertices"] == v and rec["supersteps"] == iters >= 3
    changed = rec["changed"]
    assert len(changed) == iters and changed[-1] == 0 and all(
        isinstance(c, int) and c > 0 for c in changed[:-1])
    # the first superstep by hand: min over own and incoming, then one jump
    first = np.arange(v)
    np.minimum.at(first, dst, np.arange(v)[src])
    np.minimum.at(first, src, np.arange(v)[dst])
    first = np.minimum(first, first[first])
    assert changed[0] == int((first != np.arange(v)).sum())
    assert sum(changed) >= int((np.asarray(labels) != np.arange(v)).sum())


@pytest.mark.parametrize("max_iter", [0, 2])
@pytest.mark.parametrize("family", ["sort", "bucketed"])
def test_a_sink_changes_neither_labels_nor_iterations(family, max_iter):
    from graphmine_tpu.pipeline.metrics import MetricsSink

    g, _, _, v = _fixpoint_graph()
    plan = _plan_of(family, g)
    want, it_w = connected_components(
        g, max_iter=max_iter, return_iterations=True, plan=plan)
    sink = MetricsSink()
    got, it_g = connected_components(
        g, max_iter=max_iter, return_iterations=True, plan=plan, sink=sink)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(it_g) == int(it_w) and (max_iter == 0 or it_g == max_iter)
    np.testing.assert_array_equal(  # and a call that asks for no count
        np.asarray(connected_components(g, max_iter=max_iter, plan=plan)),
        np.asarray(want))
    (rec,) = [r for r in sink.records if r["phase"] == "fixpoint"]
    assert len(rec["changed"]) == rec["supersteps"] == it_g
    if max_iter:  # cut short: the last pass still moved labels
        assert rec["changed"][-1] > 0
        assert (np.asarray(want) != np.asarray(connected_components(g, plan=plan))).any()


def test_changed_counts_past_the_carrys_slots_keep_the_last_slot(monkeypatch):
    """A run of more passes than the carry has slots keeps overwriting the
    last slot: the record is cut to the slots, labels and count are the
    fixpoint's all the same."""
    from graphmine_tpu.ops import cc
    from graphmine_tpu.pipeline.metrics import MetricsSink

    v = 301
    g = build_graph(np.arange(v - 1), np.arange(1, v), num_vertices=v)
    want, it_w = connected_components(g, return_iterations=True)
    assert 3 < int(it_w) < cc._CHANGED_SLOTS
    monkeypatch.setattr(cc, "_CHANGED_SLOTS", 3)
    cc._connected_components.clear_cache()  # the slots are read when it traces
    try:
        sink = MetricsSink()
        labels, iters = connected_components(g, return_iterations=True, sink=sink)
    finally:
        cc._connected_components.clear_cache()
    (rec,) = [r for r in sink.records if r["phase"] == "fixpoint"]
    assert iters == int(it_w) == rec["supersteps"]
    assert len(rec["changed"]) == 3 and rec["changed"][-1] == 0  # the confirming pass
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(want))
    assert not np.asarray(labels).any()
