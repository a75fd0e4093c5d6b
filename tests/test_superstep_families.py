"""The two superstep families, ``bucketed`` held to its reference ``sort``.

A five-graph zoo (power-law hubs, a ring, self-loops, isolated vertices,
duplicate edges) runs through every path that stays: the fused bucketed
LPA and CC (weighted, histogram hubs, weight-blind CC over a weighted
plan), the PageRank inflow against a NumPy power iteration, the mesh
paths (bucket rows + ``all_gather``, the sort shard body, the public
``mesh=`` entry, sharded CC) and the ring schedule, each bit-equal to the
``sort`` family on one device unless said. Then the policy module
(``ops/superstep_policy.py``) and its seams: planner, memory model, the
provenance records, the driver's degrade rung, and the refusal of the
families and keywords that PR 29 deleted.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import graphmine_tpu as gm
from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.bucketed_mode import (
    BucketedModePlan,
    lpa_superstep_bucketed,
)
from graphmine_tpu.ops.cc import (
    cc_superstep,
    cc_superstep_bucketed,
    connected_components,
)
from graphmine_tpu.ops.lpa import label_propagation, lpa_superstep
from graphmine_tpu.ops.pagerank import pagerank
from graphmine_tpu.ops.superstep_policy import (
    BUCKETED_MIN_MESSAGES,
    FAMILIES,
    crossover_thresholds,
    select_superstep_family,
)
from graphmine_tpu.parallel.sharded import (
    partition_graph,
    shard_graph_arrays,
    sharded_connected_components,
    sharded_label_propagation,
)


def _power_law(rng):
    v, e = 600, 4000
    raw = rng.pareto(1.2, size=2 * e)
    ids = np.minimum((raw * v / 50).astype(np.int64), v - 1).astype(np.int32)
    return ids[:e], ids[e:], v


def _ring(rng):
    v = 257
    src = np.arange(v, dtype=np.int32)
    return src, np.roll(src, -1).astype(np.int32), v


def _self_loops(rng):
    v, e = 300, 1500
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    dst[::7] = src[::7]
    return src, dst, v


def _isolated(rng):
    # vertices [200, 300) never appear in any edge
    v, e = 300, 1200
    src = rng.integers(0, 200, e).astype(np.int32)
    dst = rng.integers(0, 200, e).astype(np.int32)
    return src, dst, v


def _dup_edges(rng):
    v, e = 250, 900
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    # duplicate one hot edge many times (multiplicity must count)
    src[: e // 3] = src[0]
    dst[: e // 3] = dst[0]
    return src, dst, v


GRAPHS = {
    "power_law": _power_law,
    "ring": _ring,
    "self_loops": _self_loops,
    "isolated": _isolated,
    "dup_edges": _dup_edges,
}


@pytest.fixture(params=sorted(GRAPHS), ids=sorted(GRAPHS))
def edges(request):
    return GRAPHS[request.param](np.random.default_rng(3))


def _weights(src):
    return np.random.default_rng(6).random(len(src)).astype(np.float32)


def _sort_lpa(src, dst, v, weights=None, iters=5):
    """The reference: the sort family on one device."""
    g = build_graph(src, dst, num_vertices=v, edge_weights=weights)
    return np.asarray(label_propagation(g, iters, plan=None))


def _sort_cc(src, dst, v):
    return np.asarray(
        connected_components(build_graph(src, dst, num_vertices=v), plan=None)
    )


# ---- fused: bucketed against sort ------------------------------------------


def test_bucketed_lpa_bit_identical(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)


def test_bucketed_lpa_per_superstep(edges):
    """Step-for-step identity against the sort superstep, not just the
    final labels (catches off-by-one-superstep compensation)."""
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    lbl = jnp.arange(v, dtype=jnp.int32)
    for _ in range(4):
        ref = lpa_superstep(lbl, g)
        got = lpa_superstep_bucketed(lbl, g, plan)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        lbl = ref


def test_bucketed_cc_bit_identical(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    got = np.asarray(connected_components(g, plan=plan))
    np.testing.assert_array_equal(_sort_cc(src, dst, v), got)


def test_cc_superstep_bucketed_matches_oracle_step(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    lbl = jnp.arange(v, dtype=jnp.int32)
    for _ in range(3):
        ref = cc_superstep(lbl, g)
        got = cc_superstep_bucketed(lbl, plan)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        lbl = ref


def _numpy_pagerank(src, dst, v, alpha=0.85, iters=300):
    """Plain power iteration in float64: each vertex splits its rank over
    its out-edges (multiplicity counts), dangling mass goes to the uniform
    teleport vector."""
    out = np.bincount(src, minlength=v).astype(np.float64)
    inv = np.divide(1.0, out, out=np.zeros(v), where=out > 0)
    pr = np.full(v, 1.0 / v)
    for _ in range(iters):
        inflow = np.bincount(dst, weights=(pr * inv)[src], minlength=v)
        new = alpha * (inflow + pr[out == 0].sum() / v) + (1.0 - alpha) / v
        if np.abs(new - pr).sum() < 1e-12:
            return new
        pr = new
    return pr


def test_pagerank_auto_matches_numpy_power_iteration(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v, symmetric=False)
    got = np.asarray(pagerank(g, tol=1e-9, max_iter=300))  # plan="auto"
    # float32 sums against float64: tolerance, not bits
    np.testing.assert_allclose(
        got, _numpy_pagerank(src, dst, v), rtol=2e-4, atol=1e-7
    )
    assert abs(float(got.sum()) - 1.0) < 1e-4
    np.testing.assert_array_equal(
        got, np.asarray(pagerank(g, tol=1e-9, max_iter=300, plan=None))
    )


def test_weighted_bucketed_lpa_bit_identical(edges):
    src, dst, v = edges
    w = _weights(src)
    g = build_graph(src, dst, num_vertices=v, edge_weights=w)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    assert plan.weight_mat is not None
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v, weights=w), got)


def test_bucketed_lpa_histogram_hubs_bit_identical(edges, monkeypatch):
    """The mega-hub histogram path, its threshold lowered so every graph
    of the zoo sends its busiest vertices through it."""
    bm = importlib.import_module("graphmine_tpu.ops.bucketed_mode")
    monkeypatch.setattr(bm, "_HIST_MIN_DEG", 1)  # the ring's degree is 2
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    assert plan.hist_vertex_ids is not None and plan.hist_vertex_ids.size
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)
    np.testing.assert_array_equal(
        _sort_cc(src, dst, v), np.asarray(connected_components(g, plan=plan))
    )


def test_cc_ignores_the_weights_of_a_weighted_plan(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v, edge_weights=_weights(src))
    plan = BucketedModePlan.from_graph(g, with_send=True)
    assert plan.weight_mat is not None
    got = np.asarray(connected_components(g, plan=plan))
    np.testing.assert_array_equal(_sort_cc(src, dst, v), got)


# ---- the mesh: eight virtual devices against one ---------------------------


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return gm.make_mesh(8)


def _placed(g, mesh, **flags):
    return shard_graph_arrays(partition_graph(g, mesh=mesh, **flags), mesh)


def test_mesh_bucket_rows_lpa_bit_identical(edges, mesh):
    src, dst, v = edges
    sg = _placed(
        build_graph(src, dst, num_vertices=v), mesh, build_bucket_plan=True
    )
    assert sg.bucket_send and not sg.bucket_weight
    got = np.asarray(sharded_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)


def test_mesh_sort_body_lpa_bit_identical(edges, mesh):
    """The shard body the serve repair partitions for (no plan)."""
    src, dst, v = edges
    sg = _placed(build_graph(src, dst, num_vertices=v), mesh)
    assert not sg.bucket_send
    got = np.asarray(sharded_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)


def test_mesh_weighted_lpa_bit_identical(edges, mesh):
    src, dst, v = edges
    w = _weights(src)
    g = build_graph(src, dst, num_vertices=v, edge_weights=w)
    ref = _sort_lpa(src, dst, v, weights=w)
    rows = _placed(g, mesh, build_bucket_plan=True)
    assert rows.bucket_weight  # the bucket rows carry the weights
    np.testing.assert_array_equal(
        ref, np.asarray(sharded_label_propagation(rows, mesh, max_iter=5))
    )
    plain = _placed(g, mesh)
    assert plain.msg_weight is not None  # and so does the sort body
    np.testing.assert_array_equal(
        ref, np.asarray(sharded_label_propagation(plain, mesh, max_iter=5))
    )


def test_mesh_cc_bit_identical(edges, mesh):
    src, dst, v = edges
    sg = _placed(build_graph(src, dst, num_vertices=v), mesh)
    got = np.asarray(sharded_connected_components(sg, mesh))
    np.testing.assert_array_equal(_sort_cc(src, dst, v), got)


def test_mesh_entry_bit_identical(edges, mesh):
    src, dst, v = edges
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    got = np.asarray(gm.label_propagation(host, max_iter=5, mesh=mesh))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)


def test_ring_lpa_bit_identical(edges, mesh):
    from graphmine_tpu.parallel.ring import ring_label_propagation

    src, dst, v = edges
    sg = _placed(build_graph(src, dst, num_vertices=v), mesh)
    got = np.asarray(ring_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got)


def test_ring_weighted_lpa_bit_identical(edges, mesh):
    from graphmine_tpu.parallel.ring import ring_label_propagation

    src, dst, v = edges
    w = _weights(src)
    sg = _placed(build_graph(src, dst, num_vertices=v, edge_weights=w), mesh)
    got = np.asarray(ring_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v, weights=w), got)


def test_ring_cc_bit_identical(edges, mesh):
    from graphmine_tpu.parallel.ring import ring_connected_components

    src, dst, v = edges
    sg = _placed(build_graph(src, dst, num_vertices=v), mesh)
    got = np.asarray(ring_connected_components(sg, mesh))
    np.testing.assert_array_equal(_sort_cc(src, dst, v), got)


# ---- the policy module and its seams ---------------------------------------


def test_family_policy_thresholds():
    assert FAMILIES == ("bucketed", "sort")
    fam, reason = select_superstep_family(10, 100)
    assert fam == "sort" and "65536" in reason
    fam, _ = select_superstep_family(1000, BUCKETED_MIN_MESSAGES - 1)
    assert fam == "sort"
    fam, _ = select_superstep_family(1000, BUCKETED_MIN_MESSAGES)
    assert fam == "bucketed"
    assert crossover_thresholds() == {
        "bucketed_min_messages": BUCKETED_MIN_MESSAGES
    }


@pytest.mark.parametrize(
    "v,m",
    [
        (1 << 22, 1 << 27),   # the benchmark's cdlp-g500-22
        (1 << 24, 1 << 28),
        (1 << 21, 1 << 22),   # where a third family once took over
    ],
)
def test_auto_is_bucketed_at_every_large_size(v, m):
    from graphmine_tpu.pipeline.planner import plan_superstep

    fam, reason = select_superstep_family(v, m)
    assert fam == "bucketed" and str(BUCKETED_MIN_MESSAGES) in reason
    assert plan_superstep(v, m).family == "bucketed"


@pytest.mark.parametrize("gone", ["blocked", "sharded_2d"])
def test_requested_family_is_validated(gone, mesh):
    for name in FAMILIES:
        fam, reason = select_superstep_family(10, 10, requested=name)
        assert fam == name and "requested" in reason
    # the families PR 29 deleted are unknown names now, on every seam
    for devices in (1, 4):
        with pytest.raises(ValueError, match="unknown superstep family"):
            select_superstep_family(
                1 << 22, 1 << 27, requested=gone, num_devices=devices
            )
    src, dst, v = _ring(None)
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    with pytest.raises(ValueError, match="unknown superstep family"):
        gm.label_propagation(host, max_iter=1, plan=gone, mesh=mesh)
    with pytest.raises(ValueError, match="plan must be"):
        gm.label_propagation(gm.build_graph(src, dst, num_vertices=v), plan=gone)


def test_auto_is_bucketed_on_every_mesh_size():
    for d in (2, 3, 4, 8, 64):
        for m in (10, BUCKETED_MIN_MESSAGES, 1 << 30):
            fam, reason = select_superstep_family(1 << 20, m, num_devices=d)
            assert fam == "bucketed" and f"D={d}" in reason
    # a request still wins on a mesh
    assert select_superstep_family(
        10, 10, requested="sort", num_devices=4)[0] == "sort"


def test_planner_and_memmodel_read_one_degrade_order():
    from graphmine_tpu.obs import memmodel
    from graphmine_tpu.pipeline.planner import (
        SuperstepPlan,
        degradation_ladder,
        plan_superstep,
    )

    assert set(memmodel.FAMILY_DEGRADE) == set(FAMILIES)
    assert memmodel.FAMILY_DEGRADE == {"bucketed": "sort", "sort": None}
    p = plan_superstep(1000, BUCKETED_MIN_MESSAGES)
    assert (p.family, p.degrade_to) == ("bucketed", "sort")
    p = plan_superstep(1000, 10)
    assert (p.family, p.degrade_to) == ("sort", "sort")  # the floor
    assert isinstance(p, SuperstepPlan)
    # the driver's rungs and the plan-time walk follow the same order
    assert degradation_ladder("single", 1) == ["single_sort"]
    assert degradation_ladder("single", 1, family="sort") == []
    assert degradation_ladder("replicated", 8) == ["ring"]
    fam, _, steps = memmodel.predegrade_superstep(
        "bucketed", 160, 1600, 800, False, 16
    )
    assert fam == "sort"
    assert [(a, b) for a, b, _ in steps] == [("bucketed", "sort")]


def test_auto_seam_emits_selection_and_plan_build():
    """plan='auto' on a graph past the crossover: identical labels, and
    the impl_selected + plan_build pair lands in the sink with the keys
    the benchmark's layer metrics select on."""
    from graphmine_tpu.obs.schema import validate_records
    from graphmine_tpu.pipeline.metrics import MetricsSink

    rng = np.random.default_rng(13)
    v, e = 2000, BUCKETED_MIN_MESSAGES // 2
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    g = build_graph(src, dst, num_vertices=v)
    sink = MetricsSink()
    got_l = np.asarray(label_propagation(g, 5, plan="auto", sink=sink))
    got_c = np.asarray(connected_components(g, plan="auto", sink=sink))
    np.testing.assert_array_equal(_sort_lpa(src, dst, v), got_l)
    np.testing.assert_array_equal(_sort_cc(src, dst, v), got_c)

    sel = sink.of_phase("impl_selected")
    assert [r["op"] for r in sel] == ["lpa_superstep", "cc_superstep"]
    assert all(r["impl"] == "bucketed" for r in sel)
    assert all(r["thresholds"] == crossover_thresholds() for r in sel)
    builds = sink.of_phase("plan_build")
    assert [r["op"] for r in builds] == ["lpa_superstep", "cc_superstep"]
    for r in builds:
        assert r["family"] == "bucketed" and r["bins"] == 0
        assert r["width_classes"] > 0 and r["padded_slots_per_edge"] > 0
        assert r["cost"]["family"] == "bucketed"
    assert builds[0]["cached"] is False and builds[0]["seconds"] >= 0
    # the CC resolution reuses LPA's cached plan: zero build seconds
    assert builds[1]["cached"] is True and builds[1]["seconds"] == 0.0
    assert not validate_records(sink.records)


def test_auto_seam_sort_family_emits_selection_only():
    from graphmine_tpu.pipeline.metrics import MetricsSink

    src, dst, v = _self_loops(np.random.default_rng(14))  # M < 2^16 -> sort
    g = build_graph(src, dst, num_vertices=v)
    sink = MetricsSink()
    label_propagation(g, 2, plan="auto", sink=sink)
    sel = sink.of_phase("impl_selected")
    assert len(sel) == 1 and sel[0]["impl"] == "sort"
    assert sel[0]["thresholds"] == crossover_thresholds()
    assert not sink.of_phase("plan_build")


def test_driver_runs_bucketed_and_degrades_to_sort(tmp_path):
    """The single-device pipeline path runs bucketed at every size (this
    graph is below the auto crossover), and an injected OOM steps it down
    to the sort superstep with the same labels."""
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.testing import faults

    src, dst, v = _power_law(np.random.default_rng(15))
    assert 2 * len(src) < BUCKETED_MIN_MESSAGES
    p = tmp_path / "edges.txt"
    p.write_text("\n".join(f"{s} {d}" for s, d in zip(src, dst)) + "\n")
    cfg = dict(
        data_path=str(p), data_format="edgelist", outlier_method="none",
        num_devices=1, max_iter=4,
    )
    base = run_pipeline(PipelineConfig(**cfg))
    (sel,) = [r for r in base.metrics.of_phase("impl_selected")
              if r["op"] == "lpa_superstep"]
    assert sel["impl"] == "bucketed"
    (build,) = base.metrics.of_phase("plan_build")
    assert build["family"] == "bucketed" and build["bins"] == 0
    assert not base.metrics.of_phase("degrade")

    inj = faults.FaultInjector()
    inj.add("lpa_superstep", faults.oom_error, at=2)
    with inj.installed():
        squeezed = run_pipeline(PipelineConfig(**cfg))
    (deg,) = squeezed.metrics.of_phase("degrade")
    assert deg["to"] == "single_sort" and deg["mem"]["family"] == "bucketed"
    np.testing.assert_array_equal(
        np.asarray(base.labels), np.asarray(squeezed.labels)
    )


@pytest.mark.parametrize(
    "keyword", ["build_blocked_plan", "build_plan2d", "blocked_tile_slots"]
)
def test_partition_graph_rejects_the_removed_keywords(keyword):
    src, dst, v = _ring(None)
    with pytest.raises(TypeError, match=keyword):
        partition_graph(src, dst, num_vertices=v, num_shards=4, **{keyword: 1})


def test_top_level_exports_match_api_docs():
    for name in (
        "select_superstep_family", "crossover_thresholds", "plan_superstep",
        "SuperstepPlan",
    ):
        assert hasattr(gm, name), name
    assert gm.ops.BucketedModePlan is BucketedModePlan
    assert gm.select_superstep_family is select_superstep_family
    for gone in (
        "BlockedPlan", "build_graph_and_blocked_plan", "blocked_inflow",
        "lpa_superstep_blocked", "cc_superstep_blocked",
    ):
        assert not hasattr(gm, gone), gone
        assert gone not in gm.ops.__all__
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("graphmine_tpu.ops.blocking")
