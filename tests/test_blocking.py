"""Propagation-blocking superstep engine (ops/blocking.py, ISSUE 7).

Parity suite pinning blocked supersteps bit-identical to the sort-based
``segment_mode`` oracle across power-law / ring / self-loop /
isolated-vertex / duplicate-edge graphs, for LPA / CC / PageRank, fused
and sharded; plus the crossover policy owner, the planner family seam,
the ``plan_build`` observability records, the weighted-payload contract,
and the ``blocking`` bench-tier body smoke.

Marker: ``blocking`` (``tools/run_tier1.sh --blocking-only``).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.blocking import (
    BUCKETED_MIN_MESSAGES,
    BlockedPlan,
    blocked_inflow,
    build_graph_and_blocked_plan,
    cc_superstep_blocked,
    crossover_thresholds,
    lpa_superstep_blocked,
    plan_build_stats,
    select_superstep_family,
)
from graphmine_tpu.ops.cc import connected_components
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.ops.pagerank import pagerank

pytestmark = pytest.mark.blocking

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# ---- fused parity ----------------------------------------------------------


def test_lpa_blocked_bit_identical(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BlockedPlan.from_graph(g, tile_slots=193)  # force several bins
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(ref, got)


def test_lpa_blocked_per_superstep(edges):
    """Step-for-step identity against the sort superstep, not just the
    final labels (catches off-by-one-superstep compensation)."""
    import jax.numpy as jnp

    from graphmine_tpu.ops.lpa import lpa_superstep

    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BlockedPlan.from_graph(g, tile_slots=100)
    lbl = jnp.arange(v, dtype=jnp.int32)
    for _ in range(4):
        ref = lpa_superstep(lbl, g)
        got = lpa_superstep_blocked(lbl, g, plan)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        lbl = ref


def test_cc_blocked_bit_identical(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BlockedPlan.from_graph(g, tile_slots=151)
    ref = np.asarray(connected_components(g, plan=None))
    got = np.asarray(connected_components(g, plan=plan))
    np.testing.assert_array_equal(ref, got)


def test_cc_superstep_blocked_matches_oracle_step(edges):
    import jax.numpy as jnp

    from graphmine_tpu.ops.cc import cc_superstep

    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    plan = BlockedPlan.from_graph(g, tile_slots=96)
    lbl = jnp.arange(v, dtype=jnp.int32)
    for _ in range(3):
        ref = cc_superstep(lbl, g)
        got = cc_superstep_blocked(lbl, plan)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        lbl = ref


def test_pagerank_blocked_matches(edges):
    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v, symmetric=False)
    plan = BlockedPlan.from_graph(g, tile_slots=128)
    ref = np.asarray(pagerank(g, plan=None))
    got = np.asarray(pagerank(g, plan=plan))
    # float sums reassociate across the row layout: tolerance, not bits
    np.testing.assert_allclose(ref, got, rtol=2e-5, atol=1e-8)
    assert abs(float(got.sum()) - 1.0) < 1e-4


def test_blocked_inflow_matches_segment_sum():
    import jax

    rng = np.random.default_rng(9)
    src, dst, v = _power_law(rng)
    g = build_graph(src, dst, num_vertices=v, symmetric=False)
    plan = BlockedPlan.from_graph(g, tile_slots=200)
    contrib = rng.random(v).astype(np.float32)
    ref = jax.ops.segment_sum(
        contrib[np.asarray(g.src)], np.asarray(g.dst), num_segments=v
    )
    got = blocked_inflow(plan, contrib)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-5)


def test_multi_bin_layout_and_stats():
    rng = np.random.default_rng(4)
    src, dst, v = _power_law(rng)
    g, plan = build_graph_and_blocked_plan(
        src, dst, num_vertices=v, tile_slots=64
    )
    assert plan.num_bins > 1
    assert plan.tile_slots >= 64 or plan.num_bins == 1
    stats = plan_build_stats(plan, g.num_edges)
    assert stats["family"] == "blocked"
    assert stats["bins"] == plan.num_bins
    assert stats["padded_slots_per_edge"] > 0
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(ref, got)


def test_plan_graph_mismatch_refuses():
    """A same-V plan from a DIFFERENT graph must refuse on every explicit
    plan seam (LPA, CC, PageRank) — it would silently mis-reduce."""
    rng = np.random.default_rng(5)
    src, dst, v = _self_loops(rng)
    g = build_graph(src, dst, num_vertices=v)
    other = build_graph(src[: len(src) // 2], dst[: len(dst) // 2],
                        num_vertices=v)
    plan = BlockedPlan.from_graph(other)
    with pytest.raises(ValueError, match="mismatch"):
        label_propagation(g, 2, plan=plan)
    with pytest.raises(ValueError, match="mismatch"):
        connected_components(g, plan=plan)
    g_dir = build_graph(src, dst, num_vertices=v, symmetric=False)
    other_dir = build_graph(src[: len(src) // 2], dst[: len(dst) // 2],
                            num_vertices=v, symmetric=False)
    with pytest.raises(ValueError, match="mismatch"):
        pagerank(g_dir, plan=BlockedPlan.from_graph(other_dir))


# ---- weighted contract -----------------------------------------------------


def test_weighted_lpa_blocked_bit_identical(edges):
    src, dst, v = edges
    w = np.random.default_rng(6).random(len(src)).astype(np.float32)
    g = build_graph(src, dst, num_vertices=v, edge_weights=w)
    plan = BlockedPlan.from_graph(g, tile_slots=160)
    assert plan.weight_mat is not None
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(label_propagation(g, 5, plan=plan))
    np.testing.assert_array_equal(ref, got)


def test_weighted_graph_weightless_plan_refuses():
    """The serving layer's weighted contract (serve/delta.py): weights
    are never silently dropped — a blocked plan without the slot-aligned
    payload refuses loudly on a weighted graph."""
    rng = np.random.default_rng(7)
    src, dst, v = _self_loops(rng)
    w = rng.random(len(src)).astype(np.float32)
    g_unw = build_graph(src, dst, num_vertices=v)
    g_w = build_graph(src, dst, num_vertices=v, edge_weights=w)
    weightless = BlockedPlan.from_graph(g_unw)
    with pytest.raises(ValueError, match="weight"):
        lpa_superstep_blocked(
            np.arange(v, dtype=np.int32), g_w, weightless
        )


def test_pagerank_blocked_refusals():
    rng = np.random.default_rng(8)
    src, dst, v = _self_loops(rng)
    g_sym = build_graph(src, dst, num_vertices=v)
    plan_sym = BlockedPlan.from_graph(g_sym)
    with pytest.raises(ValueError, match="directed"):
        pagerank(g_sym, plan=plan_sym)
    g_dir = build_graph(src, dst, num_vertices=v, symmetric=False)
    plan_dir = BlockedPlan.from_graph(g_dir)
    w = rng.random(len(src)).astype(np.float32)
    with pytest.raises(ValueError, match="weight"):
        pagerank(g_dir, weights=w, plan=plan_dir)


# ---- sharded parity --------------------------------------------------------


def _mesh8():
    import jax

    from graphmine_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return make_mesh(8)


def test_sharded_lpa_blocked_bit_identical(edges):
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    mesh = _mesh8()
    sg = shard_graph_arrays(
        partition_graph(
            g, mesh=mesh, build_blocked_plan=True, blocked_tile_slots=48
        ),
        mesh,
    )
    assert sg.blk_src is not None
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(sharded_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(ref, got)


def test_sharded_cc_blocked_bit_identical(edges):
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_connected_components,
    )

    src, dst, v = edges
    g = build_graph(src, dst, num_vertices=v)
    mesh = _mesh8()
    sg = shard_graph_arrays(
        partition_graph(
            g, mesh=mesh, build_blocked_plan=True, blocked_tile_slots=48
        ),
        mesh,
    )
    ref = np.asarray(connected_components(g, plan=None))
    got = np.asarray(sharded_connected_components(sg, mesh))
    np.testing.assert_array_equal(ref, got)


def test_sharded_weighted_lpa_blocked_bit_identical():
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    rng = np.random.default_rng(10)
    src, dst, v = _power_law(rng)
    w = rng.random(len(src)).astype(np.float32)
    g = build_graph(src, dst, num_vertices=v, edge_weights=w)
    mesh = _mesh8()
    sg = shard_graph_arrays(
        partition_graph(
            g, mesh=mesh, build_blocked_plan=True, blocked_tile_slots=48
        ),
        mesh,
    )
    assert sg.blk_row_weight
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(sharded_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(ref, got)


def test_sharded_blocked_lpa_only_trimming():
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    rng = np.random.default_rng(11)
    src, dst, v = _self_loops(rng)
    g = build_graph(src, dst, num_vertices=v)
    mesh = _mesh8()
    sg = shard_graph_arrays(
        partition_graph(g, mesh=mesh, build_blocked_plan=True), mesh,
        lpa_only=True,
    )
    assert sg.msg_send is None  # sort-body arrays dropped
    ref = np.asarray(label_propagation(g, 5, plan=None))
    got = np.asarray(sharded_label_propagation(sg, mesh, max_iter=5))
    np.testing.assert_array_equal(ref, got)


def test_partition_plan_flags_mutually_exclusive():
    from graphmine_tpu.parallel.sharded import partition_graph

    rng = np.random.default_rng(12)
    src, dst, v = _self_loops(rng)
    with pytest.raises(ValueError, match="mutually exclusive"):
        partition_graph(
            src, dst, num_vertices=v, num_shards=4,
            build_bucket_plan=True, build_blocked_plan=True,
        )


# ---- crossover policy + planner seam ---------------------------------------


def test_family_policy_thresholds():
    fam, reason = select_superstep_family(10, 100)
    assert fam == "sort" and "65536" in reason
    fam, _ = select_superstep_family(1000, BUCKETED_MIN_MESSAGES)
    assert fam == "bucketed"
    # one device: past the bucketed crossover no size resolves anything
    # else — the value table past on-chip capacity (V >= 2^21) and
    # M >= 2^22 sent auto to blocked until the chip showed its three
    # passes 5.4x slower than the one bucketed gather (PERF.md, PR 26)
    fam, _ = select_superstep_family(1000, 1 << 22)
    assert fam == "bucketed"
    fam, reason = select_superstep_family(1 << 21, 1 << 22)
    assert fam == "bucketed" and "blocking" not in reason
    assert set(crossover_thresholds()) == {"bucketed_min_messages"}


def test_family_policy_env_overrides(monkeypatch):
    # the retired crossover knobs decide nothing any more
    monkeypatch.setenv("GRAPHMINE_BLOCKED_MIN_MESSAGES", "1")
    monkeypatch.setenv("GRAPHMINE_BLOCKED_MIN_VERTICES", "1")
    fam, _ = select_superstep_family(100, BUCKETED_MIN_MESSAGES)
    assert fam == "bucketed"
    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "sort")
    fam, reason = select_superstep_family(1 << 24, 1 << 24)
    assert fam == "sort" and "env override" in reason
    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "nope")
    with pytest.raises(ValueError, match="GRAPHMINE_SUPERSTEP_FAMILY"):
        select_superstep_family(1 << 24, 1 << 24)


@pytest.mark.parametrize(
    "v,m,graph",
    [
        (1 << 22, 1 << 27, "power_law"),   # the benchmark's cdlp-g500-22
        (1 << 24, 1 << 28, "dup_edges"),
        (1 << 21, 1 << 22, "isolated"),    # the old thresholds themselves
    ],
)
def test_auto_never_resolves_blocked_on_one_device(v, m, graph):
    """``auto`` resolves ``bucketed`` at every size that used to flip to
    ``blocked``; an explicit request still resolves ``blocked``, builds
    its plan through the auto plan cache and runs bit-identical."""
    from graphmine_tpu.ops.lpa import _cached_auto_plan
    from graphmine_tpu.pipeline.planner import plan_superstep

    fam, reason = select_superstep_family(v, m)
    assert fam == "bucketed" and "blocked" not in reason
    assert plan_superstep(v, m).family == "bucketed"
    fam, reason = select_superstep_family(v, m, requested="blocked")
    assert fam == "blocked" and "requested" in reason

    src, dst, nv = GRAPHS[graph](np.random.default_rng(3))
    g = build_graph(src, dst, num_vertices=nv)
    plan, _, cached = _cached_auto_plan(g, fam)
    assert isinstance(plan, BlockedPlan) and not cached
    ref = np.asarray(label_propagation(g, 5, plan=None))
    np.testing.assert_array_equal(
        ref, np.asarray(label_propagation(g, 5, plan=plan))
    )
    np.testing.assert_array_equal(
        ref, np.asarray(label_propagation(g, 5, plan="auto"))
    )


def test_family_policy_requested_validation():
    fam, reason = select_superstep_family(10, 10, requested="blocked")
    assert fam == "blocked" and "requested" in reason
    with pytest.raises(ValueError, match="unknown superstep family"):
        select_superstep_family(10, 10, requested="warp")


def test_planner_superstep_plan_and_ladder():
    from graphmine_tpu.pipeline.planner import (
        degradation_ladder,
        plan_superstep,
    )

    # auto never plans the blocked family; requested explicitly it
    # still carries its blocked→bucketed rung
    assert plan_superstep(1 << 21, 1 << 22).family == "bucketed"
    p = plan_superstep(1 << 21, 1 << 22, requested="blocked")
    assert p.family == "blocked" and p.degrade_to == "bucketed"
    p2 = plan_superstep(1000, BUCKETED_MIN_MESSAGES)
    assert p2.family == "bucketed" and p2.degrade_to == "sort"
    # the blocked→bucketed degradation rung shows up in the ladder
    assert degradation_ladder("single", 1, family="blocked") == [
        "single_bucketed", "single_sort",
    ]
    assert degradation_ladder("single", 1) == ["single_sort"]
    assert degradation_ladder("replicated", 8, family="blocked") == ["ring"]


# ---- auto seam + plan_build observability ----------------------------------


def test_auto_seam_resolves_blocked_with_parity(monkeypatch):
    """With the family forced process-wide, plan='auto' flips LPA and CC
    to the blocked family end-to-end — identical labels, and the
    impl_selected + plan_build provenance pair lands in the sink,
    schema-valid."""
    from graphmine_tpu.obs.schema import validate_records
    from graphmine_tpu.pipeline.metrics import MetricsSink

    rng = np.random.default_rng(13)
    src, dst, v = _power_law(rng)
    g = build_graph(src, dst, num_vertices=v)
    ref_l = np.asarray(label_propagation(g, 5, plan=None))
    ref_c = np.asarray(connected_components(g, plan=None))

    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "blocked")
    sink = MetricsSink()
    got_l = np.asarray(label_propagation(g, 5, plan="auto", sink=sink))
    got_c = np.asarray(connected_components(g, plan="auto", sink=sink))
    np.testing.assert_array_equal(ref_l, got_l)
    np.testing.assert_array_equal(ref_c, got_c)

    sel = sink.of_phase("impl_selected")
    assert [r["op"] for r in sel] == ["lpa_superstep", "cc_superstep"]
    assert all(r["impl"] == "blocked" for r in sel)
    builds = sink.of_phase("plan_build")
    assert len(builds) == 2 and builds[0]["family"] == "blocked"
    assert builds[0]["cached"] is False and builds[0]["seconds"] >= 0
    # the CC resolution reuses LPA's cached plan: zero build seconds
    assert builds[1]["cached"] is True and builds[1]["seconds"] == 0.0
    assert builds[0]["padded_slots_per_edge"] > 0
    assert not validate_records(sink.records)


def test_auto_seam_sort_family_emits_selection_only():
    from graphmine_tpu.pipeline.metrics import MetricsSink

    rng = np.random.default_rng(14)
    src, dst, v = _self_loops(rng)  # tiny: M < 2^16 -> sort
    g = build_graph(src, dst, num_vertices=v)
    sink = MetricsSink()
    label_propagation(g, 2, plan="auto", sink=sink)
    sel = sink.of_phase("impl_selected")
    assert len(sel) == 1 and sel[0]["impl"] == "sort"
    assert not sink.of_phase("plan_build")


def test_driver_runs_blocked_family(tmp_path, monkeypatch):
    """Driver e2e: the planner resolves the blocked family, the
    single-device LPA runs it, and labels match the default (bucketed)
    run bit-for-bit, with the provenance records in the stream."""
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline

    rng = np.random.default_rng(15)
    src, dst, v = _power_law(rng)
    lines = "\n".join(f"{s} {d}" for s, d in zip(src, dst))
    p = tmp_path / "edges.txt"
    p.write_text(lines + "\n")

    cfg = dict(
        data_path=str(p), data_format="edgelist", outlier_method="none",
        num_devices=1, max_iter=3,
    )
    base = run_pipeline(PipelineConfig(**cfg))
    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "blocked")
    blocked = run_pipeline(PipelineConfig(**cfg))
    np.testing.assert_array_equal(
        np.asarray(base.labels), np.asarray(blocked.labels)
    )
    sel = [
        r for r in blocked.metrics.of_phase("impl_selected")
        if r["op"] == "lpa_superstep"
    ]
    assert sel and sel[0]["impl"] == "blocked"
    builds = blocked.metrics.of_phase("plan_build")
    assert builds and builds[0]["family"] == "blocked"


def test_driver_honors_forced_sort_family(tmp_path, monkeypatch):
    """An explicit GRAPHMINE_SUPERSTEP_FAMILY=sort force is honored by
    the driver: the sort superstep actually runs (no plan built, no
    plan_build record) and the provenance record says so — the
    tiny-scale sort→bucketed coercion applies to AUTO resolutions only."""
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline

    rng = np.random.default_rng(16)
    src, dst, v = _power_law(rng)
    p = tmp_path / "edges.txt"
    p.write_text("\n".join(f"{s} {d}" for s, d in zip(src, dst)) + "\n")
    cfg = dict(
        data_path=str(p), data_format="edgelist", outlier_method="none",
        num_devices=1, max_iter=3,
    )
    base = run_pipeline(PipelineConfig(**cfg))
    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "sort")
    res = run_pipeline(PipelineConfig(**cfg))
    np.testing.assert_array_equal(
        np.asarray(base.labels), np.asarray(res.labels)
    )
    sel = [
        r for r in res.metrics.of_phase("impl_selected")
        if r["op"] == "lpa_superstep"
    ]
    assert sel and sel[0]["impl"] == "sort"
    assert not res.metrics.of_phase("plan_build")


def test_top_level_exports_match_api_docs():
    import graphmine_tpu as gm

    for name in (
        "BlockedPlan", "build_graph_and_blocked_plan",
        "lpa_superstep_blocked", "cc_superstep_blocked", "blocked_inflow",
        "select_superstep_family", "plan_superstep", "SuperstepPlan",
    ):
        assert hasattr(gm, name), name


# ---- bench tier ------------------------------------------------------------


def test_blocking_tier_body_cpu_smoke():
    """Run ``main_blocking``'s ACTUAL measurement body end-to-end on CPU
    at env-capped tiny scale (the roofline tier's convention) so the tier
    cannot fail its first-ever execution inside a real-TPU window."""
    env = dict(
        os.environ,
        GRAPHMINE_BENCH_CPU_FALLBACK="1",
        _GRAPHMINE_BENCH_CHILD="1",
        GRAPHMINE_BLOCKING_VERTICES=str(1 << 12),
        GRAPHMINE_BLOCKING_EDGES=str(1 << 13),
        GRAPHMINE_BLOCKING_ITERS="2",
        JAX_PLATFORMS="cpu",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--tier", "blocking"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert rec["metric"] == "blocking_binned_slots_per_sec_cpu_fallback"
    assert rec["value"] > 0
    assert rec["vs_baseline"] == 0.0  # CPU rates: no TPU-model ratio
    d = rec["detail"]
    for k in (
        "random_gather_slots_per_sec", "monotone_gather_slots_per_sec",
        "binned_pass_slots_per_sec", "binned_vs_random_gather",
    ):
        assert d[k] > 0, k
    assert d["messages"] == 2 * d["num_edges"]
    assert d["num_bins"] >= 1 and d["plan_build_seconds"] >= 0
