"""LDBC Graphalytics PageRank through ``gm.pagerank(..., directed=False,
tol=None)``: the message reading of an undirected graph against the
benchmark's float64 reference, on both superstep families, on graphs with
a histogram hub, isolated vertices and duplicate-free undirected edges.

The tolerances, each with its reason:

* ``LIMIT`` = 1e-4 relative per vertex: Graphalytics' epsilon match for PR,
  the limit the benchmark's cell holds the chip run to.
* ``FLOAT32`` = 1e-5: what float32 ranks, contributions and sums leave
  after ten iterations at these sizes. A rank is a sum of at most a few
  thousand positive terms, each rounded to 6e-8, and the damping shrinks
  what an iteration inherits; measured 2e-7 to 1e-6. A path that counted
  a message twice, dropped one or let a padding slot add anything would
  miss by orders of magnitude more.
* The ``sort`` family's ``segment_sum`` is a running float32 sum over a
  vertex's messages, which drifts by about sqrt(n) x 6e-8: measured 4.7e-5
  at the star's hub of 70,000 neighbours, inside ``LIMIT`` and outside
  ``FLOAT32``. That is why ``row_sums`` sums a hub in chunks, and why the
  ``sort`` family is held to ``LIMIT`` alone on that graph.
* bfloat16 contributions (8 bits of mantissa, 4e-3 a term) must FAIL
  ``LIMIT``: the tolerance is tight enough to catch the next lower
  precision.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.ops.bucketed_mode import (
    _HIST_MIN_DEG, _HUB_SUM_CHUNK, BucketedModePlan, row_sums,
)
from graphmine_tpu.pipeline.metrics import MetricsSink

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
sys.path.insert(0, BENCH)
import generators  # noqa: E402

LIMIT, FLOAT32 = 1e-4, 1e-5
TRAFFIC = {"iterations": 10, "damping": 0.85}


def _load_algorithm():
    spec = importlib.util.spec_from_file_location(
        "under_test_algorithms_pr",
        os.path.join(BENCH, "algorithms", "pr.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ALGORITHM = _load_algorithm()


def _rmat(scale, seed):
    u, v = generators.rmat_undirected(scale, 16, 0.57, 0.19, 0.19, seed)
    return u, v, 1 << scale


def _star_in_noise(leaves=70_000, seed=7):
    """One hub past the histogram threshold by far (its sum runs over many
    chunks), a sparse random graph over the leaves, and a block of
    isolated vertices at the end of the vertex space."""
    rng = np.random.default_rng(seed)
    n = leaves + 1 + 500
    a, b = rng.integers(1, leaves + 1, 2 * leaves), rng.integers(1, leaves + 1, 2 * leaves)
    keep = a < b  # duplicate-free, no self-loop, one spelling an edge
    pairs = np.unique(np.stack([a[keep], b[keep]], 1), axis=0)
    u = np.concatenate([np.zeros(leaves, np.int64), pairs[:, 0]])
    v = np.concatenate([np.arange(1, leaves + 1), pairs[:, 1]])
    return u, v, n


def _two_triangles_and_loners():
    u = np.array([0, 1, 2, 4, 5, 6, 2])
    v = np.array([1, 2, 0, 5, 6, 4, 4])
    return u, v, 10  # 3, 7, 8, 9 have no edge


GRAPHS = {
    "rmat-12": lambda: _rmat(12, 2147483659),  # the cell's rehearsal
    "rmat-14-hubs": lambda: _rmat(14, 11),
    "star-in-noise": _star_in_noise,
    "two-triangles-and-loners": _two_triangles_and_loners,
}


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / want))


def _plans(graph):
    return {"sort": None, "auto": "auto",
            "bucketed": BucketedModePlan.from_graph(graph, with_send=True)}


@pytest.fixture(scope="module", params=GRAPHS)
def case(request):
    u, v, n = GRAPHS[request.param]()
    graph = gm.build_graph(u, v, num_vertices=n)
    return request.param, u, v, n, graph, ALGORITHM.reference(u, v, n, TRAFFIC)


@pytest.mark.parametrize("family", ["sort", "bucketed", "auto"])
def test_the_message_reading_equals_the_float64_graphalytics_reference(case, family):
    name, u, v, n, graph, want = case
    got = gm.pagerank(graph, max_iter=10, tol=None, directed=False,
                      plan=_plans(graph)[family])
    assert got.dtype == np.float32 and got.shape == (n,)
    running_sum = family == "sort" and name == "star-in-noise"
    assert _gap(got, want) < (LIMIT if running_sum else FLOAT32)
    assert float(np.asarray(got, np.float64).sum()) == pytest.approx(
        1.0, abs=LIMIT if running_sum else FLOAT32)
    (record,) = ALGORITHM.compare(np.asarray(got), want)
    assert record["ok"] and record["limit"] == LIMIT and record["compared"] == n
    degree = np.bincount(np.concatenate([u, v]), minlength=n)
    if name in ("rmat-14-hubs", "star-in-noise"):
        assert degree.max() > _HIST_MIN_DEG  # a histogram hub is in it
    assert (degree == 0).any()  # and isolated vertices, the formula's dangling ones


def test_the_two_families_hold_each_other(case):
    name, _, _, _, graph, _ = case
    plans = _plans(graph)
    rows = np.asarray(gm.pagerank(graph, max_iter=10, tol=None, directed=False,
                                  plan=plans["bucketed"]), np.float64)
    segments = np.asarray(gm.pagerank(graph, max_iter=10, tol=None, directed=False,
                                      plan=None), np.float64)
    # the same float32 terms summed in another order
    assert float(np.max(np.abs(rows - segments) / segments)) < (
        LIMIT if name == "star-in-noise" else FLOAT32)


def test_bfloat16_contributions_fail_the_tolerance(case):
    """The reference's own iteration with each contribution rounded to
    bfloat16 before it is summed: not correct by Graphalytics' 1e-4."""
    import ml_dtypes

    name, u, v, n, _, want = case
    u, v = np.asarray(u, np.intp), np.asarray(v, np.intp)
    send, recv = np.concatenate([u, v]), np.concatenate([v, u])
    out = np.bincount(send, minlength=n).astype(np.float64)
    share = np.where(out > 0, 1.0 / np.maximum(out, 1.0), 0.0)
    rank = np.full(n, 1.0 / n)
    for _ in range(TRAFFIC["iterations"]):
        sent = (rank * share).astype(ml_dtypes.bfloat16).astype(np.float64)
        inflow = np.bincount(recv, weights=sent[send], minlength=n)
        rank = 0.15 / n + 0.85 * (inflow + rank[out == 0].sum() / n)
    # 2.3e-4 on the ten-vertex graph, where a rank sums two or three terms;
    # 1e-3 and more where it sums many
    assert _gap(rank, want) > (2 if n == 10 else 10) * LIMIT, name
    assert not ALGORITHM.compare(rank, want)[0]["ok"]


def test_the_directed_reading_is_the_control_and_is_far_off():
    """``gm.pagerank``'s default still ranks the edges as drawn: it equals
    the benchmark's control, and the comparison calls it wrong."""
    u, v, n = _rmat(12, 2147483659)
    graph = gm.build_graph(u, v, num_vertices=n)
    want = ALGORITHM.reference(u, v, n, TRAFFIC)
    control = ALGORITHM.control(u, v, n, TRAFFIC)
    for tol in (None, 0.0):  # a stated count, and a tolerance nothing meets
        drawn = gm.pagerank(graph, max_iter=10, tol=tol)
        assert _gap(drawn, control) < FLOAT32
    (record,) = ALGORITHM.compare(control, want)
    assert not record["ok"] and record["value"] > 1000 * LIMIT


def test_on_a_graph_built_one_way_the_two_readings_agree():
    u, v, n = _rmat(11, 3)
    graph = gm.build_graph(u, v, num_vertices=n, symmetric=False)
    drawn = np.asarray(gm.pagerank(graph, max_iter=10, tol=None), np.float64)
    for family, plan in _plans(graph).items():
        messages = gm.pagerank(graph, max_iter=10, tol=None, directed=False, plan=plan)
        assert _gap(messages, drawn) < FLOAT32, family
    assert _gap(drawn, ALGORITHM.control(u, v, n, TRAFFIC)) < FLOAT32


def test_a_stated_count_runs_exactly_that_many_and_a_tolerance_stops_early():
    u, v, n = _rmat(10, 5)
    graph = gm.build_graph(u, v, num_vertices=n)

    def iterations(**kw):
        sink = MetricsSink()
        gm.pagerank(graph, directed=False, sink=sink, **kw)
        (timing,) = [r for r in sink.records if r["phase"] == "superstep_timing"]
        return timing["window"]

    assert iterations(max_iter=10, tol=None) == 10
    assert iterations(max_iter=37, tol=None) == 37  # long past any tolerance
    assert 2 <= iterations(max_iter=100, tol=1e-6) < 37
    converged = gm.pagerank(graph, directed=False, max_iter=100, tol=1e-9)
    many = ALGORITHM.reference(u, v, n, {"iterations": 100, "damping": 0.85})
    assert _gap(converged, many) < LIMIT


def test_auto_reads_the_cached_plan_builds_no_index_and_says_so(monkeypatch):
    """A graph CDLP planned builds nothing anew, on the bucketed family the
    inflow builds no slot index, and the four records say
    ``op: pagerank_inflow``."""
    from graphmine_tpu.obs.schema import validate_records
    from graphmine_tpu.ops import lpa

    u, v, n = _rmat(13, 17)
    graph = gm.build_graph(u, v, num_vertices=n)
    gm.label_propagation(graph, max_iter=2, plan="auto")  # plans the graph
    planned, _, cached = lpa._cached_auto_plan(graph)
    assert cached

    def no_index(plan):
        raise AssertionError("PageRank asked for a slot index")

    monkeypatch.setattr(lpa, "_cached_slot_index", no_index)
    sink = MetricsSink()
    gm.pagerank(graph, max_iter=10, tol=None, directed=False, plan="auto", sink=sink)
    by_phase = {r["phase"]: r for r in sink.records}
    assert {"impl_selected", "plan_build", "device_residency",
            "superstep_timing"} <= set(by_phase)
    for phase in ("impl_selected", "plan_build", "device_residency", "superstep_timing"):
        assert by_phase[phase]["op"] == "pagerank_inflow", phase
    assert by_phase["impl_selected"]["impl"] == "bucketed"
    assert by_phase["plan_build"]["cached"] is True
    assert by_phase["plan_build"]["seconds"] == 0.0
    held = by_phase["device_residency"]
    assert held["slot_index_bytes"] == 0 == held["rows_bytes"]
    assert held["scan"] == "plain" and "stepped from the host" in held["reason"]
    assert held["plan_bytes"] == sum(
        int(x.nbytes) for x in (*planned.vertex_ids, *planned.send_idx,
                                planned.hist_vertex_ids, planned.hist_send,
                                planned.hist_row_offset) if x is not None)
    assert held["graph_bytes"] > held["plan_bytes"] > 0
    timing = by_phase["superstep_timing"]
    assert timing["family"] == "bucketed" and timing["window"] == 10
    assert timing["cost"]["padded_slots"] >= graph.num_messages
    assert validate_records(sink.records) == []


def test_under_a_callers_jit_the_rows_are_summed_in_one_program_to_the_same_ranks():
    import jax

    import importlib

    module = importlib.import_module("graphmine_tpu.ops.pagerank")
    u, v, n = _rmat(12, 2147483659)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = BucketedModePlan.from_graph(graph, with_send=True)
    stepped = gm.pagerank(graph, max_iter=10, tol=None, directed=False, plan=plan)
    programs = module._bucketed_iteration._cache_size()
    gm.pagerank(graph, max_iter=23, tol=None, directed=False, plan=plan)
    assert module._bucketed_iteration._cache_size() == programs  # another length: nothing compiled
    traced = jax.jit(lambda g, p: gm.pagerank(
        g, max_iter=10, tol=None, directed=False, plan=p))(graph, plan)
    assert _gap(traced, np.asarray(stepped, np.float64)) < FLOAT32
    auto = jax.jit(lambda g: gm.pagerank(
        g, max_iter=10, tol=None, directed=False))(graph)  # no host plan build: sort
    assert _gap(auto, np.asarray(stepped, np.float64)) < FLOAT32


def test_a_small_graph_takes_the_segment_sum_over_the_messages():
    u, v, n = _two_triangles_and_loners()
    graph = gm.build_graph(u, v, num_vertices=n)
    sink = MetricsSink()
    got = gm.pagerank(graph, max_iter=10, tol=None, directed=False, sink=sink)
    by_phase = {r["phase"]: r for r in sink.records}
    assert by_phase["impl_selected"]["impl"] == "sort"
    assert "plan_build" not in by_phase and "device_residency" not in by_phase
    assert by_phase["superstep_timing"]["family"] == "sort"
    assert _gap(got, ALGORITHM.reference(u, v, n, TRAFFIC)) < FLOAT32


def test_the_default_call_is_the_directed_tolerance_stopped_one_and_writes_no_plan_record():
    u, v, n = _rmat(13, 17)  # past the bucketed crossover: auto would plan it
    graph = gm.build_graph(u, v, num_vertices=n)
    sink = MetricsSink()
    default = gm.pagerank(graph, sink=sink)
    phases = {r["phase"] for r in sink.records}
    assert "superstep_timing" in phases
    assert not phases & {"impl_selected", "plan_build", "device_residency"}
    (timing,) = [r for r in sink.records if r["phase"] == "superstep_timing"]
    assert timing["family"] == "sort"
    assert timing["window"] < 100  # the tolerance stopped it
    many = ALGORITHM.control(u, v, n, {"iterations": 100, "damping": 0.85})
    assert _gap(default, many) < LIMIT


def test_row_sums_count_every_message_once_and_padding_adds_nothing():
    """With every contribution 1.0 a row sum is the degree, exactly: a
    slot read twice, a message dropped or a padding slot that added
    anything would show as a wrong integer."""
    u, v, n = _star_in_noise()
    plan = BucketedModePlan.from_edges(u, v, n)
    assert plan.hist_vertex_ids is not None
    assert plan.hist_send.shape[0] > 8 * _HUB_SUM_CHUNK  # several chunks a hub
    degree = np.bincount(np.concatenate([u, v]), minlength=n)
    np.testing.assert_array_equal(
        np.asarray(row_sums(np.ones(n, np.float32), plan)), degree.astype(np.float32))
    values = np.random.default_rng(0).random(n).astype(np.float32)
    want = np.bincount(np.concatenate([v, u]),
                       weights=values.astype(np.float64)[np.concatenate([u, v])],
                       minlength=n)
    got = np.asarray(row_sums(values, plan), np.float64)
    held = want > 0
    assert float(np.max(np.abs(got[held] - want[held]) / want[held])) < FLOAT32
    assert not got[~held].any()  # a vertex that receives nothing stays 0.0


def test_what_the_message_reading_refuses():
    u, v, n = _rmat(10, 5)
    graph = gm.build_graph(u, v, num_vertices=n)
    with pytest.raises(ValueError, match="directed reading"):
        gm.pagerank(graph, directed=False, weights=np.ones(len(u), np.float32))
    with pytest.raises(ValueError, match="plan must be"):
        gm.pagerank(graph, directed=False, plan="bucketed")
    other = BucketedModePlan.from_edges(u[:100], v[:100], n)
    with pytest.raises(ValueError, match="mismatch"):
        gm.pagerank(graph, directed=False, plan=other)
    with pytest.raises(ValueError, match="fused plan"):
        row_sums(np.ones(n, np.float32), BucketedModePlan.from_graph(graph))
