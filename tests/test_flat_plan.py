"""CDLP on a graph with no hubs (ISSUE 38): GAP's uniform-random draw, which
is ``benchmark/generators.py:rmat_undirected`` at a = b = c = 0.25. Its plan
is a couple of dozen classes of like size on both sides of the pairwise /
sort crossover, with no histogram hub; its labels keep moving for most of
ten supersteps and then settle at once, so the carried-rows job gathers in
full seven or eight times, drops through a rung or two and ends with K = 0.
The labels are the plain reference's label for label, with the rows carried
and with the plain scan, and the records say what the benchmark's metrics
read: ``plan_build``'s shape of the plan, ``superstep_delta``'s seconds."""

import os
import sys

import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import lpa, superstep_policy
from graphmine_tpu.ops.bucketed_mode import (
    _PAIRWISE_MAX_W,
    BucketedModePlan,
    row_slots,
)
from graphmine_tpu.ops.superstep_policy import delta_rungs
from graphmine_tpu.pipeline.metrics import MetricsSink

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "benchmark")
sys.path.insert(0, BENCH_DIR)
import generators  # noqa: E402
import references  # noqa: E402

STEPS = 10
NEW_PLAN_KEYS = ("padded_slots_per_message", "rows_pairwise", "rows_sorted",
                 "rows_hist", "max_width")


def _urand(scale, seed=7):
    u, v = generators.rmat_undirected(scale, 16, 0.25, 0.25, 0.25, seed=seed)
    return u, v, 1 << scale


def _want(u, v, n, steps=STEPS):
    return references.numpy_lpa(u, v, n, steps)


def _squeeze(monkeypatch, limit):
    monkeypatch.setattr(superstep_policy, "device_memory_stats",
                        lambda plan: {"bytes_limit": limit, "bytes_in_use": 0})


def _delta(sink):
    (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    return record


@pytest.mark.parametrize("scan", ["carried", "plain"])
@pytest.mark.parametrize("scale", [10, 11, 12])
def test_labels_equal_the_plain_reference_with_either_scan(scale, scan, monkeypatch):
    """Scale 12 goes through ``plan="auto"``, as the cell does; the two
    smaller draws have fewer messages than ``auto`` builds a plan for and
    hand the job their fused plan."""
    u, v, n = _urand(scale)
    g = gm.build_graph(u, v, num_vertices=n)
    plan = "auto" if scale == 12 else BucketedModePlan.from_edges(u, v, n)
    _squeeze(monkeypatch, 1 if scan == "plain" else 1 << 40)
    sink = MetricsSink()
    got = gm.label_propagation(g, max_iter=STEPS, plan=plan, sink=sink)
    np.testing.assert_array_equal(np.asarray(got), _want(u, v, n))
    assert validate_records(sink.records) == []
    delta = _delta(sink)
    assert len(delta["branch"]) == len(delta["changed_vertices"]) == STEPS
    if scan == "plain":
        assert delta["branch"] == ["full"] * STEPS and delta["seconds"] == []
    else:
        # no quiet tail: most supersteps gather in full, then the labels
        # settle at once and the last superstep moves nothing
        full = sum(b == "full" for b in delta["branch"])
        assert 5 <= full < STEPS and delta["branch"][:full] == ["full"] * full
        assert delta["changed_messages"][-1] == 0 == delta["changed_vertices"][-1]
        assert delta["branch"][-1] == delta_rungs(g.num_messages)[0]
    if scale == 12:
        (selected,) = [r for r in sink.records if r["phase"] == "impl_selected"]
        assert selected["impl"] == "bucketed" and selected["scan"] == scan


@pytest.mark.parametrize("scale", [10, 11, 12])
def test_the_plan_is_flat(scale):
    """No histogram hub, like-sized classes on both sides of the pairwise /
    sort crossover, under 1.05 padded slots a message (a Kronecker draw of
    this size: 1.2)."""
    u, v, n = _urand(scale)
    plan = BucketedModePlan.from_edges(u, v, n)
    assert plan.hist_vertex_ids is plan.hist_send is plan.hist_row_offset is None
    widths = [idx.shape[1] for idx in plan.send_idx]
    assert min(widths) <= _PAIRWISE_MAX_W < max(widths) < 2 * 32 + 8
    assert row_slots(plan) / plan.num_messages < 1.05
    degree = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    assert degree.min() > 0 and degree.max() < 2 * degree.mean()
    said = superstep_policy.plan_build_stats(plan, len(u))
    rows = {True: 0, False: 0}
    for idx in plan.send_idx:
        rows[idx.shape[1] <= _PAIRWISE_MAX_W] += idx.shape[0]
    assert said["rows_pairwise"] == rows[True] > 0 < rows[False] == said["rows_sorted"]
    assert said["rows_pairwise"] + said["rows_sorted"] == n and said["rows_hist"] == 0
    assert said["max_width"] == max(widths)
    assert said["padded_slots_per_message"] == pytest.approx(
        row_slots(plan) / plan.num_messages, abs=5e-5)


def _triangles_and_pairs(triangles, pairs):
    """Disjoint triangles settle on their smallest id in two supersteps and
    move no more; the two ends of a disjoint edge swap labels for ever. So
    from the third superstep on the changed vertices send ``2 * pairs``
    messages of ``6 * triangles + 2 * pairs``, exactly."""
    t = 3 * np.arange(triangles)
    p = 3 * triangles + 2 * np.arange(pairs)
    src = np.concatenate([t, t, t + 1, p])
    dst = np.concatenate([t + 1, t + 2, t + 2, p + 1])
    return src, dst, 3 * triangles + 2 * pairs


def test_k_between_two_rungs_takes_the_m_16_rung_and_still_matches():
    """The rung no benchmark cell compiled before this graph's: K above
    M / 256 and at most M / 16."""
    src, dst, n = _triangles_and_pairs(30_000, 5_000)
    g = gm.build_graph(src, dst, num_vertices=n)
    m = g.num_messages
    assert m == 190_000 and m // 256 < 10_000 <= m // 16
    sink = MetricsSink()
    got = gm.label_propagation(g, max_iter=7, plan="auto", sink=sink)
    np.testing.assert_array_equal(np.asarray(got), _want(src, dst, n, 7))
    delta = _delta(sink)
    assert delta["changed_messages"] == [m, 70_000] + [10_000] * 5
    assert delta["branch"] == ["full", "full", "full"] + [m // 16] * 4
    assert m // 16 in delta["rungs"]


def test_a_last_superstep_that_moves_nothing_matches():
    src, dst, n = _triangles_and_pairs(30_000, 0)
    g = gm.build_graph(src, dst, num_vertices=n)
    sink = MetricsSink()
    got = gm.label_propagation(g, max_iter=5, plan="auto", sink=sink)
    want = _want(src, dst, n, 5)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(want, np.repeat(3 * np.arange(30_000), 3))
    delta = _delta(sink)
    assert delta["changed_messages"] == [180_000, 60_000, 0, 0, 0]
    lowest = delta_rungs(g.num_messages)[0]
    assert delta["branch"] == ["full", "full", "full", lowest, lowest]


def test_the_records_hold_what_the_metrics_read():
    """``superstep_delta.seconds``: one reading a superstep, the host's
    clock at the fetch of K the job makes anyway; ``plan_build``: the five
    keys that say how the plan's rows reduce."""
    u, v, n = _urand(12, seed=11)
    g = gm.build_graph(u, v, num_vertices=n)
    sink = MetricsSink()
    gm.label_propagation(g, max_iter=STEPS, plan="auto", sink=sink)
    assert validate_records(sink.records) == []
    delta = _delta(sink)
    assert len(delta["seconds"]) == STEPS and all(s > 0 for s in delta["seconds"])
    (timing,) = [r for r in sink.records if r["phase"] == "superstep_timing"]
    assert sum(delta["seconds"]) <= timing["seconds"] + 1e-3  # inside the job's
    (built,) = [r for r in sink.records if r["phase"] == "plan_build"]
    assert set(NEW_PLAN_KEYS) <= set(built)
    assert 1.0 < built["padded_slots_per_message"] < 1.05 and built["rows_hist"] == 0


def test_the_seconds_are_the_clock_s_readings_at_each_fetch_of_k():
    u, v, n = _urand(10)
    g = gm.build_graph(u, v, num_vertices=n)
    plan = lpa._cached_slot_index(BucketedModePlan.from_edges(u, v, n))[0]
    ticks = iter(range(100))
    _, per_step = lpa._carried_rows_job(g, 4, None, plan, clock=lambda: next(ticks) ** 2)
    assert per_step["seconds"] == [1, 3, 5, 7]  # 1 - 0, 4 - 1, 9 - 4, 16 - 9
    assert next(ticks) == 5  # one reading before the loop, one a superstep
    assert "seconds" not in lpa._carried_rows_job(g, 4, None, plan)[1]


def test_a_job_without_a_sink_reads_no_clock(monkeypatch):
    u, v, n = _urand(10)
    g = gm.build_graph(u, v, num_vertices=n)
    plan = BucketedModePlan.from_edges(u, v, n)
    want = np.asarray(gm.label_propagation(g, max_iter=3, plan=plan))

    class NoClock:
        @staticmethod
        def perf_counter():
            raise AssertionError("a clock reading in a job that has no sink")

    monkeypatch.setattr(lpa, "time", NoClock)
    np.testing.assert_array_equal(
        np.asarray(gm.label_propagation(g, max_iter=3, plan=plan)), want)
