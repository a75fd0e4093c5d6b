"""LDBC Graphalytics BFS through ``gm.bfs_distances`` (ISSUE 49): the search
that follows its frontier over the carried bucket rows, the full-width level
stepped from the host and the ``while_loop`` over the message arrays give the
depths of the benchmark's plain reference (``benchmark/algorithms/bfs.py``,
SciPy on its own CSR) and each other's, bit for bit; a level that asks the
unreached vertices for a reached neighbour through the graph's message CSR
gives them too, and the search takes it where a place's cost says it is the
cheaper (ISSUE 50, ISSUE 53); the one
stepping loop takes BFS's stop as an argument and steps CDLP as it did; the
admission answers for this job's own programs."""

import importlib
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import lpa, superstep_policy
from graphmine_tpu.ops.paths import UNREACHABLE, bfs_parents
from graphmine_tpu.ops.superstep_policy import (
    admit_carried_rows,
    bottom_up_chunk,
    delta_rungs,
    step_carried_rows,
)
from graphmine_tpu.pipeline.metrics import MetricsSink

from test_lpa_delta import _fused, _rmat

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmark")


def _algorithm():
    spec = importlib.util.spec_from_file_location(
        "bench_algorithms_bfs", os.path.join(_BENCH, "algorithms", "bfs.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bfs = _algorithm()


def _lowest_with_an_edge(u, v) -> int:
    return int(min(u.min(), v.min()))


def _case(name):
    """``(u, v, num_vertices, sources, max_depth)`` of a named case."""
    rng = np.random.default_rng(49)
    if name == "rmat_with_a_histogram_hub":
        u, v, n = _rmat(12, 16, seed=5)
        return u, v, n, [_lowest_with_an_edge(u, v)], 0
    if name == "flat":
        u, v = rng.integers(0, 3000, 6000), rng.integers(0, 3000, 6000)
        return u, v, 3000, [_lowest_with_an_edge(u, v)], 0
    if name == "path":  # 150 levels either way from the middle
        return np.arange(299), np.arange(1, 300), 300, [150], 0
    if name == "grid":  # 30 x 30, from a corner: 59 levels
        at = np.arange(900).reshape(30, 30)
        u = np.concatenate([at[:, :-1].ravel(), at[:-1, :].ravel()])
        v = np.concatenate([at[:, 1:].ravel(), at[1:, :].ravel()])
        return u, v, 900, [0], 0
    if name == "unreached_components":  # two halves, and 500 vertices alone
        a, b = rng.integers(0, 1000, 5000), rng.integers(0, 1000, 5000)
        c, d = rng.integers(1000, 1500, 1500), rng.integers(1000, 1500, 1500)
        return np.concatenate([a, c]), np.concatenate([b, d]), 2000, [int(a[0])], 0
    if name == "isolated_source":
        u, v = rng.integers(0, 800, 4000), rng.integers(0, 800, 4000)
        return u, v, 1000, [950], 0
    if name == "several_sources":
        u, v = rng.integers(0, 2500, 5000), rng.integers(0, 2500, 5000)
        return u, v, 2500, [int(u[0]), int(v[7]), int(u[99]), int(u[0])], 0
    if name == "max_depth":
        u, v = rng.integers(0, 3000, 6000), rng.integers(0, 3000, 6000)
        return u, v, 3000, [_lowest_with_an_edge(u, v)], 3
    raise KeyError(name)


CASES = ["rmat_with_a_histogram_hub", "flat", "path", "grid",
         "unreached_components", "isolated_source", "several_sources", "max_depth"]


def _want(u, v, n, sources, max_depth):
    """The plain reference's depths, the program's way of writing them; the
    levels past ``max_depth`` unreached."""
    depths = bfs.reference(u, v, n, {"source": sources})[0]
    if max_depth:
        depths = np.where(depths > max_depth, bfs.UNREACHED, depths)
    return np.where(depths == bfs.UNREACHED, int(UNREACHABLE), depths).astype(np.int32)


def _squeeze(monkeypatch, limit):
    """The device of every plan reports ``limit`` bytes, none in use."""
    monkeypatch.setattr(superstep_policy, "device_memory_stats",
                        lambda plan: {"bytes_limit": limit, "bytes_in_use": 0})


def _records(sink) -> list:
    """The job's own records: a sink also hears of every compile."""
    return [r for r in sink.records if r["phase"] != "compile"]


def _run(path, g, plan, sources, max_depth, monkeypatch, sink=None):
    """``(depths, supersteps)`` of one of the three paths."""
    if path == "while_loop":
        plan = None
    elif path == "full_width":
        _squeeze(monkeypatch, 1)  # no room for the rows: nothing is carried
    depths, levels = gm.bfs_distances(
        g, np.asarray(sources), direction="both", max_depth=max_depth,
        plan=plan, sink=sink, return_levels=True)
    return np.asarray(depths), int(levels)


@pytest.mark.parametrize("path", ["frontier", "full_width", "while_loop"])
@pytest.mark.parametrize("name", CASES)
def test_every_path_gives_the_plain_references_depths(name, path, monkeypatch):
    u, v, n, sources, max_depth = _case(name)
    g, plan = _fused(u, v, n)
    sink = MetricsSink()
    got, levels = _run(path, g, plan, sources, max_depth, monkeypatch, sink)
    want = _want(u, v, n, sources, max_depth)
    assert got.dtype == np.int32 and np.array_equal(got, want)
    # the supersteps: one a level reached, and the one that reaches nothing
    deepest = int(want[want != int(UNREACHABLE)].max())
    assert levels == (max_depth if max_depth and deepest == max_depth else deepest + 1)
    assert (plan.hist_vertex_ids is not None) == (name == "rmat_with_a_histogram_hub")
    validate_records(sink.records)
    by_phase = {r["phase"]: r for r in _records(sink)}
    if path == "while_loop":  # one program, and what it takes of the chip
        assert set(by_phase) == {"program_memory"}
        assert by_phase["program_memory"]["program"] == "loop"
        return
    fix, delta = by_phase["fixpoint"], by_phase["superstep_delta"]
    assert fix["op"] == delta["op"] == "bfs_level" and fix["supersteps"] == levels
    # a level's count is the vertices at that depth
    assert fix["changed"] == delta["changed_vertices"] == [
        int((want == d).sum()) for d in range(1, levels + 1)]
    assert len(delta["seconds"]) == len(delta["branch"]) == levels
    if path == "full_width":
        assert set(delta["branch"]) == {"full"} and delta["changed_messages"] == []
    else:
        deg = np.bincount(np.concatenate([u, v]), minlength=n)
        assert delta["changed_messages"] == [
            int(deg[want == d].sum()) for d in range(1, levels + 1)]
        assert delta["source_messages"] == int(deg[np.unique(sources)].sum())


def test_the_control_fails_the_comparison_and_a_sound_answer_passes():
    u, v, n, sources, _ = _case("flat")
    traffic = {"source": "lowest_id_with_an_edge"}
    want = bfs.reference(u, v, n, traffic)
    assert want.shape == (2, n) and want.dtype == np.int64
    assert want[0, sources[0]] == 0 and want[1].sum() == len(np.union1d(u, v))
    g = gm.build_graph(u, v, num_vertices=n)
    got, levels = bfs.run(g, None, traffic)
    checks = bfs.compare(np.asarray(got), want)
    assert [c["check"] for c in checks] == ["bfs_depth_mismatches", "bfs_reached_share"]
    assert all(c["ok"] for c in checks) and checks[0]["compared"] == n
    assert int(levels) == checks[0]["deepest"] + 1
    broken = bfs.compare(bfs.control(u, v, n, traffic), want)
    assert not broken[0]["ok"] and broken[0]["value"] > 0
    # a source in a component of two is a trivial job, and fails the second
    lone = np.concatenate([u + 2, [0]]), np.concatenate([v + 2, [1]])
    trivial = bfs.compare(bfs.reference(*lone, n + 2, traffic),
                          bfs.reference(*lone, n + 2, traffic))
    assert trivial[0]["ok"] and not trivial[1]["ok"]


def _many_levels():
    """A sparse uniform draw: a dozen levels whose frontiers send from a
    handful of messages to a third of them all."""
    rng = np.random.default_rng(7)
    u, v = rng.integers(0, 4000, 7000), rng.integers(0, 4000, 7000)
    return u, v, 4000, [_lowest_with_an_edge(u, v)]


def test_every_branch_is_taken_and_the_record_says_so(monkeypatch):
    u, v, n, sources = _many_levels()
    g, plan = _fused(u, v, n)
    sink = MetricsSink()
    want, levels = _run("frontier", g, plan, sources, 0, monkeypatch, sink)
    sent = [r for r in sink.records if r["phase"] == "superstep_delta"][0][
        "changed_messages"]
    ks = sorted(set(sent) - {0, max(sent)})
    assert len(ks) >= 4
    rungs = tuple(ks[i * (len(ks) - 1) // 3] for i in range(4))  # four of them, spread
    monkeypatch.setattr(superstep_policy, "delta_rungs", lambda num_messages: rungs)
    sink = MetricsSink()
    got, again = _run("frontier", g, plan, sources, 0, monkeypatch, sink)
    assert np.array_equal(got, want) and again == levels
    (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    assert delta["rungs"] == list(rungs)
    assert delta["branch"][0] == "fill"  # the sources' slots, into the fill
    assert set(delta["branch"]) == {"fill", *rungs, "full"}
    # a level's branch is the lowest rung its predecessor's K fits under; on
    # a graph this small one trip of the bottom-up loop looks at as many
    # places as the graph has messages, so no level turns
    for k, taken in zip(delta["changed_messages"], delta["branch"][1:]):
        assert taken == next((r for r in rungs if k <= r), "full")
    assert set(delta["direction"]) == {"top_down"} and set(delta["places"]) == {0}


def test_many_sources_start_with_a_full_gather(monkeypatch):
    u, v, n, _ = _many_levels()
    g, plan = _fused(u, v, n)
    monkeypatch.setattr(superstep_policy, "delta_rungs", lambda num_messages: (8, 64))
    sink = MetricsSink()
    sources = np.arange(0, n, 3)
    got, _ = _run("frontier", g, plan, sources, 0, monkeypatch, sink)
    assert np.array_equal(got, _want(u, v, n, sources, 0))
    (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    assert delta["branch"][0] == "full" and delta["source_messages"] > 64


# -- the bottom-up level (ISSUE 50, ISSUE 53) -----------------------------------

_CHUNK = 64  # a trip's places in these tests: spans straddle its end, trips are many


def _small_chunks(monkeypatch):
    monkeypatch.setattr(superstep_policy, "BOTTOM_UP_CHUNK", _CHUNK)


def _whole_trips(places, edges):
    """What a bottom-up level's loop ran over: whole trips of ``_CHUNK``
    places, as many as the edges before it take, more only where a trip ends
    with the spans it may cut (a quarter of its places) and not with its
    places; none where nothing is left to look at."""
    for ran, u in zip(places, edges, strict=True):
        trips, least = ran // _CHUNK, -(-u // _CHUNK)
        assert ran % _CHUNK == 0 and least <= trips <= max(4 * least, least + 1)


def _only_bottom_up(monkeypatch):
    """Every level of a search asks the unreached vertices, ``_CHUNK`` places
    a trip."""
    from graphmine_tpu.ops import paths

    _small_chunks(monkeypatch)
    monkeypatch.setattr(
        paths, "_next_update", lambda k, places, rungs, slots, stale: (len(rungs), True))


def _bottom_up_case(name, monkeypatch):
    """``(u, v, n, sources, max_depth, graph, plan)`` of a bottom-up case."""
    if name == "many_hubs":
        # the module: ops/__init__ exports a function under its name
        monkeypatch.setattr(
            importlib.import_module("graphmine_tpu.ops.bucketed_mode"), "_HIST_MIN_DEG", 24)
        u, v, n = _rmat(11, 8, seed=50)
        sources, max_depth = [_lowest_with_an_edge(u, v)], 0
    else:
        u, v, n, sources, max_depth = _case(name)
    g, plan = _fused(u, v, n)
    hubs = 0 if plan.hist_vertex_ids is None else plan.hist_vertex_ids.shape[0]
    assert hubs >= {"many_hubs": 40, "rmat_with_a_histogram_hub": 1}.get(name, 0)
    return u, v, n, sources, max_depth, g, plan


BOTTOM_UP_CASES = ["path", "grid", "several_sources", "unreached_components",
                   "isolated_source", "rmat_with_a_histogram_hub", "many_hubs"]


@pytest.mark.parametrize("name", BOTTOM_UP_CASES)
def test_bottom_up_levels_give_the_plain_references_depths(name, monkeypatch):
    """A search of bottom-up levels alone: the reference's depths, a level a
    superstep, on many levels, many sources, a graph whose other component
    and isolated vertices keep U above 0, and graphs with histogram hubs,
    whose ids stand in the message CSR like any other (R-MAT's four; and
    eighty, hubs that are each other's neighbours among them, with the hub
    cut patched down). The record's ``places`` are the U before the level
    rounded up to the loop's trips, and its ``branch`` says the same."""
    u, v, n, sources, max_depth, g, plan = _bottom_up_case(name, monkeypatch)
    _only_bottom_up(monkeypatch)
    sink = MetricsSink()
    got, levels = _run("frontier", g, plan, sources, max_depth, monkeypatch, sink)
    want = _want(u, v, n, sources, max_depth)
    assert np.array_equal(got, want)
    assert levels == int(want[want != int(UNREACHABLE)].max()) + 1
    validate_records(sink.records)
    (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    assert delta["direction"] == ["bottom_up"] * levels
    assert delta["reduce"] == ["none"] * levels and set(delta["dirty_slots"]) == {0}
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    unreached = [int(deg[want > d].sum()) for d in range(1, levels + 1)]
    assert delta["unreached_messages"] == unreached
    if name in ("unreached_components", "isolated_source"):
        assert unreached[-1] > 0  # the other component's edges, to the end
    before = [plan.num_messages - delta["source_messages"], *unreached]
    assert delta["places"] == delta["branch"]
    _whole_trips(delta["places"], before[:-1])
    assert max(delta["places"]) > 8 * _CHUNK  # many trips


@pytest.mark.parametrize("name", BOTTOM_UP_CASES)
def test_the_bottom_up_level_equals_the_row_min_bit_for_bit(name, monkeypatch):
    """Level by level down a search, beside ``bfs_level_from_rows`` over rows
    gathered anew: the same depths from the level that reads the message
    CSR, at a chunk of a few places (every longer span straddles a trip's
    end, the last trip is part empty), at the chunk U passes by one place
    and at the one it fills, and past the last level (U = 0, or the edges
    no path reaches)."""
    bm = importlib.import_module("graphmine_tpu.ops.bucketed_mode")
    u, v, n, sources, max_depth, g, plan = _bottom_up_case(name, monkeypatch)
    rows_level = jax.jit(lambda depth: bm.bfs_level_from_rows(
        bm.gather_depth_rows(np.zeros(bm.row_slots(plan), np.int32), depth, plan),
        depth, plan))
    bottom_up = jax.jit(
        lambda depth, chunk: bm.bfs_level_bottom_up(
            depth, *bm.compact_unreached(depth, g.msg_ptr), g.msg_send, chunk)[0],
        static_argnames="chunk")
    deg = np.diff(np.asarray(g.msg_ptr))
    depth = np.full(n, int(UNREACHABLE), np.int32)
    depth[np.asarray(sources)] = 0
    edges, fitted = [], False
    for _ in range(n):
        want = np.asarray(rows_level(depth))
        left = int(deg[depth == int(UNREACHABLE)].sum())
        edges.append(left)
        chunks = [7]
        if not fitted and left > 2:
            chunks += [left - 1, left]  # one place into a second trip; a full trip
            fitted = True
        for chunk in chunks:
            assert np.array_equal(np.asarray(bottom_up(depth, chunk=chunk)), want), chunk
        if np.array_equal(want, depth):
            break
        depth = want
    assert fitted  # and the last level looked and found nothing
    if name in ("grid", "path"):
        assert edges[-1] == 0  # every vertex with an edge reached: no place, no trip
    elif name in ("unreached_components", "isolated_source"):
        assert edges[-1] > 0
    assert np.array_equal(depth, _want(u, v, n, sources, 0))


def _turning_search(monkeypatch):
    _small_chunks(monkeypatch)
    rungs = (40, 400, 1500, 5000)
    monkeypatch.setattr(superstep_policy, "delta_rungs", lambda num_messages: rungs)
    return rungs


def test_a_search_turns_once_and_issues_no_rewrite_after(monkeypatch):
    """Top-down on rungs, a full gather, then bottom-up to the end: the
    record says so, and over the stale rows no rewrite is issued."""
    from graphmine_tpu.ops import paths
    from graphmine_tpu.ops.bucketed_mode import row_slots

    u, v, n, sources = _many_levels()
    g, plan = _fused(u, v, n)
    rungs = _turning_search(monkeypatch)
    issued = []
    for program in ("_gather_program", "_rewrite_program", "_level_program",
                    "_unreached_program", "_bottom_up_program"):
        def told(*a, _run=getattr(paths, program), _name=program, **k):
            issued.append(_name.strip("_").removesuffix("_program"))
            return _run(*a, **k)
        monkeypatch.setattr(paths, program, told)
    sink = MetricsSink()
    got, levels = _run("frontier", g, plan, sources, 0, monkeypatch, sink)
    assert np.array_equal(got, _want(u, v, n, sources, 0))
    (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    turn = delta["direction"].index("bottom_up")
    assert delta["direction"] == ["top_down"] * turn + ["bottom_up"] * (levels - turn)
    assert delta["branch"][0] == "fill" and turn >= 3 and levels - turn >= 2
    assert "full" in delta["branch"][:turn] and set(delta["branch"][1:turn]) <= {*rungs, "full"}
    assert delta["reduce"] == ["full"] * turn + ["none"] * (levels - turn)
    gathers = delta["branch"].count("full")
    assert issued == (
        ["rewrite", "level"] * (turn - gathers) + ["gather", "level"] * gathers
        + ["unreached", "bottom_up"] * (levels - turn))
    # the places a bottom-up level looked at: the U its predecessor left, in
    # whole trips; one program whatever their number
    assert delta["places"][:turn] == [0] * turn
    _whole_trips(delta["places"][turn:], delta["unreached_messages"][turn - 1:-1])
    assert delta["branch"][turn:] == delta["places"][turn:]
    assert len(set(delta["places"][turn:])) >= 2
    # the rule, level for level, from the counts the record holds
    ks = [delta["source_messages"], *delta["changed_messages"]]
    us = [plan.num_messages - ks[0], *delta["unreached_messages"]]
    for i, way in enumerate(delta["direction"]):
        places = -(-us[i] // _CHUNK) * _CHUNK
        assert paths._next_update(
            ks[i], places, rungs, row_slots(plan), stale=i > turn)[1] == (way == "bottom_up")


def test_stale_rows_are_gathered_anew_where_that_is_the_cheaper(monkeypatch):
    """After a bottom-up level the alternative is a full gather, never a
    rewrite: a search made to turn early comes back through one."""
    from graphmine_tpu.ops import paths

    u, v, n, sources = _many_levels()
    g, plan = _fused(u, v, n)
    _turning_search(monkeypatch)
    rule, asked = paths._next_update, []

    def turn_early(k, places, rungs, slots, stale):
        asked.append(stale)
        if len(asked) == 2:  # the second level, whatever it would cost
            return len(rungs), True
        return rule(k, places, rungs, slots, stale)

    monkeypatch.setattr(paths, "_next_update", turn_early)
    sink = MetricsSink()
    got, _ = _run("frontier", g, plan, sources, 0, monkeypatch, sink)
    assert np.array_equal(got, _want(u, v, n, sources, 0))
    (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    assert delta["direction"][:3] == ["top_down", "bottom_up", "top_down"]
    assert delta["branch"][2] == "full" and asked[:4] == [False, False, True, False]
    assert delta["places"][0] == delta["places"][2] == 0
    _whole_trips(delta["places"][1:2], delta["unreached_messages"][:1])


_G500_24 = dict(rungs=delta_rungs(520_752_272), slots=542_524_857)


@pytest.mark.parametrize("k, places, stale, want", [
    (50, 10**6, False, (1, False)),    # K's rung, U far above it
    (50, 60, False, (1, True)),        # U under K's rung: a place costs about the same
    (50, 110, False, (1, False)),      # U over K's rung by more than the costs differ
    (5, 9, False, (0, True)),          # on the lowest rung, which costs all its places
    (10**6, 900, False, (4, True)),    # in a full gather's place
    (10**6, 3000, False, (4, False)),  # 3,000 places at 39 ns against 6,000 slots at 7.4
    (10**6, 1100, False, (4, True)),   # ... and under a fifth of the slots
    (5, 900, True, (4, True)),         # stale rows: no rewrite, whatever K
    (5, 3000, True, (4, False)),       # stale rows and too many places: gather
    (5, 0, True, (4, True)),           # nothing left to look at costs nothing
])
def test_the_rule_weighs_what_a_place_of_each_update_costs(k, places, stale, want):
    from graphmine_tpu.ops import paths
    from graphmine_tpu.ops.paths import _next_update

    assert _next_update(k, places, (10, 100, 1000, 5000), 6000, stale) == want
    # with no rung the one top-down update is the gather
    assert _next_update(k, places, (), 6000, stale) == (
        0, places * paths._BOTTOM_UP_PLACE_NS < 6000 * paths._GATHERED_SLOT_NS)


@pytest.mark.parametrize("level, k, u, want", [
    # graph500-24 from the benchmark's source (PERF.md §5, PR 50): what each
    # level's predecessor left, and the update the level takes
    (1, 1, 520_752_271, ("top_down", 127_136)),
    (2, 718, 520_751_553, ("top_down", 127_136)),
    (3, 5_252_974, 515_498_579, ("top_down", 32_547_017)),
    (4, 447_287_276, 68_211_303, ("bottom_up", 68_681_728)),  # K fits no rung: 131 trips
    (5, 67_957_465, 253_838, ("bottom_up", 1 << 19)),
    (6, 246_930, 6_908, ("bottom_up", 1 << 19)),
    (7, 719, 6_189, ("bottom_up", 1 << 19)),
    (8, 1, 6_188, ("bottom_up", 1 << 19)),
])
def test_the_rule_turns_graph500_24_at_its_fourth_level(level, k, u, want):
    from graphmine_tpu.ops.paths import _next_update

    chunk = bottom_up_chunk(520_752_272)
    assert chunk == 1 << 19
    places = -(-u // chunk) * chunk
    place, bottom_up = _next_update(k, places, stale=level > 4, **_G500_24)
    took = places if bottom_up else [*_G500_24["rungs"], "full"][place]
    assert ("bottom_up" if bottom_up else "top_down", took) == want
    assert abs(places - u) < chunk


def test_auto_takes_the_frontier_job_past_the_crossover_and_not_below_it():
    u, v, n = _rmat(12, 16, seed=5)
    g = gm.build_graph(u, v, num_vertices=n)
    assert g.num_messages >= superstep_policy.BUCKETED_MIN_MESSAGES
    sink = MetricsSink()
    source = [_lowest_with_an_edge(u, v)]
    got = np.asarray(gm.bfs_distances(g, source, direction="both", sink=sink))
    assert np.array_equal(got, _want(u, v, n, source, 0))
    validate_records(sink.records)
    phases = [r["phase"] for r in _records(sink)]
    ran = phases.count("program_memory")  # a record a program, when the job ends
    assert ran >= 3 and phases == [
        "impl_selected", "plan_build", "device_residency", "superstep_delta",
        "fixpoint", *["program_memory"] * ran]
    picked, _, held = _records(sink)[:3]
    assert picked["op"] == held["op"] == "bfs_level" and picked["impl"] == "bucketed"
    assert picked["scan"] == held["scan"] == "carried" and "row_min" in held["reason"]
    assert held["rows_bytes"] > 0 and held["slot_index_bytes"] > 0
    # the plan is the one CDLP and WCC build for this graph, found again
    sink = MetricsSink()
    gm.bfs_distances(g, source, direction="both", sink=sink)
    assert [r["cached"] for r in sink.records if r["phase"] == "plan_build"] == [True]
    assert lpa._cached_auto_plan(g)[0].out_slot is None  # the index stays out of it
    small = gm.build_graph(u[:500], v[:500], num_vertices=n)
    sink = MetricsSink()
    gm.bfs_distances(small, [int(u[0])], direction="both", sink=sink)
    assert [(r["phase"], r.get("impl", r.get("program"))) for r in _records(sink)] == [
        ("impl_selected", "sort"), ("program_memory", "loop")]


def test_a_directed_search_a_trace_and_an_unfused_plan_keep_the_loop(monkeypatch):
    u, v, n, sources, _ = _case("flat")
    g, plan = _fused(u, v, n)
    want = _want(u, v, n, sources, 0)

    def no_job(*a, **k):
        raise AssertionError("a host-stepped job ran")

    from graphmine_tpu.ops import paths

    monkeypatch.setattr(paths, "_frontier_job", no_job)
    monkeypatch.setattr(paths, "_full_width_job", no_job)
    src = np.asarray(sources)
    # under a caller's trace no count is concrete: the loop, the same depths
    traced = jax.jit(lambda g, s: gm.bfs_distances(g, s, direction="both", plan=plan))
    assert np.array_equal(np.asarray(traced(g, src)), want)
    dist, parent = bfs_parents(g, src, direction="both")
    assert np.array_equal(np.asarray(dist), want) and int(parent[sources[0]]) == -1
    # edges as drawn: the message arrays of the edge list
    one_way = bfs.control(u, v, n, {"source": sources})[0]
    out = np.asarray(gm.bfs_distances(g, src, direction="out", plan=plan))
    assert np.array_equal(out.astype(np.int64)[one_way != bfs.UNREACHED],
                          one_way[one_way != bfs.UNREACHED])
    unfused = gm.ops.BucketedModePlan.from_graph(g)
    assert unfused.send_idx is None
    assert np.array_equal(
        np.asarray(gm.bfs_distances(g, src, direction="both", plan=unfused)), want)
    with pytest.raises(ValueError, match="plan must be"):
        gm.bfs_distances(g, src, direction="both", plan="bucketed")


# -- the one stepping loop -----------------------------------------------------


def _stub_loop(ks, moved=None, **kw):
    """``step_carried_rows`` over stub programs that report the K's of
    ``ks`` (and the counts of ``moved``): the calls it makes, in order."""
    calls = []
    feed = iter(zip(ks, moved or [1] * len(ks)))

    def modes(rows, labels):
        calls.append(("modes",))
        k, count = next(feed)
        return labels, f"changed{len(calls)}", np.int32(k), np.int32(count)

    _, per_step = step_carried_rows(
        kw.pop("max_iter", len(ks)), (10, 100, 1000), kw.pop("over", 10**6),
        "rows", "labels",
        gather=lambda rows, labels: calls.append(("gather",)) or rows,
        rewrite=lambda rows, labels, changed, cap: calls.append(
            ("rewrite", cap, changed)) or rows,
        modes=modes, **kw,
    )
    return calls, per_step


def test_cdlps_arguments_step_as_they_did():
    """No start and no stop stated: the first superstep gathers in full
    (``over`` is a K above every rung), and a superstep that moves nothing
    does not end the loop: ``max_iter`` does."""
    calls, per_step = _stub_loop([7, 0, 500, 2000, 0], moved=[3, 0, 9, 4, 0])
    assert calls == [
        ("gather",), ("modes",),
        ("rewrite", 10, "changed2"), ("modes",),
        ("rewrite", 10, "changed4"), ("modes",),
        ("rewrite", 1000, "changed6"), ("modes",),
        ("gather",), ("modes",),
    ]
    assert per_step == {
        "changed_vertices": [3, 0, 9, 4, 0], "changed_messages": [7, 0, 500, 2000, 0],
        "branch": [3, 0, 0, 2, 3], "reduce": ["full"] * 5,
        "dirty_rows": [None] * 5, "dirty_slots": [None] * 5,
    }


def test_the_stop_is_an_argument_of_the_one_loop():
    """BFS's, where its rows were not admitted: the loop ends with the first
    superstep that moves nothing, or at ``max_iter``. (The search over
    carried rows steps itself, ISSUE 50: the loop has no start to take.)"""
    calls, per_step = _stub_loop(
        [50, 5000, 3, 0, 99], moved=[2, 40, 1, 0, 7], max_iter=9, until_quiet=True)
    assert calls == [
        ("gather",), ("modes",),
        ("rewrite", 100, "changed2"), ("modes",),
        ("gather",), ("modes",),
        ("rewrite", 10, "changed6"), ("modes",),
    ]
    assert per_step["branch"] == [3, 1, 3, 0]
    assert per_step["changed_vertices"] == [2, 40, 1, 0]
    cut, per_step = _stub_loop([50, 60, 70], max_iter=2, until_quiet=True)
    assert len(cut) == 4 and per_step["changed_messages"] == [50, 60]
    ticks = iter(range(100))
    _, timed = _stub_loop([5, 0, 5], moved=[1, 0, 1], until_quiet=True,
                          clock=lambda: next(ticks))
    assert timed["seconds"] == [1, 1]
    with pytest.raises(TypeError, match="changed"):
        _stub_loop([5], changed="sources")


# -- the admission answers for this job ----------------------------------------


def test_the_admission_sizes_the_bfs_jobs_own_programs(monkeypatch):
    from graphmine_tpu.obs import memmodel

    u, v, n = _rmat(12, 16, seed=5)
    g, plan = _fused(u, v, n)
    top = max(delta_rungs(plan.num_messages))
    cdlp = memmodel.carried_rows_inventory(plan, top_rung=top)
    need = memmodel.carried_rows_inventory(plan, top_rung=top, reduce="min")
    chunk = bottom_up_chunk(plan.num_messages)
    # a trip takes no more places than the graph has messages
    assert chunk == plan.num_messages < superstep_policy.BOTTOM_UP_CHUNK == 1 << 19
    sized = dict(top_rung=top, reduce="min", bottom_up_chunk=chunk)
    need = memmodel.carried_rows_inventory(plan, **sized)
    programs = memmodel.carried_job_transients(plan, **sized)
    assert sorted(programs) == ["bottom_up", "gather", "rewrite", "row_min"]
    # the level that reads no row: a trip's chunk-long vectors beside the
    # level's V-vectors, whatever the level's size
    assert 4 * (6 * chunk + 3 * n) < programs["bottom_up"] <= 4 * 8 * (chunk + n + 1)
    assert programs["bottom_up"] > memmodel.carried_job_transients(
        plan, top_rung=top, reduce="min", bottom_up_chunk=chunk // 2)["bottom_up"]
    assert memmodel.carried_job_transients(plan, top, reduce="min")["bottom_up"] == 0
    assert bottom_up_chunk(5) == 5 and bottom_up_chunk(0) == 1  # no more than M places
    assert cdlp["hub_histograms"] > 0 and need["hub_histograms"] == 0
    assert need["gather_transient"] == max(programs.values())
    for same in ("carried_rows", "slot_index", "labels", "changed_mask"):
        assert need[same] == cdlp[same]
    assert programs["gather"] == memmodel.carried_job_transients(plan, top)["gather"]
    # at this size a trip of the bottom-up loop (as many places as the graph has
    # messages) is the job's largest program, and BFS asks for more than CDLP
    total, less = sum(need.values()), sum(cdlp.values())
    assert need["gather_transient"] == programs["bottom_up"] and total > less
    room = {"bytes_limit": total, "bytes_in_use": 0}
    scan, reason = admit_carried_rows(plan, room, reduce="min")
    assert scan == "carried" and "row_min" in reason and "modes" not in reason
    assert f"bottom_up {programs['bottom_up']} B" in reason
    room["bytes_limit"] -= 1
    assert admit_carried_rows(plan, room, reduce="min")[0] == "plain"
    assert admit_carried_rows(plan, room)[0] == "carried"  # CDLP's own programs fit
    # one index a plan, one answer a job: CDLP is admitted, BFS refused
    _squeeze(monkeypatch, less)
    assert lpa._cached_slot_index(plan, reduce="min")[2][0] == "plain"
    indexed, _, scan = lpa._cached_slot_index(plan)
    assert scan[0] == "carried" and indexed.out_slot is not None
    assert lpa._cached_slot_index(plan, reduce="min")[0].out_slot is None
    assert lpa._cached_slot_index(plan)[0].out_slot is indexed.out_slot
