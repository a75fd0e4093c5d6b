"""LDBC Graphalytics' LCC through ``gm.clustering_coefficient`` (ISSUE 46):
the exact kernel that lists no wedge (``ops/triangles.py``: a plan, the
core's bit rows, the tail's row pairs) against a plain set-intersection
reference and against the benchmark's own float64 reference
(``benchmark/algorithms/lcc.py``), the edge cases of the simple undirected
graph, the three ways a plan splits its wedges, the two count words, and
what fails the 1e-4 match: counts held in bfloat16 and the sampled
estimator.

All on the CPU backend at scales 9 to 12; what only a chip shows (seconds,
the allocator's peak at graph500-22) is the cell ``lcc-g500-22``'s.
"""

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.obs.spans import Tracer
from graphmine_tpu.ops import triangles
from graphmine_tpu.ops.ktruss import k_truss
from graphmine_tpu.pipeline.metrics import MetricsSink

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
sys.path.insert(0, BENCH)
import generators  # noqa: E402

LIMIT = 1e-4


def _load_algorithm():
    spec = importlib.util.spec_from_file_location(
        "under_test_algorithms_lcc", os.path.join(BENCH, "algorithms", "lcc.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ALGORITHM = _load_algorithm()


def _rmat(scale, seed):
    u, v = generators.make("rmat_undirected", {"scale": scale, "edge_factor": 16,
                                               "a": 0.57, "b": 0.19, "c": 0.19}, seed)
    return u, v, 1 << scale


def _by_sets(u, v, n):
    """The definition word for word: neighbour sets, ordered pairs of them
    that are an edge, over ``d (d - 1)``. Returns the coefficients (float64)
    and the triangles through each vertex."""
    nbrs = [set() for _ in range(n)]
    for a, b in zip(np.asarray(u).tolist(), np.asarray(v).tolist()):
        if a != b:
            nbrs[a].add(b)
            nbrs[b].add(a)
    lcc, tri = np.zeros(n), np.zeros(n, np.int64)
    for x in range(n):
        d = len(nbrs[x])
        closed = sum(len(nbrs[x] & nbrs[y]) for y in nbrs[x])  # ordered pairs
        tri[x] = closed // 2
        if d >= 2:
            lcc[x] = closed / (d * (d - 1))
    return lcc, tri


def _gap(got, want):
    record = ALGORITHM.compare(np.asarray(got), want)
    return record[0]["value"], all(r["ok"] for r in record)


@pytest.mark.parametrize("scale,seed", [(9, 3), (10, 5), (11, 7)])
def test_the_kernel_equals_the_set_intersection_reference_on_rmat(scale, seed):
    u, v, n = _rmat(scale, seed)
    want, tri = _by_sets(u, v, n)
    graph = gm.build_graph(u, v, num_vertices=n)
    gap, ok = _gap(gm.clustering_coefficient(graph), want)
    assert ok and gap < 1e-6, gap
    counts, total = gm.triangle_count(graph)
    np.testing.assert_array_equal(np.asarray(counts), tri)
    assert total == tri.sum() // 3


def test_the_benchmarks_float64_reference_equals_the_set_intersection_one():
    u, v, n = _rmat(10, 11)
    want, _ = _by_sets(u, v, n)
    np.testing.assert_allclose(ALGORITHM.reference(u, v, n, {}), want, rtol=1e-12)
    # and on edges drawn both ways, twice, with self-loops
    uu = np.concatenate([u, v, u, np.arange(20)])
    vv = np.concatenate([v, u, v, np.arange(20)])
    np.testing.assert_allclose(ALGORITHM.reference(uu, vv, n, {}), want, rtol=1e-12)


EDGE_CASES = {
    "clique": (np.repeat(np.arange(6), 6), np.tile(np.arange(6), 6), 8,
               [1, 1, 1, 1, 1, 1, 0, 0]),
    "star": (np.zeros(7, int), np.arange(1, 8), 8, [0] * 8),
    "path": (np.arange(5), np.arange(1, 6), 6, [0] * 6),
    "isolated": (np.array([4, 5, 6]), np.array([5, 6, 4]), 9,
                 [0, 0, 0, 0, 1, 1, 1, 0, 0]),
    "duplicate_reversed_loops": (
        np.array([0, 1, 1, 2, 0, 2, 2, 3, 3, 0, 0]),
        np.array([1, 0, 2, 1, 2, 0, 3, 2, 3, 0, 1]), 5,
        # triangle 0-1-2 with a pendant 3 on 2: LCC(2) = 2 * 1 / (3 * 2)
        [1, 1, 1 / 3, 0, 0]),
    "no_edges": (np.zeros(0, int), np.zeros(0, int), 4, [0] * 4),
}


@pytest.mark.parametrize("name", sorted(EDGE_CASES))
def test_edge_cases_of_the_simple_undirected_graph(name):
    u, v, n, want = EDGE_CASES[name]
    graph = gm.build_graph(u, v, num_vertices=n)
    got = np.asarray(gm.clustering_coefficient(graph))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got.dtype == np.float32
    np.testing.assert_allclose(ALGORITHM.reference(u, v, n, {}), want, rtol=1e-12)


def _counts(plan):
    lo, hi, _ = triangles._count(plan)
    assert not np.asarray(hi).any()
    return np.asarray(lo).astype(np.int64)


def test_core_tail_and_the_wedges_that_straddle_them_add_up_to_the_whole():
    u, v, n = _rmat(11, 13)
    _, tri = _by_sets(u, v, n)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = triangles._build_plan(graph, core_vertices=64)
    stats = plan.stats
    assert stats["core_vertices"] == 64 and plan.core_classes and plan.tail_classes
    assert stats["wedges_core"] > 0 and stats["wedges_tail"] > 0
    # centres outside the core with neighbours inside it: their rows straddle
    outside = [centres for *_, centres in plan.core_classes
               if (np.asarray(centres) < plan.core_start).any()]
    assert outside
    np.testing.assert_array_equal(_counts(plan), tri)
    core_only = triangles._LccPlan(**{**plan.__dict__, "tail_classes": []})
    tail_only = triangles._LccPlan(**{**plan.__dict__, "core_classes": []})
    in_core, in_tail = _counts(core_only), _counts(tail_only)
    assert in_core.sum() > 0 and in_tail.sum() > 0
    np.testing.assert_array_equal(in_core + in_tail, tri)


@pytest.mark.parametrize("core", [64, "all"])
def test_the_plan_lays_out_every_centres_core_neighbours_as_its_csr_row_has_them(core):
    """A core class reads a block's neighbour ranks as one slice of rows the
    plan laid out (ISSUE 48), so those rows have to be what a window of the
    CSR was: for every centre with two neighbours or more in the core, its
    higher-ranked neighbours of rank ``core_start`` and up, ascending, in
    the first ``length`` of its ``w`` slots; whole blocks, the padding rows
    of length 0; and ``core_slots`` counts the slots, padding in."""
    u, v, n = _rmat(11, 13)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = triangles._build_plan(graph, core_vertices=64 if core == 64 else n)
    rank = np.asarray(plan.rank)
    higher = [[] for _ in range(n)]  # by rank: the higher-ranked neighbours in the core
    for a, b in set(zip(np.minimum(u, v).tolist(), np.maximum(u, v).tolist())):
        if a != b:
            low, high = sorted((int(rank[a]), int(rank[b])))
            if high >= plan.core_start:
                higher[low].append(high)
    slots, seen = 0, []
    for w, nb, blocks, rows, lens, centres in plan.core_classes:
        assert rows.shape == (blocks * nb * w,) and rows.dtype == jnp.int32
        assert lens.shape == centres.shape == (blocks * nb,)
        rows, lens, centres = np.asarray(rows).reshape(-1, w), np.asarray(lens), np.asarray(centres)
        held = int(np.count_nonzero(lens))
        assert (lens[:held] >= 2).all() and not lens[held:].any() and (lens <= w).all()
        assert (np.diff(lens) <= 0).all()  # a block's loops stop at its first row's length
        for row, length, centre in zip(rows[:held], lens[:held], centres[:held]):
            assert row[:length].tolist() == sorted(higher[centre]), (w, centre)
        slots += blocks * nb * w
        seen += centres[:held].tolist()
    assert sorted(seen) == [c for c in range(n) if len(higher[c]) >= 2]
    assert plan.stats["core_slots"] == slots > 0
    assert plan.stats["core_rows"] == sum(len(higher[c]) for c in seen)


def test_the_plan_holds_its_core_rows_at_four_bytes_a_slot_and_no_class_reads_the_csr():
    u, v, n = _rmat(11, 13)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = triangles._build_plan(graph, core_vertices=64)
    held = [plan.rank, plan.degree, plan.col, plan.bits, plan.tail_table]
    held += [x for c in plan.core_classes + plan.tail_classes for x in c[3:]]
    assert plan.stats["resident_bytes"] == sum(x.nbytes for x in held)
    assert sum(c[3].nbytes for c in plan.core_classes) == 4 * plan.stats["core_slots"]
    # the CSR stays for the tail's table alone: the core's programs count without it
    core_only = triangles._LccPlan(**{**plan.__dict__, "tail_classes": [], "col": None})
    assert _counts(core_only).sum() > 0


@pytest.mark.parametrize("core", ["none", "all"])
def test_an_empty_core_and_a_core_of_every_vertex_count_the_same(core):
    u, v, n = _rmat(10, 17)
    _, tri = _by_sets(u, v, n)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = triangles._build_plan(graph, core_vertices=0 if core == "none" else n)
    assert bool(plan.core_classes) == (core == "all")
    assert bool(plan.tail_classes) == (core == "none")
    wedges = plan.stats["wedges_core"] + plan.stats["wedges_tail"]
    assert plan.stats["wedges_core" if core == "all" else "wedges_tail"] == wedges
    np.testing.assert_array_equal(_counts(plan), tri)


@pytest.mark.parametrize("tail", ["table", "windows"])
def test_the_tails_table_of_rows_and_its_windows_of_the_csr_count_the_same(tail, monkeypatch):
    """Where the padded rows of the tail's vertices fit their budget a job
    fetches them whole; where they do not it cuts two windows an edge out of
    the CSR. Both with no core at all, so every triangle is the tail's."""
    u, v, n = _rmat(10, 37)
    _, tri = _by_sets(u, v, n)
    graph = gm.build_graph(u, v, num_vertices=n)
    if tail == "windows":
        monkeypatch.setattr(triangles, "_TAIL_TABLE_MAX", 0)
    plan = triangles._build_plan(graph, core_vertices=0)
    assert (plan.tail_table is not None) == (tail == "table")
    assert bool(plan.stats["tail_table_rows"]) == (tail == "table")
    if tail == "table":
        table = np.asarray(plan.tail_table)
        assert table.shape[1] % 128 == 0 and (table >= -1).all()
    np.testing.assert_array_equal(_counts(plan), tri)


def _one_middle_under_many_edges():
    """A middle outside every core with 45 lower neighbours and a row of 80:
    vertex 130 is joined to 80 of a clique of 130 hubs (all of higher degree,
    so its row is those 80: width class 96) and to 45 vertices of degree 5,
    each of which also sees a hub the middle sees (a triangle through the
    middle's row), a hub it does not, and the next of its own kind. Hubs 33
    to 64 of the clique are middles of that class too, under 30 to 110
    edges each."""
    hubs, middle = np.arange(130), 130
    lows = middle + 1 + np.arange(45)
    a, b = np.triu_indices(130, 1)
    u = np.concatenate([hubs[a], np.full(80, middle), np.full(45, middle),
                        lows, lows, lows[:-1]])
    v = np.concatenate([hubs[b], hubs[:80], lows,
                        hubs[np.arange(45) % 80], hubs[80 + np.arange(45) % 50], lows[1:]])
    return u, v, int(lows[-1]) + 1, middle


def _one_edge_a_middle():
    """Forty middles of degree 6, each under one edge: five of eight hubs
    that no edge joins (so no hub is a middle) and one vertex of degree 2
    that sees the middle and one of the middle's hubs, a triangle each."""
    hubs, middles, lows = np.arange(8), 8 + np.arange(40), 48 + np.arange(40)
    to_hubs = (np.arange(40)[:, None] + np.arange(5)[None, :]) % 8
    u = np.concatenate([np.repeat(middles, 5), lows, lows])
    v = np.concatenate([hubs[to_hubs].ravel(), middles, hubs[to_hubs[:, 2]]])
    return u, v, 88


TAIL_GRAPHS = {
    "one_edge_a_middle": _one_edge_a_middle,
    "one_middle_under_many_edges": lambda: _one_middle_under_many_edges()[:3],
    "rmat": lambda: _rmat(10, 41),
}


@pytest.mark.parametrize("name", sorted(TAIL_GRAPHS))
def test_the_tail_credits_a_middles_row_once_a_middle_and_counts_as_the_sets_do(name, monkeypatch):
    """Every tail class sums an edge's matches along its middle's run before
    the scatter, and counts the same triangles as the sets do: where every
    middle lies under one edge (a run of one is summed like any other),
    where one lies under 45, and on a Kronecker draw. Blocks of 32 edges at
    width 96, so a run of 45 edges lies in two blocks at least."""
    u, v, n = TAIL_GRAPHS[name]()
    _, tri = _by_sets(u, v, n)
    graph = gm.build_graph(u, v, num_vertices=n)
    monkeypatch.setattr(triangles, "_TAIL_BLOCK_COMPARES", 32 * 256 * 96)
    plan = triangles._build_plan(graph, core_vertices=0)
    np.testing.assert_array_equal(_counts(plan), tri)
    if name == "one_edge_a_middle":
        (w, ne, blocks, *_, run_len, _), = plan.tail_classes
        assert w == 6 and np.count_nonzero(np.asarray(run_len)) == plan.stats["tail_edges"] == 40
        assert tri.sum() == 3 * 40
    if name == "one_middle_under_many_edges":
        w, ne, blocks, *_, middles = next(c for c in plan.tail_classes if c[0] == 96)
        assert ne == 32 and blocks > 2
        rank = int(np.asarray(plan.rank)[_one_middle_under_many_edges()[3]])
        in_blocks = (np.asarray(middles).reshape(blocks, -1) == rank).any(axis=1)
        assert in_blocks.sum() >= 2  # the run straddles a block's end: credited in both


@pytest.mark.parametrize("ne", [64, 256, 1000])
def test_runs_by_middle_groups_a_middles_edges_and_deals_the_runs_evenly(ne):
    rng = np.random.default_rng(ne)
    # 300 middles under 1 to 40 edges each, as the vertex ranks have them: in any order
    mid = rng.permutation(np.repeat(rng.choice(10_000, 300, replace=False),
                                    rng.integers(1, 41, 300)))
    order, seg, ns, lead = triangles._runs_by_middle(mid, ne)
    assert sorted(order.tolist()) == list(range(len(mid)))
    blocks = -(-len(mid) // ne)
    assert ns % 8 == 0 and lead.shape == (blocks, ns)
    block = np.arange(len(mid)) // ne
    middles = np.where(lead >= 0, mid[order][lead], -1)
    # every edge slot's run is its own middle's, runs ascend from 0 within a block
    np.testing.assert_array_equal(middles[block, seg], mid[order])
    assert (seg[::ne] == 0).all() and (np.diff(seg)[block[1:] == block[:-1]] >= 0).all()
    assert (np.diff(seg)[block[1:] == block[:-1]] <= 1).all()
    # a middle's edges are neighbours
    firsts = np.flatnonzero(np.r_[True, mid[order][1:] != mid[order][:-1]])
    assert len(firsts) == 300
    # the whole blocks hold like numbers of runs: none a quarter over the mean
    held = (middles >= 0).sum(axis=1)
    if blocks > 2:
        assert held[:-1].max() <= 1.25 * held[:-1].mean() + 2, held


def test_the_plan_counts_its_tail_middles_and_the_slots_its_credits_scatter():
    u, v, n = _rmat(11, 13)
    graph = gm.build_graph(u, v, num_vertices=n)
    plan = triangles._build_plan(graph, core_vertices=64)
    stats = plan.stats
    slots, middles, fewer = 0, set(), False
    for w, ne, blocks, _, _, mid_len, _, run, _, run_len, middle in plan.tail_classes:
        ns = len(middle) // blocks
        assert len(run) == blocks * ne and int(np.asarray(run).max()) < ns <= ne
        slots += blocks * ns * w
        edges = int(np.count_nonzero(np.asarray(mid_len)))
        here = set(np.asarray(middle)[np.asarray(run_len) > 0].tolist())
        assert not here & middles  # a middle's row has one width
        middles |= here
        if len(here) < edges // 2:  # middles repeat: fewer slots than the edges would take
            fewer = True
            assert blocks * ns * w < edges * w
    assert fewer
    assert stats["tail_credit_slots"] == slots < stats["tail_edges"] * max(
        c[0] for c in plan.tail_classes)
    assert stats["tail_middles"] == len(middles)
    sink = MetricsSink(tracer=Tracer())
    triangles._count(plan, sink)
    span, = [r for r in sink.records if r["phase"] == "span" and r["name"] == "lcc_tail"]
    assert span["credit_slots"] == slots and span["edges"] == stats["tail_edges"]


def test_the_plan_is_built_once_per_graph_and_says_what_it_holds():
    u, v, n = _rmat(10, 19)
    graph = gm.build_graph(u, v, num_vertices=n)
    sink = MetricsSink()
    first = np.asarray(gm.clustering_coefficient(graph, sink=sink))
    again = np.asarray(gm.clustering_coefficient(graph, sink=sink))
    np.testing.assert_array_equal(first, again)
    built, found = [r for r in sink.records if r["phase"] == "plan_build"]
    assert built["op"] == "lcc" and not built["cached"] and found["cached"]
    assert found["seconds"] == 0.0
    for key in ("core_vertices", "core_edges", "classes", "wedges_core",
                "wedges_tail", "resident_bytes", "padded_slots_per_edge",
                "tail_middles", "tail_credit_slots", "core_slots"):
        assert key in built, key
    above = np.asarray(triangles.oriented_wedge_count(graph))
    # oriented wedges sum d+^2; the plan counts the pairs sum d+ (d+ - 1) / 2
    pairs = built["wedges_core"] + built["wedges_tail"]
    assert 2 * pairs < above
    assert ALGORITHM.facts(sink.records)["wedges_total"] == pairs


def test_counts_held_in_bfloat16_fail_the_tolerance():
    u, v, n = _rmat(11, 23)
    want = ALGORITHM.reference(u, v, n, {})
    graph = gm.build_graph(u, v, num_vertices=n)
    lo, hi, degree = triangles._triangles(graph)
    gap, ok = _gap(triangles._coefficient(lo, hi, degree), want)
    assert ok and gap < 1e-6
    rounded = lo.astype(jnp.bfloat16).astype(jnp.float32).astype(jnp.uint32)
    gap, ok = _gap(triangles._coefficient(rounded, hi, degree), want)
    assert not ok and gap > 10 * LIMIT, gap


def test_a_float32_running_sum_stops_counting_past_2_to_the_24_and_the_words_do_not():
    total = np.float32(2 ** 24)  # credits arrive a block at a time
    for _ in range(2):
        total = np.float32(total + np.float32(1))
    assert total == np.float32(2 ** 24)
    lo, hi = jnp.uint32(2 ** 24), jnp.uint32(0)
    for _ in range(2):
        lo, hi = triangles._add64(lo, hi, jnp.uint32(1))
    assert int(lo) == 2 ** 24 + 2 and int(hi) == 0


def test_the_sampled_estimator_fails_the_tolerance():
    u, v, n = _rmat(11, 29)
    want = ALGORITHM.reference(u, v, n, {})
    graph = gm.build_graph(u, v, num_vertices=n)
    gap, ok = _gap(gm.sampled_clustering_coefficient(graph, samples=64, seed=0), want)
    assert not ok and gap > 100 * LIMIT, gap


def test_a_count_past_two_to_the_31_is_held_exactly_in_two_words():
    lo = jnp.asarray([0xFFFFFFF0, 5, 0xFFFFFFFF], jnp.uint32)
    hi = jnp.asarray([0, 0, 2], jnp.uint32)
    part = jnp.asarray([0x20, 7, 1], jnp.uint32)
    lo, hi = triangles._add64(lo, hi, part)
    got = np.asarray(hi).astype(np.uint64) * 2 ** 32 + np.asarray(lo)
    assert got.tolist() == [0xFFFFFFF0 + 0x20, 12, 3 * 2 ** 32]
    # a hub of degree 163,352 closing a tenth of its 1.33e10 pairs
    triangles_of_hub, degree = 1_334_193_727, 163_352
    lo = jnp.asarray([triangles_of_hub % 2 ** 32, 3], jnp.uint32)
    hi = jnp.asarray([triangles_of_hub // 2 ** 32, 0], jnp.uint32)
    lo, hi = triangles._add64(lo, hi, jnp.asarray([0xFFFFFFFF, 0], jnp.uint32))
    exact = triangles_of_hub + 0xFFFFFFFF
    assert int(hi[0]) * 2 ** 32 + int(lo[0]) == exact and exact > 2 ** 31
    got = np.asarray(triangles._coefficient(lo, hi, jnp.asarray([degree, 3], jnp.int32)))
    want = 2.0 * exact / (degree * (degree - 1))
    assert abs(got[0] - want) <= 1e-6 * want and got[1] == 1.0
    with pytest.raises(OverflowError):
        triangles._as_counts((lo, hi, None))


def test_triangle_count_and_k_truss_keep_their_answers():
    nx = pytest.importorskip("networkx")
    u, v, n = _rmat(9, 31)
    graph = gm.build_graph(u, v, num_vertices=n)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(u.tolist(), v.tolist()))
    counts, total = gm.triangle_count(graph)
    want = nx.triangles(g)
    assert np.asarray(counts).tolist() == [want[i] for i in range(n)]
    assert np.asarray(counts).dtype == np.int32 and total == sum(want.values()) // 3
    a, b = k_truss(graph, 4)
    truss = nx.k_truss(g, 4)
    assert sorted(zip(a.tolist(), b.tolist())) == sorted(
        (min(x, y), max(x, y)) for x, y in truss.edges())
    # the frame's cached counts feed both of its answers
    frame = gm.GraphFrame((u, v), num_vertices=n)
    frame_counts, frame_total = frame.triangle_count()
    assert np.asarray(frame_counts).tolist() == np.asarray(counts).tolist()
    assert frame_total == total
    np.testing.assert_array_equal(np.asarray(frame.clustering_coefficient()),
                                  np.asarray(gm.clustering_coefficient(graph)))
