"""The width ladder's promise, for every row a plan builds (ISSUE 42).

``ops/bucketed_mode._extend_widths`` continues the 1.10x ladder past its
last entry, 2048, at the ladder's own step: a row is padded by at most a
tenth however long it is. Until PR 42 the ladder went on in 1.5x steps,
"degrees past the histogram threshold are few"; where the histogram budget
admits few hubs (``_HIST_BUDGET // V``: 4 at 2^24 vertices, none on a mesh)
they are not, and the rows past 2048 held half of graph500-24's slots, a
fifth to a third of them padding. Held here:

* for every degree up to 2^21 the class is at least the degree and at most
  a tenth over it (one more for the rounding), exact up to 20;
* the ladder up to 2048 is ``_WIDTHS`` to the entry whatever the longest
  row, so a plan with no row past 2048 is the plan it was;
* a plan whose rows past 2048 are rows (no histogram: the budget refuses
  them) gives CDLP and WCC bit-equal to the ``sort`` family's and PageRank
  inside float32's reach of the float64 reference, on one device and, for
  CDLP, through ``parallel/sharded._build_shard_bucket_plan`` on a mesh
  (WCC's and PageRank's mesh programs read a shard's messages by segment
  and build no rows).
"""

import importlib

import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.ops.bucketed_mode import _WIDTHS, BucketedModePlan, _extend_widths

bm = importlib.import_module("graphmine_tpu.ops.bucketed_mode")

_EXACT = 20  # degrees up to here get a width of their own
_HUB_DEGREES = (70_000, 7_000, 2_049, 2_253, 2_254, 2_479, 2_480, 2_727, 2_728, 3_000, 3_072)
_HUB_WIDTHS = (76_795, 7_083, 2_253, 2_253, 2_479, 2_479, 2_727, 2_727, 3_000, 3_000, 3_301)


@pytest.mark.parametrize("lo,hi", [
    (1, _EXACT), (_EXACT + 1, 2_048), (2_049, 3_072), (3_073, 1 << 16),
    ((1 << 16) + 1, 1 << 19), ((1 << 19) + 1, 1 << 21),
], ids=lambda x: str(x))
def test_every_degree_s_class_is_at_most_a_tenth_over_it(lo, hi):
    d = np.arange(lo, hi + 1, dtype=np.int64)
    widths = _extend_widths(hi)
    w = widths[np.searchsorted(widths, d)]  # as from_ptr classes a row
    assert (w >= d).all()
    if hi <= _EXACT:
        assert (w == d).all()
    else:
        over = w - np.ceil(1.10 * d).astype(np.int64)
        assert over.max() <= 1, (int(d[over.argmax()]), int(w[over.argmax()]))
        # the padding of a class's shortest row, as a share of the row
        assert ((w - d) / w).max() <= 0.10 + 1.0 / lo


@pytest.mark.parametrize("longest", [1, _EXACT, 1_890, 2_048, 2_049, 2_253, 3_072,
                                     70_000, 177_147, 1 << 21])
def test_the_ladder_up_to_2048_is_the_parent_s_to_the_entry(longest):
    widths = _extend_widths(longest)
    assert tuple(widths[:len(_WIDTHS)]) == _WIDTHS and _WIDTHS[-1] == 2_048
    assert widths[-1] >= longest and (np.diff(widths) > 0).all()
    past = widths[len(_WIDTHS) - 1:]
    if longest <= 2_048:
        assert len(past) == 1  # nothing is added that no row needs
    else:
        assert past[-2] < longest  # and no class past the longest row's
        assert (past[1:] == np.ceil(1.10 * past[:-1])).all()  # one step, the ladder's own
        assert tuple(past[:4]) == (2_048, 2_253, 2_479, 2_727)[:len(past)]


def _hubs_in_noise(seed=42):
    """Eleven hubs whose degrees sit at the edges of the classes past 2048
    (each joined to leaves only, so its degree is exact), a sparse random
    graph over the leaves, and a block of isolated vertices."""
    rng = np.random.default_rng(seed)
    first_leaf, leaves = 100, 70_000
    n = first_leaf + leaves + 300
    u = np.repeat(np.arange(len(_HUB_DEGREES)), _HUB_DEGREES)
    v = np.concatenate([first_leaf + rng.choice(leaves, d, replace=False)
                        for d in _HUB_DEGREES])
    a, b = (first_leaf + rng.integers(0, leaves, 90_000) for _ in range(2))
    pairs = np.unique(np.stack([a, b], 1)[a < b], axis=0)
    return (np.concatenate([u, pairs[:, 0]]), np.concatenate([v, pairs[:, 1]]), n)


@pytest.fixture(scope="module")
def rows_past_2048():
    """``(u, v, n, graph, plan)`` with the histogram budget at nothing, as a
    mesh has it and as 2^24 vertices nearly do: every hub is a row."""
    u, v, n = _hubs_in_noise()
    graph = gm.build_graph(u, v, num_vertices=n)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bm, "_HIST_BUDGET", 0)
        plan = BucketedModePlan.from_graph(graph, with_send=True)
    return u, v, n, graph, plan


def test_the_hubs_are_rows_of_the_ladder_s_widths(rows_past_2048):
    u, v, n, _, plan = rows_past_2048
    assert plan.hist_vertex_ids is None
    degree = np.bincount(np.concatenate([u, v]), minlength=n)
    assert tuple(degree[:len(_HUB_DEGREES)]) == _HUB_DEGREES
    width_of = {int(i): idx.shape[1] for ids, idx in zip(plan.vertex_ids, plan.send_idx)
                for i in np.asarray(ids) if i < len(_HUB_DEGREES)}
    assert tuple(width_of[h] for h in range(len(_HUB_DEGREES))) == _HUB_WIDTHS
    # the parent's 1.5x classes held these eleven rows in 3072, 10368 and 78732
    assert sum(_HUB_WIDTHS) < 1.10 * sum(_HUB_DEGREES) < 9 * 3_072 + 10_368 + 78_732


@pytest.mark.parametrize("kernel", ["cdlp", "wcc"])
def test_rows_past_2048_give_the_sort_family_s_labels(rows_past_2048, kernel):
    _, _, _, graph, plan = rows_past_2048
    run = {"cdlp": lambda p: gm.label_propagation(graph, max_iter=5, plan=p),
           "wcc": lambda p: gm.connected_components(graph, plan=p)}[kernel]
    rows, sort = np.asarray(run(plan)), np.asarray(run(None))
    np.testing.assert_array_equal(rows, sort)
    assert len(np.unique(rows)) > 300  # the loners keep their own


def test_rows_past_2048_give_pagerank_within_float32_of_the_reference(rows_past_2048):
    from test_pagerank_graphalytics import ALGORITHM, FLOAT32, LIMIT, TRAFFIC, _gap

    u, v, n, graph, plan = rows_past_2048
    want = ALGORITHM.reference(u, v, n, TRAFFIC)
    kw = dict(max_iter=10, tol=None, directed=False)
    assert _gap(gm.pagerank(graph, plan=plan, **kw), want) < FLOAT32
    # the sort family's running float32 sum drifts at a hub of 70,000
    assert _gap(gm.pagerank(graph, plan=None, **kw), want) < LIMIT


@pytest.mark.parametrize("entry", ["carried", "one-program"])
def test_rows_past_2048_on_a_mesh_give_the_sort_family_s_labels(rows_past_2048, entry):
    from graphmine_tpu.parallel.sharded import (
        partition_graph, shard_graph_arrays, sharded_label_propagation,
    )

    u, v, n, graph, _ = rows_past_2048
    want = np.asarray(gm.label_propagation(graph, max_iter=5, plan=None))
    mesh = gm.make_mesh(4)
    host = gm.build_graph(u, v, num_vertices=n, to_device=False)
    part = partition_graph(host, mesh=mesh, build_bucket_plan=True)
    # no histogram on a mesh: the shard plan's widths are the ladder's too
    assert set(_HUB_WIDTHS) <= {b.shape[2] for b in part.bucket_send}
    if entry == "carried":  # the public mesh entry, the rows carried a chip
        got = gm.label_propagation(host, max_iter=5, mesh=mesh)
    else:
        got = sharded_label_propagation(shard_graph_arrays(part, mesh), mesh, max_iter=5)
    np.testing.assert_array_equal(np.asarray(got)[:n], want)
