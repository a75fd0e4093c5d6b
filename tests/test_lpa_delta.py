"""The carried-rows LPA job (ISSUE 32; stepped from the host since ISSUE
36): ``label_propagation`` over a fused plan keeps the gathered rows across
supersteps and rewrites only the slots behind the senders whose label
changed. Its labels are those of a host loop of ``lpa_superstep_bucketed``
and of the sort family after every superstep, its rows those of a full
gather slot for slot at every superstep, and its ``superstep_delta`` record
counts what a NumPy recount counts."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.bucketed_mode import (
    _HIST_MIN_DEG,
    _SENTINEL,
    BucketedModePlan,
    gather_rows,
    lpa_superstep_bucketed,
    rewrite_rows,
    row_slots,
    with_slot_index,
)
from graphmine_tpu.ops import lpa
from graphmine_tpu.ops.lpa import label_propagation, lpa_superstep
from graphmine_tpu.ops.superstep_policy import delta_rungs
from graphmine_tpu.pipeline.metrics import MetricsSink


def _rmat(scale, edge_factor, seed):
    """A Graph500-style R-MAT draw (0.57 / 0.19 / 0.19 / 0.05), ids permuted."""
    rng = np.random.default_rng(seed)
    n_edges = edge_factor << scale
    u = np.zeros(n_edges, np.int64)
    v = np.zeros(n_edges, np.int64)
    for bit in range(scale):
        r = rng.random(n_edges)
        u |= (r >= 0.76).astype(np.int64) << bit  # c + d
        v |= (((r >= 0.57) & (r < 0.76)) | (r >= 0.95)).astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return perm[u], perm[v], 1 << scale


def _cliques(num, size):
    """``num`` disjoint cliques of ``size`` vertices: the clique's smallest
    id is every member's label at LPA's fixpoint."""
    a, b = np.triu_indices(size, k=1)
    base = (np.arange(num) * size)[:, None]
    return (base + a).ravel(), (base + b).ravel(), num * size


def _fuse(n, quiet, loud):
    """A directed shift register that feeds a fan-out: path ``p_0 -> ... ->
    p_t -> h2``, ``h1`` and ``h2`` each to all of ``n`` block vertices, each
    block vertex to a sink of its own. The path's labels shift one hop a
    superstep; ``h2`` copies the path's end, and every block vertex takes
    ``min(label(h1), label(h2))`` (two messages: a tie). ``quiet`` supersteps
    deliver one constant label (a handful of path vertices change), then
    the alternating labels arrive and ``h2``, the block and the sinks flip
    every superstep: K goes from a few messages to most of them."""
    t = quiet + loud
    path = np.arange(t)
    h1, h2 = t, t + 1
    block = t + 2 + np.arange(n)
    sinks = t + 2 + n + np.arange(n)
    src = np.concatenate([path, np.full(n, h1), np.full(n, h2), block])
    dst = np.concatenate([np.append(path[1:], h2), block, block, sinks])
    v = t + 2 + 2 * n
    labels = np.full(v, 5, np.int32)  # the block and its sinks at rest
    # p_{t-1} is the end; the label ``quiet + i`` hops from the end arrives
    # after ``quiet + i`` supersteps
    labels[path[::-1][quiet:]] = np.where(np.arange(loud) % 2 == 0, 1, 2)
    labels[path[::-1][:quiet]] = 7
    labels[h1] = 5
    labels[h2] = 7
    return src, dst, v, labels


def _host_loops(g, plan, init_labels, steps):
    """The labels after each of ``steps`` supersteps, by a host loop of the
    stateless bucketed superstep, checked against the sort family's."""
    bucketed = jax.jit(lambda lbl: lpa_superstep_bucketed(lbl, g, plan))
    sort = jax.jit(lambda lbl: lpa_superstep(lbl, g))
    labels = (
        jnp.arange(g.num_vertices, dtype=jnp.int32) if init_labels is None
        else jnp.asarray(init_labels, jnp.int32)
    )
    out = [np.asarray(labels)]
    for _ in range(steps):
        new = bucketed(labels)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(sort(labels)))
        labels = new
        out.append(np.asarray(labels))
    return out


def _out_degree(g):
    return np.bincount(np.asarray(g.msg_send), minlength=g.num_vertices)


def _rows_held_to_a_full_gather():
    """Every superstep's reduce is handed rows that equal a full gather of
    the labels it starts from, slot for slot, whichever update made them;
    and the reduce over the dirty rows alone (ISSUE 43) gives what the full
    reduce gives on those rows, bit for bit."""
    import contextlib

    real, real_dirty = lpa._modes_program, lpa._dirty_modes_program

    def watched(rows, labels, plan):
        want = gather_rows(jnp.zeros_like(rows), labels, plan)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))
        return real(rows, labels, plan)

    def watched_dirty(rows, labels, dirty, plan):
        out = real_dirty(rows, labels, dirty, plan)
        for got, full in zip(out, watched(rows, labels, plan)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
        return out

    stack = contextlib.ExitStack()
    stack.enter_context(mock.patch.object(lpa, "_modes_program", watched))
    stack.enter_context(mock.patch.object(lpa, "_dirty_modes_program", watched_dirty))
    return stack


def _check(g, plan, steps, init_labels=None):
    """The carried-rows job against the host loops: labels after every
    superstep (one job per length) and the rows before every reduce, the
    history, and the record's counts against a NumPy recount. Returns the
    ``branch`` list of the longest run."""
    want = _host_loops(g, plan, init_labels, steps)
    out_deg = _out_degree(g)
    init = None if init_labels is None else jnp.asarray(init_labels, jnp.int32)
    record = None
    for k in range(1, steps + 1):
        sink = MetricsSink()
        with _rows_held_to_a_full_gather():
            labels, history = label_propagation(
                g, max_iter=k, plan=plan, init_labels=init,
                return_history=True, sink=sink,
            )
        np.testing.assert_array_equal(np.asarray(labels), want[k])
        moved = [want[i + 1] != want[i] for i in range(k)]
        assert np.asarray(history).tolist() == [int(c.sum()) for c in moved]
        (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
        assert record["changed_vertices"] == [int(c.sum()) for c in moved]
        assert record["changed_messages"] == [int(out_deg[c].sum()) for c in moved]
        rungs = list(delta_rungs(g.num_messages))
        assert record["rungs"] == rungs and record["branch"][0] == "full"
        for took, k_before in zip(record["branch"][1:], record["changed_messages"]):
            fits = [r for r in rungs if k_before <= r]
            assert took == (fits[0] if fits else "full")
    return record["branch"]


def _fused(src, dst, v, **kw):
    g = build_graph(src, dst, num_vertices=v, **kw)
    return g, BucketedModePlan.from_graph(g, with_send=True)


def _case(name):
    """``(graph, plan, supersteps, init_labels)`` of a named case."""
    rng = np.random.default_rng(32)
    if name == "rmat_with_a_histogram_hub":
        g, plan = _fused(*_rmat(12, 16, seed=5))
        assert plan.hist_vertex_ids is not None
        return g, plan, 6, None
    if name == "bipartite_flip_flop":
        a = np.repeat(np.arange(40), 40)
        b = 40 + np.tile(np.arange(40), 40)
        return *_fused(a, b, 80), 4, None
    if name == "star":
        v = _HIST_MIN_DEG + 40  # the centre is a histogram hub
        return *_fused(np.zeros(v - 1, np.int64), np.arange(1, v), v), 3, None
    if name == "path":
        return *_fused(np.arange(59), np.arange(1, 60), 60), 5, None
    if name == "isolated_vertices":
        u, v = rng.integers(0, 300, 2000), rng.integers(0, 300, 2000)
        return *_fused(u, v, 1000), 4, None  # 700 vertices have no edge
    if name == "weighted":
        u, v = rng.integers(0, 400, 6000), rng.integers(0, 400, 6000)
        g, plan = _fused(u, v, 400, edge_weights=rng.random(6000).astype(np.float32))
        assert plan.weight_mat is not None
        return g, plan, 4, None
    if name == "directed":
        u, v = rng.integers(0, 500, 5000), rng.integers(0, 500, 5000)
        return *_fused(u, v, 500, symmetric=False), 5, None
    if name == "init_labels":
        u, v = rng.integers(0, 500, 4000), rng.integers(0, 500, 4000)
        return *_fused(u, v, 500), 4, rng.integers(0, 500, 500).astype(np.int32)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "rmat_with_a_histogram_hub", "bipartite_flip_flop", "star", "path",
    "isolated_vertices", "weighted", "directed", "init_labels",
])
def test_carried_rows_equal_the_host_loops_after_every_superstep(name):
    g, plan, steps, init = _case(name)
    branch = _check(g, plan, steps, init)
    if name == "bipartite_flip_flop":  # every label moves every superstep
        assert branch == ["full"] * steps


def test_one_superstep_is_the_full_gather():
    g, plan, _, _ = _case("directed")
    assert _check(g, plan, 1) == ["full"]


@pytest.mark.parametrize("rung", [0, 1, 2, 3])
def test_a_graph_lands_in_each_rung(rung):
    """Cliques at their fixpoint with ``n`` labels knocked off it: the first
    superstep puts exactly those back, so K = n x (size - 1) picks the
    second superstep's branch, and the third has nothing to rewrite."""
    size = 9
    src, dst, v = _cliques(600, size)
    g, plan = _fused(src, dst, v)
    rungs = delta_rungs(g.num_messages)
    assert len(rungs) == 4
    under = rungs[rung - 1] if rung else 0
    n = under // (size - 1) + 1  # K just above the rung below
    assert under < n * (size - 1) <= rungs[rung] and n <= 600
    init = np.repeat(np.arange(600) * size, size).astype(np.int32)
    init[np.arange(n) * size + 3] = v - 1 - np.arange(n)  # one a clique
    branch = _check(g, plan, 3, init)
    assert branch == ["full", rungs[rung], rungs[0]]


def test_a_quiet_graph_overflows_every_rung_from_a_mid_superstep_on():
    src, dst, v, init = _fuse(n=600, quiet=3, loud=5)
    g, plan = _fused(src, dst, v, symmetric=False)
    branch = _check(g, plan, 8, init)
    rungs = delta_rungs(g.num_messages)
    assert branch[:4] == ["full", rungs[0], rungs[0], rungs[0]]
    assert branch[4:] == ["full"] * 4  # h2, the block and its sinks flip for good


@pytest.mark.parametrize("max_iter", [1, 9, 10])
def test_a_job_of_any_length_runs_the_programs_already_compiled(max_iter):
    """``max_iter`` is the length of the host's loop and no program's
    argument: a job of another length compiles nothing (on the chip a
    program of the cells' size compiles for minutes)."""
    g, plan, _, _ = _case("rmat_with_a_histogram_hub")
    programs = (lpa._gather_program, lpa._rewrite_program, lpa._modes_program,
                lpa._dirty_modes_program)
    sink = MetricsSink()
    want = label_propagation(g, max_iter=10, plan=plan, sink=sink)
    (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    assert len(set(record["branch"])) >= 3  # full and two rungs at least
    compiled = [p._cache_size() for p in programs]
    got = label_propagation(g, max_iter=max_iter, plan=plan)
    assert [p._cache_size() for p in programs] == compiled
    if max_iter == 10:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the index alone ----------------------------------------------------------


@pytest.mark.parametrize("name", [
    "rmat_with_a_histogram_hub", "star", "directed", "weighted",
])
def test_every_real_slot_is_named_by_exactly_one_message(name):
    g, plan, _, _ = _case(name)
    indexed = with_slot_index(plan)
    s = row_slots(plan)
    flat = np.concatenate([np.asarray(m).reshape(-1) for m in plan.send_idx])
    ptr, slot = np.asarray(indexed.out_ptr), np.asarray(indexed.out_slot)
    assert ptr[0] == 0 and ptr[-1] == len(slot) == g.num_messages
    np.testing.assert_array_equal(np.diff(ptr), _out_degree(g))
    sender = np.repeat(np.arange(g.num_vertices), np.diff(ptr))
    named = slot < s
    # a named slot holds its sender; no slot twice; no sentinel slot; all real
    np.testing.assert_array_equal(flat[slot[named]], sender[named])
    assert len(np.unique(slot[named])) == named.sum() == (flat < g.num_vertices).sum()
    # what names no slot is a message to a histogram hub, sender for sender
    hubs = 0 if plan.hist_send is None else len(plan.hist_send)
    assert (slot[~named] == s).all() and (~named).sum() == hubs
    if hubs:
        np.testing.assert_array_equal(
            np.bincount(sender[~named], minlength=g.num_vertices),
            np.bincount(np.asarray(plan.hist_send), minlength=g.num_vertices),
        )


def test_the_numpy_index_is_the_native_one(monkeypatch):
    from graphmine_tpu.io import native

    g, plan, _, _ = _case("rmat_with_a_histogram_hub")
    assert native.available()
    want = with_slot_index(plan)
    monkeypatch.setattr(native, "positions_by_key", lambda *a: None)
    got = with_slot_index(plan)
    np.testing.assert_array_equal(np.asarray(got.out_ptr), np.asarray(want.out_ptr))
    np.testing.assert_array_equal(np.asarray(got.out_slot), np.asarray(want.out_slot))


def test_a_plan_with_no_rows_carries_nothing():
    g = build_graph(np.zeros(0, np.int64), np.zeros(0, np.int64), num_vertices=5)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    assert with_slot_index(plan).out_slot is None
    labels, history = label_propagation(g, max_iter=3, plan=plan, return_history=True)
    assert np.asarray(labels).tolist() == list(range(5))
    assert np.asarray(history).tolist() == [0, 0, 0]


@pytest.mark.parametrize("cap_over", [1.0, 3.0])
def test_rewritten_rows_equal_gathered_rows_slot_for_slot(cap_over):
    """``rewrite_rows`` alone: the rows of one label vector, the slots of
    the changed senders rewritten, against a gather of the other vector."""
    g, plan, _, _ = _case("rmat_with_a_histogram_hub")
    plan = with_slot_index(plan)
    rng = np.random.default_rng(7)
    old = rng.integers(0, g.num_vertices, g.num_vertices).astype(np.int32)
    new = old.copy()
    moved = rng.random(g.num_vertices) < 0.02
    new[moved] = rng.integers(0, g.num_vertices, moved.sum())
    changed = new != old
    k = int(_out_degree(g)[changed].sum())
    blank = jnp.zeros((row_slots(plan),), jnp.int32)
    rows = gather_rows(blank, jnp.asarray(old), plan)
    pad = np.concatenate([np.asarray(m).reshape(-1) for m in plan.send_idx])
    assert (np.asarray(rows)[pad == g.num_vertices] == _SENTINEL).all()
    got = rewrite_rows(
        rows, jnp.asarray(new), jnp.asarray(changed), plan, cap=int(k * cap_over)
    )
    want = gather_rows(blank, jnp.asarray(new), plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- who takes the path -------------------------------------------------------


def test_auto_and_an_explicit_fused_plan_carry_rows_and_nothing_else_does():
    u, v, n = _rmat(12, 16, seed=9)  # 131,072 messages: auto is bucketed
    g = build_graph(u, v, num_vertices=n)
    want = np.asarray(label_propagation(g, max_iter=4, plan=None))
    fused = BucketedModePlan.from_graph(g, with_send=True)
    for plan, carried in (("auto", True), (fused, True), (None, False),
                          (BucketedModePlan.from_graph(g), False)):
        sink = MetricsSink()
        got = label_propagation(g, max_iter=4, plan=plan, sink=sink)
        np.testing.assert_array_equal(np.asarray(got), want)
        records = [r for r in sink.records if r["phase"] == "superstep_delta"]
        assert len(records) == int(carried)
    # the index is paid once a plan and stays out of the plan itself
    assert fused.out_slot is None
    from graphmine_tpu.ops import lpa

    first = lpa._cached_slot_index(fused)
    again = lpa._cached_slot_index(fused)
    assert again[1] == 0.0 and again[0].out_slot is first[0].out_slot
    # connected_components shares auto's plan, without an index
    assert lpa._cached_auto_plan(g)[0].out_slot is None


def test_the_index_cache_lets_go_of_a_dropped_plan():
    import gc

    from graphmine_tpu.ops import lpa

    g, plan, _, _ = _case("path")
    before = len(lpa._slot_index_cache)
    indexed, _, _ = lpa._cached_slot_index(plan)
    assert len(lpa._slot_index_cache) == before + 1
    del plan, indexed
    gc.collect()
    assert len(lpa._slot_index_cache) == before


@pytest.mark.parametrize("plan_is", ["an_argument", "closed_over"])
def test_a_plan_under_a_trace_runs_without_an_index(plan_is):
    """Under a caller's trace the host cannot step a job: the stateless
    scan runs, whether the plan's arrays are tracers or concrete arrays the
    traced function closes over (where K would be a tracer all the same)."""
    g, plan, _, _ = _case("path")
    want = np.asarray(label_propagation(g, max_iter=3, plan=plan))
    if plan_is == "an_argument":
        got = jax.jit(lambda p: label_propagation(g, max_iter=3, plan=p))(plan)
    else:
        got = jax.jit(lambda: label_propagation(g, max_iter=3, plan=plan))()
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("fault", ["another_graph", "no_weight_payload"])
def test_a_plan_that_does_not_fit_the_graph_is_refused(fault):
    rng = np.random.default_rng(3)
    u, v = rng.integers(0, 200, 1500), rng.integers(0, 200, 1500)
    if fault == "another_graph":
        g = build_graph(u, v, num_vertices=200)
        _, plan = _fused(u[:-7], v[:-7], 200)
        match = "mismatch"
    else:
        g = build_graph(u, v, num_vertices=200, edge_weights=np.ones(1500, np.float32))
        _, plan = _fused(u, v, 200)
        match = "weight payload"
    with pytest.raises(ValueError, match=match):
        label_propagation(g, max_iter=2, plan=plan)
