"""The dirty reduce of the carried-rows LPA job (ISSUE 43): after a rewrite on
a low rung the one-chip job reduces only the rows the rewrite wrote to. A row
that was not rewritten keeps its mode, so the labels are the full reduce's bit
for bit: held here against ten stateless supersteps, against the full reduce
on the very rows each dirty superstep was handed, and against a NumPy replay
of which rows a superstep dirties."""

import functools
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphmine_tpu.obs.schema import SCHEMAS
from graphmine_tpu.ops import lpa
from graphmine_tpu.ops.bucketed_mode import (
    _HIST_MIN_DEG,
    _PAIRWISE_MAX_W,
    _class_tables,
    _dirty_groups,
    gather_rows,
    lpa_modes_from_dirty_rows,
    lpa_modes_from_rows,
    lpa_superstep_bucketed,
    rewrite_rows,
    rewrite_rows_marked,
    row_slots,
    with_slot_index,
)
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.ops.superstep_policy import (
    DIRTY_REDUCE_TOP_PLACE,
    delta_rungs,
    step_carried_rows,
)
from graphmine_tpu.pipeline.metrics import MetricsSink

from test_lpa_delta import _fused

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))


def _draw(scale, a=0.57, b=0.19, c=0.19):
    """The benchmark's own generator at the cells' ``dataset_seed``."""
    import generators

    u, v = generators.rmat_undirected(scale, 16, a, b, c, seed=2147483659)
    return u, v, 1 << scale


def _knocked_off_a_fixpoint(g, plan):
    """Labels at the graph's LPA fixpoint but for one vertex of a few
    neighbours: the first superstep puts it back, K is its messages, which
    fit the lowest rung, and the second superstep's dirty rows are its
    neighbours' (the quiet tail of a job, from its second superstep on)."""
    settled = _stateless(g, plan, None, 30)
    assert (settled[-1] == settled[-2]).all()
    labels = settled[-1].copy()
    sends = np.bincount(np.asarray(g.msg_send), minlength=g.num_vertices)
    few = np.nonzero((sends >= 2) & (sends <= delta_rungs(g.num_messages)[0]))[0]
    labels[few[0]] = (labels[few[0]] + 1) % g.num_vertices
    return labels


@functools.lru_cache(maxsize=None)
def _case(name):
    """``(graph, plan, init_labels)`` of a named case (made once: no test writes to them)."""
    rng = np.random.default_rng(43)
    if name == "narrow_pairwise_and_histogram_rows":
        # the fixture of tests/test_cdlp_program_text.py
        v = _HIST_MIN_DEG + 64
        fixture = np.random.default_rng(30)
        src = np.concatenate([np.zeros(v - 1, np.int64), fixture.integers(1, v, 9000)])
        dst = np.concatenate([np.arange(1, v), fixture.integers(1, v, 9000)])
        g, plan = _fused(src, dst, v)
        assert plan.hist_vertex_ids is not None
        return g, plan, _knocked_off_a_fixpoint(g, plan)
    if name == "kronecker_with_sorted_rows":  # from `arange`: a dozen leaves flicker for good
        g, plan = _fused(*_draw(12))
        assert max(i.shape[1] for i in plan.send_idx) > 1024
        return g, plan, None
    if name == "flat":
        g, plan = _fused(*_draw(12, 0.25, 0.25, 0.25))
        assert max(i.shape[1] for i in plan.send_idx) < 64
        return g, plan, _knocked_off_a_fixpoint(g, plan)
    if name == "init_labels":
        u, v = rng.integers(0, 3000, 24000), rng.integers(0, 3000, 24000)
        g, plan = _fused(u, v, 3000)
        return g, plan, _knocked_off_a_fixpoint(g, plan)
    if name == "weighted":
        # weighted cliques at their fixpoint, one label knocked off it: the
        # first superstep puts it back, and K = 8 takes the lowest rung
        a, b = np.triu_indices(9, k=1)
        base = (np.arange(600) * 9)[:, None]
        src, dst = (base + a).ravel(), (base + b).ravel()
        g, plan = _fused(src, dst, 5400,
                         edge_weights=(0.5 + rng.random(len(src))).astype(np.float32))
        assert plan.weight_mat is not None
        init = np.repeat(np.arange(600) * 9, 9).astype(np.int32)
        init[3] = 5399
        return g, plan, init
    raise KeyError(name)


def _stateless(g, plan, init, steps):
    step = jax.jit(lambda lbl: lpa_superstep_bucketed(lbl, g, plan))
    labels = (jnp.arange(g.num_vertices, dtype=jnp.int32) if init is None
              else jnp.asarray(init, jnp.int32))
    out = [np.asarray(labels)]
    for _ in range(steps):
        labels = step(labels)
        out.append(np.asarray(labels))
    return out


def _dirty_reduce_held_to_the_full_one(seen):
    """Every dirty superstep's program is handed rows that equal a full
    gather of the labels it starts from, gives what the full reduce gives on
    them bit for bit (labels, changed, K, count), and was told of every row
    whose vertex moved: the marks are a superset of what can move."""
    real = lpa._dirty_modes_program

    def watched(rows, labels, dirty, plan):
        want = gather_rows(jnp.zeros_like(rows), labels, plan)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))
        out = real(rows, labels, dirty, plan)
        for got, full in zip(out, lpa._modes_program(rows, labels, plan)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
        total = sum(i.shape[0] for i in plan.send_idx)
        listed = np.asarray(dirty)
        listed = listed[listed < total]
        assert (np.diff(listed) > 0).all()  # ascending, each once
        vertex_of_row = np.concatenate([np.asarray(i) for i in plan.vertex_ids])
        marked = np.zeros(plan.num_vertices, bool)
        marked[vertex_of_row[listed]] = True
        if plan.hist_vertex_ids is not None:
            marked[np.asarray(plan.hist_vertex_ids)] = True  # the hubs always run
        assert marked[np.asarray(out[1])].all()
        assert int(out[4]) == len(listed)
        seen.append(len(listed))
        return out

    return mock.patch.object(lpa, "_dirty_modes_program", watched)


@pytest.mark.parametrize("name", [
    "narrow_pairwise_and_histogram_rows", "kronecker_with_sorted_rows", "flat",
    "init_labels", "weighted",
])
def test_a_job_with_the_dirty_reduce_equals_ten_stateless_supersteps(name):
    g, plan, init = _case(name)
    want = _stateless(g, plan, init, 10)
    sink, seen = MetricsSink(), []
    with _dirty_reduce_held_to_the_full_one(seen):
        got = label_propagation(
            g, max_iter=10, plan=plan, sink=sink,
            init_labels=None if init is None else jnp.asarray(init),
        )
    np.testing.assert_array_equal(np.asarray(got), want[10])
    (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    rungs = list(delta_rungs(g.num_messages))
    low = rungs[:DIRTY_REDUCE_TOP_PLACE + 1]
    assert any(b in low for b in record["branch"])  # the job reaches its quiet tail
    if name == "weighted":  # its weights are a matrix a class: the full reduce
        assert record["reduce"] == ["full"] * 10 and not seen
        return
    assert record["reduce"] == [
        "dirty" if b in low else "full" for b in record["branch"]
    ]
    assert [n for n, r in zip(record["dirty_rows"], record["reduce"]) if r == "dirty"] == seen
    assert max(seen) > 0  # and rows are dirty in it


def test_the_dirty_rows_are_the_rows_a_numpy_replay_finds():
    """On a scale-12 draw of the cells' generator: a superstep's dirty rows
    are the vertices that receive a message from a sender its predecessor
    moved, the histogram hubs apart (``_proof/dirty_rows_replay.py``), and
    their slots those rows' widths."""
    g, plan, _ = _case("kronecker_with_sorted_rows")
    labels = _stateless(g, plan, None, 10)
    sink = MetricsSink()
    label_propagation(g, max_iter=10, plan=plan, sink=sink)
    (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    send, recv = np.asarray(g.msg_send), np.asarray(g.msg_recv)
    width_of = np.zeros(g.num_vertices, np.int64)
    for ids, idx in zip(plan.vertex_ids, plan.send_idx):
        width_of[np.asarray(ids)] = idx.shape[1]
    assert record["reduce"].count("dirty") >= 3
    for step in range(1, 10):
        if record["reduce"][step] != "dirty":
            continue
        moved = labels[step] != labels[step - 1]
        dirty = np.zeros(g.num_vertices, bool)
        dirty[recv[moved[send]]] = True
        dirty &= width_of > 0
        assert record["dirty_rows"][step] == int(dirty.sum()) > 0
        assert record["dirty_slots"][step] == int(width_of[dirty].sum())


@pytest.mark.parametrize("name", [
    "narrow_pairwise_and_histogram_rows", "kronecker_with_sorted_rows", "flat",
])
def test_every_row_dirty_is_the_full_reduce(name):
    """The reduce alone, told that every row is dirty: every coarse width
    runs, the widest row's cut is clamped to the buffer's end, and the
    labels are ``lpa_modes_from_rows``'s."""
    g, plan, _ = _case(name)
    rng = np.random.default_rng(7)
    labels = jnp.asarray(rng.integers(0, 50, g.num_vertices), jnp.int32)
    rows = gather_rows(jnp.zeros(row_slots(plan), jnp.int32), labels, plan)
    total = sum(i.shape[0] for i in plan.send_idx)
    reduce = jax.jit(lpa_modes_from_dirty_rows)
    got, count, slots = reduce(rows, labels, jnp.arange(total, dtype=jnp.int32), plan)
    full = np.asarray(jax.jit(lpa_modes_from_rows)(rows, labels, plan))
    np.testing.assert_array_equal(np.asarray(got), full)
    assert (int(count), int(slots)) == (total, row_slots(plan))
    # and told of one row in every class, only those vertices (and the hubs) move
    _, rowoffs, _ = _class_tables(plan)
    classes = len(plan.send_idx)  # (the list as long as before: one compile)
    some = jnp.asarray(np.append(rowoffs[:-1], [total] * (total - classes)), jnp.int32)
    got, count, _ = reduce(rows, labels, some, plan)
    may_move = np.zeros(g.num_vertices, bool)
    may_move[[int(np.asarray(ids)[0]) for ids in plan.vertex_ids]] = True
    if plan.hist_vertex_ids is not None:
        may_move[np.asarray(plan.hist_vertex_ids)] = True
    np.testing.assert_array_equal(
        np.asarray(got), np.where(may_move, full, np.asarray(labels))
    )
    assert int(count) == len(plan.send_idx)


def test_the_coarse_widths_cover_every_class_in_order():
    widths = [1, 2, 3, 20, 30, 33, 36, 64, 66, 2048, 2253, 164623]
    groups = _dirty_groups(widths)
    assert groups == [
        (32, 0, 5), (64, 5, 8), (128, 8, 9), (2048, 9, 10), (4096, 10, 11),
        (262144, 11, 12),
    ]
    assert groups[0][0] == _PAIRWISE_MAX_W
    for coarse, c0, c1 in groups:
        assert all(w <= coarse for w in widths[c0:c1])


@pytest.mark.parametrize("cap_over", [1.0, 3.0])
def test_a_marked_rewrite_writes_what_the_plain_one_writes_and_lists_its_rows(cap_over):
    g, plan, _ = _case("narrow_pairwise_and_histogram_rows")
    plan = with_slot_index(plan)
    rng = np.random.default_rng(11)
    old = rng.integers(0, g.num_vertices, g.num_vertices).astype(np.int32)
    new = old.copy()
    movers = rng.choice(g.num_vertices, 60, replace=False)
    new[movers] = rng.integers(0, g.num_vertices, 60)
    changed = new != old
    out_deg = np.diff(np.asarray(plan.out_ptr))
    cap = int(out_deg[changed].sum() * cap_over) + 1
    rows = gather_rows(jnp.zeros(row_slots(plan), jnp.int32), jnp.asarray(old), plan)
    want = rewrite_rows(rows, jnp.asarray(new), jnp.asarray(changed), plan, cap)
    got, dirty = rewrite_rows_marked(
        rows, jnp.asarray(new), jnp.asarray(changed), plan, cap
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    total = sum(i.shape[0] for i in plan.send_idx)
    assert dirty.shape == (min(cap, total),)
    listed = np.asarray(dirty)
    # the rows that hold a slot of a changed sender, from the index itself
    ptr, slot = np.asarray(plan.out_ptr), np.asarray(plan.out_slot)
    offs, rowoffs, widths = _class_tables(plan)
    touched = np.concatenate([slot[ptr[s]:ptr[s + 1]] for s in np.nonzero(changed)[0]])
    touched = touched[touched < row_slots(plan)]  # a hub's message has no slot
    cls = np.searchsorted(offs, touched, side="right") - 1
    rows_of = np.unique(rowoffs[cls] + (touched - offs[cls]) // widths[cls])
    np.testing.assert_array_equal(listed[listed < total], rows_of)
    assert (listed[len(rows_of):] == total).all()


def test_a_weighted_plan_is_turned_away_by_the_dirty_reduce():
    g, plan, _ = _case("weighted")
    with pytest.raises(ValueError, match="weighted"):
        lpa_modes_from_dirty_rows(
            jnp.zeros(row_slots(plan), jnp.int32),
            jnp.zeros(g.num_vertices, jnp.int32), jnp.zeros(4, jnp.int32), plan,
        )


def _stub_job(ks, dirty_modes=True):
    """``step_carried_rows`` over stub programs that report the K's of
    ``ks``: the calls it makes, in order."""
    calls, feed = [], iter(ks)
    count = lambda: (np.int32(next(feed)), np.int32(1))

    def rewrite(rows, labels, changed, cap, **kw):
        calls.append(("rewrite", cap, kw))
        return (rows, "touched") if kw.get("marked") else rows

    def dirty(rows, labels, touched):
        calls.append(("dirty_modes", touched))
        return (labels, None, *count(), np.int32(3), np.int32(30))

    _, per_step = step_carried_rows(
        len(ks), (10, 100, 1000, 5000), 10**6, "rows", "labels",
        gather=lambda rows, labels: calls.append(("gather",)) or rows,
        rewrite=rewrite,
        modes=lambda rows, labels: calls.append(("modes",)) or (labels, None, *count()),
        dirty_modes=dirty if dirty_modes else None,
    )
    return calls, per_step


def test_a_rung_above_the_stated_place_takes_the_full_reduce():
    """The host picks by the rung it already knows: the dirty reduce after a
    rewrite on the rungs up to ``DIRTY_REDUCE_TOP_PLACE`` (the lowest), the
    full reduce above them and after a full gather."""
    assert DIRTY_REDUCE_TOP_PLACE == 0
    calls, per_step = _stub_job([7, 80, 900, 4000, 6000, 10, 0])
    assert calls == [
        ("gather",), ("modes",),                                       # K over every rung
        ("rewrite", 10, {"marked": True}), ("dirty_modes", "touched"),    # 7
        ("rewrite", 100, {}), ("modes",),                               # 80
        ("rewrite", 1000, {}), ("modes",),                              # 900
        ("rewrite", 5000, {}), ("modes",),                              # 4000
        ("gather",), ("modes",),                                        # 6000
        ("rewrite", 10, {"marked": True}), ("dirty_modes", "touched"),    # 10
    ]
    assert per_step["reduce"] == ["full", "dirty", "full", "full", "full", "full", "dirty"]
    assert per_step["dirty_rows"] == [None, 3, None, None, None, None, 3]
    assert per_step["dirty_slots"] == [None, 30, None, None, None, None, 30]
    assert per_step["branch"] == [4, 0, 1, 2, 3, 4, 0]


def test_a_job_that_hands_over_no_dirty_reduce_is_stepped_as_before():
    """The mesh job's calls: no ``marked`` reaches its rewrite, every
    reduce is ``modes``."""
    calls, per_step = _stub_job([7, 80, 900, 0], dirty_modes=False)
    assert calls == [
        ("gather",), ("modes",), ("rewrite", 10, {}), ("modes",),
        ("rewrite", 100, {}), ("modes",), ("rewrite", 1000, {}), ("modes",),
    ]
    assert per_step["reduce"] == ["full"] * 4
    assert per_step["dirty_rows"] == per_step["dirty_slots"] == [None] * 4


@pytest.mark.parametrize("scan", ["carried", "plain"])
def test_the_record_says_the_reduce_and_the_dirty_share_of_every_superstep(scan):
    assert {"reduce", "dirty_rows", "dirty_slots"} <= SCHEMAS["superstep_delta"]
    g, plan, _ = _case("kronecker_with_sorted_rows")
    sink = MetricsSink()
    if scan == "plain":  # the stateless scan: a plan that never gets its index
        with mock.patch.object(lpa, "_cached_slot_index",
                               lambda plan: (plan, 0.0, ("plain", "held out"))):
            label_propagation(g, max_iter=10, plan=plan, sink=sink)
    else:
        label_propagation(g, max_iter=10, plan=plan, sink=sink)
    (record,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
    total, slots = sum(i.shape[0] for i in plan.send_idx), row_slots(plan)
    for key in ("reduce", "dirty_rows", "dirty_slots", "branch", "changed_vertices"):
        assert len(record[key]) == 10, key
    for reduce, rows, held in zip(record["reduce"], record["dirty_rows"], record["dirty_slots"]):
        if reduce == "full":
            assert (rows, held) == (total, slots)
        else:
            assert 0 <= rows < total and rows <= held < slots
    assert ("dirty" in record["reduce"]) == (scan == "carried")
