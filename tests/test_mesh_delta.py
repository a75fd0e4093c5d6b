"""The carried-rows LPA job on a mesh (ISSUE 39): over four virtual devices
``label_propagation(graph, mesh=mesh)`` keeps each shard's gathered rows
across supersteps in one donated buffer and rewrites only the slots behind
the senders whose label changed, stepped from the host on the largest
shard's K. Its labels are the one-device entry's and a plain NumPy LPA's
after every superstep, each shard's rows those of a full gather slot for
slot before every reduce, its record's counts a NumPy recount's; whoever
cannot step from the host runs the one compiled program, and gets the same
labels."""

import gc
import weakref
from unittest import mock

import jax
import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import lpa as lpa_mod
from graphmine_tpu.ops import superstep_policy
from graphmine_tpu.ops.superstep_policy import delta_rungs
from graphmine_tpu.parallel import sharded
from graphmine_tpu.parallel.sharded import (
    _shard_message_offsets,
    carried_label_propagation,
    partition_graph,
    shard_graph_arrays,
    shard_row_slots,
    sharded_label_propagation,
    with_shard_slot_index,
)
from graphmine_tpu.pipeline.metrics import MetricsSink
from test_lpa_delta import _cliques, _fuse, _rmat
from test_mesh_entry import _numpy_superstep

D = 4
PROGRAMS = (
    sharded._mesh_gather_program, sharded._mesh_rewrite_program,
    sharded._mesh_modes_program,
)


@pytest.fixture(scope="module")
def mesh():
    return gm.make_mesh(D)


def _host(src, dst, v, **kw):
    return gm.build_graph(src, dst, num_vertices=v, to_device=False, **kw)


def _indexed(host, mesh):
    """``(placed partition with its slot index, the host one, counts)``."""
    part = partition_graph(host, mesh=mesh, lpa_only=True, build_bucket_plan=True)
    counts = np.diff(_shard_message_offsets(
        np.asarray(host.msg_ptr), D, part.chunk_size))
    part = with_shard_slot_index(part, counts)
    return shard_graph_arrays(part, mesh, lpa_only=True), part, counts


def _history(host, steps, init=None):
    """The labels after each of ``steps`` supersteps by the plain NumPy
    superstep over the graph's own message CSR (directed or not)."""
    recv, send = np.asarray(host.msg_recv), np.asarray(host.msg_send)
    labels = (np.arange(host.num_vertices, dtype=np.int32) if init is None
              else np.asarray(init, np.int32))
    out = [labels]
    for _ in range(steps):
        out.append(_numpy_superstep(recv, send, out[-1]))
    return out


def _largest_shard_k(host, changed, chunk):
    """K as the mesh job counts it: the messages the ``changed`` vertices
    send into the vertex range that receives most of them."""
    recv, send = np.asarray(host.msg_recv), np.asarray(host.msg_send)
    into = np.bincount(recv[changed[send]] // chunk, minlength=D)
    return int(into.max()), int(into.sum())


def _rows_held_to_a_full_gather():
    """Every superstep's reduce is handed rows that equal, on every shard,
    a full gather of the labels it starts from, slot for slot, whichever
    update made them."""
    real = sharded._mesh_modes_program

    def watched(rows, labels, sg, mesh):
        blank = jax.device_put(np.zeros(rows.shape, np.int32), rows.sharding)
        want = sharded._mesh_gather_program(blank, labels, sg, mesh)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))
        return real(rows, labels, sg, mesh)

    return mock.patch.object(sharded, "_mesh_modes_program", watched)


def _check(host, mesh, steps, want, init=None):
    """One job of ``steps`` supersteps through the public entry: labels,
    rows before every reduce, the record against a NumPy recount. Returns
    the record."""
    sink = MetricsSink()
    with _rows_held_to_a_full_gather():
        got = gm.label_propagation(
            host, max_iter=steps, mesh=mesh, sink=sink, init_labels=init)
    np.testing.assert_array_equal(np.asarray(got), want[steps])
    assert validate_records(sink.records) == []
    by_phase = {r["phase"]: r for r in sink.records}
    assert by_phase["impl_selected"]["scan"] == "carried"
    record = by_phase["superstep_delta"]
    chunk = -(-host.num_vertices // D // 8) * 8
    moved = [want[i + 1] != want[i] for i in range(steps)]
    assert record["changed_vertices"] == [int(c.sum()) for c in moved]
    ks = [_largest_shard_k(host, c, chunk) for c in moved]
    assert record["changed_messages"] == [k for k, _ in ks]
    assert record["shards"] == D
    assert record["num_messages"] == by_phase["exchange"]["messages_per_shard_max"]
    rungs = list(delta_rungs(record["num_messages"]))
    assert record["rungs"] == rungs and record["branch"][0] == "full"
    assert len(record["seconds"]) == steps
    # the mesh job hands over no dirty reduce (ISSUE 43): every reduce runs
    # over every row of the shard, and the record says so
    assert record["reduce"] == ["full"] * steps
    assert len(set(record["dirty_rows"])) == len(set(record["dirty_slots"])) == 1
    for took, k_before in zip(record["branch"][1:], record["changed_messages"]):
        fits = [r for r in rungs if k_before <= r]
        assert took == (fits[0] if fits else "full")
    return record


@pytest.fixture(scope="module")
def kronecker():
    u, v, n = _rmat(12, 16, seed=5)
    host = _host(u, v, n)
    return host, _history(host, 10)


@pytest.mark.parametrize("max_iter", range(1, 11))
def test_the_mesh_job_equals_one_device_and_numpy_after_every_superstep(
        mesh, kronecker, max_iter):
    host, want = kronecker
    record = _check(host, mesh, max_iter, want)
    one = gm.label_propagation(
        gm.build_graph(np.asarray(host.src), np.asarray(host.dst),
                       num_vertices=host.num_vertices), max_iter=max_iter)
    np.testing.assert_array_equal(np.asarray(one), want[max_iter])
    if max_iter == 10:  # the quiet tail of a power-law graph: rungs are taken
        assert len(set(record["branch"])) >= 3


@pytest.mark.parametrize("rung", [0, 1, 2, 3])
def test_a_planted_graph_lands_in_each_rung(mesh, rung):
    """Cliques at their fixpoint with ``n`` labels knocked off it, all in
    the first shard's range: the first superstep puts exactly those back,
    so the largest shard's K = n x (size - 1) picks the second superstep's
    branch, and the third has nothing to rewrite."""
    size = 9
    src, dst, v = _cliques(2400, size)
    host = _host(src, dst, v)
    chunk = -(-v // D // 8) * 8
    largest = int(np.diff(_shard_message_offsets(
        np.asarray(host.msg_ptr), D, chunk)).max())
    rungs = delta_rungs(largest)
    assert len(rungs) == 4
    under = rungs[rung - 1] if rung else 0
    n = under // (size - 1) + 1  # K just above the rung below
    assert under < n * (size - 1) <= rungs[rung] and n * size <= chunk
    init = np.repeat(np.arange(2400) * size, size).astype(np.int32)
    init[np.arange(n) * size + 3] = v - 1 - np.arange(n)  # one a clique
    record = _check(host, mesh, 3, _history(host, 3, init), init)
    assert record["branch"] == ["full", rungs[rung], rungs[0]]
    assert record["changed_messages"][0] == n * (size - 1)


def test_a_quiet_graph_overflows_every_rung_from_a_mid_superstep_on(mesh):
    src, dst, v, init = _fuse(n=600, quiet=3, loud=5)
    host = _host(src, dst, v, symmetric=False)
    record = _check(host, mesh, 8, _history(host, 8, init), init)
    branch = record["branch"]
    assert branch[0] == "full" and all(b != "full" for b in branch[1:4])
    assert branch[4:] == ["full"] * 4  # h2, the block and its sinks flip for good


@pytest.mark.parametrize("max_iter", [1, 9, 10])
def test_a_job_of_any_length_runs_the_programs_already_compiled(
        mesh, kronecker, max_iter):
    """``max_iter`` is the length of the host's loop and no program's
    argument: a job of another length compiles nothing (on the chips a
    program of the cell's size compiles for minutes)."""
    host, want = kronecker
    gm.label_propagation(host, max_iter=10, mesh=mesh)
    compiled = [p._cache_size() for p in PROGRAMS]
    got = gm.label_propagation(host, max_iter=max_iter, mesh=mesh)
    assert [p._cache_size() for p in PROGRAMS] == compiled
    np.testing.assert_array_equal(np.asarray(got), want[max_iter])


# -- the index alone ----------------------------------------------------------


def _named(name):
    rng = np.random.default_rng(39)
    if name == "kronecker":
        u, v, n = _rmat(11, 16, seed=3)
        return _host(u, v, n)
    u, v = rng.integers(0, 700, 9000), rng.integers(0, 700, 9000)
    if name == "weighted":
        return _host(u, v, 1000, edge_weights=rng.random(9000).astype(np.float32))
    return _host(u, v, 1000, symmetric=False)  # 300 vertices have no edge


@pytest.mark.parametrize("name", ["kronecker", "weighted", "directed"])
def test_per_shard_every_real_slot_is_named_by_exactly_one_message(mesh, name):
    host = _named(name)
    _, part, counts = _indexed(host, mesh)
    s, v_pad = shard_row_slots(part), part.padded_vertices
    recv, send = np.asarray(host.msg_recv), np.asarray(host.msg_send)
    assert part.out_ptr.shape == (D * (v_pad + 1),)
    assert part.out_slot.shape == (D * counts.max(),)
    out_ptr, out_slot = part.out_ptr.reshape(D, -1), part.out_slot.reshape(D, -1)
    for d in range(D):
        flat = np.concatenate([b[d].reshape(-1) for b in part.bucket_send])
        ptr, slot = out_ptr[d], out_slot[d]
        assert ptr[0] == 0 and ptr[-1] == counts[d]
        mine = recv // part.chunk_size == d
        np.testing.assert_array_equal(
            np.diff(ptr), np.bincount(send[mine], minlength=v_pad))
        slot, tail = slot[:counts[d]], slot[counts[d]:]
        assert (tail == s).all()  # a shorter shard's padding names no slot
        sender = np.repeat(np.arange(v_pad), np.diff(ptr))
        # a named slot holds its sender; no slot twice; every real slot is
        # named, and no padding slot (they hold the sentinel) by any
        np.testing.assert_array_equal(flat[slot], sender)
        assert len(np.unique(slot)) == len(slot) == (flat < v_pad).sum()
        assert (flat[np.setdiff1d(np.arange(s), slot)] == v_pad).all()


@pytest.mark.parametrize("cap", ["the largest shard's K", "three times it",
                                 "every message of a shard"])
def test_rewritten_rows_equal_gathered_rows_slot_for_slot_per_shard(mesh, cap):
    """``_mesh_rewrite_program`` alone, with slots dropped out of range on
    every shard (``slot = S`` past the spans, ``at = cap`` past the senders:
    under ``shard_map`` the CPU's lowering of an out-of-range scatter once
    corrupted the last slot in range, see ``_shard_row_modes``): the rows of
    one label vector, the slots of the changed senders rewritten, against a
    gather of the other vector. The cap that suffices is the LARGEST
    shard's K, under the sum over shards."""
    host = _named("kronecker")
    sg, part, counts = _indexed(host, mesh)
    v, v_pad = host.num_vertices, sg.padded_vertices
    rng = np.random.default_rng(7)
    old = np.arange(v_pad, dtype=np.int32)
    old[:v] = rng.integers(0, v, v)
    new = old.copy()
    moved = np.flatnonzero(rng.random(v) < 0.02)
    new[moved] = rng.integers(0, v, len(moved))
    changed = new != old
    largest, total = _largest_shard_k(host, changed[:v], sg.chunk_size)
    assert 0 < largest < total
    cap = {"the largest shard's K": largest, "three times it": 3 * largest,
           "every message of a shard": int(counts.max())}[cap]
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    blank = lambda: jax.device_put(
        np.zeros(D * shard_row_slots(sg), np.int32), sg.out_slot.sharding)
    put = lambda x: jax.device_put(x, rep)
    rows = sharded._mesh_gather_program(blank(), put(old), sg, mesh)
    held = np.asarray(rows).reshape(D, -1)
    for d in range(D):  # padding slots hold the sentinel from the gather on
        flat = np.concatenate([b[d].reshape(-1) for b in part.bucket_send])
        assert (held[d][flat == v_pad] == np.iinfo(np.int32).max).all()
    got = sharded._mesh_rewrite_program(
        rows, put(new), put(changed), sg, mesh, cap=cap)
    want = sharded._mesh_gather_program(blank(), put(new), sg, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(want).reshape(D, -1) != held).any()


# -- who takes the path -------------------------------------------------------


def _sizes():
    return [p._cache_size() for p in PROGRAMS], sharded._sharded_lpa_jit._cache_size()


@pytest.mark.parametrize("how", [
    "under a trace", "tripwire_every", "telemetry", "a plain admission",
    "a mesh across processes", "the sort family",
])
def test_who_cannot_step_from_the_host_runs_the_one_program(
        mesh, kronecker, how, monkeypatch):
    _, want = kronecker
    u, v, n = _rmat(12, 16, seed=5)
    host = _host(u, v, n)  # a graph of its own: the answer is kept per graph
    carried, plain = _sizes()
    sink = MetricsSink()
    if how == "under a trace":
        got = jax.jit(lambda: gm.label_propagation(host, max_iter=5, mesh=mesh))()
    elif how in ("tripwire_every", "telemetry"):
        sg, _, _ = _indexed(host, mesh)
        kw = {"tripwire_every": 2} if how == "tripwire_every" else {"telemetry": True}
        got = sharded_label_propagation(sg, mesh, max_iter=5, **kw)
        got = got[0] if how == "telemetry" else got
    else:
        if how == "a plain admission":
            monkeypatch.setattr(
                superstep_policy, "mesh_memory_stats",
                lambda mesh: {"bytes_limit": 1 << 16, "bytes_in_use": 0})
            monkeypatch.setattr(
                sharded, "with_shard_slot_index", lambda *a: pytest.fail(
                    "the index was built for a job that was not admitted"))
        elif how == "a mesh across processes":
            monkeypatch.setattr(jax, "process_count", lambda: 2)
        family = "sort" if how == "the sort family" else "auto"
        got = gm.label_propagation(
            host, max_iter=5, mesh=mesh, sink=sink, plan=family)
        by_phase = {r["phase"]: r for r in sink.records}
        assert validate_records(sink.records) == []
        assert by_phase["impl_selected"]["scan"] == "plain"
        assert "superstep_delta" not in by_phase
        if family == "auto":
            held = by_phase["device_residency"]
            assert held["scan"] == "plain" and held["shards"] == D
            assert held["rows_bytes"] == held["slot_index_bytes"] == 0
            assert held["reason"] == by_phase["impl_selected"]["scan_reason"]
        (_, placed), = lpa_mod._mesh_partition_cache[id(host.msg_ptr)][1].items()
        assert placed[0].out_slot is None and placed[1]["scan"][0] == "plain"
    np.testing.assert_array_equal(np.asarray(got), want[5])
    now_carried, now_plain = _sizes()
    assert now_carried == carried and now_plain >= plain


def test_under_a_trace_nothing_is_asked_and_nothing_kept(mesh, kronecker):
    _, want = kronecker
    u, v, n = _rmat(12, 16, seed=5)
    host = _host(u, v, n)
    jax.jit(lambda: gm.label_propagation(host, max_iter=2, mesh=mesh))()
    assert id(host.msg_ptr) not in lpa_mod._mesh_partition_cache
    # a later call from the top level asks, and carries
    sink = MetricsSink()
    got = gm.label_propagation(host, max_iter=5, mesh=mesh, sink=sink)
    np.testing.assert_array_equal(np.asarray(got), want[5])
    (selected,) = [r for r in sink.records if r["phase"] == "impl_selected"]
    assert selected["scan"] == "carried"
    # and a trace over the kept partition runs the one program on it
    got = jax.jit(lambda: gm.label_propagation(host, max_iter=5, mesh=mesh))()
    np.testing.assert_array_equal(np.asarray(got), want[5])


def test_the_records_say_what_one_chip_holds(mesh, kronecker):
    host, _ = kronecker
    sink = MetricsSink()
    gm.label_propagation(host, max_iter=2, mesh=mesh, sink=sink)
    by_phase = {r["phase"]: r for r in sink.records}
    (_, placed), = lpa_mod._mesh_partition_cache[id(host.msg_ptr)][1].items()
    sg = placed[0]
    held = by_phase["device_residency"]
    per_chip = lambda *trees: sum(x.nbytes for t in trees for x in jax.tree.leaves(t)) // D
    assert held["shards"] == D and held["scan"] == "carried"
    assert held["graph_bytes"] == 0 and "code_bytes" not in held
    assert held["plan_bytes"] == per_chip(sg.bucket_send, sg.bucket_target)
    assert held["rows_bytes"] == 4 * shard_row_slots(sg)
    assert held["slot_index_bytes"] == per_chip(sg.out_ptr, sg.out_slot) == 4 * (
        sg.padded_vertices + 1 + by_phase["exchange"]["messages_per_shard_max"])
    assert held["labels_bytes"] == 8 * sg.padded_vertices
    assert held["reason"] == by_phase["impl_selected"]["scan_reason"]
    assert held["reason"].startswith(f"a shard of {D}, on the fullest chip: rows")
    build = by_phase["plan_build"]
    assert 0 <= build["index_seconds"] <= build["seconds"]


def test_the_index_goes_with_the_graph(mesh):
    u, v, n = _rmat(11, 16, seed=8)
    host = _host(u, v, n)
    gm.label_propagation(host, max_iter=2, mesh=mesh)
    key = id(host.msg_ptr)
    (_, placed), = lpa_mod._mesh_partition_cache[key][1].items()
    index = placed[0].out_slot
    assert index is not None and len(index.sharding.device_set) == D
    index = weakref.ref(index)
    del host, placed
    gc.collect()
    assert key not in lpa_mod._mesh_partition_cache and index() is None


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_a_weighted_graph_carries_its_weights_on_any_mesh(devices):
    """``bucket_weight`` is slot-aligned and never moves: the carried job
    takes weighted graphs too, on a mesh of any size (one shard: the
    exchange moves nothing and K is the one shard's)."""
    rng = np.random.default_rng(2)
    u, v = rng.integers(0, 3000, 40000), rng.integers(0, 3000, 40000)
    w = rng.integers(1, 4, 40000).astype(np.float32)  # auto is bucketed on one
    host = _host(u, v, 3000, edge_weights=w)
    sink = MetricsSink()
    got = gm.label_propagation(host, max_iter=6, mesh=gm.make_mesh(devices), sink=sink)
    want = gm.label_propagation(
        gm.build_graph(u, v, num_vertices=3000, edge_weights=w), max_iter=6, plan=None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    by_phase = {r["phase"]: r for r in sink.records}
    assert by_phase["impl_selected"]["scan"] == "carried"
    assert by_phase["superstep_delta"]["shards"] == devices


def test_the_job_asks_for_an_index(mesh, kronecker):
    host, _ = kronecker
    part = partition_graph(host, mesh=mesh, lpa_only=True, build_bucket_plan=True)
    with pytest.raises(ValueError, match="no slot index"):
        carried_label_propagation(shard_graph_arrays(part, mesh, lpa_only=True), mesh)
