"""The one public mesh entry: ``label_propagation(graph, mesh=mesh)`` on a
host-resident graph over four virtual devices (PR 27). Labels are held to
a plain NumPy synchronous LPA and to the one-device entry, label for
label; no shard array ever sits whole on one device; the four shards'
owned ranges add up to the whole result; the host-side offsets hold past
2^30 messages."""

import numpy as np
import pytest

import jax

import graphmine_tpu as gm
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import lpa as lpa_mod
from graphmine_tpu.parallel.sharded import (
    _shard_message_offsets,
    partition_graph,
)
from graphmine_tpu.pipeline.metrics import MetricsSink

D = 4
FAMILIES = ("auto", "bucketed", "sort")  # auto = bucketed on a mesh


def _graph(seed: int, v: int = 600, e: int = 5000):
    """Power-law-ish endpoints: hubs, isolated vertices, duplicate edges
    and self-loops all occur."""
    rng = np.random.default_rng(seed)
    ids = np.minimum((rng.pareto(1.1, 2 * e) * v / 20).astype(np.int64), v - 1)
    return ids[:e].astype(np.int32), ids[e:].astype(np.int32), v


def _numpy_superstep(recv, send, labels):
    """The most frequent incoming label, the smallest on a tie; a vertex
    that receives nothing keeps its label."""
    new = labels.copy()
    order = np.lexsort((labels[send], recv))
    r, lab = recv[order], labels[send][order]
    for vtx in np.unique(r):
        vals, counts = np.unique(lab[r == vtx], return_counts=True)
        new[vtx] = vals[np.argmax(counts)]  # first maximum = smallest label
    return new


def _numpy_lpa(src, dst, v, iters):
    recv = np.concatenate([dst, src])
    send = np.concatenate([src, dst])
    labels = np.arange(v, dtype=np.int32)
    for _ in range(iters):
        labels = _numpy_superstep(recv, send, labels)
    return labels


@pytest.fixture(scope="module")
def mesh():
    return gm.make_mesh(D)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("seed", (0, 1, 2))
def test_mesh_entry_equals_numpy_and_one_device(mesh, seed, family):
    src, dst, v = _graph(seed)
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    assert isinstance(host.msg_send, np.ndarray)
    sink = MetricsSink()
    got = np.asarray(gm.label_propagation(
        host, max_iter=5, plan=family, mesh=mesh, sink=sink))
    np.testing.assert_array_equal(got, _numpy_lpa(src, dst, v, 5))
    one = gm.label_propagation(gm.build_graph(src, dst, num_vertices=v), max_iter=5)
    np.testing.assert_array_equal(got, np.asarray(one))
    # the records of one call, registered and complete
    assert validate_records(sink.records) == []
    by_phase = {r["phase"]: r for r in sink.records}
    assert by_phase["impl_selected"]["impl"] == (
        "bucketed" if family == "auto" else family)
    assert by_phase["partition"]["shards"] == D
    exchange = by_phase["exchange"]
    vc = -(-v // D // 8) * 8
    assert exchange["bytes_per_superstep"] == 4 * vc * (D - 1)
    assert exchange["messages_per_shard_max"] >= exchange["messages_per_shard_mean"]
    assert exchange["messages_per_shard_mean"] * D == 2 * len(src)


def test_mesh_entry_partitions_once_and_keeps_nothing_whole(mesh):
    src, dst, v = _graph(7, v=2000, e=40000)
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    sink = MetricsSink()
    gm.label_propagation(host, max_iter=2, mesh=mesh, sink=sink)
    gm.label_propagation(host, max_iter=2, mesh=mesh, sink=sink)
    assert [r["cached"] for r in sink.records if r["phase"] == "partition"] == [
        False, True]
    (_, placed), = lpa_mod._mesh_partition_cache[id(host.msg_ptr)][1].items()
    sg = placed[0]
    assert sg.msg_send is None and sg.msg_recv_local is None  # lpa_only trimming
    leaves = jax.tree_util.tree_leaves(sg)
    assert leaves
    for leaf in leaves:
        # every array is split over the four devices, none committed to one
        # (a stacked [D, ...] array a row each, the flat slot index of the
        # carried rows, PR 39, a quarter of its length each)
        assert len(leaf.sharding.device_set) == D
        assert leaf.addressable_shards[0].data.shape[0] * D == leaf.shape[0]
    total = sum(leaf.size for leaf in leaves)
    for dev in mesh.devices.flat:
        held = sum(s.data.size for leaf in leaves
                   for s in leaf.addressable_shards if s.device == dev)
        assert held * D == total  # a quarter each, the padding included
    # nothing of the graph's size sits whole on one device (a chip's own
    # piece of a placed array is its share: on this skewed draw the first
    # shard's slot index alone is longer than the edge list)
    pieces = {id(s.data) for leaf in leaves for s in leaf.addressable_shards}
    for arr in jax.live_arrays():
        if arr.size >= host.num_edges and id(arr) not in pieces:
            assert len(arr.sharding.device_set) == D
    # the cache goes with the graph
    key = id(host.msg_ptr)
    del host, sg, placed, leaves, leaf
    import gc

    gc.collect()
    assert key not in lpa_mod._mesh_partition_cache


@pytest.mark.parametrize("seed", (3, 4))
def test_shards_owned_ranges_add_up_to_the_whole(mesh, seed):
    """The share-adds-up test: each shard, given only the messages its own
    vertex range receives, yields its range of the superstep; the four
    ranges, concatenated, are the whole superstep and what the mesh entry
    returns."""
    src, dst, v = _graph(seed)
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    sg = partition_graph(host, num_shards=D)
    vc = sg.chunk_size
    labels = np.arange(D * vc, dtype=np.int32)
    parts = []
    for s in range(D):
        keep = sg.msg_recv_local[s] < vc
        recv = sg.msg_recv_local[s][keep] + s * vc
        new = _numpy_superstep(recv, sg.msg_send[s][keep], labels)
        untouched = np.ones(D * vc, bool)
        untouched[s * vc:(s + 1) * vc] = False
        np.testing.assert_array_equal(new[untouched], labels[untouched])
        parts.append(new[s * vc:(s + 1) * vc])
    whole = np.concatenate(parts)[:v]
    np.testing.assert_array_equal(whole, _numpy_lpa(src, dst, v, 1))
    got = gm.label_propagation(host, max_iter=1, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), whole)


def test_mesh_entry_arguments(mesh):
    src, dst, v = _graph(5)
    host = gm.build_graph(src, dst, num_vertices=v, to_device=False)
    with pytest.raises(ValueError, match="return_history"):
        gm.label_propagation(host, mesh=mesh, return_history=True)
    with pytest.raises(ValueError, match="family name"):
        gm.label_propagation(host, mesh=mesh, plan=None)
    with pytest.raises(ValueError, match="unknown superstep family"):
        gm.label_propagation(host, mesh=mesh, plan="ring")
    # custom initial labels ride the mesh too (no histogram path there)
    init = np.arange(v, dtype=np.int32)[::-1].copy()
    got = gm.label_propagation(host, max_iter=3, init_labels=init, mesh=mesh)
    dev = gm.build_graph(src, dst, num_vertices=v)
    want = gm.label_propagation(dev, max_iter=3, init_labels=init)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a mesh of one device is one shard and an exchange of nothing
    sink = MetricsSink()
    one = gm.label_propagation(host, max_iter=3, mesh=gm.make_mesh(1), sink=sink)
    np.testing.assert_array_equal(
        np.asarray(one), np.asarray(gm.label_propagation(dev, max_iter=3)))
    (exchange,) = [r for r in sink.records if r["phase"] == "exchange"]
    assert exchange["shards"] == 1 and exchange["bytes_per_superstep"] == 0


# ---- host side past 2^30 messages, through shapes only ---------------------


def test_shard_offsets_hold_past_int32():
    """graph500-25 has 1.047 G messages; a host CSR may hold more than
    2^31. The shard boundaries come off the int64 row pointers exactly."""
    v, per_vertex = 64, 1 << 25            # M = 2^31 > int32
    ptr = np.arange(v + 1, dtype=np.int64) * per_vertex
    assert int(ptr[-1]) == 1 << 31
    offsets = _shard_message_offsets(ptr, 4, 16)
    assert offsets.dtype == np.int64
    assert offsets.tolist() == [0, 1 << 29, 1 << 30, 3 << 29, 1 << 31]
    # vertex count not a multiple of the chunk: the tail shard stops at V
    offsets = _shard_message_offsets(ptr, 3, 24)
    assert offsets.tolist() == [0, 24 << 25, 48 << 25, 1 << 31]
    assert np.diff(offsets).max() < (1 << 31)


def test_partition_refuses_a_shard_past_int32_before_any_copy():
    """A shard whose message run passes 2^31-1 is refused loudly; the
    zero-stride arrays make the M = 2^31 + 8 messages cost no memory, so
    the refusal provably comes before any slicing."""
    from graphmine_tpu.graph.container import Graph

    v, m = 8, (1 << 31) + 8
    ptr = np.arange(v + 1, dtype=np.int64) * (m // v)
    zeros = np.broadcast_to(np.int32(0), (m,))
    g = Graph(src=zeros[:1], dst=zeros[:1], msg_recv=zeros, msg_send=zeros,
              msg_ptr=ptr, num_vertices=v)
    with pytest.raises(ValueError, match="int32 gather-index bound"):
        partition_graph(g, num_shards=1)


def test_planner_sends_graph500_25_to_four_chips():
    from graphmine_tpu.pipeline.planner import (
        PlanError,
        messages_per_device,
        plan_run,
    )

    v, e = 1 << 25, 523_598_893
    hbm = 16 * 10**9
    with pytest.raises(PlanError):
        plan_run(v, e, num_devices=1, hbm=hbm)
    plan = plan_run(v, e, num_devices=4, hbm=hbm)
    assert (plan.schedule, plan.family, plan.lpa_only) == (
        "replicated", "bucketed", True)
    assert messages_per_device("replicated", e, 4) < (1 << 31)
    assert plan_run(v, e, num_devices=4, hbm=hbm, requested="ring").family is None


def test_native_csr_threads_equal_the_numpy_sort():
    """Past 2^22 edges the native counting sort runs one thread per
    receiver range; the layout stays the single-threaded one, which is the
    NumPy stable sort's."""
    from graphmine_tpu.graph.container import _message_csr

    rng = np.random.default_rng(11)
    v, e = 100_003, (1 << 22) + 1234
    src = rng.integers(0, v, e).astype(np.int32)
    dst = np.minimum((rng.pareto(1.2, e) * 50).astype(np.int64), v - 1).astype(np.int32)
    for symmetric in (True, False):
        native = _message_csr(src, dst, v, symmetric, use_native=True)
        plain = _message_csr(src, dst, v, symmetric, use_native=False)
        for a, b in zip(native[:3], plain[:3]):
            np.testing.assert_array_equal(a, b)
