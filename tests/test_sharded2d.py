"""2D edge partition + neighbor-only frontier exchange (r16, ISSUE 15).

The ``sharded_2d`` family replaces the per-superstep label all_gather —
O(V) bytes per chip regardless of the live frontier — with per-peer
boundary ``ppermute`` shifts carrying exactly the label slots each
peer's bins read. This suite pins, on the 8-virtual-device CPU mesh:

* LPA **and** CC bit-parity against the single-device sort oracle over
  power-law / ring / self-loop / isolated-vertex / duplicate-edge
  graphs, weighted included (the r8 order-independence contract);
* per-peer boundary index-table exactness on hand-built 3-shard graphs
  (the gather tables reconstruct the blocked stream's global sender ids
  slot-for-slot);
* the crossover policy + env-override pins (the single policy owner in
  ``ops/blocking.select_superstep_family``) and the degradation rung
  back to the one-all_gather family;
* costmodel / memmodel exact arithmetic for the new family (modeled
  exchange bytes strictly below the 4·Vc·(D-1) ladder);
* plan-time per-peer-buffer pre-degrade with the inventory in the
  record (the r15 contract);
* the serve warm-repair e2e through the 2D family (sampled exact check
  still passes) and the exchange bench tier's CPU-fallback capture.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.cc import connected_components
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.parallel import make_mesh
from graphmine_tpu.parallel.sharded import (
    partition_graph,
    shard_graph_arrays,
    sharded_connected_components,
    sharded_label_propagation,
    sharded_lpa_fixpoint,
)

pytestmark = pytest.mark.sharded2d

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(8)


def _graphs(rng):
    """The parity graph zoo: power-law, ring (high diameter — the
    local-pointer-jump CC convergence case), self-loops, isolated
    vertices, duplicate edges."""
    v = 96
    deg = rng.pareto(1.2, 400)
    pl_src = np.minimum((deg * v / 40).astype(np.int64), v - 1).astype(np.int32)
    pl_dst = rng.integers(0, v, 400).astype(np.int32)
    ring_src = np.arange(64, dtype=np.int32)
    ring_dst = ((ring_src + 1) % 64).astype(np.int32)
    loops = np.arange(0, 40, 2, dtype=np.int32)
    dup = rng.integers(0, 30, 50).astype(np.int32)
    return [
        ("powerlaw", pl_src, pl_dst, v),
        ("ring", ring_src, ring_dst, 64),
        ("self_loops", np.concatenate([pl_src[:100], loops]),
         np.concatenate([pl_dst[:100], loops]), v),
        # vertices 90..95 isolated (edges only touch [0, 90))
        ("isolated", pl_src[:200] % 90, pl_dst[:200] % 90, v),
        ("duplicates", np.concatenate([dup, dup]),
         np.concatenate([dup[::-1], dup[::-1]]), 30),
    ]


def _partition_2d(g, mesh, **kw):
    return shard_graph_arrays(
        partition_graph(g, mesh=mesh, build_plan2d=True, **kw), mesh
    )


# ---- bit-parity vs the sort oracle -----------------------------------------


def test_2d_lpa_cc_bit_parity(mesh8, rng):
    for name, src, dst, v in _graphs(rng):
        g = build_graph(src, dst, num_vertices=v)
        sg = _partition_2d(g, mesh8)
        assert sg.blk_src is None and sg.x2d_src_local is not None, name
        want = np.asarray(label_propagation(g, max_iter=4))
        got = np.asarray(sharded_label_propagation(sg, mesh8, max_iter=4))
        np.testing.assert_array_equal(got, want, err_msg=f"lpa/{name}")
        want_cc = np.asarray(connected_components(g))
        got_cc = np.asarray(sharded_connected_components(sg, mesh8))
        np.testing.assert_array_equal(got_cc, want_cc, err_msg=f"cc/{name}")


def test_2d_weighted_lpa_bit_parity(mesh8, rng):
    v, e = 80, 400
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    w = rng.uniform(0.1, 3.0, e).astype(np.float32)
    g = build_graph(src, dst, num_vertices=v, edge_weights=w)
    want = np.asarray(label_propagation(g, max_iter=4))
    sg = _partition_2d(g, mesh8)
    assert sg.blk_row_weight, "weighted partition must carry weight mats"
    got = np.asarray(sharded_label_propagation(sg, mesh8, max_iter=4))
    np.testing.assert_array_equal(got, want)


def test_2d_matches_blocked_family_per_superstep(mesh8, rng):
    """Stronger than final-label parity for LPA: every superstep count
    agrees with the one-all_gather blocked family (the tile contents are
    value-for-value identical by construction)."""
    v, e = 70, 300
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    g = build_graph(src, dst, num_vertices=v)
    mesh = mesh8
    sg_blk = shard_graph_arrays(
        partition_graph(g, mesh=mesh, build_blocked_plan=True), mesh
    )
    sg_2d = _partition_2d(g, mesh)
    for it in (1, 2, 3, 5):
        a = np.asarray(sharded_label_propagation(sg_blk, mesh, max_iter=it))
        b = np.asarray(sharded_label_propagation(sg_2d, mesh, max_iter=it))
        np.testing.assert_array_equal(a, b, err_msg=f"superstep {it}")


def test_2d_fixpoint_and_warm_start(mesh8, rng):
    """The serve repair entry: warm-started fixpoint through the 2D
    family converges to the same labels as the cold oracle, and a
    fixpoint stays a fixpoint under one more superstep (the sampled
    exact check's predicate)."""
    v, e = 90, 350
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    g = build_graph(src, dst, num_vertices=v)
    sg = _partition_2d(g, mesh8)
    labels, it, conv = sharded_lpa_fixpoint(sg, mesh8, max_iter=64)
    assert conv and it >= 1
    import jax.numpy as jnp

    again, it2, conv2 = sharded_lpa_fixpoint(
        sg, mesh8, max_iter=1, init_labels=jnp.asarray(labels)
    )
    assert conv2
    np.testing.assert_array_equal(np.asarray(again), np.asarray(labels))


def test_2d_multi_axis_mesh_rejected(rng):
    from graphmine_tpu.parallel.mesh import make_multislice_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = make_multislice_mesh(2, 2)
    src = rng.integers(0, 40, 200).astype(np.int32)
    dst = rng.integers(0, 40, 200).astype(np.int32)
    g = build_graph(src, dst, num_vertices=40)
    sg = _partition_2d(g, mesh)
    with pytest.raises(ValueError, match="1-D mesh"):
        sharded_label_propagation(sg, mesh, max_iter=2)


# ---- per-peer index tables (hand-built 3-shard graphs) ---------------------


def _decode_table_ids(sg):
    """Global sender id of every compact-table slot, per shard — padding
    slots decode arbitrarily but are never referenced by real stream
    entries (asserted by the caller via the blocked twin)."""
    d, vc, b = sg.num_shards, sg.chunk_size, sg.x2d_boundary
    tab = np.asarray(sg.x2d_send_tab)
    ids = np.zeros((d, vc + (d - 1) * b + 1), dtype=np.int64)
    for s in range(d):
        ids[s, :vc] = s * vc + np.arange(vc)
        for r in range(1, d):
            owner = (s - r) % d
            ids[s, vc + (r - 1) * b: vc + r * b] = (
                owner * vc + tab[owner, r - 1]
            )
        ids[s, -1] = vc * d  # the sentinel slot decodes to the sentinel id
    return ids


def test_index_tables_reconstruct_stream_3_shards(rng):
    """Decoding each shard's compact table through the send tables must
    reproduce the blocked family's global sender stream slot-for-slot —
    the strongest statement that every peer ships exactly (and only)
    the label slots its neighbor's bins read."""
    v = 18
    src = np.array([0, 3, 7, 11, 15, 17, 2, 9, 9, 4], dtype=np.int32)
    dst = np.array([6, 13, 1, 5, 0, 12, 2, 16, 16, 10], dtype=np.int32)
    for pad in (1, 8):
        blk = partition_graph(
            src, dst, num_vertices=v, num_shards=3,
            build_blocked_plan=True, pad_multiple=pad,
        )
        sg = partition_graph(
            src, dst, num_vertices=v, num_shards=3,
            build_plan2d=True, pad_multiple=pad,
        )
        ids = _decode_table_ids(sg)
        decoded = np.take_along_axis(
            ids, np.asarray(sg.x2d_src_local, np.int64), axis=1
        )
        np.testing.assert_array_equal(decoded, np.asarray(blk.blk_src))


def test_boundary_sets_are_unique_sorted_and_exact():
    """Hand-computed boundary sets on a 3-shard graph (pad_multiple=1 →
    Vc = 2): shard 0 owns {0,1}, shard 1 {2,3}, shard 2 {4,5}. Edges are
    symmetric messages, so each endpoint is a sender toward the other."""
    # edges: 0-2, 1-4, 3-5  (messages both directions)
    src = np.array([0, 1, 3], dtype=np.int32)
    dst = np.array([2, 4, 5], dtype=np.int32)
    sg = partition_graph(
        src, dst, num_vertices=6, num_shards=3,
        build_plan2d=True, pad_multiple=1,
    )
    d, vc, b = 3, sg.chunk_size, sg.x2d_boundary
    assert vc == 2
    tab = np.asarray(sg.x2d_send_tab)
    # need(shard, offset r) == what owner (shard - r) % 3 ships at shift r
    # shard 0 reads: sender 2 (owner 1, r=2), sender 4 (owner 2, r=1)
    # shard 1 reads: sender 0 (owner 0, r=1), sender 5 (owner 2, r=2)
    # shard 2 reads: sender 1 (owner 0, r=2), sender 3 (owner 1, r=1)
    want = {
        # (owner, r) -> local ids shipped
        (2, 1): [0],   # 4 -> shard 0
        (1, 2): [0],   # 2 -> shard 0
        (0, 1): [0],   # 0 -> shard 1
        (2, 2): [1],   # 5 -> shard 1
        (1, 1): [1],   # 3 -> shard 2
        (0, 2): [1],   # 1 -> shard 2
    }
    for (owner, r), ids in want.items():
        got = tab[owner, r - 1, : len(ids)].tolist()
        assert got == ids, ((owner, r), got, ids)
    assert sg.x2d_boundary_total == 6
    assert b >= 1


def test_plan2d_mutually_exclusive_with_bucket_plan(rng):
    src = rng.integers(0, 20, 50).astype(np.int32)
    dst = rng.integers(0, 20, 50).astype(np.int32)
    with pytest.raises(ValueError, match="mutually exclusive"):
        partition_graph(
            src, dst, num_vertices=20, num_shards=2,
            build_bucket_plan=True, build_plan2d=True,
        )


# ---- crossover policy + planner ladder -------------------------------------


def test_policy_mesh_auto_is_bucket_rows_not_2d():
    """PR 27: on four chips the 2D family's three passes lost to one pass
    of per-shard bucket rows + one all_gather (PERF.md §6), so ``auto`` on
    a mesh resolves ``bucketed`` at every size and 2D only on request."""
    from graphmine_tpu.ops.blocking import select_superstep_family

    for m in (1 << 10, 1 << 14, 1 << 30):
        fam, reason = select_superstep_family(1 << 16, m, num_devices=8)
        assert fam == "bucketed" and "all_gather" in reason
    # single device: never 2D, whatever the size
    fam, _ = select_superstep_family(1 << 22, 1 << 23, num_devices=1)
    assert fam != "sharded_2d"


def test_policy_requested_2d_on_one_device_is_loud():
    from graphmine_tpu.ops.blocking import select_superstep_family

    with pytest.raises(ValueError, match="2-device mesh"):
        select_superstep_family(100, 100, requested="sharded_2d")
    fam, reason = select_superstep_family(
        100, 100, requested="sharded_2d", num_devices=4
    )
    assert fam == "sharded_2d" and "requested" in reason


def test_policy_env_overrides(monkeypatch):
    from graphmine_tpu.ops.blocking import (
        crossover_thresholds,
        select_superstep_family,
    )

    # the model-seeded 2D thresholds and their env overrides went with
    # the branch (PR 27)
    monkeypatch.setenv("GRAPHMINE_SHARDED2D_MIN_MESSAGES", "10")
    monkeypatch.setenv("GRAPHMINE_SHARDED2D_MIN_DEVICES", "3")
    assert not [k for k in crossover_thresholds() if "2d" in k]
    fam, _ = select_superstep_family(100, 10, num_devices=3)
    assert fam == "bucketed"
    # the process-wide family override applies to sharded resolutions
    # but silently does NOT apply on one device (fused ops keep working)
    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "sharded_2d")
    fam, reason = select_superstep_family(100, 5, num_devices=2)
    assert fam == "sharded_2d" and "env override" in reason
    fam, _ = select_superstep_family(100, 5, num_devices=1)
    assert fam != "sharded_2d"


def test_planner_ladder_degrades_2d_to_one_allgather():
    from graphmine_tpu.obs.memmodel import FAMILY_DEGRADE
    from graphmine_tpu.pipeline.planner import (
        _SUPERSTEP_DEGRADE,
        plan_superstep,
    )

    assert _SUPERSTEP_DEGRADE["sharded_2d"] == "blocked"
    assert FAMILY_DEGRADE["sharded_2d"] == "blocked"
    plan = plan_superstep(
        1 << 16, 1 << 14, requested="sharded_2d", num_devices=8
    )
    assert plan.family == "sharded_2d" and plan.degrade_to == "blocked"
    # auto on a mesh: the bucket rows, one rung above sort (PR 27)
    plan = plan_superstep(1 << 16, 1 << 14, num_devices=8)
    assert plan.family == "bucketed" and plan.degrade_to == "sort"
    # single-device resolution is byte-identical to the pre-r16 policy
    plan1 = plan_superstep(1 << 16, 1 << 14)
    assert plan1.family != "sharded_2d"


# ---- costmodel / memmodel exact arithmetic ---------------------------------


def _tiny_2d_partition(rng, v=4096, e=8192, d=4):
    # power-law-skewed sources (the bench graph's shape): boundaries
    # stay well under Vc, so the strictly-below pins have real margin
    raw = rng.pareto(1.2, e)
    src = np.minimum((raw * v / 50).astype(np.int64), v - 1).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    return partition_graph(
        src, dst, num_vertices=v, num_shards=d, build_plan2d=True
    )


def test_costmodel_exchange_bytes_exact_and_below_ladder(rng):
    from graphmine_tpu.obs.costmodel import (
        allgather_exchange_bytes,
        neighbor_exchange_bytes,
        neighbor_frontier_bytes,
        sharded_superstep_cost,
    )

    for d in (4, 8):
        sg = _tiny_2d_partition(rng, d=d)
        cost = sharded_superstep_cost("lpa_superstep", sg, 8192)
        assert cost.family == "sharded_2d"
        assert cost.devices == d
        # WIRE bytes, exact: (D-1) padded shared-width buffers per chip
        assert cost.exchange_bytes == 4 * (d - 1) * sg.x2d_boundary
        assert cost.exchange_bytes == neighbor_exchange_bytes(sg)
        # frontier floor, exact: ceil(unpadded total / D) * 4 bytes
        frontier = neighbor_frontier_bytes(sg)
        assert frontier == 4 * -(-sg.x2d_boundary_total // d)
        assert frontier <= cost.exchange_bytes
        ladder = allgather_exchange_bytes(sg)
        assert ladder == 4 * sg.chunk_size * (d - 1)
        # the acceptance pin: strictly below the one-all_gather model —
        # for the honest WIRE bytes, padding included
        assert cost.exchange_bytes < ladder
        # compute model matches the blocked family's shapes
        mp = int(np.asarray(sg.x2d_src_local).shape[1])
        rows = sum(
            int(r.shape[1]) * int(r.shape[2]) for r in sg.blk_row_idx
        )
        assert cost.padded_slots == mp + rows


def test_memmodel_footprint_exact_against_shapes(rng):
    from graphmine_tpu.obs.memmodel import sharded_superstep_footprint

    d = 4
    sg = _tiny_2d_partition(rng, d=d)
    est = sharded_superstep_footprint("lpa_superstep", sg)
    assert est.family == "sharded_2d" and est.exact
    b = sg.x2d_boundary
    inv = est.inventory
    assert inv["exchange_send_tab"] == 4 * (d - 1) * b
    assert inv["exchange_recv_bufs"] == 4 * (d - 1) * b
    assert inv["labels_sharded"] == 2 * 4 * sg.chunk_size
    assert "labels_replicated" not in inv and "exchange_buffer" not in inv
    mp = int(np.asarray(sg.x2d_src_local).shape[1])
    assert inv["stream"] == 4 * mp + 4 * mp  # src_local + blk_pos
    # the record round-trips through the schema's mem sub-record shape
    rec = est.record()
    assert rec["family"] == "sharded_2d" and rec["total_bytes"] > 0


def test_predegrade_per_peer_buffers(monkeypatch):
    """A plan whose per-peer buffer footprint exceeds the budget
    pre-degrades at plan time, with the oversized inventory carried in
    the steps trail (r15 contract); a generous budget keeps the 2D
    family."""
    from graphmine_tpu.obs.memmodel import (
        predegrade_superstep,
        superstep_footprint,
    )

    v, m, e, d = 1 << 16, 1 << 17, 1 << 16, 8
    est = superstep_footprint(
        "lpa_superstep", "sharded_2d", v, m, num_edges=e, num_devices=d
    )
    assert not est.exact and est.devices == d
    vc = -(-v // d)
    assert est.inventory["exchange_send_tab"] == 4 * vc * (d - 1)
    # budget below the 2D model: walks off the family, first rung is the
    # one-all_gather blocked family, inventory attached
    fam, _fit, steps = predegrade_superstep(
        "sharded_2d", v, m, e, False, est.total_bytes // 4, num_devices=d
    )
    assert fam != "sharded_2d" and steps
    assert steps[0][0] == "sharded_2d" and steps[0][1] == "blocked"
    assert steps[0][2].total_bytes == est.total_bytes
    # generous budget: stays
    fam2, _f, steps2 = predegrade_superstep(
        "sharded_2d", v, m, e, False, 1 << 40, num_devices=d
    )
    assert fam2 == "sharded_2d" and not steps2
    with pytest.raises(ValueError, match="num_devices >= 2"):
        superstep_footprint(
            "lpa_superstep", "sharded_2d", v, m, num_edges=e
        )


def test_shard_exchange_record_shape(rng):
    import time

    from graphmine_tpu.obs.costmodel import emit_shard_exchange
    from graphmine_tpu.obs.schema import validate_record

    class Sink:
        def emit(self, phase, **kv):
            return dict(phase=phase, t=time.time(), **kv)

    sg = _tiny_2d_partition(rng)
    rec = emit_shard_exchange(Sink(), "delta_repair", sg)
    assert validate_record(rec) == []
    assert rec["family"] == "sharded_2d" and rec["peers"] == 3
    assert rec["frontier_bytes"] <= rec["exchange_bytes"]
    assert rec["frontier_frac"] == round(
        rec["frontier_bytes"] / rec["ladder_bytes"], 4
    )
    # the one-all_gather families emit frac 1.0 by construction
    sg_sort = partition_graph(
        np.arange(8, dtype=np.int32), np.arange(8, dtype=np.int32)[::-1],
        num_vertices=8, num_shards=2,
    )
    rec2 = emit_shard_exchange(Sink(), "delta_repair", sg_sort)
    assert rec2["family"] == "sort" and rec2["frontier_frac"] == 1.0
    assert emit_shard_exchange(None, "x", sg) is None


# ---- serve warm-repair e2e -------------------------------------------------


def _community_edges(rng, v=60):
    half = v // 2
    src = np.concatenate(
        [rng.integers(0, half, 120), rng.integers(half, v, 120)]
    ).astype(np.int32)
    dst = np.concatenate(
        [rng.integers(0, half, 120), rng.integers(half, v, 120)]
    ).astype(np.int32)
    return src, dst


def test_serve_warm_repair_selects_2d(tmp_path, monkeypatch, rng):
    """The acceptance e2e: past the (env-lowered) crossover the sharded
    ingestor repairs through the 2D family — asserted from the
    shard_exchange record and last_shard_family — and the published
    labels still pass the sampled exact check (method == warm) and match
    the cold oracle."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from graphmine_tpu.obs.spans import Tracer
    from graphmine_tpu.pipeline.checkpoint import graph_fingerprint
    from graphmine_tpu.pipeline.metrics import MetricsSink
    from graphmine_tpu.serve.delta import (
        DeltaIngestor,
        EdgeDelta,
        cold_recompute,
        splice_edges,
        validate_delta,
    )
    from graphmine_tpu.serve.snapshot import SnapshotStore

    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "sharded_2d")
    v = 60
    src, dst = _community_edges(rng, v)
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    sink = MetricsSink(tracer=Tracer())
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {"src": src, "dst": dst, "labels": labels, "cc_labels": cc,
         "lof": np.zeros(v, np.float32)},
        fingerprint=graph_fingerprint(src, dst), sink=sink,
    )
    ing = DeltaIngestor(
        store, sink=sink, lof_k=4, check_samples=16, num_shards=8,
        quality=False,
    )
    delta = EdgeDelta.from_pairs(
        insert=[(40, 12), (40, 13), (40, 14)], delete=[(0, 1)]
    )
    snap = ing.apply(delta)
    assert ing.last_shard_family == "sharded_2d"
    ex = [r for r in sink.records if r.get("phase") == "shard_exchange"]
    assert ex and ex[-1]["family"] == "sharded_2d"
    # at this toy scale the pad_multiple floor dominates the WIRE bytes;
    # the exact frontier content is what the tiny repair saves
    assert ex[-1]["frontier_bytes"] < ex[-1]["ladder_bytes"]
    rec = [r for r in sink.records if r.get("phase") == "delta_apply"][-1]
    assert rec["method"] == "warm"
    clean, _ = validate_delta(delta, v)
    src2, dst2, v2, _ = splice_edges(src, dst, v, clean)
    cold_l, cold_c, _ = cold_recompute(build_graph(src2, dst2, num_vertices=v2))
    np.testing.assert_array_equal(snap["labels"], cold_l)
    np.testing.assert_array_equal(snap["cc_labels"], cold_c)


def test_serve_predegrades_2d_on_tiny_budget(tmp_path, monkeypatch, rng):
    """A per-peer buffer footprint past the HBM budget pre-degrades at
    plan time: the repair routes through the one-all_gather partition,
    the degrade record carries the oversized memmodel inventory, and the
    published labels are still exact."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from graphmine_tpu.obs.spans import Tracer
    from graphmine_tpu.pipeline.checkpoint import graph_fingerprint
    from graphmine_tpu.pipeline.metrics import MetricsSink
    from graphmine_tpu.serve.delta import (
        DeltaIngestor,
        EdgeDelta,
        cold_recompute,
    )
    from graphmine_tpu.serve.snapshot import SnapshotStore

    monkeypatch.setenv("GRAPHMINE_SUPERSTEP_FAMILY", "sharded_2d")
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", "512")  # nothing 2D fits
    v = 60
    src, dst = _community_edges(rng, v)
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    sink = MetricsSink(tracer=Tracer())
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {"src": src, "dst": dst, "labels": labels, "cc_labels": cc,
         "lof": np.zeros(v, np.float32)},
        fingerprint=graph_fingerprint(src, dst), sink=sink,
    )
    ing = DeltaIngestor(
        store, sink=sink, lof_k=4, check_samples=16, num_shards=8,
        quality=False,
    )
    ing.apply(EdgeDelta.from_pairs(insert=[(40, 12), (40, 13)]))
    assert ing.last_shard_family == "sort"
    deg = [
        r for r in sink.records
        if r.get("phase") == "degrade" and r.get("kind") == "mem_plan"
    ]
    assert deg and deg[0]["stage"] == "delta_repair_plan"
    assert deg[0]["mem"]["family"] == "sharded_2d"
    assert "exchange_send_tab" in deg[0]["mem"]["inventory"]
    ex = [r for r in sink.records if r.get("phase") == "shard_exchange"]
    assert ex and ex[-1]["family"] == "sort"


# ---- bench exchange tier ---------------------------------------------------


def test_exchange_tier_body_cpu_smoke():
    """Run ``main_exchange``'s ACTUAL measurement body end-to-end on an
    8-virtual-device CPU mesh at env-capped tiny scale (the blocking
    tier's convention), and pin the acceptance criterion: modeled 2D
    exchange bytes strictly below the one-all_gather 4·Vc·(D-1) on the
    bench power-law graph at D >= 4, read from the costmodel-derived
    record of the CPU-fallback capture."""
    sys.path.insert(0, _REPO)
    try:
        import __graft_entry__

        env = __graft_entry__._load_envscrub().virtual_cpu_env(8)
    finally:
        sys.path.pop(0)
    env.update(
        GRAPHMINE_BENCH_CPU_FALLBACK="1",
        _GRAPHMINE_BENCH_CHILD="1",
        GRAPHMINE_EXCHANGE_VERTICES=str(1 << 13),
        GRAPHMINE_EXCHANGE_EDGES=str(1 << 14),
        GRAPHMINE_EXCHANGE_ITERS="2",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--tier",
         "exchange"],
        capture_output=True, text=True, timeout=420, env=env, cwd=_REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert rec["metric"] == "exchange_neighbor_bytes_frac_cpu_fallback"
    assert 0 < rec["value"] < 1
    d = rec["detail"]
    assert d["neighbor_vs_allgather"] > 0
    for dd in ("2", "4", "8"):
        row = d["per_devices"][dd]
        assert row["agree"], f"parity failed at D={dd}"
        # the ladder model exactly: 4·Vc·(D-1), Vc = ceil(V/D) padded
        # to the partitioner's multiple of 8
        n = int(dd)
        vc = -(-(-(-d["num_vertices"] // n)) // 8) * 8
        assert row["allgather_exchange_bytes"] == 4 * vc * (n - 1)
    # THE acceptance pin: strictly below the ladder at D >= 4
    for dd in ("4", "8"):
        row = d["per_devices"][dd]
        assert (
            row["neighbor_exchange_bytes"] < row["allgather_exchange_bytes"]
        ), f"2D exchange bytes not below the all_gather ladder at D={dd}"


def test_exchange_tier_registered():
    """Tier order / timeout / manifest / bench_diff registration — the
    next silicon window captures the crossover alongside the blocking
    backlog."""
    sys.path.insert(0, _REPO)
    try:
        import importlib

        bench = importlib.import_module("bench")
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            bench_diff = importlib.import_module("bench_diff")
        finally:
            sys.path.pop(0)
    finally:
        sys.path.pop(0)
    assert "exchange" in bench._TIER_ORDER
    assert "exchange" in bench._FALLBACK_TIERS
    assert "exchange" in bench._CHILD_TIMEOUT_S
    assert tuple(bench._TIER_ORDER) == bench_diff.ALL_TIERS
    assert bench_diff.SUB_RECORDS["exchange"] == ("neighbor_vs_allgather",)
    assert "frac" in bench_diff.LOWER_BETTER_UNITS
    # the orchestrator hands the exchange child a virtual multi-device
    # mesh unless the operator marks a real multi-chip window
    env = bench._tier_child_env("exchange", dict(os.environ))
    assert env.get("GRAPHMINE_BENCH_CPU_FALLBACK") == "1"
    assert "xla_force_host_platform_device_count=8" in env.get("XLA_FLAGS", "")
