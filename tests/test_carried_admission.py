"""The admission of the carried-rows LPA scan (ISSUE 33): the memory model
counts the carried rows and the slot index to the byte, the policy admits
them under the device's free memory and answers ``plain`` otherwise,
before any index is built; either way the labels are the same bit for bit,
and the ``device_residency`` record says what the device holds."""

import sys

import jax
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs import memmodel
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import lpa, superstep_policy
from graphmine_tpu.ops.bucketed_mode import row_slots, with_slot_index
from graphmine_tpu.ops.lpa import label_propagation
from graphmine_tpu.ops.superstep_policy import admit_carried_rows
from graphmine_tpu.pipeline.metrics import MetricsSink

from test_lpa_delta import _case, _fused, _rmat

# the package exports a function of the module's name
bucketed_mode = sys.modules["graphmine_tpu.ops.bucketed_mode"]

PLAN_TERMS = ("plan_mats", "plan_vertex_ids", "plan_hub_offsets", "weight_mats",
              "slot_index")


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _footprint(g, plan, **kw):
    return memmodel.superstep_footprint(
        "lpa_superstep", "bucketed", g.num_vertices, g.num_messages,
        num_edges=g.num_edges, plan=plan, **kw).inventory


@pytest.mark.parametrize("name", [
    "rmat_with_a_histogram_hub", "star", "path", "isolated_vertices", "weighted",
    "directed",
])
def test_the_footprint_of_an_indexed_plan_is_its_arrays_to_the_byte(name):
    g, plan, _, _ = _case(name)
    indexed = with_slot_index(plan)
    inv = _footprint(g, indexed)
    assert sum(inv.get(term, 0) for term in PLAN_TERMS) == _nbytes(indexed)
    assert inv["slot_index"] == indexed.out_ptr.nbytes + indexed.out_slot.nbytes
    assert inv["carried_rows"] == 4 * row_slots(plan)
    # as the chip's compiler counts them: the scan's further copies of its
    # rows, the hubs' [n, V] histograms and the scatter's copy of them
    assert inv["gather_transient"] == 3 * inv["carried_rows"]
    hubs = 0 if plan.hist_vertex_ids is None else plan.hist_vertex_ids.shape[0]
    assert inv["hub_histograms"] == 8 * hubs * g.num_vertices
    assert (hubs > 0) == (name in ("rmat_with_a_histogram_hub", "star"))
    # the graph's own arrays: endpoints, the message CSR, the weights
    graph_terms = ("edge_endpoints", "message_csr", "msg_weights")
    assert sum(inv.get(term, 0) for term in graph_terms) == _nbytes(g)
    # before the index is built the same terms are known from the shapes
    assert memmodel.carried_rows_inventory(plan).items() <= inv.items()
    plain = _footprint(g, plan)
    assert not set(memmodel.carried_rows_inventory(plan)) - {"gather_transient"} & set(plain)
    assert plain["gather_transient"] == 4 * (row_slots(plan) + (
        0 if plan.hist_send is None else plan.hist_send.shape[0]))
    assert sum(plain.get(term, 0) for term in PLAN_TERMS) == _nbytes(plan)


def _need(g, plan) -> int:
    return sum(memmodel.carried_rows_inventory(plan).values())


@pytest.mark.parametrize("free,want", [(-1, "plain"), (0, "carried"), (1 << 20, "carried")])
def test_the_admission_follows_the_devices_free_memory(free, want):
    g, plan, _, _ = _case("rmat_with_a_histogram_hub")
    in_use = 123_456
    limit = in_use + _need(g, plan) + free
    scan, reason = admit_carried_rows(plan, {"bytes_limit": limit, "bytes_in_use": in_use})
    assert scan == want
    assert f"{_need(g, plan)} B" in reason and f"of {limit} B" in reason
    assert "the host's compile memory is not sized" in reason


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}],
                         ids=["no-statistics", "empty", "no-limit"])
def test_a_device_that_reports_no_limit_admits_the_carried_rows(stats):
    _, plan, _, _ = _case("path")
    scan, reason = admit_carried_rows(plan, stats)
    assert scan == "carried" and "no limit" in reason


def test_a_plan_with_no_rows_is_not_asked_to_carry():
    g, plan = _fused(np.zeros(0, np.int64), np.zeros(0, np.int64), 5)
    assert admit_carried_rows(plan, None)[0] == "plain"
    assert lpa._cached_slot_index(plan)[2][0] == "plain"


def _squeeze(monkeypatch, limit):
    """The device of every plan reports ``limit`` bytes, none in use."""
    monkeypatch.setattr(superstep_policy, "device_memory_stats",
                        lambda plan: {"bytes_limit": limit, "bytes_in_use": 0})


@pytest.mark.parametrize("name", ["rmat_with_a_histogram_hub", "weighted", "directed"])
def test_both_scans_give_the_same_labels_and_the_same_family(name, monkeypatch):
    g, _, steps, _ = _case(name)
    _, tight, _, _ = _case(name)  # a plan of its own: the answer is kept per plan
    _, roomy, _, _ = _case(name)
    out = {}
    for scan, plan, limit in (("plain", tight, _need(g, tight) - 1),
                              ("carried", roomy, _need(g, roomy))):
        _squeeze(monkeypatch, limit)
        sink = MetricsSink()
        labels = label_propagation(g, max_iter=steps, plan=plan, sink=sink)
        ran = lpa._cached_slot_index(plan)
        assert ran[2][0] == scan and (ran[0].out_slot is not None) == (scan == "carried")
        (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
        assert len(delta["branch"]) == len(delta["changed_vertices"]) == steps
        if scan == "plain":  # nothing kept: every superstep gathers in full
            assert set(delta["branch"]) == {"full"}
            assert delta["changed_messages"] == delta["rungs"] == []
        out[scan] = (np.asarray(labels), delta["changed_vertices"])
    np.testing.assert_array_equal(out["plain"][0], out["carried"][0])
    assert out["plain"][1] == out["carried"][1]  # the labels each superstep moved
    np.testing.assert_array_equal(
        out["plain"][0], np.asarray(label_propagation(g, max_iter=steps, plan=None)))


def test_auto_says_which_scan_it_admitted_and_what_the_device_holds(monkeypatch):
    u, v, n = _rmat(12, 16, seed=9)  # 131,072 messages: auto is bucketed
    records = {}
    for scan in ("plain", "carried"):
        g = build_graph(u, v, num_vertices=n)
        plan = lpa._cached_auto_plan(g)[0]
        limit = _need(g, plan) - (scan == "plain")
        _squeeze(monkeypatch, limit)
        sink = MetricsSink()
        labels = label_propagation(g, max_iter=5, plan="auto", sink=sink)
        assert validate_records(sink.records) == []
        by_phase = {r["phase"]: r for r in sink.records}
        selected, held = by_phase["impl_selected"], by_phase["device_residency"]
        assert selected["impl"] == "bucketed" and selected["scan"] == scan
        assert selected["scan_reason"] == held["reason"] and held["scan"] == scan
        assert held["bytes_limit"] == limit and held["code_bytes"] is None
        assert held["graph_bytes"] == _nbytes(g) and held["plan_bytes"] == _nbytes(plan)
        assert held["labels_bytes"] == 8 * n
        indexed = lpa._cached_slot_index(plan)[0]
        if scan == "carried":
            assert held["rows_bytes"] == 4 * row_slots(plan)
            assert held["slot_index_bytes"] == _nbytes(indexed) - _nbytes(plan)
        else:
            assert held["rows_bytes"] == held["slot_index_bytes"] == 0
            assert indexed.out_slot is None
        sparse = [b for b in by_phase["superstep_delta"]["branch"] if b != "full"]
        assert bool(sparse) == (scan == "carried")
        assert "transient_bytes" not in held  # arrays only: no guess at temporaries
        records[scan] = np.asarray(labels)
    np.testing.assert_array_equal(records["plain"], records["carried"])


def test_the_answer_is_taken_once_a_plan_and_plain_builds_no_index(monkeypatch):
    g, plan, steps, _ = _case("isolated_vertices")

    def no_index(plan):
        raise AssertionError("the index was built for a scan that was not admitted")

    monkeypatch.setattr(bucketed_mode, "with_slot_index", no_index)
    _squeeze(monkeypatch, 1)
    want = np.asarray(label_propagation(g, max_iter=steps, plan=None))
    np.testing.assert_array_equal(
        np.asarray(label_propagation(g, max_iter=steps, plan=plan)), want)
    _squeeze(monkeypatch, 1 << 40)  # room now: the plan keeps its first answer
    assert lpa._cached_slot_index(plan)[2][0] == "plain"
    np.testing.assert_array_equal(
        np.asarray(label_propagation(g, max_iter=steps, plan=plan)), want)


def test_on_a_host_graph_the_record_counts_no_graph_bytes():
    import graphmine_tpu as gm

    u, v, n = _rmat(10, 16, seed=3)
    host = gm.build_graph(u, v, num_vertices=n, to_device=False)
    plan = bucketed_mode.BucketedModePlan.from_edges(u, v, n)
    sink = MetricsSink()
    superstep_policy.emit_device_residency(
        sink, "lpa_superstep", host, plan, ("plain", "a test"))
    (held,) = sink.records
    assert held["graph_bytes"] == 0 and held["plan_bytes"] == _nbytes(plan)
    assert held["bytes_limit"] is None and validate_records(sink.records) == []


@pytest.mark.parametrize("plan", ["auto", None], ids=["bucketed", "sort"])
def test_host_and_device_graphs_give_equal_labels_on_one_device(plan):
    """The one-device entry takes the host-resident graph the mesh entry
    takes; a warm fused job moves nothing of it to the device."""
    import graphmine_tpu as gm

    u, v, n = _rmat(12, 16, seed=21)
    host = gm.build_graph(u, v, num_vertices=n, to_device=False)
    assert isinstance(host.msg_send, np.ndarray)
    want = np.asarray(label_propagation(build_graph(u, v, num_vertices=n), max_iter=6,
                                        plan=plan))
    sink = MetricsSink()
    got = label_propagation(host, max_iter=6, plan=plan, sink=sink)
    np.testing.assert_array_equal(np.asarray(got), want)
    held = [r for r in sink.records if r["phase"] == "device_residency"]
    if plan == "auto":
        assert held[0]["graph_bytes"] == 0 and held[0]["plan_bytes"] > 0
        with jax.transfer_guard_host_to_device("disallow"):
            again = label_propagation(host, max_iter=6, plan=plan)
        np.testing.assert_array_equal(np.asarray(again), want)
    else:
        assert held == []


def test_without_a_sink_the_record_asks_the_device_nothing(monkeypatch):
    def asked(plan):
        raise AssertionError("a PJRT query on the path with tracing off")

    monkeypatch.setattr(superstep_policy, "device_memory_stats", asked)
    _, plan, _, _ = _case("path")
    assert superstep_policy.emit_device_residency(
        None, "lpa_superstep", None, plan, ("plain", "a test")) is None
