"""The admission of the carried-rows LPA job (ISSUE 33; the rows held once
since ISSUE 36): the memory model counts the carried rows and the slot
index to the byte and the temporaries of the job's largest program from
the plan's shapes, the policy admits them under the device's free memory
and answers ``plain`` otherwise, before any index is built; either way the
labels are the same bit for bit, and the ``device_residency`` record says
what the device holds."""

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
from graphmine_tpu.ops.superstep_policy import admit_carried_rows, delta_rungs
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
    assert inv["carried_rows"] == 4 * row_slots(plan)  # once: updated in place
    assert inv["labels"] == 8 * g.num_vertices and inv["changed_mask"] == g.num_vertices
    # as the chip holds them: the largest program's temporaries (without a
    # rung the full gather or the row modes), its largest class in the
    # chip's tiles and the padded labels twice; the hubs' [n, V] histograms
    # and the scatter's copy of them
    by_program = memmodel.carried_job_transients(plan)
    assert by_program["rewrite"] == 0
    assert inv["gather_transient"] == max(by_program.values())
    assert by_program["gather"] == 8 * (g.num_vertices + 1) + max(
        sum(memmodel._tiled(*idx.shape)) for idx in plan.send_idx)
    assert by_program["modes"] >= by_program["gather"]
    hubs = 0 if plan.hist_vertex_ids is None else plan.hist_vertex_ids.shape[0]
    assert inv["hub_histograms"] == 8 * hubs * g.num_vertices
    assert (hubs > 0) == (name in ("rmat_with_a_histogram_hub", "star"))
    # the graph's own arrays: endpoints, the message CSR, the weights
    graph_terms = ("edge_endpoints", "message_csr", "msg_weights")
    assert sum(inv.get(term, 0) for term in graph_terms) == _nbytes(g)
    # before the index is built the same terms are known from the shapes
    assert memmodel.carried_rows_inventory(plan).items() <= inv.items()
    plain = _footprint(g, plan)
    assert not set(memmodel.carried_rows_inventory(plan)) - {"gather_transient", "labels"} & set(plain)
    assert plain["gather_transient"] == 4 * (row_slots(plan) + (
        0 if plan.hist_send is None else plan.hist_send.shape[0]))
    assert sum(plain.get(term, 0) for term in PLAN_TERMS) == _nbytes(plan)


def _need(g, plan) -> int:
    """What the admission holds against the free memory: the inventory with
    the rewrite at the plan's top rung among the programs."""
    top = max(delta_rungs(plan.num_messages), default=0)
    return sum(memmodel.carried_rows_inventory(plan, top_rung=top).values())


# -- the plan of the graph that fills one chip, from its shapes ---------------

# graphalytics-g500-24's fused plan, [n, w] a class (a host-only build of the
# configuration's draw, _proof/shapes24.py): V = 2^24, M = 520,752,272,
# S = 542,524,857 padded slots, four histogram hubs
_G500_24_CLASSES = [
    (2157572, 1), (1102139, 2), (681618, 3), (465124, 4), (366473, 5),
    (330564, 6), (307882, 7), (269267, 8), (216382, 9), (159026, 10),
    (107252, 11), (69081, 12), (46079, 13), (35663, 14), (35137, 15),
    (42527, 16), (53486, 17), (67211, 18), (80915, 19), (93489, 20),
    (210322, 22), (211937, 24), (181899, 26), (134477, 28), (85468, 30),
    (61899, 33), (19908, 36), (4881, 39), (1041, 42), (462, 46), (1589, 50),
    (10003, 55), (36574, 60), (113755, 66), (189136, 72), (218013, 79),
    (120469, 86), (39756, 94), (5562, 103), (311, 113), (5, 124), (73, 179),
    (3272, 196), (48159, 215), (169957, 236), (113231, 259), (11286, 284),
    (126, 312), (1011, 665), (70739, 731), (62433, 804), (413, 884),
    # past 2048 the ladder keeps its 1.10x step (PR 42; 1.5x before:
    # (42504, 3072), (545, 6912), (10081, 10368), (2024, 23328),
    # (276, 78732), (21, 177147), 607,595,159 slots in all)
    (11092, 2253), (31411, 2479), (1, 2727), (7322, 7083), (3304, 7792),
    (2024, 22240), (276, 63466), (21, 164623),
]
_G500_24_LIMIT, _G500_24_IN_USE = 16_909_336_064, 8_850_000_000  # PERF.md §4


def _g500_24_plan():
    """Shapes only: the admission reads nothing else of a plan."""
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, np.int32)
    v, m, hist_send = 1 << 24, 520_752_272, 897_725
    return bucketed_mode.BucketedModePlan(
        vertex_ids=tuple(i32(n) for n, _ in _G500_24_CLASSES), msg_idx=None,
        num_vertices=v, num_messages=m,
        send_idx=tuple(i32(n, w) for n, w in _G500_24_CLASSES),
        hist_vertex_ids=i32(4), hist_send=i32(hist_send),
        hist_row_offset=i32(hist_send),
    )


def test_the_inventory_of_graph500_24_s_plan_term_by_term():
    """Each term from the shapes, beside what the chip's compiler assigned
    the programs compiled alone for a v5e (PERF.md §6, PR 36; PR 42 for
    the plan whose rows past 2048 are on the 1.10x ladder)."""
    plan = _g500_24_plan()
    v, m, s = plan.num_vertices, plan.num_messages, 542_524_857
    top = delta_rungs(m)[-1]
    assert row_slots(plan) == s and top == m // 6 == 86_792_045
    inv = memmodel.carried_rows_inventory(plan, top_rung=top)
    # the largest class is [31411, 2479] now ([42504, 3072] before): no
    # multiple of 128, so the chip keeps it column-major as 31488 x 2480
    # and the flat rows' slice of it is 31416 rows of 2560 lanes
    kept, lanes = 4 * 2_480 * 31_488, 4 * 31_416 * 2_560
    assert memmodel._tiled(31_411, 2_479) == (kept, lanes) == (312_360_960, 321_699_840)
    assert inv == {
        "carried_rows": 4 * s,            # 2.17 GB, once (2.43 before)
        "slot_index": 4 * (m + v + 1),    # 2.15 GB
        "labels": 8 * v, "changed_mask": v,
        "hub_histograms": 8 * 4 * v,      # 0.54 GB
        # the rewrite at M / 6: five cap-long vectors (compiled: 1,736,506,880)
        "gather_transient": 20 * top,
    }
    # without a rung the largest program is the row modes: the widest
    # class's sort, key and stability iota in and out (four of the class
    # as kept: its slice of the rows is not the matrix the sort reads),
    # and the labels twice (compiled: 1,667,266,560, which is 0.28 GB over
    # this term: a hub histogram's 268,435,456 lies beside the sort now,
    # inside the inventory's own `hub_histograms` beside it); the full
    # gather holds the class as kept and as its lanes (compiled: 705,036,800)
    # the dirty reduce (PR 43): ten V-vectors, the 8,870,505 rows' vertex
    # ids end to end, a trip's pairwise forms and eight forms of the widest
    # coarse width's [8, 262144] (164,623 up to a power of two); compiled:
    # 677,249,536 with the hubs' histograms' 536,870,912 in it. Never the
    # largest program: the admission asks what it asked before
    rows_of_plan = sum(n for n, _ in _G500_24_CLASSES)
    dirty = 4 * (10 * (v + 1) + rows_of_plan + 3 * 2048 * 32 + 8 * 8 * 262_144)
    assert rows_of_plan == 8_870_505 and dirty == 774_465_996
    assert memmodel.carried_job_transients(plan, top_rung=top) == {
        "gather": kept + lanes + 8 * (v + 1), "modes": 4 * kept + 8 * (v + 1),
        "rewrite": 20 * top, "dirty_modes": dirty}
    assert 20 * top > 4 * kept + 8 * (v + 1) > dirty > kept + lanes + 8 * (v + 1) > 32 * v
    # no rung, no rewrite, no dirty reduce
    assert set(memmodel.carried_job_transients(plan)) == {"gather", "modes", "rewrite"}
    no_rung = memmodel.carried_rows_inventory(plan)
    assert no_rung["gather_transient"] == 4 * kept + 8 * (v + 1) == 1_383_661_576
    # a lower rung's rewrite is its sort of V keys, in and out (compiled:
    # 537,257,984 at M / 4096)
    low = memmodel.carried_rows_inventory(plan, top_rung=delta_rungs(m)[0])
    assert low["gather_transient"] == no_rung["gather_transient"]
    assert 32 * v == 536_870_912 < no_rung["gather_transient"]
    assert sum(inv.values()) == 6_743_924_140
    # PageRank's one iteration on the same plan (compiled: 716,777,984)
    assert memmodel.row_sum_transients(plan) == kept + lanes + 8 * (v + 1) + 20 * v \
        == 1_103_822_856


# GAP Urand at scale 24 (benchmark/configs/gap-urand-24.json): V = 2^24,
# M = 536,870,374, S = 551,072,031 padded slots in 28 narrow classes, no hub
# (_proof/urand_24_shapes.json, a host-only build of the configuration's draw)
_URAND_24_CLASSES = [
    (2, 7), (5, 8), (21, 9), (77, 10), (168, 11), (495, 12), (1305, 13),
    (2913, 14), (6085, 15), (12189, 16), (23232, 17), (40998, 18), (69383, 19),
    (111343, 20), (413837, 22), (796345, 24), (1299908, 26), (1821602, 28),
    (2214635, 30), (3504377, 33), (2936114, 36), (1917093, 39), (994879, 42),
    (482345, 46), (108040, 50), (18555, 55), (1229, 60), (41, 66),
]


def _urand_24_plan():
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, np.int32)
    return bucketed_mode.BucketedModePlan(
        vertex_ids=tuple(i32(n) for n, _ in _URAND_24_CLASSES), msg_idx=None,
        num_vertices=1 << 24, num_messages=536_870_374,
        send_idx=tuple(i32(n, w) for n, w in _URAND_24_CLASSES),
    )


def test_the_inventory_of_gap_urand_24_s_plan_term_by_term():
    """A flat plan's largest programs are the gather and the modes whatever
    the rung: the chip keeps a narrow class column-major in tiles of 8 x 128
    (3,504,377 x 33 as 3,504,384 x 40) and the flat rows row-major, so the
    class passes through 3,504,384 rows of 128 LANES, 3.9 times its size, on
    its way into the rows and out of them. Beside what the chip's compiler
    assigned the programs compiled alone for a v5e (PERF.md §6, PR 38)."""
    plan = _urand_24_plan()
    v, m, s = plan.num_vertices, plan.num_messages, 551_072_031
    top = delta_rungs(m)[-1]
    assert row_slots(plan) == s and top == m // 6 == 89_478_395
    kept, lanes = 4 * 40 * 3_504_384, 4 * 3_504_384 * 128
    assert memmodel._tiled(3_504_377, 33) == (kept, lanes) == (560_701_440, 1_794_244_608)
    assert memmodel.carried_job_transients(plan, top_rung=top) == {
        "gather": kept + lanes + 8 * (v + 1),  # compiled: 2,423,228,928
        # in and out through the lanes; the sort's four, 4 x kept, are less
        "modes": kept + lanes + 8 * (v + 1),   # compiled: 2,359,736,320
        "rewrite": 20 * top,                   # compiled: 1,790,246,400
        # ten V-vectors, 16,777,216 ids, the pairwise forms, [8, 128] eight times
        "dirty_modes": 4 * (10 * (v + 1) + (1 << 24) + 3 * 2048 * 32 + 8 * 8 * 128),
    }
    assert kept + lanes > 4 * kept and 20 * top == 1_789_567_900
    inv = memmodel.carried_rows_inventory(plan, top_rung=top)
    assert inv == {
        "carried_rows": 4 * s,            # 2.20 GB, once
        "slot_index": 4 * (m + v + 1),    # 2.21 GB
        "labels": 8 * v, "changed_mask": v, "hub_histograms": 0,
        "gather_transient": 2_489_163_784,
    }
    # the term does not lean on the top rung: it is the same without one
    assert memmodel.carried_rows_inventory(plan) == inv
    assert sum(inv.values()) == 7_059_037_216
    # the chip tiles this plan to 2.47 GB where its nbytes say 2.20: beside
    # the 6.51 GB graph 7.86 GB are free, not graph500-24's 8.06
    scan, reason = admit_carried_rows(
        plan, {"bytes_limit": _G500_24_LIMIT, "bytes_in_use": 9_051_000_000})
    assert scan == "carried" and "7059037216 B against 7858336064 B free" in reason
    assert ("(gather; gather 2489163784 B, modes 2489163784 B, rewrite 1789567900 B, "
            "dirty_modes 739016744 B)") in reason
    scan, reason = admit_carried_rows(
        plan, {"bytes_limit": _G500_24_LIMIT, "bytes_in_use": _G500_24_LIMIT - 7_059_037_215})
    assert scan == "plain" and "a full gather every superstep" in reason


@pytest.mark.parametrize("copy,owner", [
    ("_PAIRWISE_MAX_W", "_PAIRWISE_MAX_W"), ("_DIRTY_TRIP_ROWS", "_DIRTY_GROUP_ROWS")])
def test_the_constants_the_transients_count_by_are_the_plan_builder_s(copy, owner):
    """``obs/memmodel.py`` imports no jax and so keeps its own copy of the
    width above which a class is reduced by the row sort, and of the rows a
    trip of the dirty reduce takes."""
    assert getattr(memmodel, copy) == getattr(bucketed_mode, owner)


@pytest.mark.parametrize("n,w,want", [
    (1000, 1, (4096, 4096)),              # a single column is a vector
    (1000, 2, (4 * 8 * 1024, 4 * 1000 * 128)),
    (1000, 33, (4 * 40 * 1024, 4 * 1000 * 128)),
    (1001, 128, (4 * 1008 * 128,) * 2),   # whole lanes: row-major is the smaller
    (42504, 3072, (4 * 42504 * 3072,) * 2),
    (31411, 2479, (4 * 2480 * 31488, 4 * 31416 * 2560)),  # no whole lanes, either way
    (41, 66, (4 * 48 * 128,) * 2),
], ids=lambda x: str(x))
def test_a_class_s_bytes_on_the_chip_follow_its_tiles(n, w, want):
    assert memmodel._tiled(n, w) == want


@pytest.mark.parametrize("in_use,want", [
    (_G500_24_IN_USE, "carried"),                            # 8.06 GB free: the cell
    (_G500_24_LIMIT - 6_743_924_140, "carried"),             # to the byte
    (_G500_24_LIMIT - 6_743_924_140 + 1, "plain"),
    (_G500_24_LIMIT - 4 * 542_524_857, "plain"),             # room for the rows alone
], ids=["beside-the-resident-graph", "exactly", "a-byte-short", "rows-only"])
def test_graph500_24_s_plan_is_admitted_beside_its_device_resident_graph(in_use, want):
    """PR 33 counted the rows four times (12.41 GB) and answered ``plain``
    at 8.06 GB free; held once the job asked for 7.00 GB (PR 36), and 6.74
    GB since the ladder keeps its step past 2048 (PR 42)."""
    scan, reason = admit_carried_rows(
        _g500_24_plan(), {"bytes_limit": _G500_24_LIMIT, "bytes_in_use": in_use})
    assert scan == want
    assert "6743924140 B" in reason and "held once" in reason
    assert f"against {_G500_24_LIMIT - in_use} B free" in reason
    assert "not sized" in reason


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
        assert held["bytes_limit"] == limit and "code_bytes" not in held
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


# -- a shard's share, on a mesh (ISSUE 39) ------------------------------------

# graph500-25 over four chips (benchmark/configs/graphalytics-g500-25.json):
# _proof/g500_25_x4_shapes.json, the stacked plan's per-shard class shapes
# and the shards' message counts from a host-only build of the
# configuration's own draw (_proof/mesh_shapes_and_k.py): no draw here
_X4_LIMIT, _X4_IN_USE = 16_909_336_064, 1_986_000_000  # PERF.md §4: 11.7 % a chip
_X4_SUM = 4_957_738_916


def _g500_25_x4():
    """``(one shard's plan by shapes, shards)``, as the mesh entry hands it
    to the admission (``parallel/sharded.shard_plan_shapes``)."""
    import json
    import os

    from graphmine_tpu.parallel.sharded import ShardedGraph, shard_plan_shapes

    said = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "_proof", "g500_25_x4_shapes.json")))
    d = said["shards"]
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, np.int32)
    sg = ShardedGraph(
        msg_recv_local=None, msg_send=None, degrees=None,
        num_vertices=said["num_vertices"], chunk_size=said["chunk_size"],
        num_shards=d,
        bucket_send=tuple(i32(d, n, w) for n, w in said["classes"]),
        bucket_target=tuple(i32(d, n) for n, _ in said["classes"]),
    )
    return shard_plan_shapes(sg, max(said["messages_per_shard"])), d


def test_the_inventory_of_a_shard_of_graph500_25_term_by_term():
    """What ONE of four chips holds for the carried mesh job, beside what
    the chip's compiler assigned the mesh programs compiled for four
    described chips from these shapes (PERF.md §6, PR 39)."""
    plan, d = _g500_25_x4()
    v_pad, largest, s = 1 << 25, 265_143_516, 278_577_418
    assert (d, plan.num_vertices, plan.num_messages, row_slots(plan)) == (
        4, v_pad, largest, s)
    # no hubs on a mesh: every vertex past 2048 is a row, on the ladder's own
    # 1.10x step since PR 42 (ten classes past 2048, the longest [1, 687684];
    # six on the 1.5x steps, 62 classes and 307,343,931 slots in all)
    assert len(plan.send_idx) == 66 and plan.hist_vertex_ids is None
    rungs = delta_rungs(largest)
    assert rungs == (64_732, 1_035_716, 16_571_469, 44_190_586)
    # the gather holds its largest class as kept and as its row-major form,
    # as on one chip; the mesh `modes` its three largest classes at once
    # (its classes do not take turns): the row sorts of [13302, 3632],
    # [38187, 1175] and [3197, 11411], four of each as the chip keeps it
    # (column-major: 13312 x 3632, 38272 x 1176, 3200 x 11416; none is whole
    # lanes where it lies in the rows); the labels, padded, twice
    wide, wider, third = 13_312 * 3_632 * 4, 38_272 * 1_176 * 4, 3_200 * 11_416 * 4
    lanes = 13_304 * 3_712 * 4  # [13302, 3632] row-major
    labels = 8 * (v_pad + 1)
    assert memmodel.carried_job_transients(plan, top_rung=rungs[-1], shards=d) == {
        "gather": wide + lanes + labels,                     # compiled: 527,318,016
        "modes": 4 * (wide + wider + third) + labels,        # compiled: 1,678,616,576
        "rewrite": 32 * v_pad,             # compiled: 1,074,322,432 at most (M/16)
    }
    # on one chip the classes take turns: the same shapes count one class
    # (the one-chip job's dirty reduce, which the mesh job never runs, is
    # not a shard's to expect)
    one_chip = memmodel.carried_job_transients(plan, top_rung=rungs[-1])
    assert {k: one_chip[k] for k in ("gather", "modes", "rewrite")} == {
        "gather": wide + lanes + labels, "modes": 4 * wide + labels,
        "rewrite": 32 * v_pad}
    assert 32 * v_pad > 20 * rungs[-1]  # the sort of V keys, not the top rung
    inv = memmodel.carried_rows_inventory(plan, top_rung=rungs[-1], shards=d)
    assert inv == {
        "carried_rows": 4 * s,                           # 1.11 GB, once (1.23 before)
        "slot_index": 4 * (largest + v_pad + 1),         # 1.19 GB
        "labels": 8 * v_pad, "changed_mask": v_pad,      # replicated: a chip holds all
        "hub_histograms": 0,                             # no hubs on a mesh
        "gather_transient": 4 * (wide + wider + third) + labels,
    }
    assert sum(inv.values()) == _X4_SUM


@pytest.mark.parametrize("in_use,want", [
    (_X4_IN_USE, "carried"),                    # 14.9 GB free: the cell
    (_X4_LIMIT - _X4_SUM, "carried"),           # to the byte
    (_X4_LIMIT - _X4_SUM + 1, "plain"),
], ids=["beside-the-placed-plan", "exactly", "a-byte-short"])
def test_a_shard_of_graph500_25_is_admitted_by_the_fullest_chip_s_free_bytes(in_use, want):
    plan, d = _g500_25_x4()
    scan, reason = admit_carried_rows(
        plan, {"bytes_limit": _X4_LIMIT, "bytes_in_use": in_use}, shards=d)
    assert scan == want
    assert reason.startswith("a shard of 4, on the fullest chip: rows, held once")
    assert f"= {_X4_SUM} B against {_X4_LIMIT - in_use} B free" in reason
    assert "(modes; gather" in reason and "not sized" in reason


def test_the_fullest_chip_of_a_mesh_decides():
    """Every chip runs the one SPMD program: the admission is asked of the
    one with the least free memory; a chip that keeps no statistics (the
    CPU) leaves the mesh without a limit."""
    from types import SimpleNamespace

    chip = lambda limit, in_use: SimpleNamespace(memory_stats=lambda: (
        None if limit is None else {"bytes_limit": limit, "bytes_in_use": in_use}))
    mesh = lambda *chips: SimpleNamespace(devices=np.array(chips, dtype=object))
    stats = superstep_policy.mesh_memory_stats(
        mesh(chip(100, 10), chip(100, 60), chip(90, 45), chip(100, 20)))
    assert stats == {"bytes_limit": 100, "bytes_in_use": 60}
    assert superstep_policy.mesh_memory_stats(mesh(chip(100, 10), chip(None, 0))) is None
