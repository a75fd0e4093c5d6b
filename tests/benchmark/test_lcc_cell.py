"""The LCC cell off the chip: its configuration is ``cdlp-g500-22``'s draw
under LCC's guarantees, it rehearses with both values of ``--trace`` with
the widest gap under Graphalytics' 1e-4 and one pass a job, its control (the
out-neighbour reading of the edges as drawn) comes out far over the limit,
the bytes module counts what it says, the driver hands a timed job's stage
spans to ``lcc_core_s`` / ``lcc_tail_s`` and the warm-up job's plan record to
``lcc_core_wedge_share``, and a program without the kernel that lists no
wedge is turned away before any input is made."""

import os
import sys

import numpy as np
import pytest

from _bench import BENCH_DIR, lines as _lines, load, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

if BENCH_DIR not in sys.path:  # the drivers import the generators by name, as under run.py
    sys.path.insert(0, BENCH_DIR)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "lcc-g500-22", "graphalytics-g500-22-lcc", "lcc-batch"
SHARED = ("evps", "superstep_ms", "device_idle_share.kernel", "graph_build_s.setup",
          "peak_hbm_share.kernel")
OWN = ("lcc_core_s", "lcc_tail_s", "lcc_core_wedge_share", "lcc_roofline_share")
LIMIT = 1e-4


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_the_cdlp_cells_draw_under_lccs_guarantees(bench):
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-22.json")
    for key in ("generator", "generator_args", "dataset_seed", "rehearsal", "chips"):
        assert config[key] == sibling[key], key  # the same draw: the kernel alone differs
    assert config["dataset_seed"] == 2147483659 and config["chips"] == 1
    assert config["rehearsal"]["generator_args"]["scale"] == 12
    assert config["reduced"] == [] and config["guarantees"] != sibling["guarantees"]
    assert config["source"] == sibling["source"].replace(
        "algorithm CDLP, 10 iterations", "algorithm LCC, epsilon match 1e-4")
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json",
        source=config["source"], reduced=[])
    said = " ".join(config["guarantees"])
    for word in ("undirected", "both endpoints", "simple graph", "self-loop",
                 "every wedge", "none sampled", "degree is under 2", "1e-4",
                 "float64", "exactly 0"):
        assert word in said, word
    assumed = config["assumed"]
    assert assumed["edges"] == sibling["assumed"]["edges"]
    for word in ("2**22", "1,797,266", "scored 0", "not counted in EVPS"):
        assert word in assumed["vertex_ids"], word
    for word in ("two uint32 words", "float32 division"):
        assert word in assumed["precision"], word
    for word in ("2,397,038", "64,153,343", "163,352", "1.448e10", "7,208,093,229"):
        assert word in assumed["counts"], word
    for word in ("GB", "edge list", "oriented CSR", "bit rows", "allocator",
                 "16.9 GB"):
        assert word in config["deployment"], word


def test_the_cell_is_one_chip_under_its_own_traffic_and_reports_these_metrics(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200 and "one pass a job" in cell["why"]
    traffic = bench.data("traffic", TRAFFIC + ".json")
    assert traffic == dict(traffic, driver="graph_kernel_job_spans", algorithm="lcc",
                           traced_jobs=1)
    assert "iterations" not in traffic  # one pass, no count to state
    for word in ("closed batch, one client", "gm.build_graph",
                 "gm.clustering_coefficient", "untimed warm-up job",
                 "one whole job always runs", "reports 1 as its supersteps"):
        assert word in traffic["loop"], word
    for kind, name in (("algorithms", "lcc"), ("drivers", "graph_kernel_job_spans")):
        assert os.path.exists(os.path.join(bench.dir, kind, name + ".py"))
    assert bench.reported_by(CELL) == {*SHARED, *OWN}
    for name in (*SHARED, *OWN):
        assert bench.lists(name, CELL), name
    # no superstep's bytes, no carried rows, no plan of bucket rows
    for name in ("superstep_roofline_share", "plan_resident_gb",
                 "plan_slots_per_message", "pr_iteration_roofline_share"):
        assert not bench.lists(name, CELL), name
    assert bench.end_to_end_of(CELL) == {"evps", "setup_s"}
    for name in OWN:
        metric = bench.metric(name)
        assert metric == dict(metric, layer="LCC kernel", moves="evps"), name
        assert metric["workloads"].count(CELL) == 1
    assert bench.metric("lcc_core_s") == dict(
        bench.metric("lcc_core_s"), unit="s", better="lower", source="program_span")
    assert bench.metric("lcc_roofline_share") == dict(
        bench.metric("lcc_roofline_share"), unit="%", better="higher",
        source="device_trace")
    for name, stage in (("lcc_core_s", "lcc_core"), ("lcc_tail_s", "lcc_tail")):
        assert bench.reader_of(name) == {"reader": "phase_seconds", "args": {
            "scope": "job", "select": [{"phase": "span", "name": stage}]}}
    assert bench.reader_of("lcc_core_wedge_share") == {"reader": "fact_value", "args": {
        "fact": "wedges_core", "over": "wedges_total", "scale": 100.0}}
    assert bench.reader_of("lcc_roofline_share") == {"reader": "roofline", "args": {
        "bytes_module": "roofline_lcc", "bytes_function": "lcc_min_bytes",
        "bytes_args": ["num_vertices", "num_messages"], "calls_per_job": "iterations"}}


# -- the bytes module and the algorithm file ----------------------------------


def test_the_bytes_module_counts_a_word_a_message_and_two_a_vertex():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "under_test_roofline_lcc", os.path.join(BENCH_DIR, "roofline_lcc.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    count = module.lcc_min_bytes
    assert count(0, 0) == 0 and count(1, 0) == 8 and count(0, 1) == 4
    assert count(10, 100) == 4 * (100 + 2 * 10)
    # graph500-22: 0.55 GB a pass, 0.67 ms at the chip's 819 GB/s
    assert count(1 << 22, 2 * 64_153_343) == 546_781_176


def test_the_reference_is_the_definition_and_the_control_reads_out_neighbours_only():
    lcc = load("algorithms", "lcc")
    # a triangle 0-1-2, a pendant 3 on 2, a loner 4; 1 -> 0 drawn backwards, twice
    u, v, n = np.array([1, 1, 1, 0, 2, 3]), np.array([0, 0, 2, 2, 3, 3]), 5
    np.testing.assert_allclose(lcc.reference(u, v, n, {}), [1, 1, 1 / 3, 0, 0],
                               rtol=1e-15)
    # out-neighbours as drawn: 1 -> {0, 2}, 0 -> {2}, 2 -> {3}: the triangle is
    # vertex 0's (its smallest id), who has one out-neighbour; 1's pair is closed
    # but the count is not his
    np.testing.assert_array_equal(lcc.control(u, v, n, {}), [0, 0, 0, 0, 0])
    # a clique of four drawn upwards: vertex 0 keeps its three triangles over
    # 3 * 2 ordered pairs, vertex 1 the one it is the smallest id of; 2 and 3
    # have under two out-neighbours
    cu, cv = np.triu_indices(4, 1)
    np.testing.assert_allclose(lcc.reference(cu, cv, 4, {}), [1, 1, 1, 1], rtol=1e-15)
    np.testing.assert_allclose(lcc.control(cu, cv, 4, {}), [1, 1, 0, 0], rtol=1e-15)
    want = lcc.reference(u, v, n, {})
    same, zeros = lcc.compare(want.astype(np.float32), want)
    assert list(same) == ["check", "value", "limit", "ok", "compared", "at_vertex",
                          "nonzero", "mean"]
    assert same == dict(same, check="lcc_widest_relative_gap", limit=LIMIT, ok=True,
                        compared=5, nonzero=3) and same["value"] < 1e-7
    assert zeros == {"check": "lcc_nonzero_where_reference_is_zero", "value": 0,
                     "limit": 0, "ok": True}
    off = want.copy()
    off[2] *= 1 + 2e-4
    off[4] = 1e-9  # not exactly 0 where the reference is
    wrong, zeros = lcc.compare(off, want)
    assert not wrong["ok"] and wrong["at_vertex"] == 2
    assert wrong["value"] == pytest.approx(2e-4, rel=1e-6)
    assert not zeros["ok"] and zeros["value"] == 1


def test_the_references_two_lookups_agree(monkeypatch):
    """Pairs of two hubs are read from a table, the others from the sorted
    keys: with no hub and with every vertex a hub the triangles are the same."""
    import generators

    lcc = load("algorithms", "lcc")
    u, v = generators.make("rmat_undirected", {"scale": 10, "edge_factor": 16,
                                               "a": 0.57, "b": 0.19, "c": 0.19}, 41)
    want = lcc._triangles(u, v, 1024)
    assert want.sum() > 0
    for hubs in (0, 8 * 1024):
        monkeypatch.setattr(lcc, "_HUBS", hubs)
        np.testing.assert_array_equal(lcc._triangles(u, v, 1024), want)


# -- run.py on the cell, off the chip -----------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_with_the_gap_under_its_limit_and_one_pass_a_job(trace):
    out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
               "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    drawn = next(r for r in lines if "vertices" in r)
    assert drawn["vertices"] == 4096 and drawn["algorithm"] == "lcc"
    assert drawn["supersteps"] == 1 and drawn["plan_build_s"] > 0
    stated = next(r["algorithm_facts"] for r in lines if "algorithm_facts" in r)
    assert stated["wedges_total"] == stated["wedges_core"] + stated["wedges_tail"] > 0
    assert stated["core_vertices"] > 0 and stated["resident_bytes"] > 0
    checks = {r["check"]: r for r in lines if "check" in r}
    gap = checks["lcc_widest_relative_gap"]
    assert gap["ok"] and gap["value"] < 1e-6 < gap["limit"] == LIMIT
    assert gap["compared"] == 4096
    assert checks["lcc_nonzero_where_reference_is_zero"]["ok"]
    assert checks["jobs_that_disagree_on_supersteps"]["supersteps"] == 1
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        metrics = last["metrics"]
        # the timed job's own stage spans, through the driver
        assert metrics["lcc_core_s"]["value"] > 0 and metrics["lcc_core_s"]["unit"] == "s"
        assert metrics["lcc_tail_s"]["value"] >= 0
        assert metrics["lcc_core_s"]["value"] + metrics["lcc_tail_s"]["value"] <= \
            metrics["superstep_ms"]["value"] * 1e-3 + 1e-3
        assert metrics["lcc_core_wedge_share"] == {
            "value": pytest.approx(100.0 * stated["wedges_core"] / stated["wedges_total"]),
            "unit": "%"}
        assert metrics["graph_build_s.setup"]["value"] >= drawn["plan_build_s"]
        # read from a device trace and a device's allocator: nothing on a CPU
        assert not {"lcc_roofline_share", "peak_hbm_share.kernel",
                    "device_idle_share.kernel"} & set(metrics)
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}


def test_the_control_reads_out_neighbours_only_and_comes_out_far_over_the_limit():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    control = {r["check"]: r for r in lines if r.get("control") is True}
    failing = control["lcc_widest_relative_gap"]
    assert not failing["ok"] and failing["value"] > 1000 * failing["limit"]
    assert failing["limit"] == LIMIT and failing["compared"] == 4096
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


def test_the_driver_hands_on_a_timed_jobs_stage_spans_and_the_algorithms_facts():
    driver = load("drivers", "graph_kernel_job_spans")
    state = {"setup_records": [{"phase": "plan_build", "seconds": 2.0, "scope": "setup"}],
             "job_spans": [[{"phase": "span", "name": "lcc_core", "seconds": 3.0,
                             "scope": "job", "job": 0}],
                           [{"phase": "span", "name": "lcc_core", "seconds": 5.0,
                             "scope": "job", "job": 1}]],
             "algorithm_facts": {"wedges_core": 3, "wedges_total": 4},
             "fixpoint_facts": {}, "num_vertices": 8, "u": [0] * 5, "iterations": 1}
    jobs = [{"seconds": 4.0, "supersteps": 1}, {"seconds": 6.0, "supersteps": 1}]
    records = driver.records(state, jobs)
    assert {"phase": "job", "seconds": 6.0, "scope": "job", "job": 1} in records
    reader = load("readers", "phase_seconds")
    args = {"scope": "job", "select": [{"phase": "span", "name": "lcc_core"}]}
    assert reader.read(args, {"records": records, "jobs": jobs}) == 4.0  # mean a job
    assert reader.read(dict(args, select=[{"phase": "span", "name": "lcc_tail"}]),
                       {"records": records, "jobs": jobs}) is None
    assert driver.facts(state) == {"wedges_core": 3, "wedges_total": 4,
                                   "num_vertices": 8, "num_messages": 10,
                                   "iterations": 1}


_PARENTS_PROGRAM = """
import runpy, sys
from graphmine_tpu.obs import schema
# the parent commit's program: its exact counts list every wedge on the host
# and register no stage of the kernel that does not
schema.STAGE_SPANS = frozenset(schema.STAGE_SPANS - {{"lcc_core", "lcc_tail"}})
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""

_SAMPLED_ALL_THE_SAME = """
import runpy, sys
import jax.numpy as jnp
import graphmine_tpu as gm
# registers the stages and answers with the wedge-sampled estimate all the same
def sampled(graph, sink=None):
    return jnp.asarray(gm.sampled_clustering_coefficient(graph, samples=64, seed=0))
gm.clustering_coefficient = sampled
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_program_without_the_new_kernel_is_turned_away_before_any_input_is_made():
    """The driver tries a new cell on the parent commit first: it must fail
    cleanly, in seconds, and not be killed allocating 405 GB."""
    out = _run("--workload", CELL, "--seed", "3", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_PARENTS_PROGRAM.format(run=RUN))
    assert out.returncode not in (0, 4, 5), out.stdout[-2000:]
    assert "registers no stage span ['lcc_core', 'lcc_tail']" in out.stderr
    assert "it cannot run this cell" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


def test_a_timed_path_that_samples_its_wedges_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_SAMPLED_ALL_THE_SAME.format(run=RUN))
    assert out.returncode == 1, out.stderr[-3000:]
    checks = {r["check"]: r for r in _lines(out) if "check" in r}
    assert not checks["lcc_widest_relative_gap"]["ok"]
    assert checks["lcc_widest_relative_gap"]["value"] > 100 * LIMIT
    assert _lines(out)[-1]["rehearsal"] == "failed"
