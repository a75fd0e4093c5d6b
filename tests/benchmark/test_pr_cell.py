"""The PageRank cell off the chip: its configuration is ``cdlp-g500-24``'s
draw under PageRank's guarantees, it rehearses with both values of
``--trace`` with the widest gap under Graphalytics' 1e-4 and ten iterations
a job, its control (the directed reading, which is what the parent commit's
``gm.pagerank`` computes) comes out far over the limit, the bytes module
counts what it says, the metrics it shares with the CDLP cells read the
warm-up job's program records, and a program whose ``pagerank`` lacks the
new parameters is turned away before any input is made."""

import os

import numpy as np
import pytest

from _bench import BENCH_DIR, lines as _lines, load, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "pr-g500-24", "graphalytics-g500-24-pr", "pr-batch-large"
SHARED = ("evps", "superstep_ms", "device_idle_share.kernel", "graph_build_s.setup",
          "peak_hbm_share.kernel", "plan_resident_gb", "plan_slots_per_message")
OWN = "pr_iteration_roofline_share"
LIMIT = 1e-4


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_the_cdlp_cells_draw_under_pageranks_guarantees(bench):
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-24.json")
    for key in ("generator", "generator_args", "dataset_seed", "rehearsal", "chips"):
        assert config[key] == sibling[key], key  # the same draw: the kernel alone differs
    assert config["dataset_seed"] == 2147483659 and config["chips"] == 1
    assert config["reduced"] == [] and config["guarantees"] != sibling["guarantees"]
    assert config["source"] == sibling["source"].replace(
        "algorithm CDLP, 10 iterations",
        "algorithm PR, damping 0.85, 10 iterations, epsilon match 1e-4")
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json",
        source=config["source"], reduced=[])
    said = " ".join(config["guarantees"])
    for word in ("synchronous", "1/|V|", "undirected", "both ways", "degree",
                 "send nothing", "spread evenly", "exactly the stated count",
                 "no tolerance", "1e-4", "float64"):
        assert word in said, word
    assumed = config["assumed"]
    assert assumed["edges"] == sibling["assumed"]["edges"]
    assert assumed["draw_counts"] == sibling["assumed"]["draw_counts"]
    for word in ("2**24", "7,906,707", "dangling", "47 %", "not counted in EVPS"):
        assert word in assumed["vertex_ids"], word
    for word in ("0.85", "10 iterations", "from memory", "no network"):
        assert word in assumed["parameters"], word
    assert "float32" in assumed["precision"]
    for word in ("GB", "device-resident", "no slot index", "no carried rows",
                 "stepped from the host", "allocator"):
        assert word in config["deployment"], word


def test_the_cell_is_one_chip_under_its_own_traffic_and_reports_these_metrics(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200 and "every iteration gathers" in cell["why"]
    traffic = bench.data("traffic", TRAFFIC + ".json")
    assert traffic == dict(traffic, driver="graph_kernel_job_large",
                           algorithm="pr", iterations=10, damping=0.85,
                           traced_jobs=1)
    for word in ("closed batch, one client", "uniform ranks", "gm.build_graph",
                 "gm.pagerank", "one whole job always runs"):
        assert word in traffic["loop"], word
    for kind, name in (("algorithms", "pr"), ("drivers", "graph_kernel_job_large")):
        assert os.path.exists(os.path.join(bench.dir, kind, name + ".py"))
    assert bench.reported_by(CELL) == {*SHARED, OWN}
    for name in (*SHARED, OWN):
        assert bench.lists(name, CELL), name
    # CDLP's bytes and the carried rows' metrics are not this cell's
    for name in ("superstep_roofline_share", "cdlp_sparse_superstep_share",
                 "full_superstep_ms"):
        assert not bench.lists(name, CELL), name
    assert bench.end_to_end_of(CELL) == {"evps", "setup_s"}
    metric = bench.metric(OWN)
    assert metric == dict(metric, unit="%", better="higher", source="device_trace",
                          layer="superstep kernel", moves="evps")
    assert bench.reader_of(OWN) == {"reader": "roofline", "args": {
        "bytes_module": "roofline_pagerank",
        "bytes_function": "pagerank_iteration_min_bytes",
        "bytes_args": ["num_vertices", "num_messages"],
        "calls_per_job": "iterations"}}


# -- the bytes module and the algorithm file ----------------------------------


def test_the_bytes_module_counts_two_words_a_message_and_three_a_vertex():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "under_test_roofline_pagerank", os.path.join(BENCH_DIR, "roofline_pagerank.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    count = module.pagerank_iteration_min_bytes
    assert count(0, 0) == 0 and count(1, 0) == 12 and count(0, 1) == 8
    assert count(10, 100) == 4 * (2 * 100 + 3 * 10)
    # graph500-24: 4.37 GB an iteration, 5.3 ms at the chip's 819 GB/s
    assert count(1 << 24, 520_752_272) == 4_367_344_768
    # CDLP's floor (roofline.lpa_superstep_min_bytes) plus two words a vertex
    assert count(1 << 24, 520_752_272) - 4 * (2 * 520_752_272 + (1 << 24)) == 8 << 24


def test_the_algorithm_files_reference_is_the_formula_and_its_control_the_directed_reading():
    pagerank = load("algorithms", "pr")
    traffic = {"iterations": 1, "damping": 0.85}
    # a path 0 - 1 - 2 and a loner: degrees 1, 2, 1, 0
    u, v, n = np.array([0, 1]), np.array([1, 2]), 4
    start = 0.25
    dangling = 0.85 * start / n + 0.15 / n  # the loner's rank, spread, and the teleport
    np.testing.assert_allclose(
        pagerank.reference(u, v, n, traffic),
        [dangling + 0.85 * start / 2, dangling + 0.85 * 2 * start,
         dangling + 0.85 * start / 2, dangling], rtol=1e-15)
    # drawn one way, 2 and the loner send nothing; 0 receives nothing
    dangling = 0.85 * 2 * start / n + 0.15 / n
    np.testing.assert_allclose(
        pagerank.control(u, v, n, traffic),
        [dangling, dangling + 0.85 * start, dangling + 0.85 * start, dangling],
        rtol=1e-15)
    want = pagerank.reference(u, v, n, {"iterations": 10, "damping": 0.85})
    assert want.sum() == pytest.approx(1.0, abs=1e-12)
    (same,) = pagerank.compare(want.astype(np.float32), want)
    assert list(same) == ["check", "value", "limit", "ok", "compared", "at_vertex",
                          "rank_sum"]
    assert same == dict(same, check="rank_widest_relative_gap", limit=LIMIT, ok=True,
                        compared=4) and same["value"] < 1e-7
    off = want.copy()
    off[2] *= 1 + 2e-4
    (wrong,) = pagerank.compare(off, want)
    assert not wrong["ok"] and wrong["at_vertex"] == 2
    assert wrong["value"] == pytest.approx(2e-4, rel=1e-6)


# -- run.py on the cell, off the chip -----------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_with_the_gap_under_its_limit_and_ten_iterations(trace):
    out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
               "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    drawn = next(r for r in lines if "vertices" in r)
    assert drawn["vertices"] == 4096 and drawn["algorithm"] == "pr"
    said = next(r for r in lines if "device_residency" in r)
    assert said["family"] == "bucketed" and said["supersteps"] == 10
    held = said["device_residency"]
    assert held == dict(held, op="pagerank_inflow", scan="plain", rows_bytes=0,
                        slot_index_bytes=0)
    assert held["graph_bytes"] > held["plan_bytes"] > 0
    assert said["superstep_timing"] == dict(
        said["superstep_timing"], op="pagerank_inflow", family="bucketed", window=10)
    checks = {r["check"]: r for r in lines if "check" in r}
    gap = checks["rank_widest_relative_gap"]
    assert gap["ok"] and gap["value"] < gap["limit"] == LIMIT
    assert gap["compared"] == 4096
    assert checks["jobs_that_disagree_on_supersteps"]["supersteps"] == 10
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        metrics = last["metrics"]
        assert metrics["plan_resident_gb"] == {
            "value": pytest.approx((held["graph_bytes"] + held["plan_bytes"]) * 1e-9),
            "unit": "GB"}
        assert 1.0 < metrics["plan_slots_per_message"]["value"] < 1.5
        assert metrics["superstep_ms"]["value"] > 0
        assert {"superstep_ms", "graph_build_s.setup"} <= set(metrics)
        # read from a device trace and a device's allocator: nothing on a CPU
        assert not {OWN, "peak_hbm_share.kernel", "device_idle_share.kernel"} & \
            set(metrics)
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}


def test_the_control_is_the_directed_reading_and_comes_out_far_over_the_limit():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    control = {r["check"]: r for r in lines if r.get("control") is True}
    failing = control["rank_widest_relative_gap"]
    assert not failing["ok"] and failing["value"] > 1000 * failing["limit"]
    assert failing["limit"] == LIMIT and failing["compared"] == 4096
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_PARENTS_PAGERANK = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.pagerank
# the parent commit's entry: the edges as drawn, stopped on a tolerance
def parent(graph, alpha=0.85, max_iter=100, tol=1e-6, reset=None, weights=None,
           plan="auto", sink=None):
    return sound(graph, alpha=alpha, max_iter=max_iter, tol=tol, reset=reset,
                 weights=weights, plan=plan, sink=sink)
gm.pagerank = parent
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""

_DIRECTED_ALL_THE_SAME = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.pagerank
# takes the new parameters and ranks the edges as drawn all the same
def deaf(graph, alpha=0.85, max_iter=100, tol=1e-6, reset=None, weights=None,
         plan="auto", sink=None, directed=True):
    return sound(graph, alpha=alpha, max_iter=max_iter, tol=tol, reset=reset,
                 weights=weights, sink=sink)
gm.pagerank = deaf
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_program_whose_pagerank_lacks_the_new_parameters_is_turned_away_at_once():
    """The driver tries a new cell on the parent commit first: it must fail
    cleanly, in seconds, before anything is drawn."""
    out = _run("--workload", CELL, "--seed", "3", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_PARENTS_PAGERANK.format(run=RUN))
    assert out.returncode not in (0, 4, 5), out.stdout[-2000:]
    assert "this program's pagerank takes no ['directed']" in out.stderr
    assert "it cannot run this cell" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


def test_a_timed_path_that_ranks_the_edges_as_drawn_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_DIRECTED_ALL_THE_SAME.format(run=RUN))
    assert out.returncode == 1, out.stderr[-3000:]
    checks = {r["check"]: r for r in _lines(out) if "check" in r}
    assert not checks["rank_widest_relative_gap"]["ok"]
    assert checks["rank_widest_relative_gap"]["value"] > 1000 * LIMIT
    assert _lines(out)[-1]["rehearsal"] == "failed"
