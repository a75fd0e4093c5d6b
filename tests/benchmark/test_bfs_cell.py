"""The BFS cell off the chip: its configuration is ``cdlp-g500-24``'s draw
under BFS's guarantees with nothing reduced, the cell stands once in every
list it reports through, it rehearses with both values of ``--trace`` with
0 depth mismatches and every job agreeing on its levels, its control (every
edge walked one way only) fails the comparison, the bytes module imports
nothing of the program and counts what it says, and a program whose
``bfs_distances`` takes no plan is turned away before any input is made."""

import ast
import os

import numpy as np
import pytest

from _bench import BENCH_DIR, lines as _lines, load, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "bfs-g500-24", "graphalytics-g500-24-bfs", "bfs-batch-large"
SHARED = ("evps", "superstep_ms", "device_idle_share.kernel", "graph_build_s.setup",
          "peak_hbm_share.kernel", "plan_resident_gb", "plan_slots_per_message")
OWN = ("bfs_levels", "bfs_sparse_level_share", "bfs_full_level_ms",
       "bfs_roofline_share", "bfs_bottom_up_level_share")
UNREACHED = 9223372036854775807


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_the_cdlp_cells_draw_under_bfs_guarantees(bench):
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-24.json")
    for key in ("generator", "generator_args", "dataset_seed", "rehearsal", "chips"):
        assert config[key] == sibling[key], key  # the same draw: the kernel alone differs
    assert config["dataset_seed"] == 2147483659 and config["chips"] == 1
    assert config["reduced"] == [] and config["guarantees"] != sibling["guarantees"]
    assert config["source"] == sibling["source"].replace(
        "algorithm CDLP, 10 iterations",
        "algorithm BFS from one source vertex, to the last level, output exact")
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json",
        source=config["source"], reduced=[])
    said = " ".join(config["guarantees"])
    for word in ("depth 0", "least number of edges", "undirected", "either way",
                 "until a level reaches nothing", "no cap", str(UNREACHED),
                 "isolated", "exact", "no tolerance"):
        assert word in said, word
    assumed = config["assumed"]
    for same in ("edges", "vertex_ids", "draw_counts"):
        assert assumed[same] == sibling["assumed"][same], same
    for word in ("one file", "--seed does not redraw", "graphalytics-g500-24's draw"):
        assert word in assumed["dataset_seed"], word
    for word in ("properties file", "no network", "lowest vertex id that has an edge",
                 "permuted", "resolves to", "reaches"):
        assert word in assumed["source_vertex"], word
    for word in ("GB", "device-resident", "slot index", "rows", "allocator"):
        assert word in config["deployment"], word


def test_the_cell_is_one_chip_under_its_own_traffic_and_reports_these_metrics(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200 and "to its last level" in cell["why"]
    traffic = bench.data("traffic", TRAFFIC + ".json")
    assert traffic == dict(traffic, driver="graph_kernel_job_large", algorithm="bfs",
                           source="lowest_id_with_an_edge", traced_jobs=1)
    assert "iterations" not in traffic  # the levels are the search's answer
    for word in ("closed batch, one client", "one stated source", "to its last level",
                 "gm.build_graph", "gm.bfs_distances", "one whole job always runs"):
        assert word in traffic["loop"], word
    for kind, name in (("algorithms", "bfs"), ("drivers", "graph_kernel_job_large")):
        assert os.path.exists(os.path.join(bench.dir, kind, name + ".py"))
    assert bench.reported_by(CELL) == {*SHARED, *OWN}
    for name in (*SHARED, *OWN):
        assert bench.lists(name, CELL), name
    # CDLP's and PageRank's bytes, and CDLP's reading of the rungs, are not this cell's
    for name in ("superstep_roofline_share", "pr_iteration_roofline_share",
                 "cdlp_sparse_superstep_share", "full_superstep_ms"):
        assert not bench.lists(name, CELL), name
    assert bench.end_to_end_of(CELL) == {"evps", "setup_s"}
    for name, unit, better, source in (
            ("bfs_levels", "count", "lower", "program_counter"),
            ("bfs_sparse_level_share", "%", "higher", "program_counter"),
            ("bfs_full_level_ms", "ms", "lower", "program_counter"),
            ("bfs_roofline_share", "%", "higher", "device_trace")):
        metric = bench.metric(name)
        assert metric == dict(metric, unit=unit, better=better, source=source,
                              layer="superstep kernel", moves="evps")
    assert bench.reader_of("bfs_levels") == {
        "reader": "fact_value", "args": {"fact": "iterations"}}
    assert bench.reader_of("bfs_sparse_level_share") == {
        "reader": "fact_value",
        "args": {"fact": "sparse_supersteps", "over": "iterations", "scale": 100.0}}
    assert bench.reader_of("bfs_full_level_ms") == {
        "reader": "fact_value",
        "args": {"fact": "full_superstep_seconds", "scale": 1000.0}}
    # a call is a level and a level's bytes the job's over its levels: the
    # share is the whole job's bytes over the whole job's device-busy seconds
    assert bench.reader_of("bfs_roofline_share") == {"reader": "roofline", "args": {
        "bytes_module": "roofline_bfs",
        "bytes_function": "bfs_level_share_of_job_min_bytes",
        "bytes_args": ["num_vertices", "num_messages", "iterations"],
        "calls_per_job": "iterations"}}


# -- the bytes module and the algorithm file ----------------------------------


def test_the_bytes_module_imports_nothing_of_the_program_and_grows_with_v_and_m_alone():
    path = os.path.join(BENCH_DIR, "roofline_bfs.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name)
                for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert imported == {"__future__"}
    module = load(".", "roofline_bfs")
    count = module.bfs_job_min_bytes
    assert count(0, 0) == 0 and count(1, 0) == 8 and count(0, 1) == 4
    assert count(10, 100) == 4 * 100 + 8 * 10
    # graph500-24: 2.22 GB a job, 2.7 ms at the chip's 819 GB/s
    assert count(1 << 24, 520_752_272) == 2_217_226_816
    # the reader divides the busy seconds by the levels: so are the bytes
    spread = module.bfs_level_share_of_job_min_bytes
    for levels in (1, 7, 300):
        assert spread(1 << 24, 520_752_272, levels) * levels == pytest.approx(
            count(1 << 24, 520_752_272))
    run = {"trace": {"busy_s": 7.0}, "jobs": [{}, {}], "device": {"kind": "TPU v5 lite"},
           "facts": {"num_vertices": 1 << 24, "num_messages": 520_752_272,
                     "iterations": 7}}
    import sys

    sys.path.insert(0, BENCH_DIR)
    try:
        roofline = load("readers", "roofline")
        share = roofline.read(
            {"bytes_module": "roofline_bfs",
             "bytes_function": "bfs_level_share_of_job_min_bytes",
             "bytes_args": ["num_vertices", "num_messages", "iterations"],
             "calls_per_job": "iterations"}, run)
        peak = __import__("roofline").peaks("TPU v5 lite")["hbm_bytes_per_s"]
    finally:
        sys.path.remove(BENCH_DIR)
    # two jobs in 7 s of device time: 3.5 s a job
    assert share == pytest.approx(100 * count(1 << 24, 520_752_272) / peak / 3.5)
    assert 0 < share < 1


def test_the_algorithm_files_reference_walks_either_way_and_its_control_one_way():
    bfs = load("algorithms", "bfs")
    traffic = {"source": "lowest_id_with_an_edge"}
    # 1 -> 2 -> 3 and 4 -> 2 as drawn; 0 and 5 alone; 6 - 7 apart
    u, v, n = np.array([1, 2, 4, 6]), np.array([2, 3, 2, 7]), 8
    depths, touched = bfs.reference(u, v, n, traffic)
    assert depths.dtype == np.int64
    assert depths.tolist() == [UNREACHED, 0, 1, 2, 2, UNREACHED, UNREACHED, UNREACHED]
    assert touched.tolist() == [0, 1, 1, 1, 1, 0, 1, 1]
    one_way = bfs.control(u, v, n, traffic)[0]
    assert one_way.tolist() == [UNREACHED, 0, 1, 2, UNREACHED, UNREACHED, UNREACHED,
                                UNREACHED]
    both = bfs.reference(u, v, n, {"source": [3, 7]})[0]
    assert both.tolist() == [UNREACHED, 2, 1, 0, 2, UNREACHED, 1, 0]
    with pytest.raises(ValueError, match="no source rule"):
        bfs.reference(u, v, n, {"source": "highest_degree"})
    want = bfs.reference(u, v, n, traffic)
    got = np.where(depths == UNREACHED, np.iinfo(np.int32).max, depths).astype(np.int32)
    same, share = bfs.compare(got, want)
    assert list(same) == ["check", "value", "limit", "ok", "compared", "deepest"]
    assert same == dict(same, check="bfs_depth_mismatches", value=0, limit=0, ok=True,
                        compared=8, deepest=2)
    assert share == dict(share, check="bfs_reached_share", limit=0.5, ok=True,
                         reached=4, with_an_edge=6)
    assert share["value"] == pytest.approx(4 / 6)
    wrong, _ = bfs.compare(bfs.control(u, v, n, traffic), want)
    assert not wrong["ok"] and wrong["value"] == 1


# -- run.py on the cell, off the chip -----------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_with_no_mismatch_and_every_job_agreeing_on_its_levels(
        bench, trace):
    out = bench.run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
                    "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    drawn = next(r for r in lines if "vertices" in r)
    assert drawn["vertices"] == 4096 and drawn["algorithm"] == "bfs"
    said = next(r for r in lines if "device_residency" in r)
    levels = said["supersteps"]
    assert said["family"] == "bucketed" and 4 <= levels <= 8
    held = said["device_residency"]
    assert held == dict(held, op="bfs_level", scan="carried")
    assert held["rows_bytes"] > 0 and held["slot_index_bytes"] > 0
    assert "row_min" in held["reason"] and "modes" not in held["reason"]
    checks = {r["check"]: r for r in lines if "check" in r}
    exact = checks["bfs_depth_mismatches"]
    assert exact == dict(exact, ok=True, value=0, limit=0, compared=4096,
                         deepest=levels - 1)
    assert checks["bfs_reached_share"]["ok"] and checks["bfs_reached_share"]["value"] > 0.9
    agree = checks["jobs_that_disagree_on_supersteps"]
    assert agree == dict(agree, ok=True, value=0, supersteps=levels)
    assert agree["jobs"] >= 1
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        metrics = last["metrics"]
        assert metrics["bfs_levels"] == {"value": float(levels), "unit": "count"}
        # the first level writes the source's slots into the fill: no level
        # of a rehearsal is all gathers
        assert 0 < metrics["bfs_sparse_level_share"]["value"] <= 100
        assert metrics["plan_resident_gb"] == {
            "value": pytest.approx((held["graph_bytes"] + held["plan_bytes"]
                                    + held["slot_index_bytes"]) * 1e-9), "unit": "GB"}
        assert 1.0 < metrics["plan_slots_per_message"]["value"] < 1.5
        assert metrics["superstep_ms"]["value"] > 0
        # read from a device trace and a device's allocator: nothing on a CPU
        assert not {"bfs_roofline_share", "peak_hbm_share.kernel",
                    "device_idle_share.kernel"} & set(metrics)
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}


def test_the_control_walks_every_edge_one_way_and_fails_the_comparison(bench):
    out = bench.run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
                    "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    control = {r["check"]: r for r in lines if r.get("control") is True}
    failing = control["bfs_depth_mismatches"]
    assert not failing["ok"] and failing["value"] > 100 and failing["limit"] == 0
    assert failing["compared"] == 4096
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_PARENTS_BFS = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.bfs_distances
# the parent commit's entry: one loop, full width in every pass
def parent(graph, sources, direction="out", max_depth=0):
    return sound(graph, sources, direction=direction, max_depth=max_depth, plan=None)
gm.bfs_distances = parent
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_program_whose_bfs_takes_no_plan_is_turned_away_at_once():
    """The driver tries a new cell on the parent commit first: it must fail
    cleanly, in seconds, before anything is drawn."""
    out = _run("--workload", CELL, "--seed", "3", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_PARENTS_BFS.format(run=RUN))
    assert out.returncode not in (0, 4, 5), out.stdout[-2000:]
    assert ("this program's bfs_distances takes no ['plan', 'sink', 'return_levels']"
            in out.stderr)
    assert "it cannot run this cell" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn
