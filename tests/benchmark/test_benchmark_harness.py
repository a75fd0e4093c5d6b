"""The benchmark's harness off the chip: BENCHMARK.json names files that
exist, every cell rehearses on the CPU and never prints a result there, the
trace reduction and the roofline arithmetic match hand counts, each cell's
control and a broken timed path come out not correct, and a new cell is
added with data files alone."""

import json
import os
import re
import shutil
import sys

import pytest

from _bench import BENCH_DIR, REPO, Bench, lines as _lines, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
ACCEPTED = Bench()
BENCH = ACCEPTED.json
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

sys.path.insert(0, BENCH_DIR)
import roofline  # noqa: E402
import trace_reduce  # noqa: E402


def _has_result(out) -> bool:
    return any("correct" in r and "metrics" in r and "device" in r
               for r in _lines(out))


def _reporting(bench, metric) -> list:
    """The cells a per-layer metric is read in."""
    return metric.get("workloads") or [
        w["name"] for w in bench.json["workloads"]
        if metric["moves"] in bench.end_to_end_of(w["name"])]


# -- BENCHMARK.json and the files it names ------------------------------------


def test_named_files_exist_and_names_are_well_formed(bench):
    b = bench.json
    assert b["command"][1] == "benchmark/run.py"
    assert os.path.exists(os.path.join(bench.root, b["command"][1]))
    for path in b["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
    for config in b["configs"]:
        assert NAME.match(config["name"])
        assert len(config["source"]) <= 200
        with open(os.path.join(bench.root, config["file"])) as f:
            body = json.load(f)
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
        assert "rehearsal" in body and body["chips"] in (1, 4)
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)  # no two configurations share a file
    for cell in b["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert len(cell["why"]) <= 200
        assert cell["chips"] == bench.data(
            "configs", bench.config(cell["config"])["file"].rsplit("/", 1)[-1])["chips"]
        traffic = bench.data("traffic", cell["traffic"] + ".json")
        assert os.path.exists(
            os.path.join(bench.dir, "drivers", traffic["driver"] + ".py"))
        if traffic["driver"] == "graph_kernel_job":  # a kernel is a file
            assert os.path.exists(
                os.path.join(bench.dir, "algorithms", traffic["algorithm"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for metric in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        listed = metric.get("workloads", [])
        assert len(set(listed)) == len(listed)  # a cell stands once in a list
        for cell in listed:
            bench.cell(cell)
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    assert all(bench.end_to_end_of(w["name"]) - {"setup_s"} for w in b["workloads"])


def _moves_what_each_of_its_cells_reports(bench, metric):
    spec = bench.reader_of(metric["name"])
    assert os.path.exists(os.path.join(bench.dir, "readers", spec["reader"] + ".py"))
    if "bytes_module" in spec.get("args", {}):  # a kernel's least bytes is a file
        assert os.path.exists(
            os.path.join(bench.dir, spec["args"]["bytes_module"] + ".py"))
    cells = _reporting(bench, metric)
    assert cells, "a per-layer metric that no cell reports"
    for cell in cells:
        assert metric["moves"] in bench.end_to_end_of(cell)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_each_of_its_cells_reports(metric):
    _moves_what_each_of_its_cells_reports(ACCEPTED, metric)


def test_every_per_layer_metric_of_the_grown_benchmark_does_too(grown_root):
    grown = Bench(grown_root)
    assert {m["name"] for m in grown.json["per_layer"]} > {m["name"] for m in BENCH["per_layer"]}
    for metric in grown.json["per_layer"]:
        _moves_what_each_of_its_cells_reports(grown, metric)


# -- run.py off the chip ------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_without_a_tpu_it_fails_and_prints_no_result(cell):
    out = _run("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
    assert "TPU" in out.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_exits_4_and_prints_no_result(cell, trace):
    out = _run("--workload", cell, "--seed", "2147483659", "--seconds", "1",
               "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    assert not _has_result(out)
    last = _lines(out)[-1]
    assert last["rehearsal"] == "passed"
    wanted = (
        {m["name"] for m in BENCH["per_layer"]
         if cell in _reporting(ACCEPTED, m) and m["source"] != "device_trace"
         # the CPU reports no memory statistics, whatever the metric is called
         and ACCEPTED.reader_of(m["name"])["reader"] != "peak_memory_share"}
        if trace == "1" else ACCEPTED.end_to_end_of(cell)
    )
    assert wanted <= set(last["metrics"]), last["metrics"]
    checks = [r for r in _lines(out) if "check" in r]
    assert checks and all(r["ok"] and "limit" in r and "value" in r for r in checks)


@pytest.mark.parametrize("cell,failing", [
    ("cdlp-g500-22", "labels_after_10_supersteps_mismatches"),
    ("pipeline-outlinks-262k", "lof_median_relative_gap"),
])
def test_the_control_comes_out_not_correct(cell, failing):
    """The reference with one stated guarantee broken (messages one way
    only) or computed in the next lower precision (bfloat16), in the
    program's place."""
    out = _run("--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    assert not _has_result(out)
    checks = {r["check"]: r for r in _lines(out) if r.get("control") is True}
    assert not checks[failing]["ok"]
    assert checks[failing]["value"] > 3 * checks[failing]["limit"]
    assert {"sound_run_correct": True} in _lines(out)
    assert _lines(out)[-1] == {"control": "compared", "correct": False}


_BREAK_KERNEL = """
import runpy, sys
import jax.numpy as jnp
import graphmine_tpu as gm
# a step that returns its state unchanged
gm.label_propagation = lambda graph, **kw: jnp.arange(graph.num_vertices, dtype=jnp.int32)
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""

_BREAK_PIPELINE = """
import runpy, sys
import numpy as np
import graphmine_tpu.pipeline.driver as driver
sound = driver.run_pipeline
def altered(config):
    result = sound(config)
    labels = np.array(result.labels)
    labels[::97] = labels[0]  # answers altered where they are produced
    result.labels = labels
    return result
driver.run_pipeline = altered
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


@pytest.mark.parametrize("cell,code,failing", [
    ("cdlp-g500-22", _BREAK_KERNEL, "labels_after_10_supersteps_mismatches"),
    ("pipeline-outlinks-262k", _BREAK_PIPELINE, "lpa_label_mismatches"),
], ids=["kernel-step-returns-its-state", "pipeline-labels-altered"])
def test_a_broken_timed_path_comes_out_not_correct(cell, code, failing):
    out = _run("--workload", cell, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=code.format(run=RUN))
    assert out.returncode == 1, out.stderr[-3000:]
    assert not _has_result(out)
    checks = {r["check"]: r for r in _lines(out) if "check" in r}
    assert not checks[failing]["ok"]
    assert _lines(out)[-1]["rehearsal"] == "failed"


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """A later PR adds files and entries and edits nothing: run.py and the
    drivers stay where they are, the data lives under another root."""
    root = tmp_path / "root"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), root / "benchmark" / sub)
    with open(root / "benchmark" / "configs" / "graphalytics-g500-22.json") as f:
        config = json.load(f)
    config["name"] = "dummy-config"
    config["rehearsal"]["generator_args"]["scale"] = 10
    (root / "benchmark" / "configs" / "dummy-config.json").write_text(json.dumps(config))
    with open(root / "benchmark" / "traffic" / "cdlp-batch.json") as f:
        traffic = json.load(f)
    traffic["iterations"] = 3
    (root / "benchmark" / "traffic" / "dummy-traffic.json").write_text(json.dumps(traffic))
    (root / "benchmark" / "layer_metrics" / "dummy_job_ms.json").write_text(json.dumps(
        {"reader": "job_seconds_per", "args": {"per": "iterations", "scale": 3000.0}}))
    bench = json.loads(json.dumps(BENCH))  # a copy: the entries below are appended
    bench["configs"].append({
        "name": "dummy-config", "source": config["source"],
        "file": "benchmark/configs/dummy-config.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy-config", "traffic": "dummy-traffic",
        "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("dummy-cell")
    bench["per_layer"].append({
        "name": "dummy_job_ms", "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "superstep kernel", "moves": "evps", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = _run("--root", str(root), "--workload", "dummy-cell", "--seed", "3",
               "--seconds", "1", "--trace", "1", "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    last = _lines(out)[-1]
    assert last["rehearsal"] == "passed"
    assert set(last["metrics"]) == {"dummy_job_ms"}
    assert [r["check"] for r in _lines(out) if "check" in r] == \
        ["labels_after_3_supersteps_mismatches"]


# -- the trace reduction, on hand-made events ----------------------------------

# one device: a while loop spanning 0-10 s whose body ran 1-2, 2.5-4 and 5-6,
# then one copy at 12-13; the traced window is 0-20 s
DEVICE = [("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0), ("fusion.2", 2.5, 4.0),
          ("fusion.1", 5.0, 6.0), ("copy", 12.0, 13.0)]
HOST = [("bench_job", 0.0, 20.0), ("load", 13.5, 19.0), ("census", 6.0, 11.9)]


def test_union_merges_overlapping_and_touching_intervals():
    assert trace_reduce.union([(3, 4), (0, 1), (1, 2), (1.5, 2.5), (5, 5)]) == \
        [(0, 2.5), (3, 4)]


def test_leaf_events_leave_out_the_loop_that_spans_its_body():
    assert [e[0] for e in trace_reduce.leaf_events(DEVICE)] == \
        ["fusion.1", "fusion.2", "fusion.1", "copy"]


# the same loop with the markers the profiler writes inside an operation: an
# event that ends where it starts, in the body's second fusion and in the copy
MARKED = DEVICE + [("marker", 3.0, 3.0), ("marker", 12.5, 12.5), ("marker", 15.0, 15.0)]


def test_an_event_of_no_duration_is_neither_a_leaf_nor_a_child():
    """It holds no time. Taken for a child, it made the operation around it
    a parent, and the whole operation was booked idle."""
    assert trace_reduce.leaf_events(MARKED) == trace_reduce.leaf_events(DEVICE)
    # the copy at 12-13 s stays busy with a marker inside it
    assert ("copy", 12.0, 13.0) in trace_reduce.leaf_events(
        [("copy", 12.0, 13.0), ("marker", 12.0, 12.0), ("marker", 13.0, 13.0)])
    got = trace_reduce.reduce_events({"/device:TPU:0": MARKED}, HOST, (0.0, 20.0))
    assert got == trace_reduce.reduce_events({"/device:TPU:0": DEVICE}, HOST, (0.0, 20.0))
    assert got["busy_s"] == pytest.approx(4.5)
    assert "marker" not in [name for name, _ in got["device_ops"]]


def test_nested_loops_read_as_before():
    """A ``while`` in a ``while`` with events that share a start or an end:
    only what runs innermost counts, as before."""
    nested = [("while.outer", 0.0, 10.0), ("while.inner", 0.0, 4.0),
              ("fusion.1", 0.0, 1.0), ("fusion.2", 3.0, 4.0),
              ("call", 6.0, 10.0), ("fusion.3", 8.0, 10.0), ("copy", 10.0, 11.0)]
    assert trace_reduce.leaf_events(nested) == [
        ("fusion.1", 0.0, 1.0), ("fusion.2", 3.0, 4.0), ("fusion.3", 8.0, 10.0),
        ("copy", 10.0, 11.0)]
    busy, gaps = trace_reduce.busy_and_gaps(
        trace_reduce.leaf_events(nested), (0.0, 12.0))
    assert busy == pytest.approx(5.0)
    assert gaps == [(4.0, 8.0), (1.0, 3.0), (11.0, 12.0)]


def test_reduce_events_gives_the_hand_computed_busy_gaps_and_operations():
    got = trace_reduce.reduce_events({"/device:TPU:0": DEVICE}, HOST, (0.0, 20.0))
    assert got["busy_s"] == pytest.approx(4.5) and got["window_s"] == 20.0
    assert 100 * (1 - got["busy_s"] / got["window_s"]) == pytest.approx(77.5)
    assert got["device_ops"] == [["fusion.1", 2.0], ["fusion.2", 1.5], ["copy", 1.0]]
    # longest first, each named by the innermost host span over half of it
    assert got["idle_gaps"] == [["load", 7.0], ["census", 6.0], ["bench_job", 1.0],
                                ["bench_job", 1.0], ["bench_job", 0.5]]


def test_reduce_events_clips_to_the_window_and_averages_over_devices():
    got = trace_reduce.reduce_events(
        {"a": [("x", -1.0, 1.0)], "b": [("y", 0.0, 4.0), ("z", 9.0, 12.0)]},
        [], (0.0, 10.0), k_gaps=1)
    assert got["busy_s"] == pytest.approx((1.0 + 5.0) / 2) and got["devices"] == 2
    assert got["device_ops"][0] == ["y", 4.0]
    assert got["idle_gaps"] == [["untraced", 9.0]]  # device a idled longest


def test_reduce_events_without_a_device_reads_nothing():
    got = trace_reduce.reduce_events({}, HOST, (0.0, 20.0))
    assert got["busy_s"] == 0.0 and got["devices"] == 0 and got["idle_gaps"] == []


# -- the roofline arithmetic ---------------------------------------------------


def test_superstep_bytes_match_a_hand_count():
    # 5 vertices, 12 messages: 12 sender indices + 12 gathered labels + 5 labels
    # written, 4 bytes each
    assert roofline.lpa_superstep_min_bytes(5, 12) == 4 * (12 + 12 + 5) == 116


def test_roofline_share_is_least_seconds_over_measured_seconds():
    # 819 GB at 819 GB/s is one second of HBM time; ten device seconds = 10 %
    assert roofline.roofline_share_percent(819e9, 10.0, "TPU v5 lite") == \
        pytest.approx(10.0)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
