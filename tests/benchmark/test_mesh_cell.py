"""The four-chip cell off the chip: it rehearses over four virtual devices
and says so, its control fails, a program without the mesh entry is turned
away before any input is made, and the two new readers match hand counts."""

import functools
import os
import sys

import pytest

import _bench
from _bench import BENCH_DIR, lines as _lines
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "cdlp-g500-25-x4", "graphalytics-g500-25", "cdlp-batch-mesh"
SHARED = ("evps", "superstep_ms", "device_idle_share.kernel", "graph_build_s.setup")
OWN = {
    "superstep_roofline_share.x4": {"reader": "roofline_mesh", "args": {
        "bytes_function": "lpa_superstep_min_bytes_per_chip",
        "bytes_args": ["num_vertices", "num_messages", "chips"],
        "calls_per_job": "iterations"}},
    "peak_hbm_share.x4": {"reader": "peak_memory_share"},
    "partition_s.setup": {"reader": "phase_seconds", "args": {
        "scope": "setup", "select": [{"phase": "partition"}]}},
    "exchange_mb_per_superstep": {"reader": "fact_value", "args": {
        "fact": "bytes_per_superstep", "scale": 1e-06}},
    "shard_message_imbalance": {"reader": "fact_value", "args": {
        "fact": "messages_per_shard_max", "over": "messages_per_shard_mean"}},
    # the carried-rows job's facts, which the one-chip CDLP cells brought and
    # this driver states too since PR 51 (`handover.program_facts`)
    "cdlp_sparse_superstep_share": {"reader": "fact_value", "args": {
        "fact": "sparse_supersteps", "over": "iterations", "scale": 100.0}},
    "full_superstep_ms": {"reader": "fact_value", "args": {
        "fact": "full_superstep_seconds", "scale": 1000.0}},
}

sys.path.insert(0, BENCH_DIR)
import roofline_mesh  # noqa: E402  (benchmark/roofline_mesh.py, not the reader)

_run = functools.partial(_bench.run, devices=4)
_reader = functools.partial(_bench.load, "readers")


def test_this_cell_asks_for_four_chips_and_its_metrics_are_its_own(bench):
    """What is true of this cell. How many cells ask for four chips is the
    driver's rule (at most half of them, rounded down), not a test's."""
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=4)
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-22.json")
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json", reduced=[],
        source=config["source"])
    assert config["chips"] == 4 and config["reduced"] == []
    assert config["guarantees"] == sibling["guarantees"]  # word for word
    assert config["generator_args"] == dict(sibling["generator_args"], scale=25)
    assert config["source"] == sibling["source"].replace(
        "graph500-22", "graph500-25").replace("scale 22", "scale 25").replace(
        "class S", "class L")
    assert bench.data("traffic", TRAFFIC + ".json")["driver"] == "kernel_job_mesh"
    assert bench.reported_by(CELL) == {*SHARED, *OWN}
    for name in (*SHARED, *OWN):
        assert bench.lists(name, CELL), name
    for name, reader in OWN.items():
        assert bench.reader_of(name) == reader
        assert bench.metric(name)["moves"] == (
            "setup_s" if name == "partition_s.setup" else "evps")
    four = [w for w in bench.json["workloads"] if w["chips"] == 4]
    assert 2 * len(four) <= len(bench.json["workloads"])  # the driver's own rule


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_on_four_devices_and_says_so(trace):
    out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
               "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    said = next(r for r in lines if "shards" in r)
    assert said["shards"] == 4 and said["family"] == "bucketed"
    assert "all_gather" in said["reason"]
    assert len(said["per_device_peak_bytes"]) == 4
    assert said["bytes_per_superstep"] == 4 * 1024 * 3  # 4 * Vc * (D - 1)
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        assert last["metrics"]["exchange_mb_per_superstep"]["value"] == \
            pytest.approx(4 * 1024 * 3 / 1e6)
        assert last["metrics"]["shard_message_imbalance"]["value"] >= 1.0
        assert last["metrics"]["partition_s.setup"]["value"] > 0.0
    (check,) = [r for r in lines if "check" in r]
    assert check["ok"] and check["compared"] == 4096 and check["limit"] == 0


def test_the_control_comes_out_not_correct_on_the_mesh():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    (control,) = [r for r in lines if r.get("control") is True]
    assert not control["ok"] and control["value"] > 0 == control["limit"]
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_NO_MESH_ENTRY = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.label_propagation
# the parent commit's entry: no mesh=
gm.label_propagation = lambda graph, max_iter=5, init_labels=None, \\
    return_history=False, plan="auto", sink=None: sound(graph, max_iter)
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_program_without_the_mesh_entry_is_turned_away_at_once():
    """The driver tries a new cell on the parent commit first: that has to
    fail cleanly and soon, before a graph of 524 M edges is drawn."""
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_NO_MESH_ENTRY.format(run=RUN))
    assert out.returncode not in (0, 4), out.stdout[-2000:]
    assert "takes no mesh=" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


# -- the new readers, on hand-made runs ---------------------------------------


def test_bytes_per_chip_match_a_hand_count():
    # 8 vertices, 24 messages, 4 chips: a chip reads 6 sender indices, gathers
    # 6 labels and writes its 2 labels, then is handed the other 6 labels
    assert roofline_mesh.lpa_superstep_min_bytes_per_chip(8, 24, 4) == \
        4 * (6 + 6 + 2) + 4 * 6 == 80
    # one chip: the one-chip count, nothing received
    import roofline

    assert roofline_mesh.lpa_superstep_min_bytes_per_chip(5, 12, 1) == \
        roofline.lpa_superstep_min_bytes(5, 12)


def test_roofline_mesh_reads_one_chips_share_of_its_own_peak():
    reader = _reader("roofline_mesh")
    args = {"bytes_function": "lpa_superstep_min_bytes_per_chip",
            "bytes_args": ["num_vertices", "num_messages", "chips"],
            "calls_per_job": "iterations"}
    # 4 (2 M + V) / 4 + 3 V = 2 M + 4 V = 819e9 bytes: one second at the peak
    m = (819 * 10**9 - 4 * 10**9) // 2
    facts = {"num_vertices": 10**9, "num_messages": m, "chips": 4, "iterations": 10}
    run = {"trace": {"busy_s": 200.0, "devices": 4}, "jobs": [{"seconds": 1}] * 2,
           "facts": facts, "device": {"kind": "TPU v5 lite"}}
    # mean busy seconds per chip per superstep: 200 / (2 jobs x 10) = 10 s
    assert reader.read(args, run) == pytest.approx(10.0)
    assert reader.read(args, dict(run, trace=None)) is None
    assert reader.read(args, dict(run, jobs=[])) is None
    # a program that states no chip count: nothing to read, no error
    bare = {k: v for k, v in facts.items() if k != "chips"}
    assert reader.read(args, dict(run, facts=bare)) is None


def test_fact_value_scales_divides_and_reads_nothing_where_nothing_is_said():
    fact_value = _reader("fact_value")
    facts = {"bytes_per_superstep": 100_663_296, "messages_per_shard_max": 300,
             "messages_per_shard_mean": 250.0, "zero": 0}
    run = {"facts": facts}
    assert fact_value.read({"fact": "bytes_per_superstep", "scale": 1e-6}, run) == \
        pytest.approx(100.663296)
    assert fact_value.read({"fact": "messages_per_shard_max",
                            "over": "messages_per_shard_mean"}, run) == \
        pytest.approx(1.2)
    assert fact_value.read({"fact": "absent"}, run) is None
    assert fact_value.read({"fact": "bytes_per_superstep", "over": "absent"}, run) is None
    assert fact_value.read({"fact": "bytes_per_superstep", "over": "zero"}, run) is None
    assert fact_value.read({"fact": "zero"}, run) == 0.0
