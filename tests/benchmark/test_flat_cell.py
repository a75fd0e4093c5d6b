"""The one-chip cell on a graph with no hubs, off the chip (ISSUE 38): its
configuration is ``graphalytics-g500-24``'s but for the source, the three
quadrant weights, ``reduced`` and ``assumed``; the draw is uniform; the cell
rehearses with both values of ``--trace`` and every listed metric is read;
its control comes out not correct; a program without the two new records
leaves the two new metrics out; and the cell's name stands once in each list
it reports under: the five shared ones, the three it shares with
``cdlp-g500-24`` under the accepted names (its twins and its driver went
with ISSUE 40) and the two that came with it."""

import os
import sys

import numpy as np
import pytest

from _bench import (BENCH_DIR, SETUP_ACCOUNT, Bench, lines as _lines, load as _load,
                    run as _run)
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "cdlp-urand-24", "gap-urand-24", "cdlp-batch-flat"
SHARED = ("evps", "superstep_ms", "superstep_roofline_share",
          "device_idle_share.kernel", "graph_build_s.setup")
# read under the names cdlp-g500-24 brought: one reader, one list, two cells
WITH_THE_SIBLING = {
    "cdlp_sparse_superstep_share": {
        "reader": "fact_value",
        "args": {"fact": "sparse_supersteps", "over": "iterations", "scale": 100.0}},
    "plan_resident_gb": {
        "reader": "fact_value", "args": {"fact": "resident_bytes", "scale": 1e-09}},
    "peak_hbm_share.kernel": {"reader": "peak_memory_share"},
}
NEW_COUNTERS = {
    "full_superstep_ms": {
        "reader": "fact_value",
        "args": {"fact": "full_superstep_seconds", "scale": 1000.0}},
    "plan_slots_per_message": {
        "reader": "fact_value", "args": {"fact": "padded_slots_per_message"}},
}
_json = Bench().data

sys.path.insert(0, BENCH_DIR)
import generators  # noqa: E402


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_graph500_24_s_but_for_the_skew(bench):
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-24.json")
    for key in ("generator", "dataset_seed", "guarantees", "chips"):
        assert config[key] == sibling[key], key  # the five guarantees word for word
    assert len(config["guarantees"]) == 5
    flat = {"a": 0.25, "b": 0.25, "c": 0.25}
    assert config["generator_args"] == dict(sibling["generator_args"], **flat)
    assert config["rehearsal"] == {
        "generator_args": dict(sibling["rehearsal"]["generator_args"], **flat)}
    assert config["reduced"] == ["scale"] and set(config["reduced_why"]) == {"scale"}
    for word in ("2**27", "2**24", "16.91 GB"):
        assert word in config["reduced_why"]["scale"]
    assert set(config) == set(sibling) | {"reduced_why"}
    assert "GAP Benchmark Suite" in config["source"] and "Urand" in config["source"]
    assert "arXiv:1508.03619" in config["source"] and len(config["source"]) <= 200
    assumed = config["assumed"]
    assert set(assumed) == set(sibling["assumed"]) | {"kernel"}
    assert "Graphalytics" in assumed["kernel"] and "GAP has no" in assumed["kernel"]
    for count in ("16,777,216", "268,435,187"):
        assert count in assumed["draw_counts"]  # the draw's own counts
    for word in ("GB", "device-resident", "B per edge", "scale 27"):
        assert word in config["deployment"]


def test_the_cell_is_one_chip_under_the_flat_batch_traffic(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json",
        reduced=["scale"], source=bench.data("configs", CONFIG + ".json")["source"])
    # the loop rule, the driver and the algorithm word for word the sibling's:
    # the traffic file keeps its name because the ledger knows the cell by it
    assert bench.data("traffic", TRAFFIC + ".json") == \
        bench.data("traffic", "cdlp-batch-large.json")
    assert not os.path.exists(os.path.join(bench.dir, "drivers", "kernel_job_flat.py"))


def test_the_name_stands_once_in_every_list_the_cell_reports_under(bench):
    wanted = {*SHARED, *WITH_THE_SIBLING, *NEW_COUNTERS}  # evps + nine per layer
    assert bench.reported_by(CELL) == wanted
    for name in wanted:
        assert bench.lists(name, CELL), name
    for name, reader in {**WITH_THE_SIBLING, **NEW_COUNTERS}.items():
        assert bench.reader_of(name) == reader
        assert bench.metric(name)["moves"] == "evps"
    # no twin is left: not an entry, not a file
    names = {m["name"] for m in bench.json["per_layer"]}
    files = os.listdir(os.path.join(bench.dir, "layer_metrics"))
    assert not [n for n in (*names, *files) if ".flat" in n]


@pytest.fixture(scope="module")
def traced_rehearsal():
    out = _run("--workload", CELL, "--seed", "2147483701", "--seconds", "1",
               "--trace", "1", "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    return _lines(out)


@pytest.mark.parametrize("accepted", sorted(WITH_THE_SIBLING))
def test_an_accepted_metric_lists_both_cells_and_reads_on_this_one(
        accepted, traced_rehearsal):
    """The three readings this cell shares with ``cdlp-g500-24`` stand under
    the names that cell brought (PR 33): one entry, one reader file, both
    cells in its list, one driver that hands both the same facts, and the
    value read in this cell's rehearsal is what its own records say."""
    bench = Bench()
    assert bench.lists(accepted, CELL) and bench.lists(accepted, "cdlp-g500-24")
    assert bench.reader_of(accepted) == WITH_THE_SIBLING[accepted]
    for cell in (CELL, "cdlp-g500-24"):
        traffic = bench.data("traffic", bench.cell(cell)["traffic"] + ".json")
        assert traffic["driver"] == "kernel_job_large"
    said = next(r for r in traced_rehearsal if "device_residency" in r)
    held, delta = said["device_residency"], said["superstep_delta"]
    metrics = traced_rehearsal[-1]["metrics"]
    want = {
        "cdlp_sparse_superstep_share":
            10.0 * sum(b != "full" for b in delta["branch"]),
        "plan_resident_gb": 1e-9 * (held["graph_bytes"] + held["plan_bytes"]
                                    + held["slot_index_bytes"]),
        "peak_hbm_share.kernel": None,  # a CPU keeps no memory statistics
    }[accepted]
    if want is None:
        assert accepted not in metrics
        memory = {"memory_peak_bytes": 13_845_838_336,
                  "memory_limit_bytes": 16_909_336_064}
        read = _load("readers", bench.reader_of(accepted)["reader"]).read
        assert read({}, {"memory": memory}) == pytest.approx(81.8828, abs=1e-3)
    else:
        assert metrics[accepted]["value"] == pytest.approx(want)
    assert not [name for name in metrics if ".flat" in name]


# -- the draw -------------------------------------------------------------------


@pytest.mark.parametrize("scale", [12, 16])
def test_the_draw_is_uniform(scale):
    """GAP's Urand: every bit of both endpoints a fair coin. Degrees are
    Binomial around 32, there is no hub, and from scale 16 every vertex of
    the space has an edge (an isolated one has probability e^-32)."""
    config = _json("configs", CONFIG + ".json")
    args = dict(config["generator_args"], scale=scale)
    assert args == dict(config["rehearsal"]["generator_args"], scale=scale)
    u, v = generators.make(config["generator"], args, config["dataset_seed"])
    n = 1 << scale
    degree = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    # 16 n draws less self-loops (1 in n) and duplicates (16 in n)
    lost = 16 * n * (1 + 16) / n
    assert 16 * n - 4 * lost - 40 < len(u) <= 16 * n
    assert degree.mean() == pytest.approx(32.0, rel=0.01)
    assert degree.max() < 2 * degree.mean()
    assert degree.std() == pytest.approx(np.sqrt(32.0), rel=0.05)
    if scale >= 16:
        assert degree.min() > 0
    # against the Kronecker draw of the sibling configuration: hubs
    kron = _json("configs", "graphalytics-g500-24.json")
    ku, kv = generators.make(kron["generator"],
                             dict(kron["generator_args"], scale=scale),
                             kron["dataset_seed"])
    kdeg = np.bincount(ku, minlength=n) + np.bincount(kv, minlength=n)
    assert kdeg.max() > 20 * degree.max() and (kdeg == 0).mean() > 0.1


# -- run.py on the cell, off the chip -----------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_and_every_listed_metric_is_read(trace, request):
    if trace == "1":
        lines = request.getfixturevalue("traced_rehearsal")
    else:
        out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
                   "--trace", trace, "--rehearse")
        assert out.returncode == 4, out.stderr[-3000:]
        lines = _lines(out)
    said = next(r for r in lines if "device_residency" in r)
    assert said["family"] == "bucketed" and said["scan"] == "carried"
    # the reason states the sum it compared, by program
    assert "gather " in said["scan_reason"] and "modes " in said["scan_reason"]
    held, delta = said["device_residency"], said["superstep_delta"]
    assert held["scan"] == "carried" and held["graph_bytes"] > 0 < held["rows_bytes"]
    assert len(delta["branch"]) == len(delta["seconds"]) == 10
    full = [s for s, b in zip(delta["seconds"], delta["branch"]) if b == "full"]
    assert 6 <= len(full) < 10 and delta["branch"][:len(full)] == ["full"] * len(full)
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        metrics = last["metrics"]
        # nine per layer, less the two a CPU cannot read (a trace of the
        # device, the allocator's peak), and set-up's account (PR 51)
        assert set(metrics) == {*SHARED, *WITH_THE_SIBLING, *NEW_COUNTERS,
                                *SETUP_ACCOUNT} - {
            "evps", "superstep_roofline_share", "device_idle_share.kernel",
            "peak_hbm_share.kernel"}
        assert metrics["full_superstep_ms"] == {
            "value": pytest.approx(1000.0 * float(np.median(full))), "unit": "ms"}
        assert metrics["plan_slots_per_message"]["unit"] == "ratio"
        assert 1.0 < metrics["plan_slots_per_message"]["value"] < 1.05
        assert metrics["cdlp_sparse_superstep_share"] == {
            "value": pytest.approx(10.0 * (10 - len(full))), "unit": "%"}
        resident = held["graph_bytes"] + held["plan_bytes"] + held["slot_index_bytes"]
        assert metrics["plan_resident_gb"] == {
            "value": pytest.approx(resident * 1e-9), "unit": "GB"}
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}
    (check,) = [r for r in lines if "check" in r]
    assert check["ok"] and check["compared"] == 4096 and check["limit"] == 0


def test_the_control_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    (control,) = [r for r in lines if r.get("control") is True]
    assert not control["ok"] and control["value"] > 0 == control["limit"]
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_PARENT_S_RECORDS = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.label_propagation
# the parent commit's program: plan_build without the plan's shape,
# superstep_delta without its seconds; the same labels
def older(graph, max_iter=5, plan="auto", sink=None):
    out = sound(graph, max_iter=max_iter, plan=plan, sink=sink)
    for r in (sink.records if sink is not None else ()):
        for key in ({dropped}):
            r.pop(key, None)
    return out
gm.label_propagation = older
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


@pytest.mark.parametrize("dropped,left_out", [
    ('"seconds", "padded_slots_per_message"', set(NEW_COUNTERS)),
    ('"padded_slots_per_message",', {"plan_slots_per_message"}),
], ids=["the-parent-s-records", "no-plan-shape"])
def test_a_program_without_the_two_records_leaves_the_two_metrics_out(dropped, left_out):
    """The driver runs a new cell on the parent commit with this benchmark's
    files laid over it: that program states neither fact, the two metrics
    are left out of the line and nothing raises."""
    out = _run("--workload", CELL, "--seed", "8", "--seconds", "1", "--trace", "1",
               "--rehearse", code=_PARENT_S_RECORDS.format(run=RUN, dropped=dropped))
    assert out.returncode == 4, out.stderr[-3000:]
    metrics = _lines(out)[-1]["metrics"]
    assert not left_out & set(metrics)
    assert set(NEW_COUNTERS) - left_out <= set(metrics)
    assert {"superstep_ms", "graph_build_s.setup", "plan_resident_gb",
            "cdlp_sparse_superstep_share"} <= set(metrics)


# -- the facts and the readers, on hand-made records ---------------------------


@pytest.mark.parametrize("metric,facts,want", [
    ("full_superstep_ms", {"full_superstep_seconds": 4.1}, 4100.0),
    ("full_superstep_ms", {}, None),
    ("plan_slots_per_message", {"padded_slots_per_message": 1.0265}, 1.0265),
    ("plan_slots_per_message", {}, None),
    ("plan_resident_gb", {"resident_bytes": 10_994_000_000}, 10.994),
    ("cdlp_sparse_superstep_share", {"sparse_supersteps": 2, "iterations": 10}, 20.0),
    ("cdlp_sparse_superstep_share", {"iterations": 10}, None),
])
def test_the_new_fact_metrics_read_their_facts(metric, facts, want):
    spec = _json("layer_metrics", metric + ".json")
    got = _load("readers", spec["reader"]).read(spec["args"], {"facts": facts})
    assert got == (None if want is None else pytest.approx(want))


def test_the_peak_share_is_the_devices_peak_over_its_limit():
    spec = _json("layer_metrics", "peak_hbm_share.kernel.json")
    read = _load("readers", spec["reader"]).read
    memory = {"memory_peak_bytes": 13_800_000_000, "memory_limit_bytes": 16_909_336_064}
    assert read(spec.get("args", {}), {"memory": memory}) == pytest.approx(81.6117, abs=1e-3)
    nothing = {"memory_peak_bytes": None, "memory_limit_bytes": None}
    assert read(spec.get("args", {}), {"memory": nothing}) is None
