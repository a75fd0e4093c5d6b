"""The one-chip cell on a graph with no hubs, off the chip (ISSUE 38): its
configuration is ``graphalytics-g500-24``'s but for the source, the three
quadrant weights, ``reduced`` and ``assumed``; the draw is uniform; the cell
rehearses with both values of ``--trace`` and every listed metric is read;
its control comes out not correct; a program without the two new records
leaves the two new metrics out; and the cell's name sits once in the five
shared lists, with ``wcc-g500-22`` still last."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmark")
RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "cdlp-urand-24", "gap-urand-24", "cdlp-batch-flat"
SHARED = ("evps", "superstep_ms", "superstep_roofline_share",
          "device_idle_share.kernel", "graph_build_s.setup")
TWINS = {"cdlp_sparse_superstep_share.flat": "cdlp_sparse_superstep_share",
         "plan_resident_gb.flat": "plan_resident_gb",
         "peak_hbm_share.flat": "peak_hbm_share.kernel"}
NEW_COUNTERS = ("full_superstep_ms", "plan_slots_per_message")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

sys.path.insert(0, BENCH_DIR)
import generators  # noqa: E402


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"flat_cell_{kind}_{name}", os.path.join(BENCH_DIR, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def _run(*argv, code=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, RUN] if code is None else [sys.executable, "-c", code]
    return subprocess.run([*cmd, *argv], capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=REPO)


def _lines(out):
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_graph500_24_s_but_for_the_skew():
    config = _json("configs", CONFIG + ".json")
    sibling = _json("configs", "graphalytics-g500-24.json")
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


def test_the_cell_is_one_chip_under_the_flat_batch_traffic():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=CONFIG, traffic=TRAFFIC, chips=1)
    assert BENCH["workloads"][-1]["name"] == CELL and len(cells[CELL]["why"]) <= 200
    entry = BENCH["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == ["scale"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == _json("configs", CONFIG + ".json")["source"]
    # the loop rule word for word; the driver alone differs
    assert _json("traffic", TRAFFIC + ".json") == dict(
        _json("traffic", "cdlp-batch-large.json"), driver="kernel_job_flat")
    assert [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    assert len(BENCH["workloads"]) == 6 and len(BENCH["configs"]) == 6


def test_the_name_sits_once_in_the_five_shared_lists_and_wcc_is_still_last():
    listing = {m["name"]: m.get("workloads", []) for m in
               BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert listing[name].count(CELL) == 1 and listing[name][-1] == "wcc-g500-22"
        assert listing[name].index(CELL) == listing[name].index("cdlp-g500-24") + 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in (*TWINS, *NEW_COUNTERS):
        assert listing[name] == [CELL] and by_name[name]["moves"] == "evps"
    reporting = {name for name, cells in listing.items() if CELL in cells}
    assert reporting == {*SHARED, *TWINS, *NEW_COUNTERS}  # evps + nine per layer
    # the lists that test_large_cell.py holds to one name are as they were
    for name in TWINS.values():
        assert listing[name] == ["cdlp-g500-24"]
    assert [m["name"] for m in BENCH["per_layer"]][-5:] == [*TWINS, *NEW_COUNTERS]


@pytest.mark.parametrize("twin,accepted", sorted(TWINS.items()))
def test_a_twin_reads_what_the_accepted_metric_reads(twin, accepted):
    """Three lists are pinned to ``cdlp-g500-24`` alone by an accepted test,
    so this cell reports the same readings under names of its own: the same
    reader, the same arguments, the same unit, layer and source."""
    assert _json("layer_metrics", twin + ".json") == \
        _json("layer_metrics", accepted + ".json")
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert by_name[twin][key] == by_name[accepted][key], key


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
def test_the_cell_rehearses_and_every_listed_metric_is_read(trace):
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
        # device, the allocator's peak)
        assert set(metrics) == {*SHARED, *TWINS, *NEW_COUNTERS} - {
            "evps", "superstep_roofline_share", "device_idle_share.kernel",
            "peak_hbm_share.flat"}
        assert metrics["full_superstep_ms"] == {
            "value": pytest.approx(1000.0 * float(np.median(full))), "unit": "ms"}
        assert metrics["plan_slots_per_message"]["unit"] == "ratio"
        assert 1.0 < metrics["plan_slots_per_message"]["value"] < 1.05
        assert metrics["cdlp_sparse_superstep_share.flat"] == {
            "value": pytest.approx(10.0 * (10 - len(full))), "unit": "%"}
        resident = held["graph_bytes"] + held["plan_bytes"] + held["slot_index_bytes"]
        assert metrics["plan_resident_gb.flat"] == {
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
    assert {"superstep_ms", "graph_build_s.setup", "plan_resident_gb.flat",
            "cdlp_sparse_superstep_share.flat"} <= set(metrics)


# -- the facts and the readers, on hand-made records ---------------------------


def test_the_flat_driver_is_the_large_driver_with_wider_facts():
    flat, large = _load("drivers", "kernel_job_flat"), _load("drivers", "kernel_job_large")
    for name in ("setup", "job", "end_to_end", "records", "facts", "check"):
        theirs = getattr(large, name)
        mine = getattr(flat, name)
        assert mine.__code__ is not None and mine.__name__ == theirs.__name__
        assert mine.__code__.co_code == theirs.__code__.co_code, name
    held = {"phase": "device_residency", "scan": "carried", "graph_bytes": 6_500,
            "plan_bytes": 2_300, "slot_index_bytes": 2_200, "rows_bytes": 2_200}
    built = {"phase": "plan_build", "padded_slots_per_message": 1.0265}
    delta = {"phase": "superstep_delta",
             "branch": ["full"] * 8 + [89478395, 2097149],
             "seconds": [31.0, 4.1, 4.0, 4.2, 4.1, 4.3, 4.1, 4.0, 3.5, 0.9]}
    narrow = large._program_facts([held, built, delta])
    assert narrow == {"scan": "carried", "resident_bytes": 11_000, "sparse_supersteps": 2}
    # the median of the eight full supersteps: the first loaded the programs
    assert flat._program_facts([held, built, delta]) == dict(
        narrow, padded_slots_per_message=1.0265, full_superstep_seconds=4.1)
    # the parent's records: neither fact, and the narrow ones as they were
    older = [held, {"phase": "plan_build"}, {k: v for k, v in delta.items()
                                             if k != "seconds"}]
    assert flat._program_facts(older) == narrow
    # the plain scan: ten full supersteps in one program, no seconds to read
    plain = {"phase": "superstep_delta", "branch": ["full"] * 10, "seconds": []}
    assert "full_superstep_seconds" not in flat._program_facts([held, built, plain])
    # the large driver's own module is not touched by the flat one's
    assert large._program_facts([held, built, delta]) == narrow


@pytest.mark.parametrize("metric,facts,want", [
    ("full_superstep_ms", {"full_superstep_seconds": 4.1}, 4100.0),
    ("full_superstep_ms", {}, None),
    ("plan_slots_per_message", {"padded_slots_per_message": 1.0265}, 1.0265),
    ("plan_slots_per_message", {}, None),
    ("plan_resident_gb.flat", {"resident_bytes": 10_994_000_000}, 10.994),
    ("cdlp_sparse_superstep_share.flat", {"sparse_supersteps": 2, "iterations": 10}, 20.0),
    ("cdlp_sparse_superstep_share.flat", {"iterations": 10}, None),
])
def test_the_new_fact_metrics_read_their_facts(metric, facts, want):
    spec = _json("layer_metrics", metric + ".json")
    got = _load("readers", spec["reader"]).read(spec["args"], {"facts": facts})
    assert got == (None if want is None else pytest.approx(want))


def test_the_peak_share_is_the_devices_peak_over_its_limit():
    spec = _json("layer_metrics", "peak_hbm_share.flat.json")
    read = _load("readers", spec["reader"]).read
    memory = {"memory_peak_bytes": 13_800_000_000, "memory_limit_bytes": 16_909_336_064}
    assert read(spec.get("args", {}), {"memory": memory}) == pytest.approx(81.6117, abs=1e-3)
    nothing = {"memory_peak_bytes": None, "memory_limit_bytes": None}
    assert read(spec.get("args", {}), {"memory": nothing}) is None
