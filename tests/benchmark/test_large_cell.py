"""The one-chip cell that fills the chip, off the chip: its configuration is
the scale-22 sibling's but for the scale, it rehearses with both values of
``--trace`` and its three new metrics read the warm-up job's program records
(and read nothing from a program that writes none), its control and a broken
timed path come out not correct, and a program that lacks what the driver
needs is turned away before any input is made."""

import os
import statistics

import pytest

from _bench import BENCH_DIR, Bench, lines as _lines, load as _load, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "cdlp-g500-24", "graphalytics-g500-24", "cdlp-batch-large"
SHARED = ("evps", "superstep_ms", "superstep_roofline_share",
          "device_idle_share.kernel", "graph_build_s.setup")
NEW_METRICS = ("peak_hbm_share.kernel", "plan_resident_gb",
               "cdlp_sparse_superstep_share")
# two that PR 38 brought for the flat sibling and that read here too
WIDER_FACTS = ("full_superstep_ms", "plan_slots_per_message")
# two that read the handed-over `superstep_delta` record itself (PR 51)
FROM_THE_RECORD = ("sparse_superstep_ms", "cdlp_dirty_slot_share")
_json = Bench().data


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_the_scale_22_siblings_but_for_the_scale(bench):
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-22.json")
    for key in ("generator", "dataset_seed", "guarantees", "reduced", "rehearsal",
                "chips"):
        assert config[key] == sibling[key], key  # the guarantees word for word
    assert config["reduced"] == [] and len(config["guarantees"]) == 5
    assert config["generator_args"] == dict(sibling["generator_args"], scale=24)
    assert config["source"] == sibling["source"].replace(
        "graph500-22", "graph500-24").replace("scale 22", "scale 24").replace(
        "class S", "class M")
    up = lambda text: text.replace("graph500-22", "graph500-24").replace("2**22", "2**24")
    assumed, theirs = config["assumed"], sibling["assumed"]
    assert assumed["edges"] == up(theirs["edges"])
    assert assumed["vertex_ids"] == up(theirs["vertex_ids"])
    # the same sentence up to the compile seconds, which are this graph's own
    until = theirs["dataset_seed"].index("anew") + len("anew")
    assert assumed["dataset_seed"][:until] == up(theirs["dataset_seed"])[:until]
    assert "PR 33" in assumed["dataset_seed"][until:]
    assert set(assumed) == set(theirs) | {"draw_counts"}
    for count in ("8,870,509", "260,376,136", "8.87 M", "260.4 M"):
        assert count in assumed["draw_counts"]  # the draw's counts beside LDBC's
    assert config["deployment"] != sibling["deployment"]
    # what runs since PR 36: the rows carried and admitted, four full gathers
    for word in ("GB", "device-resident", "B per edge", "carried rows", "admitted",
                 "four of ten supersteps gather in full"):
        assert word in config["deployment"]
    assert "not admitted" not in config["deployment"]
    assert set(config) == set(sibling)


def test_the_cell_is_one_chip_under_the_large_batch_traffic(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200 and "four full gathers" in cell["why"]
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json", reduced=[],
        source=bench.data("configs", CONFIG + ".json")["source"])
    traffic = bench.data("traffic", TRAFFIC + ".json")
    small = bench.data("traffic", "cdlp-batch.json")
    assert traffic == dict(small, driver="kernel_job_large")  # the loop rule word for word
    assert bench.reported_by(CELL) == {*SHARED, *NEW_METRICS, *WIDER_FACTS,
                                       *FROM_THE_RECORD}
    for name in (*SHARED, *NEW_METRICS, *WIDER_FACTS, *FROM_THE_RECORD):
        assert bench.lists(name, CELL), name
    for name in (*NEW_METRICS, *WIDER_FACTS, *FROM_THE_RECORD):
        assert bench.metric(name)["moves"] == "evps"
    assert bench.reader_of("peak_hbm_share.kernel") == {"reader": "peak_memory_share"}
    assert bench.reader_of("plan_resident_gb") == {
        "reader": "fact_value", "args": {"fact": "resident_bytes", "scale": 1e-09}}
    assert bench.reader_of("cdlp_sparse_superstep_share") == {
        "reader": "fact_value",
        "args": {"fact": "sparse_supersteps", "over": "iterations", "scale": 100.0}}
    # read on the chip alone, as peak_hbm_share.x4 is
    assert bench.metric("peak_hbm_share.kernel")["source"] == \
        bench.metric("peak_hbm_share.x4")["source"] == "device_trace"


# -- run.py on the cell, off the chip -----------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_and_its_metrics_read_the_program_records(trace):
    out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
               "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    said = next(r for r in lines if "device_residency" in r)
    assert said["family"] == "bucketed" and said["scan"] == "carried"
    held, delta = said["device_residency"], said["superstep_delta"]
    assert held["scan"] == "carried" and held["graph_bytes"] > 0 < held["rows_bytes"]
    assert delta["branch"][0] == "full" and len(delta["branch"]) == 10
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        metrics = last["metrics"]
        resident = held["graph_bytes"] + held["plan_bytes"] + held["slot_index_bytes"]
        assert metrics["plan_resident_gb"] == {
            "value": pytest.approx(resident * 1e-9), "unit": "GB"}
        sparse = sum(b != "full" for b in delta["branch"])
        assert 0 < sparse < 10
        assert metrics["cdlp_sparse_superstep_share"] == {
            "value": pytest.approx(10.0 * sparse), "unit": "%"}
        assert "peak_hbm_share.kernel" not in metrics  # a CPU keeps no statistics
        # the two facts this driver states since the flat driver folded into it
        full = [s for s, b in zip(delta["seconds"], delta["branch"]) if b == "full"]
        assert metrics["full_superstep_ms"] == {
            "value": pytest.approx(1000.0 * statistics.median(full)), "unit": "ms"}
        assert metrics["plan_slots_per_message"]["unit"] == "ratio"
        assert 1.0 < metrics["plan_slots_per_message"]["value"] < 1.5
        assert {"superstep_ms", "graph_build_s.setup"} <= set(metrics)
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}
    (check,) = [r for r in lines if "check" in r]
    assert check["ok"] and check["compared"] == 4096 and check["limit"] == 0


_NO_ROOM = """
import runpy, sys
from graphmine_tpu.ops import superstep_policy
# a device with no room for the rows: the chip's answer at graph500-24
superstep_policy.device_memory_stats = lambda plan: {{"bytes_limit": 1, "bytes_in_use": 0}}
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_device_without_room_runs_the_plain_scan_and_the_program_says_so():
    """What the chip does at full size: the rows are not admitted, the
    stateless scan runs, and the share of sparse supersteps is the 0 of 10
    that the program's own ``superstep_delta`` record states. The reference
    runs after the window: set-up holds none of its seconds."""
    out = _run("--workload", CELL, "--seed", "9", "--seconds", "1", "--trace", "1",
               "--rehearse", code=_NO_ROOM.format(run=RUN))
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    said = next(r for r in lines if "device_residency" in r)
    assert said["scan"] == "plain" and "not sized" in said["scan_reason"]
    held, delta = said["device_residency"], said["superstep_delta"]
    assert held["rows_bytes"] == held["slot_index_bytes"] == 0 < held["plan_bytes"]
    assert delta["branch"] == ["full"] * 10 and delta["changed_messages"] == []
    metrics = lines[-1]["metrics"]
    assert metrics["cdlp_sparse_superstep_share"] == {"value": 0.0, "unit": "%"}
    assert metrics["plan_resident_gb"]["value"] == pytest.approx(
        (held["graph_bytes"] + held["plan_bytes"]) * 1e-9)
    assert not [r for r in lines if "reference_s" in r]
    order = [next(i for i, r in enumerate(lines) if key in r)
             for key in ("setup_s", "window_s", "check")]
    assert order == sorted(order)
    (check,) = [r for r in lines if "check" in r]
    assert check["ok"] and check["compared"] == 4096


_NO_RECORDS = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.label_propagation
# a program that writes neither record: the same labels, nothing to read
def silent(graph, max_iter=5, plan="auto", sink=None):
    out = sound(graph, max_iter=max_iter, plan=plan, sink=sink)
    if sink is not None:
        sink.records[:] = [r for r in sink.records
                           if r["phase"] not in ({dropped})]
    return out
gm.label_propagation = silent
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


@pytest.mark.parametrize("dropped,left_out", [
    ('"device_residency", "superstep_delta"',
     {"plan_resident_gb", "cdlp_sparse_superstep_share"}),
    ('"device_residency",', {"plan_resident_gb"}),  # the parent commit's program
], ids=["neither-record", "the-parent-s-records"])
def test_a_program_without_the_records_leaves_the_metrics_out(dropped, left_out):
    """The driver runs a new cell on the parent commit with this benchmark's
    files laid over it: that program writes no ``device_residency`` record,
    and the metric that reads it is left out of the line."""
    out = _run("--workload", CELL, "--seed", "8", "--seconds", "1", "--trace", "1",
               "--rehearse", code=_NO_RECORDS.format(run=RUN, dropped=dropped))
    assert out.returncode == 4, out.stderr[-3000:]
    metrics = _lines(out)[-1]["metrics"]
    assert not left_out & set(metrics)
    assert set(NEW_METRICS) - left_out - {"peak_hbm_share.kernel"} <= set(metrics)
    assert {"superstep_ms", "graph_build_s.setup"} <= set(metrics)


def test_the_control_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    (control,) = [r for r in lines if r.get("control") is True]
    assert not control["ok"] and control["value"] > 0 == control["limit"]
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_BREAK_KERNEL = """
import runpy, sys
import jax.numpy as jnp
import graphmine_tpu as gm
# a step that returns its state unchanged
gm.label_propagation = lambda graph, max_iter=5, plan="auto", sink=None: \\
    jnp.arange(graph.num_vertices, dtype=jnp.int32)
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""

_NO_SINK = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.label_propagation
gm.label_propagation = lambda graph, max_iter=5, plan="auto": sound(graph, max_iter)
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_broken_timed_path_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_BREAK_KERNEL.format(run=RUN))
    assert out.returncode == 1, out.stderr[-3000:]
    (check,) = [r for r in _lines(out) if "check" in r]
    assert not check["ok"] and check["value"] > 0
    assert _lines(out)[-1]["rehearsal"] == "failed"


_NO_ADMISSION = """
import runpy, sys
from graphmine_tpu.obs import schema
schema.SCHEMAS.pop("device_residency")  # the parent commit: no such record
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


@pytest.mark.parametrize("code,said", [
    (_NO_SINK, "label_propagation takes no ['sink']"),
    (_NO_ADMISSION, "this program registers no device_residency record"),
], ids=["no-sink", "the-parent-commit"])
def test_a_program_that_lacks_what_the_driver_needs_is_turned_away_at_once(code, said):
    """The driver tries a new cell on the parent commit first: that program
    sizes nothing against the device and would die in its compile after
    seven minutes, so it has to fail cleanly and soon."""
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=code.format(run=RUN))
    assert out.returncode not in (0, 4), out.stdout[-2000:]
    assert said in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


# -- the facts and the readers, on hand-made records ---------------------------


_HELD = {"phase": "device_residency", "scan": "carried", "graph_bytes": 6_000,
         "plan_bytes": 2_500, "slot_index_bytes": 2_100, "rows_bytes": 2_400}
_BUILT = {"phase": "plan_build", "padded_slots_per_message": 1.167}
_DELTA = {"phase": "superstep_delta",
          "branch": ["full", "full", "full", "full", 2034188] + [127136] * 5,
          "seconds": [31.0, 5.8, 5.9, 5.8, 0.9, 0.5, 0.5, 0.5, 0.5, 0.5]}
_NARROW = {"scan": "carried", "resident_bytes": 10_600, "sparse_supersteps": 6}
_NO_SECONDS = {k: v for k, v in _DELTA.items() if k != "seconds"}
_RESIDENT = {"scan": "carried", "resident_bytes": 10_600}


def test_the_program_facts_are_what_the_records_say():
    driver = _load("drivers", "kernel_job_large")
    assert driver._program_facts([_HELD, _NO_SECONDS]) == _NARROW
    plain = dict(_HELD, scan="plain", slot_index_bytes=0, rows_bytes=0)
    every_one_full = {"phase": "superstep_delta", "branch": ["full"] * 10}
    assert driver._program_facts([plain, every_one_full]) == {
        "scan": "plain", "resident_bytes": 8_500, "sparse_supersteps": 0}
    # the share is the program's word alone: no record, no fact
    assert driver._program_facts([plain]) == {"scan": "plain", "resident_bytes": 8_500}
    assert driver._program_facts([{"phase": "plan_build"}]) == {}


@pytest.mark.parametrize("records,want", [
    # the median of the four full supersteps: the first loaded the programs
    ([_HELD, _BUILT, _DELTA], dict(_NARROW, padded_slots_per_message=1.167,
                                   full_superstep_seconds=5.85)),
    # the parent of PR 38: a plan_build without the plan's shape, a
    # superstep_delta without its seconds
    ([_HELD, {"phase": "plan_build"}, _NO_SECONDS], _NARROW),
    ([_HELD, _BUILT], dict(_RESIDENT, padded_slots_per_message=1.167)),
    ([_HELD, _DELTA], dict(_NARROW, full_superstep_seconds=5.85)),
    # the plain scan: ten full supersteps in one program, no seconds to read
    ([_HELD, _BUILT, {"phase": "superstep_delta", "branch": ["full"] * 10,
                      "seconds": []}],
     dict(_RESIDENT, sparse_supersteps=0, padded_slots_per_message=1.167)),
], ids=["both-records", "the-parent-s-records", "no-superstep-delta",
        "no-plan-build", "the-plain-scan"])
def test_the_two_wider_facts_are_stated_from_the_records_and_left_out_without(
        records, want):
    """What ``kernel_job_flat`` stated (PR 38) is this driver's own since PR
    40: each fact from its record, each left out when the record or the key
    it reads is missing, and the narrow facts as they were."""
    assert _load("drivers", "kernel_job_large")._program_facts(records) == want


@pytest.mark.parametrize("metric,facts,want", [
    ("plan_resident_gb", {"resident_bytes": 10_939_298_560}, 10.93929856),
    ("plan_resident_gb", {}, None),
    ("cdlp_sparse_superstep_share", {"sparse_supersteps": 7, "iterations": 10}, 70.0),
    ("cdlp_sparse_superstep_share", {"sparse_supersteps": 0, "iterations": 10}, 0.0),
    ("cdlp_sparse_superstep_share", {"iterations": 10}, None),
])
def test_the_new_fact_metrics_read_their_facts(metric, facts, want):
    spec = _json("layer_metrics", metric + ".json")
    got = _load("readers", spec["reader"]).read(spec["args"], {"facts": facts})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("memory,want", [
    ({"memory_peak_bytes": 13_500_000_000, "memory_limit_bytes": 16_875_000_000}, 80.0),
    ({"memory_peak_bytes": None, "memory_limit_bytes": None}, None),
])
def test_the_peak_share_is_the_devices_peak_over_its_limit(memory, want):
    spec = _json("layer_metrics", "peak_hbm_share.kernel.json")
    got = _load("readers", spec["reader"]).read(spec.get("args", {}), {"memory": memory})
    assert got == (None if want is None else pytest.approx(want))
