"""Set-up's account and the hand-over of the warm-up job's program records
(PR 51), off the chip and in this process: the six metrics that move
``setup_s`` list every cell and read what their files say, the three new
readers give the hand counts on hand-made records (a load from the cache, no
load, no record at all; the remainder to the microsecond; the list reader's
three reductions on the lists PERF.md section 5 records for ``cdlp-g500-22``
at PR 43 and ``bfs-g500-24`` at PR 50), every driver's ``records()`` hands on
what it handed on before, in the old order, then the warm-up's with ``scope:
"warmup"``, and a later PR's driver that hands no warm-up record on leaves
those metrics out and breaks nothing. The rehearsals of
``test_benchmark_harness.py`` hold each of these metrics to read in each of
its cells; no process is started here."""

import sys
import types

import pytest

from _bench import (BENCH_DIR, DUMMY_CELL, SETUP_ACCOUNT, Bench, load)
from _bench import bench, grown_root  # noqa: F401  (fixtures)

sys.path.insert(0, BENCH_DIR)  # as run.py puts its own directory first
import handover  # noqa: E402

CELLS = [w["name"] for w in Bench().json["workloads"]]
FROM_THE_RECORD = {
    "sparse_superstep_ms": ("ms", ["cdlp-g500-22", "cdlp-g500-24"]),
    "cdlp_dirty_slot_share": ("%", ["cdlp-g500-22", "cdlp-g500-24"]),
    "bfs_bottom_up_level_share": ("%", ["bfs-g500-24"]),
}
DRIVERS = ("kernel_job", "graph_kernel_job", "kernel_job_mesh", "kernel_job_large",
           "graph_kernel_job_large", "graph_kernel_job_spans", "pipeline_job")


def _read(metric: str, run: dict, directory: str = BENCH_DIR):
    spec = Bench().reader_of(metric)
    return load("readers", spec["reader"], directory).read(spec.get("args", {}), run)


def _compile(stage, seconds, cache_hit=None):
    return {"phase": "compile", "stage": stage, "fun_name": "f", "seconds": seconds,
            "cache_hit": cache_hit}


# -- BENCHMARK.json -----------------------------------------------------------


@pytest.mark.parametrize("name", SETUP_ACCOUNT)
def test_a_metric_of_the_account_lists_every_cell_and_moves_setup_s(bench, name):
    metric = bench.metric(name)
    assert metric == dict(metric, unit="s", better="lower", moves="setup_s")
    assert metric["source"] == ("program_counter" if name.startswith(
        ("compile_s", "program_load_s")) else "host_clock")
    assert metric["layer"] == ("compile (all chapters)" if name.startswith(
        ("compile_s", "program_load_s")) else "set-up")
    for cell in CELLS:  # an explicit list: a metric without one would bar the next cell
        assert bench.lists(name, cell), cell
    assert "setup_s" in bench.end_to_end_of(CELLS[0])


@pytest.mark.parametrize("name", FROM_THE_RECORD)
def test_a_metric_of_the_record_is_read_by_the_list_reader_in_its_cells(bench, name):
    unit, cells = FROM_THE_RECORD[name]
    metric = bench.metric(name)
    assert metric == dict(metric, unit=unit, source="program_counter",
                          layer="superstep kernel", moves="evps")
    assert all(bench.lists(name, cell) for cell in cells)
    spec = bench.reader_of(name)
    assert spec["reader"] == "record_list"
    assert spec["args"]["select"]["phase"] == "superstep_delta"


@pytest.mark.parametrize("name", ["cdlp_sparse_superstep_share", "full_superstep_ms"])
def test_the_two_cdlp_facts_reach_the_two_cdlp_cells_that_lacked_them(bench, name):
    for cell in ("cdlp-g500-24", "cdlp-urand-24", "cdlp-g500-22", "cdlp-g500-25-x4"):
        assert bench.lists(name, cell), cell
    assert bench.reader_of(name)["reader"] == "fact_value"  # the file untouched


# -- the readers, on hand-made records ------------------------------------------

_WARM = handover.warmup([
    _compile("trace", 0.25), _compile("lower", 0.5), _compile("backend", 36.0, True),
    _compile("trace", 0.125), _compile("lower", 0.0625),
    _compile("backend", 172.0, False),
    {"phase": "plan_build", "seconds": 5.0},
])
_STAGES = [handover.stage(phase, seconds) for phase, seconds in (
    ("process_start", 3.25), ("backend_start", 6.5), ("generate", 5.5),
    ("count_vertices", 0.375), ("build_graph", 0.9375), ("plan_build", 5.0),
    ("warmup_job", 13.75))]


@pytest.mark.parametrize("records,load_s,compile_s", [
    (_WARM, 36.0, 0.25 + 0.5 + 0.125 + 0.0625 + 172.0),
    ([r for r in _WARM if r.get("cache_hit") is not True], 0.0, 172.9375),  # none a hit
    ([r for r in _WARM if r["phase"] != "compile"], None, None),  # no compile record
    ([dict(r, scope="job") for r in _WARM], None, None),  # a timed job's are not set-up's
], ids=["a-hit", "no-hit", "no-record", "another-scope"])
def test_load_and_compile_seconds_are_told_apart_by_the_cache_hit(records, load_s,
                                                                  compile_s):
    run = {"records": records + _STAGES, "jobs": [{"seconds": 1.0}] * 3}
    assert _read("program_load_s.setup", run) == load_s
    assert _read("compile_s.setup", run) == compile_s
    if load_s is not None:  # 0.0, not nothing: a tier-1 rehearsal loads no program
        assert type(_read("program_load_s.setup", run)) is float


def test_the_stage_metrics_sum_their_stages_and_the_remainder_is_exact():
    run = {"records": _STAGES + _WARM, "jobs": [], "setup_s": 32.05}
    assert _read("process_start_s.setup", run) == 9.75
    assert _read("generate_s.setup", run) == 5.875  # the draw and the count of its vertices
    assert _read("warmup_job_s.setup", run) == 13.75
    assert _read("graph_build_s.setup", run) == 5.9375  # as before: build + plan
    # 32.05 - (3.25 + 6.5 + 5.5 + 0.375 + 0.9375 + 13.75); plan_build lies in the warm-up job
    assert _read("setup_other_s", run) == pytest.approx(1.7375, abs=1e-6)
    parquet = [handover.stage("write_parquet", 2.0)]
    assert _read("generate_s.setup", dict(run, records=_STAGES + parquet)) == 7.875
    assert _read("setup_other_s", dict(run, records=_STAGES + parquet)) == \
        pytest.approx(-0.2625, abs=1e-6)
    # a driver that names no stage leaves all of set-up here; no setup_s, nothing
    assert _read("setup_other_s", dict(run, records=[])) == 32.05
    assert _read("setup_other_s", {"records": _STAGES}) is None
    assert _read("generate_s.setup", dict(run, records=_WARM)) is None


# PERF.md section 5, `cdlp-g500-22` since PR 43: three full gathers, the M/6, the
# M/256 and five M/4096 rungs, the dirty reduce after the lowest rung
_CDLP = {"phase": "superstep_delta", "op": "lpa_superstep",
         "branch": ["full"] * 3 + [21384447, 501197] + [31324] * 5,
         "seconds": [1.0332, 1.0330, 1.0332, 0.9037, 0.1542, 0.0613, 0.0589, 0.0590,
                     0.0588, 0.0590],
         "reduce": ["full"] * 5 + ["dirty"] * 5,
         "dirty_slots": [132889083] * 5 + [7433029, 6460860, 6456429, 6456426, 6456426]}
# the same section, `bfs-g500-24` since PR 50: four levels top-down, four bottom-up
_BFS = {"phase": "superstep_delta", "op": "bfs_level",
        "branch": ["fill", 127136, 32547017, "full", 2034188] + [127136] * 3,
        "direction": ["top_down"] * 4 + ["bottom_up"] * 4,
        "seconds": [0.233, 0.233, 1.692, 4.174, 0.231, 0.107, 0.113, 0.106]}


def test_the_list_reader_gives_the_recorded_lists_hand_counts():
    run = {"records": handover.warmup([{"phase": "plan_build"}, _CDLP, _BFS])}
    assert _read("sparse_superstep_ms", run) == pytest.approx(59.0)  # of seven
    assert _read("cdlp_dirty_slot_share", run) == pytest.approx(
        100 * 6456429 / 132889083)  # 4.86 %: the median of five over the plan's slots
    assert _read("bfs_bottom_up_level_share", run) == 50.0
    # where bfs_sparse_level_share counts the bottom-up levels among the sparse ones
    assert handover.program_facts([_BFS])["sparse_supersteps"] == 7
    assert handover.program_facts([_CDLP]) == {
        "sparse_supersteps": 7, "full_superstep_seconds": 1.0332}


def test_the_list_reader_reads_nothing_where_there_is_nothing_to_read():
    reader = load("readers", "record_list")
    args = Bench().reader_of("sparse_superstep_ms")["args"]
    read = lambda record, **more: reader.read(
        dict(args, **more), {"records": handover.warmup([record])})
    every_one_full = dict(_CDLP, branch=["full"] * 10)
    assert read(every_one_full) is None  # a median of nothing
    assert read(every_one_full, reduce="share") == 0.0  # none of ten is a share
    assert read(dict(_CDLP, seconds=[])) is None  # the stateless scan writes no seconds
    assert read({"phase": "superstep_delta", "op": "lpa_superstep"}) is None
    assert read(_BFS) is None  # another kernel's record
    assert reader.read(args, {"records": [dict(_CDLP, scope="job")]}) is None
    # the newest of two: a second call's record stands for the job as it runs now
    newest = reader.read(args, {"records": handover.warmup(
        [every_one_full, _CDLP])})
    assert newest == pytest.approx(59.0)
    dirty = Bench().reader_of("cdlp_dirty_slot_share")["args"]
    no_dirty = dict(_CDLP, reduce=["full"] * 10)
    assert reader.read(dirty, {"records": handover.warmup([no_dirty])}) is None


# -- the hand-over, driver by driver --------------------------------------------


def _stub(driver: str):
    """A state as the driver's ``setup`` leaves it, and two timed jobs."""
    old = [{"phase": "build_graph", "seconds": 1.0, "scope": "setup"},
           {"phase": "plan_build", "seconds": 2.0, "scope": "setup"}]
    program = [_compile("backend", 3.0, True), dict(_CDLP)]
    state = {"setup_records": old, "warmup_records": handover.warmup(program),
             "job_spans": [[{"phase": "span", "name": "lcc_core", "seconds": 0.5,
                             "scope": "job", "job": 0}], []]}
    jobs = [{"seconds": 4.0, "supersteps": 1}, {"seconds": 6.0, "supersteps": 1}]
    before = old + [{"phase": "job", "seconds": 4.0, "scope": "job", "job": 0},
                    {"phase": "job", "seconds": 6.0, "scope": "job", "job": 1}]
    if driver == "graph_kernel_job_spans":
        before = before + state["job_spans"][0]
    if driver == "pipeline_job":  # a job's records are the program's own
        jobs = [{"seconds": 4.0, "records": [{"phase": "load", "seconds": 2.5}]},
                {"seconds": 6.0, "records": [{"phase": "load", "seconds": 3.5}]}]
        state["setup_records"] = [handover.stage("generate", 1.0)]
        before = [{"phase": "load", "seconds": 2.5, "scope": "job", "job": 0},
                  {"phase": "load", "seconds": 3.5, "scope": "job", "job": 1}]
    return state, jobs, before, program


@pytest.mark.parametrize("driver", DRIVERS)
def test_a_driver_hands_on_what_it_did_then_the_warm_ups_records(driver):
    module = load("drivers", driver)
    state, jobs, before, program = _stub(driver)
    handed = module.records(state, jobs)
    assert handed[:len(before)] == before  # unchanged, and in the old order
    rest = handed[len(before):]
    if driver == "pipeline_job":  # it stated no stage of set-up before
        assert rest[0] == {"phase": "generate", "seconds": 1.0, "scope": "setup"}
        rest = rest[1:]
    assert rest == [dict(r, scope="warmup") for r in program]
    assert "scope" not in program[0]  # copies: the program's records are not written to
    # a state that kept no warm-up record hands on what it did before, and no more
    del state["warmup_records"]
    assert module.records(state, jobs)[:len(before)] == before
    assert not [r for r in module.records(state, jobs) if r.get("scope") == "warmup"]


def test_the_carried_rows_facts_are_one_function_under_every_name():
    large = load("drivers", "kernel_job_large")
    assert large._program_facts is handover.program_facts
    assert load("drivers", "graph_kernel_job_large")._program_facts is \
        handover.program_facts
    held = {"phase": "device_residency", "scan": "carried", "graph_bytes": 1,
            "plan_bytes": 2, "slot_index_bytes": 4}
    facts = handover.program_facts([held, _CDLP])
    assert facts == {"scan": "carried", "resident_bytes": 7, "sparse_supersteps": 7,
                     "full_superstep_seconds": 1.0332}
    # the two CDLP drivers that stated none of them state them beside their own
    state = {"program_facts": facts, "num_vertices": 8, "u": [0] * 5, "iterations": 10}
    assert load("drivers", "kernel_job").facts(state) == dict(
        facts, num_vertices=8, num_messages=10, iterations=10)
    mesh = {"program_facts": facts, "exchange": {"bytes_per_superstep": 96},
            "num_vertices": 8, "num_edges": 5, "iterations": 10, "shards": 4}
    assert load("drivers", "kernel_job_mesh").facts(mesh) == dict(
        facts, bytes_per_superstep=96, num_vertices=8, num_messages=10, iterations=10,
        chips=4)


# -- a later PR's driver that hands no warm-up record on --------------------------


def test_a_kit_driver_without_the_hand_over_leaves_the_metrics_out(grown_root):
    """The grown benchmark's new cell under a driver written before PR 51:
    two harness sums and a ``job`` record a job, no stage of set-up, no record
    of the program. Every per-layer metric that lists the cell is read as
    ``run.py`` reads it: the ones that need the program's records return
    nothing, the remainder holds all of set-up but ``run.py``'s own stages,
    and no reader raises."""
    grown = Bench(grown_root)
    kit = types.SimpleNamespace(
        records=lambda state, jobs: state["setup_records"] + handover.job_records(jobs),
        facts=lambda state: {"num_vertices": 1024, "num_messages": 4096,
                             "iterations": 3})
    state = {"setup_records": [
        {"phase": "build_graph", "seconds": 0.25, "scope": "setup"},
        {"phase": "plan_build", "seconds": 0.5, "scope": "setup"}]}
    jobs = [{"seconds": 0.003}, {"seconds": 0.006}]
    run = {"jobs": jobs, "window_s": 0.01, "trace": None, "setup_s": 12.0,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1},
           "memory": {"memory_peak_bytes": None, "memory_limit_bytes": None},
           "records": kit.records(state, jobs) + [
               handover.stage("process_start", 2.0), handover.stage("backend_start", 1.0)],
           "facts": kit.facts(state)}
    sys.path.insert(0, grown.dir)
    try:
        read = {m["name"]: load("readers", grown.reader_of(m["name"])["reader"],
                                grown.dir).read(
                    grown.reader_of(m["name"]).get("args", {}), run)
                for m in grown.json["per_layer"] if DUMMY_CELL in m.get("workloads", [])}
    finally:
        sys.path.remove(grown.dir)
    assert set(SETUP_ACCOUNT) <= set(read)  # grow() appended the cell to each list
    said = {name for name, value in read.items() if value is not None}
    assert {"superstep_ms", "dummy_iteration_ms", "graph_build_s.setup",
            "process_start_s.setup", "setup_other_s"} <= said
    assert not said & {"generate_s.setup", "warmup_job_s.setup", "compile_s.setup",
                       "program_load_s.setup", *FROM_THE_RECORD}
    assert read["graph_build_s.setup"] == 0.75 and read["process_start_s.setup"] == 3.0
    assert read["setup_other_s"] == 12.0 - 3.0 - 0.25
