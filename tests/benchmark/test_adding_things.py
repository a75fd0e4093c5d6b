"""What ``benchmark/README.md`` says under "Adding things" holds: a later PR's
whole kit (a configuration, a one-chip cell on a new traffic mix, a
four-chip cell, a kernel as ``algorithms/<name>.py`` with a float answer
under a tolerance, a kernel's least bytes as a module beside ``roofline.py``,
two per-layer metrics) goes in as NEW files and APPENDED entries
(``_bench.grow``), nothing that is there is edited, and the new cell runs.
The structure tests of the other files run on the same copy through the
``bench`` fixture."""

import filecmp
import os

import pytest

from _bench import (BENCH_DIR, DUMMY_ALGORITHM, DUMMY_BYTES, DUMMY_CELL,
                    DUMMY_CELL_X4, Bench, lines, load)
from _bench import grown_root  # noqa: F401  (fixture)


@pytest.fixture(scope="module")
def grown(grown_root):
    return Bench(grown_root)


def test_the_kit_is_new_files_and_appended_entries_and_nothing_is_edited(grown):
    accepted = Bench()
    cmp = filecmp.dircmp(BENCH_DIR, grown.dir, ignore=["__pycache__"])
    stack, added = [cmp], []
    while stack:
        d = stack.pop()
        assert not d.left_only and not d.diff_files and not d.funny_files, d.right
        added += [os.path.relpath(os.path.join(d.right, f), grown.dir)
                  for f in d.right_only]
        stack += d.subdirs.values()
    assert sorted(added) == sorted([
        f"algorithms/{DUMMY_ALGORITHM}.py", f"{DUMMY_BYTES}.py",
        "configs/dummy-config.json", "configs/dummy-config-x4.json",
        "traffic/dummy-traffic.json", "layer_metrics/dummy_iteration_ms.json",
        "layer_metrics/dummy_iteration_roofline.json"])
    for key in ("configs", "workloads", "per_layer"):
        before, after = accepted.json[key], grown.json[key]
        assert len(after) > len(before)
        for old, new in zip(before, after):  # appended: the old ones come first
            assert {k: v for k, v in new.items() if k != "workloads"} == \
                {k: v for k, v in old.items() if k != "workloads"}
            assert new.get("workloads", [])[:len(old.get("workloads", []))] == \
                old.get("workloads", [])
    for key in ("command", "paths", "run_seconds"):
        assert grown.json[key] == accepted.json[key]
    # every list there was has a name behind those that were there
    for m in accepted.json["end_to_end"] + accepted.json["per_layer"]:
        if "workloads" in m:
            longer = grown.metric(m["name"])["workloads"]
            assert set(longer[len(m["workloads"]):]) & {DUMMY_CELL, DUMMY_CELL_X4}
    assert grown.cell(DUMMY_CELL_X4)["chips"] == 4
    on_four = lambda b: {w["name"] for w in b.json["workloads"] if w["chips"] == 4}
    assert on_four(grown) - on_four(accepted) == {DUMMY_CELL_X4}  # a second one


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_new_cell_rehearses_with_its_float_answer_under_its_tolerance(grown, trace):
    out = grown.run("--workload", DUMMY_CELL, "--seed", "2147483700", "--seconds", "1",
                    "--trace", trace, "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    said = next(r for r in lines(out) if "algorithm" in r)
    assert said["algorithm"] == DUMMY_ALGORITHM and said["supersteps"] == 3
    checks = {r["check"]: r for r in lines(out) if "check" in r}
    gap = checks["rank_widest_relative_gap"]
    assert gap["ok"] and 0 < gap["value"] < gap["limit"] == 1e-4
    assert gap["compared"] == 1024
    # the stated count is the fact `iterations`, and every job ran it
    assert checks["jobs_that_disagree_on_supersteps"]["supersteps"] == 3
    last = lines(out)[-1]
    assert last["rehearsal"] == "passed"
    if trace == "1":
        assert {"dummy_iteration_ms", "superstep_ms", "graph_build_s.setup"} <= \
            set(last["metrics"])
        assert last["metrics"]["dummy_iteration_ms"] == last["metrics"]["superstep_ms"]
        assert "dummy_iteration_roofline" not in last["metrics"]  # no device trace
    else:
        assert set(last["metrics"]) == {"evps", "setup_s"}


def test_the_new_cells_control_comes_out_not_correct(grown):
    """One iteration short of the stated count: ranks off by tens of percent
    against a tolerance of 1e-4."""
    out = grown.run("--workload", DUMMY_CELL, "--seed", "5", "--seconds", "1",
                    "--trace", "0", "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    control = {r["check"]: r for r in lines(out) if r.get("control") is True}
    failing = control["rank_widest_relative_gap"]
    assert not failing["ok"] and failing["value"] > 100 * failing["limit"]
    assert {"sound_run_correct": True} in lines(out)
    assert lines(out)[-1] == {"control": "compared", "correct": False}


# -- a kernel's least bytes is a file ------------------------------------------

_RUN = {"trace": {"busy_s": 60.0}, "jobs": [{"seconds": 1}] * 2,
        "facts": {"num_vertices": 10**9, "num_messages": 4 * 10**9, "iterations": 3},
        "device": {"kind": "TPU v5 lite"}}


def _roofline_reader(directory):
    return load("readers", "roofline", directory)


def test_the_roofline_reader_counts_with_roofline_py_when_no_module_is_named(monkeypatch):
    monkeypatch.syspath_prepend(BENCH_DIR)  # as run.py puts its own directory first
    args = Bench().reader_of("superstep_roofline_share")["args"]
    assert "bytes_module" not in args  # the accepted file, untouched
    # 4 (2 M + V) = 36e9 bytes at 819 GB/s over 60 s / (2 jobs x 3 calls)
    want = 100.0 * (36e9 / 819e9) / 10.0
    assert _roofline_reader(BENCH_DIR).read(args, _RUN) == pytest.approx(want)
    assert _roofline_reader(BENCH_DIR).read(
        dict(args, bytes_module="roofline"), _RUN) == pytest.approx(want)
    assert _roofline_reader(BENCH_DIR).read(args, dict(_RUN, trace=None)) is None


def test_the_roofline_reader_counts_with_the_module_a_metric_names(grown, monkeypatch):
    monkeypatch.syspath_prepend(grown.dir)  # as run.py puts its own directory first
    args = grown.reader_of("dummy_iteration_roofline")["args"]
    assert args["bytes_module"] == DUMMY_BYTES
    # 4 (2 V + M) = 24e9 bytes; the peaks and the share stay roofline.py's
    want = 100.0 * (24e9 / 819e9) / 10.0
    assert _roofline_reader(grown.dir).read(args, _RUN) == pytest.approx(want)
    with pytest.raises(KeyError, match="no published peaks"):
        _roofline_reader(grown.dir).read(
            args, dict(_RUN, device={"kind": "TPU v9 imaginary"}))
    with pytest.raises(ModuleNotFoundError):
        _roofline_reader(grown.dir).read(dict(args, bytes_module="no_such_bytes"), _RUN)
