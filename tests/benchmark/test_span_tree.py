"""The ``span_tree`` reader on hand-made records: stages anywhere under a
parent, zero against nothing-to-read, the parent's self time, and the
non-span records under a parent."""

import os
import sys

import pytest

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmark")
sys.path.insert(0, os.path.join(BENCH_DIR, "readers"))
import span_tree  # noqa: E402


def _span(path, seconds, job=0):
    return {"phase": "span", "name": path.rsplit("/", 1)[-1], "span_path": path,
            "seconds": seconds, "scope": "job", "job": job}


def _job(job=0, lof=10.0, with_ivf=True):
    lof_path = "run/outliers_lof"
    rung = lof_path + "/rung:primary"
    records = [
        _span("run/load", 2.0, job),
        _span(lof_path, lof, job),
        _span(lof_path + "/lof_features", 2.0, job),
        _span(lof_path + "/lof_features/triangles_host", 1.0, job),  # inside a stage
        _span(rung + "/lof_formula", 0.5, job),
        # the same stage names elsewhere in the run are not the chapter's
        _span("run/snapshot_publish/rung:primary/lof_formula", 9.0, job),
        {"phase": "compile", "stage": "backend", "seconds": 0.25,
         "span_path": rung + "/ivf_search", "scope": "job", "job": job},
        {"phase": "compile", "stage": "trace", "seconds": 0.05,
         "span_path": "run", "scope": "job", "job": job},
        {"phase": "outliers_lof", "seconds": lof, "span_path": lof_path,
         "scope": "job", "job": job},
    ]
    if with_ivf:
        records += [_span(rung + "/ivf_lists", 1.0, job),
                    _span(rung + "/ivf_search", 4.0, job),
                    _span(rung + "/ivf_lists", 0.5, job)]
    else:
        records.append(_span(rung + "/knn_exact", 6.0, job))
    return records


def _read(args, records):
    return span_tree.read(args, {"records": records})


def test_stages_are_summed_anywhere_under_the_parent_and_averaged_over_jobs():
    records = _job(0) + _job(1, lof=12.0)
    lists = {"parent": "outliers_lof", "stages": ["ivf_lists"]}
    assert _read(lists, records) == pytest.approx(1.5)
    search = {"parent": "outliers_lof", "stages": ["ivf_search", "knn_exact"]}
    assert _read(search, records) == pytest.approx(4.0)
    formula = {"parent": "outliers_lof", "stages": ["lof_formula"]}
    assert _read(formula, records) == pytest.approx(0.5)  # not publish's 9 s


def test_zero_when_the_parent_ran_with_other_stages_none_without():
    exact = _job(0, with_ivf=False)
    lists = {"parent": "outliers_lof", "stages": ["ivf_lists"]}
    assert _read(lists, exact) == 0.0
    # the chapter did not run at all: nothing to read
    assert _read(lists, [_span("run/load", 2.0)]) is None
    # the chapter ran, but the program names no stage under it
    bare = [_span("run/outliers_lof", 10.0)]
    assert _read(lists, bare) is None
    assert _read({"parent": "load", "stages": ["ingest_decode"]}, exact) is None
    # records of set-up are not a job's
    assert _read(lists, [dict(r, scope="setup") for r in _job(0)]) is None


def test_self_time_is_the_parent_minus_the_listed_stages():
    stages = ["lof_features", "ivf_lists", "ivf_search", "knn_exact", "lof_formula"]
    args = {"parent": "outliers_lof", "stages": stages, "mode": "self"}
    # 10 - (2 + 1 + 0.5 + 4 + 0.5); triangles_host lies inside lof_features
    # and is not listed, so nothing is taken twice
    assert _read(args, _job(0)) == pytest.approx(2.0)
    nested = dict(args, stages=stages + ["triangles_host"])
    with pytest.raises(ValueError, match="contains another listed stage"):
        _read(nested, _job(0))


def test_records_under_a_parent_are_summed_by_pattern():
    compile_s = {"parent": "run", "records": {"phase": "compile"}}
    assert _read(compile_s, _job(0)) == pytest.approx(0.30)
    backend = {"parent": "outliers_lof",
               "records": {"phase": "compile", "stage": "backend"}}
    assert _read(backend, _job(0) + _job(1)) == pytest.approx(0.25)
    quiet = [r for r in _job(0) if r["phase"] != "compile"]
    assert _read(compile_s, quiet) == 0.0  # the run ran, and compiled nothing
    assert _read(compile_s, []) is None
