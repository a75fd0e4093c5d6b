"""The seven per-layer metrics of the write path to a snapshot (PR 35):
each reads a value in the pipeline cell's rehearsal, the four parts of the
publish chapter add up to the chapter on a hand-made record list, and
``run_self_s`` is the root span minus the chapters, nothing where the
program writes no root span."""

import os
import sys

import pytest

from _bench import BENCH_DIR, Bench, lines as _lines, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

CELL = "pipeline-outlinks-262k"
METRICS = ("publish_chapter_s", "publish_cc_s", "publish_host_s",
           "publish_write_s", "publish_self_s", "triangles_host_s", "run_self_s")
_reader_of = Bench().reader_of

sys.path.insert(0, os.path.join(BENCH_DIR, "readers"))
import phase_seconds  # noqa: E402
import span_tree  # noqa: E402

READERS = {"phase_seconds": phase_seconds, "span_tree": span_tree}


def _read(metric, records):
    spec = _reader_of(metric)
    jobs = sorted({r["job"] for r in records})
    return READERS[spec["reader"]].read(
        spec["args"], {"records": records, "jobs": jobs})


def _span(path, seconds, job=0):
    return {"phase": "span", "name": path.rsplit("/", 1)[-1], "span_path": path,
            "seconds": seconds, "scope": "job", "job": job}


_CHAPTERS = {"load": 2.25, "build_graph": 0.5, "lpa": 0.25, "census": 0.125,
             "outliers_recursive_lpa": 0.75, "outliers_lof": 5.0}
_STAGES = {"publish_cc": 0.4375, "publish_fetch": 0.03125,
           "publish_canary": 0.0625, "publish_fingerprint": 0.25,
           "publish_write": 0.125, "publish_quality": 0.015625}


def _job(job=0, root=True, publish=1.0, stages=_STAGES):
    rung = "run/snapshot_publish/rung:primary"
    records = [_span("run/" + name, s, job) for name, s in _CHAPTERS.items()]
    records += [
        # a chapter's name further down the tree is not a chapter
        _span("run/outliers_recursive_lpa/rung:primary/masked_lpa", 0.375, job),
        _span("run/outliers_lof/lof_features", 2.0, job),
        _span("run/outliers_lof/lof_features/triangles_host", 1.0, job),
        _span("run/outliers_lof/lof_features/triangles_device", 0.5, job),
        _span("run/snapshot_publish", publish, job),
        # the canary's LOF opens its own stages: under publish_quality,
        # or straight under the rung (which writes no span) before PR 35
        _span(rung + ("/publish_quality" if stages else "") + "/lof_formula",
              0.0078125, job),
        # the store's own timed record, publish_s's: not a span
        {"phase": "snapshot_publish", "seconds": 0.1171875,
         "span_path": rung + "/publish_write", "scope": "job", "job": job},
    ]
    records += [_span(f"{rung}/{name}", s, job) for name, s in stages.items()]
    if root:
        records.append(_span("run", sum(_CHAPTERS.values()) + publish + 0.125, job))
    return records


def test_the_four_parts_of_the_publish_chapter_add_up_to_it():
    records = _job(0) + _job(1, publish=1.5)
    got = {m: _read(m, records) for m in METRICS}
    assert got["publish_chapter_s"] == pytest.approx(1.25)
    assert got["publish_cc_s"] == pytest.approx(0.4375)
    assert got["publish_host_s"] == pytest.approx(0.03125 + 0.0625 + 0.25)
    assert got["publish_write_s"] == pytest.approx(0.125 + 0.015625)
    assert got["publish_self_s"] == pytest.approx(1.25 - sum(_STAGES.values()))
    assert (got["publish_cc_s"] + got["publish_host_s"] + got["publish_write_s"]
            + got["publish_self_s"]) == pytest.approx(got["publish_chapter_s"])
    assert got["triangles_host_s"] == pytest.approx(1.0)
    # the root minus the seven chapters: what the driver spends between them
    assert got["run_self_s"] == pytest.approx(0.125)


def test_a_program_without_the_new_spans_reads_nothing_and_raises_nothing():
    """The parent of PR 35 writes the chapter span and no stage under it
    but the canary's LOF, and no root span."""
    records = _job(0, root=False, stages={})
    assert _read("run_self_s", records) is None
    assert _read("publish_chapter_s", records) == pytest.approx(1.0)
    for metric in ("publish_cc_s", "publish_host_s", "publish_write_s"):
        assert _read(metric, records) == 0.0
    assert _read("publish_self_s", records) == pytest.approx(1.0)
    assert _read("triangles_host_s", records) == pytest.approx(1.0)
    # and one that never published has no chapter to read
    no_publish = [r for r in records if "snapshot_publish" not in r["span_path"]]
    for metric in METRICS[:5]:
        assert _read(metric, no_publish) is None


def test_the_seven_read_a_value_in_the_cells_rehearsal():
    out = _run("--workload", CELL, "--seed", "3000000019", "--seconds", "1",
               "--trace", "1", "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    last = _lines(out)[-1]
    got = {m: last["metrics"][m]["value"] for m in METRICS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert got["publish_chapter_s"] > 0 and got["publish_cc_s"] > 0
    assert got["publish_write_s"] > 0 and got["triangles_host_s"] > 0
    # the readers sum span records rounded to 1e-4 s: seven of them here
    assert (got["publish_cc_s"] + got["publish_host_s"] + got["publish_write_s"]
            + got["publish_self_s"]) == pytest.approx(
                got["publish_chapter_s"], abs=1e-9)
    assert got["publish_self_s"] < 0.05 * got["publish_chapter_s"] + 1e-3


@pytest.mark.parametrize("metric", METRICS)
def test_each_of_the_seven_is_the_pipeline_cells_and_moves_its_makespan(metric, bench):
    assert bench.lists(metric, CELL)
    entry = bench.metric(metric)
    assert entry == dict(entry, moves="makespan_s", source="program_span", unit="s",
                         better="lower")
    assert bench.reader_of(metric)["reader"] in READERS
    assert "makespan_s" in bench.end_to_end_of(CELL)
