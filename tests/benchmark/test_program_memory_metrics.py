"""The three per-layer metrics that read the program's ``program_memory``
records (PR 52), off the chip: their files load, name a reader that exists and
list a cell that exists, on the benchmark as it stands and on the grown copy;
the one reader gives the hand counts on hand-made records of scope ``warmup``
(the numbers PERF.md quotes for GAP Urand at PR 38) and nothing where no
record matches, as on the parent, whose program writes none; and the two CDLP
cells the issue names still rehearse.

They list ``cdlp-g500-22`` alone. The records are written in every kernel cell
(the four-chip rehearsal below reads all three through the reader by hand), but
the accepted tests of the seven other kernel cells hold each cell's listed
metrics, and ``test_flat_cell.py`` the rehearsal line's, to an exact set: a
``benchmark`` PR appends the other cells to the three lists and the names to
those sets (PERF.md section 7)."""

import os

import pytest

from _bench import BENCH_DIR, Bench, lines, load, run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

CELL = "cdlp-g500-22"
# metric -> (layer, moves, field, reduce)
METRICS = {
    "program_code_gb": ("device", "setup_s", "code_bytes", "sum"),
    "program_temp_peak_gb": ("device", "evps", "temp_bytes", "max"),
    "admission_temp_overcount_gb": ("superstep kernel", "evps",
                                    "reckoned_temp_bytes", "at_largest"),
}


def _read(metric: str, records: list, directory: str = BENCH_DIR):
    spec = Bench().reader_of(metric)
    reader = load("readers", spec["reader"], directory)
    return reader.read(spec["args"], {"records": records})


def _program(program, code, temp, reckoned=None, scope="warmup", **more):
    record = {"phase": "program_memory", "op": "lpa_superstep", "program": program,
              "code_bytes": code, "temp_bytes": temp, "argument_bytes": 1,
              "output_bytes": 1, "alias_bytes": 0, "cached": False, "scope": scope}
    if reckoned is not None:
        record["reckoned_temp_bytes"] = reckoned
    return dict(record, **more)


# GAP Urand at scale 24 as PR 38 sized it by hand: the gather's temporaries
# reckoned and compiled, the modes' and the top rung's rewrite's reckoned
URAND = [
    _program("blank_rows", 1_000_000, 0),
    _program("gather", 89_000_000, 2_423_228_928, 2_489_163_784),
    _program("modes", 70_000_000, 2_300_000_000, 2_380_000_000),
    _program("rewrite", 40_000_000, 1_700_000_000, 1_790_000_000, cap=44_739_242),
    _program("rewrite", 40_000_000, 600_000_000, cap=2_796_202, marked=False),
]


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_is_a_file_a_reader_and_a_cell_that_exists(bench, name):
    layer, moves, field, reduce = METRICS[name]
    metric = bench.metric(name)
    assert metric == dict(metric, unit="GB", better="lower", source="program_counter",
                          layer=layer, moves=moves)
    assert bench.lists(name, CELL)
    assert not bench.lists(name, "pipeline-outlinks-262k")  # its peak is the IVF search's
    there = {w["name"] for w in bench.json["workloads"]}
    assert set(metric["workloads"]) <= there
    assert all(moves in bench.end_to_end_of(cell) for cell in metric["workloads"])
    spec = bench.reader_of(name)
    assert spec["reader"] == "record_fields"
    assert os.path.exists(os.path.join(bench.dir, "readers", spec["reader"] + ".py"))
    assert spec["args"] == dict(spec["args"], select={"phase": "program_memory"},
                                field=field, reduce=reduce, scale=1e-09)
    assert _read(name, URAND, bench.dir) is not None


def test_the_reader_gives_the_hand_counts():
    assert _read("program_code_gb", URAND) == pytest.approx(0.240)
    assert _read("program_temp_peak_gb", URAND) == pytest.approx(2.423228928)
    # signed, at the record the admission counts the most for: the gather's
    assert _read("admission_temp_overcount_gb", URAND) == pytest.approx(0.065934856)
    under = [_program("gather", 1, 3_000_000_000, 2_500_000_000), *URAND[2:]]
    assert _read("admission_temp_overcount_gb", under) == pytest.approx(-0.5)
    # on the CPU the executables state no code: a number all the same
    assert _read("program_code_gb", [_program("loop", 0, 64)]) == 0.0


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_gives_nothing(name):
    """The parent writes no such record; a timed job's are not the warm-up's;
    an executable that states nothing leaves ``None``; and a job the admission
    counts no program of has no record with both fields."""
    assert _read(name, []) is None
    assert _read(name, [{"phase": "device_residency", "scope": "warmup"}]) is None
    assert _read(name, [_program("gather", 5, 7, 9, scope="job")]) is None
    assert _read(name, [_program("gather", None, None, None)]) is None
    if name == "admission_temp_overcount_gb":
        assert _read(name, [_program("loop", 5, 7)]) is None


@pytest.mark.parametrize("cell,devices", [(CELL, None), ("cdlp-g500-25-x4", 4)])
def test_the_cdlp_cells_still_rehearse_and_the_listed_one_reads_the_three(cell, devices):
    out = run("--workload", cell, "--seed", "2147483700", "--seconds", "1",
              "--trace", "1", "--rehearse", devices=devices)
    assert out.returncode == 4, out.stderr[-3000:]
    metrics = lines(out)[-1]["metrics"]
    assert (set(METRICS) <= set(metrics)) == (cell == CELL), sorted(metrics)
    if cell == CELL:
        assert metrics["program_code_gb"]["value"] == 0.0  # the CPU states no code size
        assert metrics["program_temp_peak_gb"] == {
            "value": pytest.approx(metrics["program_temp_peak_gb"]["value"]), "unit": "GB"}
        assert metrics["program_temp_peak_gb"]["value"] > 0
        assert abs(metrics["admission_temp_overcount_gb"]["value"]) < 1.0
