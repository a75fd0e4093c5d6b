"""The WCC cell off the chip: its configuration is the CDLP cell's draw
under WCC's guarantees, the plain min-label reference agrees with SciPy's
union-find and with both superstep families of the program label for
label, the control (the reference stopped after two supersteps) and a
broken timed path come out not correct, an unknown algorithm is turned
away before any input is made, and the two new metrics read the
``fixpoint`` record and read nothing from a program that writes none."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

from _bench import BENCH_DIR, Bench, lines as _lines, load, run as _run
from _bench import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(BENCH_DIR, "run.py")
CELL, CONFIG, TRAFFIC = "wcc-g500-22", "graphalytics-g500-22-wcc", "wcc-batch"
SHARED = ("evps", "superstep_ms", "superstep_roofline_share",
          "device_idle_share.kernel", "graph_build_s.setup")
OWN = {"wcc_supersteps": {"fact": "iterations"},
       "wcc_quiet_pass_share": {"fact": "quiet_passes", "over": "fixpoint_supersteps",
                                "scale": 100.0}}

sys.path.insert(0, BENCH_DIR)
import generators  # noqa: E402
import references  # noqa: E402
import references_wcc  # noqa: E402


# -- the configuration and the cell -------------------------------------------


def test_the_configuration_is_the_cdlp_cells_draw_under_wccs_guarantees(bench):
    cell = bench.cell(CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    config = bench.data("configs", CONFIG + ".json")
    sibling = bench.data("configs", "graphalytics-g500-22.json")
    assert bench.config(CONFIG) == dict(
        bench.config(CONFIG), file=f"benchmark/configs/{CONFIG}.json",
        source=config["source"], reduced=[])
    for key in ("generator", "generator_args", "dataset_seed", "rehearsal", "chips"):
        assert config[key] == sibling[key]  # the same draw: the kernel alone differs
    assert config["reduced"] == [] and config["guarantees"] != sibling["guarantees"]
    assert config["source"] == sibling["source"].replace(
        "algorithm CDLP, 10 iterations",
        "algorithm WCC, to the fixpoint, output by equivalence")
    said = " ".join(config["guarantees"])
    for word in ("weakly", "fixpoint", "path", "smallest vertex id", "isolated", "exact"):
        assert word in said
    traffic = bench.data("traffic", TRAFFIC + ".json")
    assert traffic["driver"] == "graph_kernel_job" and traffic["algorithm"] == "wcc"
    assert "iterations" not in traffic  # the supersteps are the program's answer
    assert os.path.exists(os.path.join(bench.dir, "algorithms", "wcc.py"))
    assert bench.reported_by(CELL) == {*SHARED, *OWN}
    for name in (*SHARED, *OWN):
        assert bench.lists(name, CELL), name
    for name, args in OWN.items():
        assert bench.reader_of(name) == {"reader": "fact_value", "args": args}
        assert bench.metric(name)["moves"] == "evps"


# -- the reference, SciPy and both families of the program --------------------


def _rmat(scale, seed):
    u, v = generators.rmat_undirected(scale, 16, 0.57, 0.19, 0.19, seed)
    return u, v, 1 << scale


def _chain(n=3000):
    return np.arange(n - 1), np.arange(1, n), n


def _messy(n=500, e=260, seed=3):
    """Isolated vertices (ids from 400 up have no edge), self-loops,
    duplicate edges and both spellings of an edge."""
    rng = np.random.default_rng(seed)
    u, v = rng.integers(0, 400, e), rng.integers(0, 400, e)
    loops = rng.integers(0, 400, 20)
    return (np.concatenate([u, v[:60], loops, u[:40]]),
            np.concatenate([v, u[:60], loops, v[:40]]), n)


GRAPHS = {
    "rmat-10": lambda: _rmat(10, 11), "rmat-11": lambda: _rmat(11, 2147483659),
    "rmat-12": lambda: _rmat(12, 13), "chain": _chain, "messy": _messy,
}


@pytest.mark.parametrize("graph", GRAPHS)
def test_both_families_equal_the_min_label_reference_and_scipy(graph):
    import graphmine_tpu as gm
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    u, v, n = GRAPHS[graph]()
    plain, supersteps = references_wcc.numpy_min_label(u, v, n)
    want = references.canonical_partition(plain)
    np.testing.assert_array_equal(
        want, references.canonical_partition(references.scipy_cc(u, v, n)))
    np.testing.assert_array_equal(plain, want)  # it states the smallest member id too
    assert supersteps >= 2
    g = gm.build_graph(u, v, num_vertices=n)
    plans = {"sort": None, "bucketed": BucketedModePlan.from_graph(g, with_send=True),
             "auto": "auto"}
    for family, plan in plans.items():
        got, iters = gm.connected_components(g, plan=plan, return_iterations=True)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=family)
        assert 0 < int(iters) <= supersteps  # pointer jumping never takes longer


def test_the_reference_stopped_after_two_supersteps_is_not_the_partition():
    u, v, n = _chain()
    early, supersteps = references_wcc.numpy_min_label(u, v, n, max_supersteps=2)
    assert supersteps == 2
    np.testing.assert_array_equal(early[:5], [0, 0, 0, 1, 2])  # two hops, no further
    assert references.partition_mismatches(early, references.scipy_cc(u, v, n)) > 0
    whole, supersteps = references_wcc.numpy_min_label(u, v, n)
    assert supersteps == n and not whole.any()  # n - 1 hops and the confirming pass
    u, v, n = _rmat(12, 2147483659)  # the rehearsal's scale
    early = references_wcc.numpy_min_label(u, v, n, max_supersteps=2)[0]
    assert references.partition_mismatches(early, references.scipy_cc(u, v, n)) > 0


# -- run.py on the cell, off the chip -----------------------------------------


def test_the_control_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0",
               "--rehearse", "--control")
    assert out.returncode == 5, out.stderr[-3000:]
    lines = _lines(out)
    control = {r["check"]: r for r in lines if r.get("control") is True}
    failing = control["wcc_label_mismatches"]
    assert not failing["ok"] and failing["value"] > 0 == failing["limit"]
    assert failing["compared"] == 4096 and failing["components"] > 1
    assert {"sound_run_correct": True} in lines
    assert lines[-1] == {"control": "compared", "correct": False}


_BREAK_WCC = """
import runpy, sys
import jax.numpy as jnp
import graphmine_tpu as gm
# a fixpoint loop that gives up at once: every vertex its own component
gm.connected_components = lambda graph, **kw: (
    jnp.arange(graph.num_vertices, dtype=jnp.int32), 1)
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""

_NO_FIXPOINT_RECORD = """
import runpy, sys
import graphmine_tpu as gm
sound = gm.connected_components
# the parent commit's program: the same answers, no `fixpoint` record
def parent(graph, plan="auto", return_iterations=False, sink=None):
    out = sound(graph, plan=plan, return_iterations=return_iterations, sink=sink)
    if sink is not None:
        sink.records[:] = [r for r in sink.records if r["phase"] != "fixpoint"]
    return out
gm.connected_components = parent
sys.argv[0] = {run!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_a_broken_timed_path_comes_out_not_correct():
    out = _run("--workload", CELL, "--seed", "6", "--seconds", "1", "--trace", "0",
               "--rehearse", code=_BREAK_WCC.format(run=RUN))
    assert out.returncode == 1, out.stderr[-3000:]
    checks = {r["check"]: r for r in _lines(out) if "check" in r}
    assert not checks["wcc_label_mismatches"]["ok"]
    assert checks["wcc_label_mismatches"]["value"] > 0
    assert _lines(out)[-1]["rehearsal"] == "failed"


def test_an_unknown_algorithm_is_turned_away_before_anything_is_generated(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(os.path.join(BENCH_DIR, "configs"), root / "benchmark" / "configs")
    (root / "benchmark" / "traffic").mkdir()
    with open(os.path.join(BENCH_DIR, "traffic", "wcc-batch.json")) as f:
        traffic = dict(json.load(f), algorithm="pagerank")
    (root / "benchmark" / "traffic" / "wcc-batch.json").write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(dict(Bench().json, per_layer=[])))
    out = _run("--root", str(root), "--workload", CELL, "--seed", "3",
               "--seconds", "1", "--trace", "0", "--rehearse")
    assert out.returncode not in (0, 4), out.stdout[-2000:]
    assert "graph_kernel_job has no algorithm 'pagerank'" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


def test_the_algorithm_files_compare_prints_the_accepted_record():
    """``algorithms/wcc.py`` states what ``graph_kernel_job``'s table row
    stated before ISSUE 40: the ledger's ``last_line_numbers`` know the
    comparison by these keys, in this order."""
    wcc = load("algorithms", "wcc")
    want = np.array([0, 0, 2, 2, 4, 0])
    assert wcc.compare(want.copy(), want) == [{
        "check": "wcc_label_mismatches", "value": 0, "limit": 0, "ok": True,
        "compared": 6, "components": 3}]
    (record,) = wcc.compare(np.array([0, 1, 2, 2, 4, 5]), want)
    assert list(record) == ["check", "value", "limit", "ok", "compared", "components"]
    assert record == dict(record, value=2, ok=False, limit=0, components=3)
    u, v, n = _chain(50)
    np.testing.assert_array_equal(wcc.reference(u, v, n, {}), np.zeros(n, int))
    np.testing.assert_array_equal(wcc.control(u, v, n, {})[:5], [0, 0, 0, 1, 2])


def test_the_two_new_metrics_read_the_fixpoint_record_on_a_rehearsal():
    out = _run("--workload", CELL, "--seed", "2147483700", "--seconds", "1",
               "--trace", "1", "--rehearse")
    assert out.returncode == 4, out.stderr[-3000:]
    lines = _lines(out)
    said = next(r for r in lines if "vertices" in r)
    assert said["family"] == ["bucketed"] and said["algorithm"] == "wcc"
    changed = said["changed"]
    assert len(changed) == said["supersteps"] and changed[-1] == 0 < changed[0]
    metrics = lines[-1]["metrics"]
    assert metrics["wcc_supersteps"] == {"value": float(said["supersteps"]),
                                         "unit": "count"}
    quiet = sum(c < 0.01 * said["vertices_with_edge"] for c in changed)
    assert 1 <= quiet < len(changed)
    assert metrics["wcc_quiet_pass_share"]["value"] == \
        pytest.approx(100.0 * quiet / len(changed))
    checks = {r["check"]: r for r in lines if "check" in r}
    assert checks["jobs_that_disagree_on_supersteps"]["supersteps"] == said["supersteps"]


def test_a_program_without_the_record_leaves_the_share_out_and_does_not_fail():
    """The driver runs a new cell on the parent commit with this
    benchmark's files laid over it: that program writes no ``fixpoint``
    record, and the metric that reads it is left out of the line."""
    out = _run("--workload", CELL, "--seed", "8", "--seconds", "1", "--trace", "1",
               "--rehearse", code=_NO_FIXPOINT_RECORD.format(run=RUN))
    assert out.returncode == 4, out.stderr[-3000:]
    metrics = _lines(out)[-1]["metrics"]
    assert "wcc_quiet_pass_share" not in metrics
    assert metrics["wcc_supersteps"]["value"] >= 2
    assert {"superstep_ms", "graph_build_s.setup"} <= set(metrics)
