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
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmark")
RUN = os.path.join(BENCH_DIR, "run.py")
CELL = "wcc-g500-22"

sys.path.insert(0, BENCH_DIR)
import generators  # noqa: E402
import references  # noqa: E402
import references_wcc  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


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


def test_the_configuration_is_the_cdlp_cells_draw_under_wccs_guarantees():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config="graphalytics-g500-22-wcc",
                               traffic="wcc-batch", chips=1)
    with open(os.path.join(BENCH_DIR, "configs", "graphalytics-g500-22-wcc.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "configs", "graphalytics-g500-22.json")) as f:
        sibling = json.load(f)
    for key in ("generator", "generator_args", "dataset_seed", "rehearsal", "chips"):
        assert config[key] == sibling[key]  # the same draw: the kernel alone differs
    assert config["reduced"] == [] and config["guarantees"] != sibling["guarantees"]
    assert config["source"] == sibling["source"].replace(
        "algorithm CDLP, 10 iterations",
        "algorithm WCC, to the fixpoint, output by equivalence")
    said = " ".join(config["guarantees"])
    for word in ("weakly", "fixpoint", "path", "smallest vertex id", "isolated", "exact"):
        assert word in said
    with open(os.path.join(BENCH_DIR, "traffic", "wcc-batch.json")) as f:
        traffic = json.load(f)
    assert traffic["driver"] == "graph_kernel_job" and traffic["algorithm"] == "wcc"
    assert "iterations" not in traffic  # the supersteps are the program's answer
    listing = {m["name"]: m.get("workloads", []) for m in
               BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("evps", "superstep_ms", "superstep_roofline_share",
                 "device_idle_share.kernel", "graph_build_s.setup"):
        assert listing[name][-1] == CELL
    assert listing["wcc_supersteps"] == listing["wcc_quiet_pass_share"] == [CELL]


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
    (root / "BENCHMARK.json").write_text(json.dumps(dict(BENCH, per_layer=[])))
    out = _run("--root", str(root), "--workload", CELL, "--seed", "3",
               "--seconds", "1", "--trace", "0", "--rehearse")
    assert out.returncode not in (0, 4), out.stdout[-2000:]
    assert "graph_kernel_job has no algorithm 'pagerank'" in out.stderr
    assert not [r for r in _lines(out) if "vertices" in r]  # nothing was drawn


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
