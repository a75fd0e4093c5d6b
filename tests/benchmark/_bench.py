"""What the tests of ``tests/benchmark/`` read the benchmark through.

A structure test says what is true of ITS cell, configuration and metrics,
and nothing of the others: the cell's name stands once in a list, its entry
names this configuration, traffic and ``chips``, its metric has this reader,
these arguments and this ``moves``. No test counts the cells, the four-chip
cells, the configurations or the metrics, and none holds a name to a place
in a list: the next PR appends to all of them.

The ``bench`` fixture hands every structure test a ``Bench`` twice (a test
file imports it and ``grown_root`` from here: a ``conftest.py`` in this
directory would shadow ``tests/conftest.py``, which other tests import by
name): the benchmark as it stands, and ``grow``'s copy of it in a
temporary directory, to which a later PR's whole kit has been added as NEW
files and APPENDED entries: a configuration, a one-chip cell on a new
traffic mix and a new algorithm file, a four-chip cell, two per-layer
metrics (one through a new bytes module), and the new cells' names at the
end of the ``workloads`` lists. A pin on a count or on a place fails in that
second case, in the PR that writes it.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmark")

DUMMY_CELL, DUMMY_CELL_X4 = "dummy-cell", "dummy-cell-x4"
DUMMY_ALGORITHM, DUMMY_BYTES = "dummy_rank", "dummy_bytes"
# set-up's account: six metrics that every cell reports and that move `setup_s`
# (held by test_setup_account.py); a cell's own test says nothing of them
SETUP_ACCOUNT = ("process_start_s.setup", "generate_s.setup", "warmup_job_s.setup",
                 "compile_s.setup", "program_load_s.setup", "setup_other_s")


class Bench:
    """``BENCHMARK.json`` of one root and the data files beside it, by name."""

    def __init__(self, root: str = REPO):
        self.root = str(root)
        self.dir = os.path.join(self.root, "benchmark")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.json = json.load(f)

    def _one(self, entries: list, name: str) -> dict:
        found = [e for e in entries if e["name"] == name]
        assert len(found) == 1, f"{name!r} stands {len(found)} times"
        return found[0]

    def cell(self, name: str) -> dict:
        return self._one(self.json["workloads"], name)

    def config(self, name: str) -> dict:
        return self._one(self.json["configs"], name)

    def metric(self, name: str) -> dict:
        return self._one(self.json["end_to_end"] + self.json["per_layer"], name)

    def lists(self, metric: str, cell: str) -> bool:
        """``cell`` stands exactly once in the metric's ``workloads`` list."""
        return self.metric(metric).get("workloads", []).count(cell) == 1

    def reported_by(self, cell: str) -> set:
        """The names of the metrics whose ``workloads`` list names the cell,
        set-up's account (``SETUP_ACCOUNT``: every cell's) left out."""
        return {m["name"] for m in self.json["end_to_end"] + self.json["per_layer"]
                if cell in m.get("workloads", [])} - set(SETUP_ACCOUNT)

    def end_to_end_of(self, cell: str) -> set:
        return {m["name"] for m in self.json["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}

    def data(self, *parts) -> dict:
        """A JSON file under the root's ``benchmark/``."""
        with open(os.path.join(self.dir, *parts)) as f:
            return json.load(f)

    def reader_of(self, metric: str) -> dict:
        return self.data("layer_metrics", metric + ".json")

    def run(self, *argv, code=None, devices=None, timeout=900):
        """The root's own ``benchmark/run.py`` (or ``code`` that ends by
        running it) on the CPU."""
        return run(*argv, code=code, devices=devices, timeout=timeout, root=self.root)


def run(*argv, code=None, devices=None, timeout=900, root=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    script = os.path.join(root, "benchmark", "run.py")
    cmd = [sys.executable, script] if code is None else [sys.executable, "-c", code]
    return subprocess.run([*cmd, *argv], capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=root)


def load(kind: str, name: str, directory: str = BENCH_DIR):
    """``<directory>/<kind>/<name>.py`` loaded by path, as ``run.py`` loads it."""
    spec = importlib.util.spec_from_file_location(
        f"under_test_{kind}_{name}", os.path.join(directory, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lines(out) -> list:
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


@pytest.fixture(scope="session")
def grown_root(tmp_path_factory):
    return grow(tmp_path_factory.mktemp("grown"))


@pytest.fixture(params=["as-it-stands", "grown"])
def bench(request):
    if request.param == "grown":
        return Bench(request.getfixturevalue("grown_root"))
    return Bench()


# -- a later PR's kit, as new files and appended entries ----------------------

# A kernel with a float answer, a stated count of iterations and a stated
# tolerance: PageRank's power iteration over the edges as they are drawn
# (u -> v), the rank of a vertex without an out-edge spread over all. The
# reference is float64 NumPy; the control runs one iteration short.
_DUMMY_ALGORITHM = '''
"""A test's algorithm: a float answer under a relative tolerance."""
import numpy as np

TOLERANCE = 1e-4  # relative, per vertex, as LDBC Graphalytics validates PageRank


def run(graph, sink, traffic):
    import graphmine_tpu as gm

    ranks = gm.pagerank(graph, max_iter=traffic["iterations"], tol=0.0, sink=sink)
    return ranks, traffic["iterations"]


def _power(u, v, n, iterations):
    out = np.bincount(u, minlength=n).astype(np.float64)
    share = np.where(out > 0, 1.0 / np.maximum(out, 1.0), 0.0)
    rank = np.full(n, 1.0 / n)
    for _ in range(iterations):
        inflow = np.bincount(v, weights=(rank * share)[u], minlength=n)
        rank = 0.85 * (inflow + rank[out == 0].sum() / n) + 0.15 / n
    return rank


def reference(u, v, num_vertices, traffic):
    return _power(u, v, num_vertices, traffic["iterations"])


def control(u, v, num_vertices, traffic):
    return _power(u, v, num_vertices, traffic["iterations"] - 1)


def compare(got, want):
    gap = float(np.max(np.abs(np.asarray(got, np.float64) - want) / want))
    return [{"check": "rank_widest_relative_gap", "value": gap, "limit": TOLERANCE,
             "ok": gap <= TOLERANCE, "compared": len(want)}]
'''

_DUMMY_BYTES = '''
"""A test's count of least bytes: a rank read and a rank written a vertex, a
sender index and a rank read an edge."""


def rank_iteration_min_bytes(num_vertices, num_messages):
    return 4 * (2 * int(num_vertices) + int(num_messages))
'''


def grow(root) -> str:
    """A copy of the benchmark under ``root`` with a later PR's kit added:
    new files, appended entries, and no file or entry that is there edited.
    The copy runs the program of this checkout (``graphmine_tpu`` and
    ``native`` are links)."""
    root = str(root)
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("graphmine_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    bench = Bench(REPO)
    grown = os.path.join(root, "benchmark")

    def add(text, *parts):
        path = os.path.join(grown, *parts)
        assert not os.path.exists(path), f"{path} is there already"
        with open(path, "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text, indent=2))

    config = dict(bench.data("configs", "graphalytics-g500-22.json"),
                  name="dummy-config", source="a test's configuration")
    config["rehearsal"] = {"generator_args": dict(
        config["rehearsal"]["generator_args"], scale=10)}
    add(config, "configs", "dummy-config.json")
    config_x4 = dict(bench.data("configs", "graphalytics-g500-25.json"),
                     name="dummy-config-x4", source="a test's four-chip configuration")
    add(config_x4, "configs", "dummy-config-x4.json")
    add({"driver": "graph_kernel_job", "algorithm": DUMMY_ALGORITHM, "iterations": 3,
         "loop": "closed batch, one client", "traced_jobs": 1},
        "traffic", "dummy-traffic.json")
    add(_DUMMY_ALGORITHM, "algorithms", DUMMY_ALGORITHM + ".py")
    add(_DUMMY_BYTES, DUMMY_BYTES + ".py")
    add({"reader": "job_seconds_per", "args": {"per": "iterations", "scale": 1000.0}},
        "layer_metrics", "dummy_iteration_ms.json")
    add({"reader": "roofline", "args": {
        "bytes_module": DUMMY_BYTES, "bytes_function": "rank_iteration_min_bytes",
        "bytes_args": ["num_vertices", "num_messages"], "calls_per_job": "iterations"}},
        "layer_metrics", "dummy_iteration_roofline.json")

    b = json.loads(json.dumps(bench.json))
    b["configs"] += [
        {"name": "dummy-config", "source": config["source"], "reduced": [],
         "file": "benchmark/configs/dummy-config.json", "why": "a test's"},
        {"name": "dummy-config-x4", "source": config_x4["source"], "reduced": [],
         "file": "benchmark/configs/dummy-config-x4.json", "why": "a test's"},
    ]
    b["workloads"] += [
        {"name": DUMMY_CELL, "config": "dummy-config", "traffic": "dummy-traffic",
         "chips": 1, "why": "a test's one-chip cell"},
        {"name": DUMMY_CELL_X4, "config": "dummy-config-x4",
         "traffic": "cdlp-batch-mesh", "chips": 4, "why": "a test's four-chip cell"},
    ]
    # the one-chip cell is run: it stands where a kernel cell stands (`evps`
    # and what moves it or `setup_s`); the four-chip cell is never run and
    # stands at the end of every list there is
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            if m["name"] == "evps" or m.get("moves") in ("evps", "setup_s"):
                m["workloads"].append(DUMMY_CELL)
            m["workloads"].append(DUMMY_CELL_X4)
    b["per_layer"] += [
        {"name": "dummy_iteration_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "superstep kernel", "moves": "evps",
         "workloads": [DUMMY_CELL]},
        {"name": "dummy_iteration_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "superstep kernel", "moves": "evps",
         "workloads": [DUMMY_CELL]},
    ]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f, indent=1)
    return root
