"""The ``program_memory`` records (ISSUE 52): what a job's compiled programs
take of the chip, said by the executables it ran.

- every sink'd kernel entry writes one record for each program it ran, with
  the executable's integers, and the admission's count beside the compiler's
  where the admission counts that program;
- the executable is asked once a (plan, program): a second sink'd job on the
  same plan copies the records (``cached: True``) and asks nothing;
- asking compiles nothing (no backend-compile event fires inside an ask, and
  a job compiles as many programs with a sink as without);
- without a sink nothing is asked and nothing kept;
- the mesh job's records say ``shards``.
"""

import gc
import os
import sys

import jax
import numpy as np
import pytest

import graphmine_tpu as gm
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.ops import superstep_policy as policy
from graphmine_tpu.pipeline.metrics import MetricsSink

SIZES = ("code_bytes", "temp_bytes", "argument_bytes", "output_bytes", "alias_bytes")
_BACKEND = "/jax/core/compile/backend_compile_duration"
_compiles = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _, **kw: _compiles.__setitem__(0, _compiles[0] + (event == _BACKEND))
)


def _edges(seed=0, vertices=5000, edges=60000):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vertices, edges), rng.integers(0, vertices, edges), vertices


@pytest.fixture(scope="module")
def graph():
    u, v, n = _edges()  # 120,000 messages: past the crossover, so a plan and a slot index
    return gm.build_graph(u, v, num_vertices=n)


@pytest.fixture(scope="module")
def host_graph():
    u, v, n = _edges()
    return gm.build_graph(u, v, num_vertices=n, to_device=False)


@pytest.fixture()
def asks(monkeypatch):
    """A counting stand-in for the one function that enters ``lower``: how
    often it was entered, and the backend compiles that fired inside it."""
    seen = {"asks": 0, "compiles_inside": 0}
    real = policy._ask_executable

    def counting(fn, args, statics):
        before = _compiles[0]
        seen["asks"] += 1
        try:
            return real(fn, args, statics)
        finally:
            seen["compiles_inside"] += _compiles[0] - before

    monkeypatch.setattr(policy, "_ask_executable", counting)
    return seen


# kernel -> (the job, its op, programs it must have run, programs the admission counts)
KERNELS = {
    "lpa": (lambda g, sink: gm.label_propagation(g, max_iter=10, sink=sink),
            "lpa_superstep", {"blank_rows", "gather", "modes", "rewrite"},
            {"gather", "modes"}),
    "bfs": (lambda g, sink: gm.bfs_distances(g, [0], direction="both", sink=sink),
            "bfs_level", {"start", "level", "rewrite"}, {"level"}),
    "pagerank": (lambda g, sink: gm.pagerank(g, max_iter=5, tol=None, directed=False,
                                             sink=sink),
                 "pagerank_inflow", {"start", "iteration"}, {"iteration"}),
    "wcc": (lambda g, sink: gm.connected_components(g, sink=sink),
            "cc_superstep", {"loop"}, set()),
    "lcc": (lambda g, sink: gm.clustering_coefficient(g, sink=sink),
            "lcc", {"core", "by_id"}, set()),
    "lpa_sort": (lambda g, sink: gm.label_propagation(g, max_iter=3, plan=None, sink=sink),
                 "lpa_superstep", {"scan"}, set()),
    "mesh": (lambda g, sink: gm.label_propagation(g, max_iter=10, mesh=gm.make_mesh(4),
                                                  sink=sink),
             "lpa_superstep", {"start", "gather", "modes"}, {"gather", "modes"}),
}


def _memory(sink):
    return [r for r in sink.records if r["phase"] == "program_memory"]


def _said(record):
    return {k: v for k, v in record.items() if k not in ("t", "cached", "asked_s")}


@pytest.mark.parametrize("kernel", KERNELS)
def test_a_sinked_job_writes_a_record_a_program_and_asks_once_a_plan(
    kernel, graph, host_graph, asks
):
    job, op, ran, counted = KERNELS[kernel]
    g = host_graph if kernel == "mesh" else graph

    first = MetricsSink()
    job(g, first)
    records = _memory(first)
    assert validate_records(first.records) == []
    assert records and {r["op"] for r in records} == {op}
    assert ran <= {r["program"] for r in records}
    # one record a program: name and static arguments tell them apart
    identity = [tuple(sorted((k, v) for k, v in r.items()
                             if k not in ("t", "asked_s", "cached", "reckoned_temp_bytes", *SIZES)))
                for r in records]
    assert len(set(identity)) == len(records)
    for r in records:
        assert all(type(r[k]) is int and r[k] >= 0 for k in SIZES), r
        assert r["cached"] is False
        assert ("shards" in r) == (kernel == "mesh") and r.get("shards", 4) == 4
    assert any(r["temp_bytes"] > 0 for r in records)
    assert {r["program"] for r in records if "reckoned_temp_bytes" in r} >= counted
    assert all(type(r["reckoned_temp_bytes"]) is int and r["reckoned_temp_bytes"] > 0
               for r in records if "reckoned_temp_bytes" in r)
    if kernel in ("lpa", "mesh", "bfs"):
        # the rewrite is reckoned at the top rung, and on that record alone
        top = max(next(r["rungs"] for r in first.records
                       if r["phase"] == "superstep_delta"))
        assert all(r["cap"] == top for r in records
                   if r["program"] == "rewrite" and "reckoned_temp_bytes" in r)
    assert [("asked_s" in r) for r in records] == [False] * (len(records) - 1) + [True]
    # every program was asked once, and asking compiled nothing
    assert asks == {"asks": len(records), "compiles_inside": 0}

    # a second sink'd job on the same plan: the same records, copied
    compiled, second = _compiles[0], MetricsSink()
    job(g, second)
    again = _memory(second)
    assert [_said(r) for r in again] == [_said(r) for r in records]
    assert all(r["cached"] is True for r in again)
    assert asks["asks"] == len(records) and _compiles[0] == compiled

    # and a job without a sink enters nothing and keeps nothing more
    kept = {k: dict(v[1]) for k, v in policy._program_memory.items()}
    job(g, None)
    assert asks["asks"] == len(records)
    assert {k: dict(v[1]) for k, v in policy._program_memory.items()} == kept


def test_without_a_sink_no_log_is_made_and_nothing_is_kept(monkeypatch):
    u, v, n = _edges()  # the fixture's draw in a graph of its own: a plan of its own
    g = gm.build_graph(u, v, num_vertices=n)

    def never(*a, **k):
        raise AssertionError("entered without a sink")

    monkeypatch.setattr(policy.ProgramLog, "__init__", never)
    monkeypatch.setattr(policy, "_ask_executable", never)
    monkeypatch.setattr(policy, "emit_program_memory",
                        lambda sink, op, programs: programs is None or never())
    kept = len(policy._program_memory)
    gm.label_propagation(g, max_iter=3)
    gm.connected_components(g)
    gm.pagerank(g, max_iter=2, tol=None, directed=False)
    gm.bfs_distances(g, [0], direction="both")
    assert len(policy._program_memory) == kept


def test_what_is_kept_goes_with_the_plan():
    u, v, n = _edges(seed=2, vertices=3000, edges=40000)
    g = gm.build_graph(u, v, num_vertices=n)
    before = len(policy._program_memory)
    gm.connected_components(g, sink=MetricsSink())
    assert len(policy._program_memory) == before + 1
    del g
    gc.collect()
    assert len(policy._program_memory) == before


def test_the_hubs_histograms_are_counted_with_the_programs_that_hold_them():
    """The admission's sum holds them as a term of its own; the compiler
    counts them among the temporaries of ``modes`` and ``dirty_modes``."""
    from graphmine_tpu.obs.memmodel import carried_job_transients, carried_rows_inventory
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    rng = np.random.default_rng(4)
    hub = np.zeros(3000, np.int64)  # vertex 0 receives 3,000 messages: past _HIST_MIN_DEG
    u = np.concatenate([hub, rng.integers(1, 4000, 30000)])
    v = np.concatenate([rng.integers(1, 4000, 3000), rng.integers(1, 4000, 30000)])
    plan = BucketedModePlan.from_edges(u, v, 4000)
    assert plan.hist_vertex_ids is not None
    sized = policy._admission_sizes(plan, 1, "mode")
    by_program = carried_job_transients(plan, **sized)
    hubs = carried_rows_inventory(plan, **sized)["hub_histograms"]
    reckoned = policy.reckoned_temp_bytes(plan)
    assert hubs > 0
    assert reckoned[("modes", None)] == by_program["modes"] + hubs
    assert reckoned[("dirty_modes", None)] == by_program["dirty_modes"] + hubs
    assert reckoned[("gather", None)] == by_program["gather"]
    assert reckoned[("rewrite", sized["top_rung"])] == by_program["rewrite"]
    assert policy.reckoned_temp_bytes(plan, reduce="min")[("level", None)] == \
        carried_job_transients(plan, **policy._admission_sizes(plan, 1, "min"))["row_min"]


def test_a_job_compiles_as_many_programs_with_a_sink_as_without():
    """The literal count: the same job on the same shapes, compiled afresh
    once under a sink (which asks its executable) and once without."""
    u, v, n = _edges(seed=3, vertices=2500, edges=40000)
    g = gm.build_graph(u, v, num_vertices=n)
    counts = []
    for sink in (MetricsSink(), None):
        jax.clear_caches()
        before = _compiles[0]
        gm.label_propagation(g, max_iter=2, plan=None, sink=sink)
        counts.append(_compiles[0] - before)
    assert counts[0] == counts[1] > 0


def test_schema_lint_knows_the_record():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"))
    import schema_lint

    assert "program_memory" in schema_lint.SCHEMAS
    assert schema_lint.main([]) == 0
