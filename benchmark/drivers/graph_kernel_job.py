"""Driver ``graph_kernel_job``: one job is one whole run of a graph kernel
through the program's public entry point, on a graph built once in set-up.

Which kernel runs is a file, ``benchmark/algorithms/<name>.py``, named by the
traffic file's ``algorithm`` and loaded by path, as ``run.py`` loads drivers
and readers. The module states

- ``run(graph, sink, traffic) -> (answer on the device, supersteps)``: a
  kernel that says itself when it is done (WCC, to its fixpoint) returns the
  supersteps it took; one that runs a stated count returns the traffic
  file's ``iterations``;
- ``reference(u, v, num_vertices, traffic)``: the plain answer;
- ``control(u, v, num_vertices, traffic)``: an answer with one stated
  guarantee broken, or computed in the next lower precision;
- ``compare(got, want) -> list of check records``: each with ``check``,
  ``value``, ``limit`` and ``ok``; an exact answer states the limit 0, a
  float answer the tolerance its source states.

The next kernel is such a file, a reference and a traffic file, not a
driver. What is common stays here: set-up, the timed job, ``evps``, the
records, the facts, and the check that the jobs of one window agree on
their supersteps. The last job's count is the fact ``iterations``, as
``kernel_job`` states CDLP's 10.

The superstep family is whatever ``auto`` resolves, the driver pins none.
The plan is built by the warm-up job and cached by the program per graph,
so the timed jobs hold processing only, as LDBC Graphalytics separates
loading from processing time. The warm-up job alone carries a
``MetricsSink``; every record the program wrote into it is handed on under
``scope: "warmup"``, beside set-up's stages and a ``job`` record a timed job
(``benchmark/handover.py``).
"""

from __future__ import annotations

import os
import time

import numpy as np

import generators
import handover

# A superstep is quiet when it moves the label of under this share of the
# vertices that have an edge: a frontier would not run it at full width.
QUIET_SHARE = 0.01

_ALGORITHMS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "algorithms")


def _timed(algorithm, graph, traffic, sink=None):
    """One job, ended by a sync on both of its results."""
    t0 = time.perf_counter()
    answer, supersteps = algorithm.run(graph, sink, traffic)
    answer.block_until_ready()
    supersteps = int(supersteps)
    return answer, supersteps, time.perf_counter() - t0


def setup(ctx) -> dict:
    traffic = ctx["traffic"]
    name = traffic["algorithm"]
    have = sorted(f[:-3] for f in os.listdir(_ALGORITHMS) if f.endswith(".py"))
    if name not in have:  # before any input is made
        raise ValueError(f"graph_kernel_job has no algorithm {name!r}; have {have}")
    algorithm = ctx["load_module"]("algorithms", name)

    import graphmine_tpu as gm
    from graphmine_tpu.pipeline.metrics import MetricsSink

    generator_args = ctx["sizes"]["generator_args"]
    t0 = time.perf_counter()
    u, v = generators.make(ctx["config"]["generator"], generator_args,
                           ctx["config"]["dataset_seed"])
    num_vertices = 1 << generator_args["scale"]
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    graph = gm.build_graph(u, v, num_vertices=num_vertices)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    touched = np.zeros(num_vertices, bool)
    touched[u] = True
    touched[v] = True
    vertices_with_edge = int(touched.sum())
    count_s = time.perf_counter() - t0
    sink = MetricsSink()
    _, supersteps, warm_s = _timed(algorithm, graph, traffic, sink)  # builds the plan too
    plan_s = sum(r.get("seconds", 0.0) for r in sink.records
                 if r.get("phase") == "plan_build")
    family = [r.get("impl") for r in sink.records
              if r.get("phase") == "impl_selected"]
    # what each superstep moved; a program without the record states no such facts
    changed = next((r["changed"] for r in sink.records
                    if r.get("phase") == "fixpoint"), None)
    state = {
        "ctx": ctx, "algorithm": algorithm, "u": u, "v": v,
        "num_vertices": num_vertices, "graph": graph,
        "iterations": supersteps, "answer": None, "fixpoint_facts": {},
        "edges_plus_vertices": vertices_with_edge + len(u),
        "setup_records": handover.stages(
            build_graph=build_s, plan_build=plan_s, generate=gen_s,
            count_vertices=count_s, warmup_job=warm_s),
        "warmup_records": handover.warmup(sink.records),  # the program's, whole
    }
    if changed is not None:
        state["fixpoint_facts"] = {
            "fixpoint_supersteps": len(changed),
            "quiet_passes": sum(c < QUIET_SHARE * vertices_with_edge
                                for c in changed),
        }
    ctx["say"](vertices=num_vertices, vertices_with_edge=vertices_with_edge,
               edges=len(u), algorithm=name, family=family, generate_s=gen_s,
               build_graph_s=build_s, count_vertices_s=count_s, plan_build_s=plan_s,
               warmup_job_s=warm_s, supersteps=supersteps, changed=changed)
    return state


def job(state, index: int) -> dict:
    state["answer"], supersteps, seconds = _timed(
        state["algorithm"], state["graph"], state["ctx"]["traffic"])
    state["iterations"] = supersteps
    return {"seconds": seconds, "supersteps": supersteps}


def end_to_end(state, jobs, window_s: float) -> dict:
    # Graphalytics' EVPS, over all the jobs and all the time of the window
    return {"evps": state["edges_plus_vertices"] * len(jobs) / window_s}


records = handover.records


def facts(state) -> dict:
    return dict(state["fixpoint_facts"],
                num_vertices=state["num_vertices"],
                num_messages=2 * len(state["u"]),
                iterations=state["iterations"])


# -- correctness --------------------------------------------------------------


def check(state, jobs, control: bool) -> list:
    """What the window's last job produced against the plain reference's, by
    the algorithm's own ``compare``, over the whole vertex space at the
    timed size. A job that reports no superstep, or jobs of one window that
    disagree on how many the same graph took, fail too: the count is what
    two per-layer metrics divide by."""
    algorithm, traffic = state["algorithm"], state["ctx"]["traffic"]
    u, v, n = state["u"], state["v"], state["num_vertices"]
    want = algorithm.reference(u, v, n, traffic)
    got = (algorithm.control(u, v, n, traffic) if control
           else np.asarray(state["answer"]))
    counts = [j["supersteps"] for j in jobs]
    odd = sum(c <= 0 or c != counts[-1] for c in counts)
    return algorithm.compare(got, want) + [
        {"check": "jobs_that_disagree_on_supersteps", "value": odd, "limit": 0,
         "ok": odd == 0, "supersteps": counts[-1], "jobs": len(counts)},
    ]
