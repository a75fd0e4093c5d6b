"""Driver ``graph_kernel_job``: one job is one whole run of a graph kernel
through the program's public entry point, on a graph built once in set-up,
for kernels that say themselves when they are done.

``kernel_job`` times a stated count of supersteps (CDLP's 10). Here the
count is the program's answer: a job runs to its fixpoint, reports the
supersteps it took, and the driver states the last job's count as the fact
``iterations``. Which kernel runs is a row of ``ALGORITHMS``, keyed by the
traffic file's ``algorithm``: how to run it, its plain reference, its
control and the name of its comparison. The next kernel is a row and a
reference, not a driver.

The superstep family is whatever ``auto`` resolves, the driver pins none.
The plan is built by the warm-up job and cached by the program per graph,
so the timed jobs hold processing only, as LDBC Graphalytics separates
loading from processing time. The warm-up job alone carries a
``MetricsSink``.
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple

import numpy as np

import generators
import references
import references_wcc

# A superstep is quiet when it moves the label of under this share of the
# vertices that have an edge: a frontier would not run it at full width.
QUIET_SHARE = 0.01


class Algorithm(NamedTuple):
    run: Callable        # (graph, sink) -> (answer on the device, supersteps)
    reference: Callable  # (u, v, num_vertices) -> the answer, exact
    control: Callable    # (u, v, num_vertices) -> an answer with one guarantee broken
    check: str           # the name of the comparison
    classes: str         # what the distinct values of the answer are called


def _run_wcc(graph, sink):
    import graphmine_tpu as gm

    return gm.connected_components(
        graph, plan="auto", return_iterations=True, sink=sink)


def _wcc_reference(u, v, num_vertices: int):
    return references.canonical_partition(references.scipy_cc(u, v, num_vertices))


def _wcc_control(u, v, num_vertices: int):
    """The fixpoint guarantee broken: an engine that stops after two
    supersteps."""
    return references_wcc.numpy_min_label(u, v, num_vertices, max_supersteps=2)[0]


ALGORITHMS = {
    "wcc": Algorithm(_run_wcc, _wcc_reference, _wcc_control,
                     "wcc_label_mismatches", "components"),
}


def _timed(algorithm: Algorithm, graph, sink=None):
    """One job, ended by a sync on both of its results."""
    t0 = time.perf_counter()
    answer, supersteps = algorithm.run(graph, sink)
    answer.block_until_ready()
    supersteps = int(supersteps)
    return answer, supersteps, time.perf_counter() - t0


def setup(ctx) -> dict:
    name = ctx["traffic"]["algorithm"]
    if name not in ALGORITHMS:
        raise ValueError(f"graph_kernel_job has no algorithm {name!r}; "
                         f"have {sorted(ALGORITHMS)}")
    algorithm = ALGORITHMS[name]

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
    touched = np.zeros(num_vertices, bool)
    touched[u] = True
    touched[v] = True
    vertices_with_edge = int(touched.sum())
    sink = MetricsSink()
    _, supersteps, warm_s = _timed(algorithm, graph, sink)  # builds the plan too
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
        "setup_records": [
            {"phase": "build_graph", "seconds": build_s, "scope": "setup"},
            {"phase": "plan_build", "seconds": plan_s, "scope": "setup"},
        ],
    }
    if changed is not None:
        state["fixpoint_facts"] = {
            "fixpoint_supersteps": len(changed),
            "quiet_passes": sum(c < QUIET_SHARE * vertices_with_edge
                                for c in changed),
        }
    ctx["say"](vertices=num_vertices, vertices_with_edge=vertices_with_edge,
               edges=len(u), algorithm=name, family=family, generate_s=gen_s,
               build_graph_s=build_s, plan_build_s=plan_s, warmup_job_s=warm_s,
               supersteps=supersteps, changed=changed)
    return state


def job(state, index: int) -> dict:
    state["answer"], supersteps, seconds = _timed(state["algorithm"], state["graph"])
    state["iterations"] = supersteps
    return {"seconds": seconds, "supersteps": supersteps}


def end_to_end(state, jobs, window_s: float) -> dict:
    # Graphalytics' EVPS, over all the jobs and all the time of the window
    return {"evps": state["edges_plus_vertices"] * len(jobs) / window_s}


def records(state, jobs) -> list:
    return state["setup_records"] + [
        {"phase": "job", "seconds": j["seconds"], "scope": "job", "job": i}
        for i, j in enumerate(jobs)
    ]


def facts(state) -> dict:
    return dict(state["fixpoint_facts"],
                num_vertices=state["num_vertices"],
                num_messages=2 * len(state["u"]),
                iterations=state["iterations"])


# -- correctness --------------------------------------------------------------


def check(state, jobs, control: bool) -> list:
    """Every value the window's last job produced against the plain
    reference's, over the whole vertex space at the timed size. The answer
    is integers and stated exact: the limit is 0. A job that reports no
    superstep, or jobs of one window that disagree on how many the same
    graph took, fail too: the count is what two per-layer metrics divide by."""
    algorithm = state["algorithm"]
    u, v, n = state["u"], state["v"], state["num_vertices"]
    want = algorithm.reference(u, v, n)
    got = algorithm.control(u, v, n) if control else np.asarray(state["answer"])
    bad = int((got != want).sum())
    counts = [j["supersteps"] for j in jobs]
    odd = sum(c <= 0 or c != counts[-1] for c in counts)
    return [
        {"check": algorithm.check, "value": bad, "limit": 0, "ok": bad == 0,
         "compared": n, algorithm.classes: int(len(np.unique(want)))},
        {"check": "jobs_that_disagree_on_supersteps", "value": odd, "limit": 0,
         "ok": odd == 0, "supersteps": counts[-1], "jobs": len(counts)},
    ]
