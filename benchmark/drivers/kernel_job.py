"""Driver ``kernel_job``: one job is one whole run of a graph algorithm
through the program's public entry point, on a graph built once in set-up.

Today's one algorithm is ``cdlp`` (``label_propagation(graph, max_iter=N,
plan="auto")``): the superstep family is whatever ``auto`` resolves, the
driver pins none. The plan is built by the warm-up job and cached by the
program per graph, so the timed jobs hold processing only, as LDBC
Graphalytics separates loading from processing time.

The warm-up job alone carries a ``MetricsSink``. ``records()`` hands on
set-up's stages by the harness's clock, a ``job`` record a timed job, and
every record the program wrote into that sink, as it wrote it, under ``scope:
"warmup"`` (``benchmark/handover.py``, every driver's); ``facts()`` states the
carried-rows job's facts from the same records (``handover.program_facts``).
"""

from __future__ import annotations

import time

import numpy as np

import generators
import handover
import references


def _run(graph, iterations: int, sink=None):
    import graphmine_tpu as gm

    t0 = time.perf_counter()
    labels = gm.label_propagation(graph, max_iter=iterations, plan="auto", sink=sink)
    labels.block_until_ready()
    return labels, time.perf_counter() - t0


def setup(ctx) -> dict:
    import graphmine_tpu as gm
    from graphmine_tpu.pipeline.metrics import MetricsSink

    traffic = ctx["traffic"]
    if traffic["algorithm"] != "cdlp":
        raise ValueError(f"kernel_job has no algorithm {traffic['algorithm']!r}")
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
    count_s = time.perf_counter() - t0
    sink = MetricsSink()
    _, warm_s = _run(graph, traffic["iterations"], sink)  # builds the plan too
    plan_s = sum(r.get("seconds", 0.0) for r in sink.records
                 if r.get("phase") == "plan_build")
    family = [r.get("impl") for r in sink.records
              if r.get("phase") == "impl_selected"]
    state = {
        "ctx": ctx, "u": u, "v": v, "num_vertices": num_vertices, "graph": graph,
        "iterations": traffic["iterations"], "labels": None,
        "edges_plus_vertices": int(touched.sum()) + len(u),
        "setup_records": handover.stages(
            build_graph=build_s, plan_build=plan_s, generate=gen_s,
            count_vertices=count_s, warmup_job=warm_s),
        # the warm-up job's records whole, and the carried-rows job's facts
        "warmup_records": handover.warmup(sink.records),
        "program_facts": handover.program_facts(sink.records),
    }
    ctx["say"](vertices=num_vertices, vertices_with_edge=int(touched.sum()),
               edges=len(u), family=family, generate_s=gen_s, build_graph_s=build_s,
               count_vertices_s=count_s, plan_build_s=plan_s, warmup_job_s=warm_s)
    return state


def job(state, index: int) -> dict:
    state["labels"], seconds = _run(state["graph"], state["iterations"])
    return {"seconds": seconds}


def end_to_end(state, jobs, window_s: float) -> dict:
    # Graphalytics' EVPS, over all the jobs and all the time of the window
    return {"evps": state["edges_plus_vertices"] * len(jobs) / window_s}


records = handover.records


def facts(state) -> dict:
    return dict(state["program_facts"], num_vertices=state["num_vertices"],
                num_messages=2 * len(state["u"]),
                iterations=state["iterations"])


# -- correctness --------------------------------------------------------------


def check(state, jobs, control: bool) -> list:
    """Every label the window's last job produced against the plain
    reference's, on the whole graph at the timed size and iteration count.
    Labels are integers and the output is stated exact: the limit is 0. The
    control is the reference with one stated guarantee broken: the graph
    taken as directed, a message flowing one way along each edge."""
    u, v, n, iters = state["u"], state["v"], state["num_vertices"], state["iterations"]
    want = references.threaded_lpa(u, v, n, iters)
    got = (references.threaded_lpa(u, v, n, iters, one_way=True) if control
           else np.asarray(state["labels"]))
    bad = int((got != want).sum())
    return [{"check": f"labels_after_{iters}_supersteps_mismatches", "value": bad,
             "limit": 0, "ok": bad == 0, "compared": n,
             "communities": int(len(np.unique(want)))}]
