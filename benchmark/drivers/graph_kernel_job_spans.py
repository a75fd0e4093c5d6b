"""Driver ``graph_kernel_job_spans``: ``graph_kernel_job``'s contract for a
kernel whose job has stages worth a per-layer metric each. One job is one
whole run of ``benchmark/algorithms/<algorithm>.py`` through the program's
public entry point, on a graph built once in set-up; set-up, ``evps``, the
comparison and the facts are ``graph_kernel_job``'s own functions, loaded by
path. This driver names no algorithm. It differs in four things:

- a file that states ``check_program()`` has it called before any input is
  made, so that a program that cannot run the cell ends in seconds with a
  message (``SystemExit``) and not in the middle of a job;
- every timed job carries a fresh ``MetricsSink`` with a tracer, and the
  stage spans the program closed in it are handed on as records of scope
  ``job`` beside the ``job`` record (``phase: span``, the span's ``name``
  and ``seconds``), for the reader ``phase_seconds``, before the warm-up
  job's records, which ``graph_kernel_job`` hands on. A stage span ends in a
  sync on its own outputs: microseconds of a job of seconds;
- a file that states ``facts(records)`` is handed the warm-up job's records,
  and what it returns joins ``facts()``;
- the plain answers (the algorithm's ``reference`` and ``control``) are made
  in a one-heap child, which is ``graph_kernel_job_large``'s own (its
  ``_plain_answer``, on the edges written to the scratch directory). A
  reference that closes 7.2e9 pairs in blocks maps and unmaps half a
  terabyte of NumPy temporaries, which the chip tool's machine counts until
  it has taken them back (``kernel_job_mesh._on_one_heap``'s note): in the
  process that holds the chip the first run of this cell was ended at the
  machine's 40 GiB with 11.7 GB resident (PERF.md, PR 46).
"""

from __future__ import annotations

import importlib.util
import os
import types

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _by_path(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_" + name, os.path.join(_HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_base = _by_path("graph_kernel_job")
_large = _by_path("graph_kernel_job_large")  # the plain answers' one-heap child

end_to_end, check = _base.end_to_end, _base.check


def setup(ctx) -> dict:
    warmup_sinks = []

    def load(kind: str, name: str):
        """The algorithm's module, checked against the program first; its
        ``run`` notes the sink ``graph_kernel_job`` gives the warm-up job."""
        algorithm = ctx["load_module"](kind, name)
        if hasattr(algorithm, "check_program"):
            algorithm.check_program()  # SystemExit, in seconds

        def run(graph, sink, traffic):
            if sink is not None:
                warmup_sinks.append(sink)
            return algorithm.run(graph, sink, traffic)

        made = {}  # a run compares with the reference twice under --control

        def in_a_child(which: str):
            def plain(u, v, num_vertices, traffic):
                for name, edges in (("u.npy", u), ("v.npy", v)):
                    path = os.path.join(ctx["scratch"], name)
                    if not os.path.exists(path):
                        np.save(path, edges)
                if which not in made:
                    made[which] = _large._plain_answer(
                        {"ctx": ctx, "num_vertices": num_vertices}, which)
                return made[which]
            return plain

        return types.SimpleNamespace(
            run=run, program_run=algorithm.run, reference=in_a_child("reference"),
            control=in_a_child("control"), compare=algorithm.compare,
            facts=getattr(algorithm, "facts", None))

    state = _base.setup(dict(ctx, load_module=load))
    state["ctx"] = ctx
    state["job_spans"] = []
    state["algorithm"].run = state["algorithm"].program_run  # the warm-up is over
    stated = state["algorithm"].facts
    state["algorithm_facts"] = (
        stated([r for s in warmup_sinks for r in s.records]) if stated else {})
    if state["algorithm_facts"]:
        ctx["say"](algorithm_facts=state["algorithm_facts"])
    return state


def job(state, index: int) -> dict:
    from graphmine_tpu.obs.spans import Tracer
    from graphmine_tpu.pipeline.metrics import MetricsSink

    sink = MetricsSink(tracer=Tracer())
    state["answer"], supersteps, seconds = _base._timed(
        state["algorithm"], state["graph"], state["ctx"]["traffic"], sink)
    state["iterations"] = supersteps
    state["job_spans"].append(
        [{"phase": "span", "name": r["name"], "seconds": r["seconds"],
          "scope": "job", "job": index}
         for r in sink.records if r.get("phase") == "span"])
    return {"seconds": seconds, "supersteps": supersteps}


def records(state, jobs) -> list:
    return _base.records(state, jobs,
                         more=[r for spans in state["job_spans"] for r in spans])


def facts(state) -> dict:
    return dict(state["algorithm_facts"], **_base.facts(state))
