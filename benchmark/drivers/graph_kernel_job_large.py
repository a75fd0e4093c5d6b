"""Driver ``graph_kernel_job_large``: ``graph_kernel_job``'s contract for a
graph that fills one chip. One job is one whole run of a graph kernel
through the program's public entry point, on a graph built once in set-up
and handed to the program device-resident.

Which kernel runs is a file, ``benchmark/algorithms/<name>.py``, named by
the traffic file's ``algorithm`` and loaded by path, with the four
functions ``graph_kernel_job`` states (``run``, ``reference``, ``control``,
``compare``). This driver names no algorithm. A file may also state
``check_program()``: what it asks of the program, checked before any input
is made, so that a program that cannot run the cell ends in seconds with a
message (``SystemExit``) and not after a draw of 260 M edges.

What differs from ``graph_kernel_job`` is the host side, which a 40 GiB
machine bounds before the chip does, as ``kernel_job_large``'s note says:
the draw and the reference run in one-heap children on every core
(``kernel_job_mesh._on_one_heap`` for the draw; the reference's child is
this file, since the algorithm's own ``reference`` and ``control`` run
there, on the edges the draw left in the scratch directory); set-up ends by
handing the compiler's freed heap back (``malloc_trim``), or the
reference's child would not fit after the window. And the warm-up job's
program records are handed on: ``facts()`` states what the device keeps for
this graph between jobs (``resident_bytes``, from ``device_residency``)
and how the plan's rows are padded (``padded_slots_per_message``, from
``plan_build``), each left out where the program writes no such record;
``records()`` hands the records themselves on, whole, under ``scope:
"warmup"`` (``benchmark/handover.py``).
The memory lines of the log, those facts and the trim are
``kernel_job_large``'s own functions, loaded by path.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_ALGORITHMS = os.path.join(os.path.dirname(_HERE), "algorithms")


def _by_path(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the host side of a graph this size has one owner: the one-heap children
# and their environment (kernel_job_mesh, through it), the allocator's and
# the host's memory for the log, the facts read from the warm-up job's
# records, and the trim of the heap the compiler freed
_large = _by_path("bench_drivers_kernel_job_large",
                  os.path.join(_HERE, "kernel_job_large.py"))
_mesh_driver, handover = _large._mesh_driver, _large.handover
_memory, _program_facts = _large._memory, _large._program_facts


def _algorithm(name: str):
    """``algorithms/<name>.py`` by path: here and in the reference's child."""
    path = os.path.join(_ALGORITHMS, name + ".py")
    if not os.path.exists(path):
        have = sorted(f[:-3] for f in os.listdir(_ALGORITHMS) if f.endswith(".py"))
        raise ValueError(f"graph_kernel_job_large has no algorithm {name!r}; have {have}")
    return _by_path(f"bench_algorithms_{name}", path)


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
    algorithm = _algorithm(name)  # before any input is made
    if hasattr(algorithm, "check_program"):
        algorithm.check_program()  # SystemExit, in seconds

    import jax

    import graphmine_tpu as gm
    from graphmine_tpu.pipeline.metrics import MetricsSink

    device = jax.devices()[0]
    generator_args = ctx["sizes"]["generator_args"]
    scratch = ctx["scratch"]
    num_vertices = 1 << generator_args["scale"]
    gen_s = _mesh_driver._on_one_heap(
        "generate", scratch, generator=ctx["config"]["generator"],
        generator_args=generator_args, dataset_seed=ctx["config"]["dataset_seed"])
    t0 = time.perf_counter()
    u = np.load(os.path.join(scratch, "u.npy"))  # the files stay: the
    v = np.load(os.path.join(scratch, "v.npy"))  # reference reads them
    gen_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    graph = gm.build_graph(u, v, num_vertices=num_vertices)
    jax.block_until_ready(graph)  # the transfer is build_graph's, not the plan's
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    touched = np.zeros(num_vertices, bool)
    touched[u] = True
    touched[v] = True
    num_edges, with_edge = len(u), int(touched.sum())
    del u, v, touched  # the reference reads its own copy, in its own process
    count_s = time.perf_counter() - t0
    ctx["say"](vertices=num_vertices, vertices_with_edge=with_edge, edges=num_edges,
               algorithm=name, generate_s=gen_s, build_graph_s=build_s,
               count_vertices_s=count_s, memory=_memory(device))
    sink = MetricsSink()
    # builds or finds the plan, compiles or loads the program
    _, supersteps, warm_s = _timed(algorithm, graph, traffic, sink)
    after_warmup = _memory(device)
    _large._hand_back_the_freed_heap()  # or the reference's child would not fit
    by_phase = {r["phase"]: r for r in sink.records}
    plan_s = sum(r.get("seconds", 0.0) for r in sink.records
                 if r["phase"] == "plan_build")
    selected = by_phase.get("impl_selected", {})
    ctx["say"](family=selected.get("impl"), plan_build_s=plan_s,
               warmup_job_s=warm_s, supersteps=supersteps,
               memory_after_warmup=after_warmup, memory=_memory(device),
               device_residency={k: v for k, v in
                                 by_phase.get("device_residency", {}).items()
                                 if k not in ("phase", "t")},
               superstep_delta={k: v for k, v in
                                by_phase.get("superstep_delta", {}).items()
                                if k not in ("phase", "t")},
               superstep_timing={k: v for k, v in
                                 by_phase.get("superstep_timing", {}).items()
                                 if k in ("op", "family", "window", "seconds")})
    return {
        "ctx": ctx, "algorithm": algorithm, "num_edges": num_edges,
        "num_vertices": num_vertices, "graph": graph, "device": device,
        "iterations": supersteps, "answer": None, "reference": None,
        "edges_plus_vertices": with_edge + num_edges,
        "program_facts": _program_facts(sink.records),
        "setup_records": handover.stages(
            build_graph=build_s, plan_build=plan_s, generate=gen_s,
            count_vertices=count_s, warmup_job=warm_s),
        "warmup_records": handover.warmup(sink.records),  # the program's, whole
    }


def job(state, index: int) -> dict:
    state["answer"], supersteps, seconds = _timed(
        state["algorithm"], state["graph"], state["ctx"]["traffic"])
    state["iterations"] = supersteps
    return {"seconds": seconds, "supersteps": supersteps}


end_to_end = _mesh_driver.end_to_end  # Graphalytics' EVPS over the window
records = _mesh_driver.records


def facts(state) -> dict:
    return dict(state["program_facts"], num_vertices=state["num_vertices"],
                num_messages=2 * state["num_edges"],
                iterations=state["iterations"])


# -- correctness --------------------------------------------------------------


def _plain_answer(state, which: str) -> np.ndarray:
    """The algorithm's own ``reference`` or ``control`` on the drawn edges,
    in a one-heap child of its own (this file's ``__main__``)."""
    ctx = state["ctx"]
    out = os.path.join(ctx["scratch"], which + ".npy")
    args = {"algorithm": ctx["traffic"]["algorithm"], "which": which,
            "num_vertices": state["num_vertices"], "traffic": ctx["traffic"],
            "out": out}
    subprocess.run([sys.executable, os.path.abspath(__file__), ctx["scratch"],
                    json.dumps(args)],
                   env={**os.environ, **_mesh_driver._ONE_HEAP}, check=True)
    return np.load(out)


def check(state, jobs, control: bool) -> list:
    """``graph_kernel_job``'s comparison with the plain answers made in
    one-heap children: what the window's last job produced against the
    algorithm's reference, by the algorithm's own ``compare``, over the
    whole vertex space at the timed size; jobs of one window that disagree
    on their supersteps fail too."""
    state["ctx"]["say"](memory=_memory(state["device"]))
    if state["reference"] is None:
        state["reference"] = _plain_answer(state, "reference")
    got = (_plain_answer(state, "control") if control
           else np.asarray(state["answer"]))
    counts = [j["supersteps"] for j in jobs]
    odd = sum(c <= 0 or c != counts[-1] for c in counts)
    return state["algorithm"].compare(got, state["reference"]) + [
        {"check": "jobs_that_disagree_on_supersteps", "value": odd, "limit": 0,
         "ok": odd == 0, "supersteps": counts[-1], "jobs": len(counts)},
    ]


if __name__ == "__main__":  # the reference's child: no program, no chip
    sys.path.insert(0, os.path.dirname(_HERE))  # references*.py, by name
    _scratch, _args = sys.argv[1], json.loads(sys.argv[2])
    _plain = getattr(_algorithm(_args["algorithm"]), _args["which"])
    np.save(_args["out"], _plain(
        np.load(os.path.join(_scratch, "u.npy")),
        np.load(os.path.join(_scratch, "v.npy")),
        _args["num_vertices"], _args["traffic"]))
