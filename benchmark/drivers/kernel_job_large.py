"""Driver ``kernel_job_large``: ``kernel_job``'s contract for a graph that
fills one chip. One job is one whole run of a graph algorithm through the
program's public entry point, on a graph built once in set-up and handed
to the program device-resident, as ``kernel_job`` hands it.

Today's one algorithm is ``cdlp`` (``label_propagation(graph, max_iter=N,
plan="auto")`` on one device: no mesh, no pinned family; the superstep
family and whether the scan keeps its gathered rows are the program's own
answers). The plan, its slot index and the program are the warm-up job's,
cached by the program per graph, so the timed jobs hold processing only,
as LDBC Graphalytics separates loading from processing time.

What differs from ``kernel_job`` is the host side, which a 40 GiB machine
bounds before the chip does. At 521 M messages a superstep the draw and the
reference churn NumPy temporaries past glibc's mmap threshold, which the
chip tool's machine counts until it takes them back
(``kernel_job_mesh._on_one_heap``): both run there, in one-heap children
on every core, and the process that holds the chip stays on default arenas,
where the compiler's threads do not queue. The reference runs after the
window, as in every cell, and takes 20 GB at its peak; the chip's compiler
takes 20 to 28 GB for a program of this size, frees it, and glibc keeps it
(two runs ended at the machine's limit with the child beside it, PERF.md
§6, PR 33). So set-up ends by handing that heap back (``malloc_trim``:
20.2 GB resident before, 1.6 GB after, 1.2 s). And the warm-up job's
program records are handed on: ``facts()`` states what the device keeps for
this graph between jobs (``device_residency``), which scan was admitted,
how many supersteps rewrote rows instead of gathering them anew and what
one that gathers every row anew costs (``superstep_delta``), and how the
plan's rows are padded (``plan_build``): ``handover.program_facts``, which
every driver of the carried-rows job states. A program that writes no such
record states no such fact. ``records()`` hands the records themselves on
too, whole, under ``scope: "warmup"`` (``benchmark/handover.py``).

A program that sizes nothing against the device (it registers no
``device_residency`` record) is turned away before any input is made: at
this scale it builds the carried-rows program whatever the device and the
host can hold, and dies in its compile after seven minutes.
"""

from __future__ import annotations

import ctypes
import importlib.util
import inspect
import os
import time

import numpy as np

# the one-heap children, their tasks (draw, reference) and the comparison
# have one owner
_spec = importlib.util.spec_from_file_location(
    "bench_drivers_kernel_job_mesh",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernel_job_mesh.py"))
_mesh_driver = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mesh_driver)
_on_one_heap, handover = _mesh_driver._on_one_heap, _mesh_driver.handover
_program_facts = handover.program_facts  # graph_kernel_job_large takes it by this name

# what this driver asks of the program, checked before any input is made
_NEEDS = {"label_propagation": ("max_iter", "plan", "sink"),
          "build_graph": ("num_vertices",)}


def _run(state, sink=None):
    import graphmine_tpu as gm

    t0 = time.perf_counter()
    labels = gm.label_propagation(state["graph"], max_iter=state["iterations"],
                                  plan="auto", sink=sink)
    labels.block_until_ready()
    return labels, time.perf_counter() - t0


def _memory(device) -> dict:
    """The device's allocator and the host's free memory, for the run's log."""
    stats = device.memory_stats() or {}
    said = {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "peak_bytes_reserved", "bytes_limit")}
    try:
        with open("/proc/meminfo") as f:
            host = dict(line.split(":") for line in f)
        said["host_available_kb"] = int(host["MemAvailable"].split()[0])
    except (OSError, KeyError, ValueError):
        pass
    return said


def _hand_back_the_freed_heap() -> None:
    """What the chip's compiler freed stays in glibc's arenas; the
    reference's child needs the room (the module's note)."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # another C library: nothing is kept this way


def setup(ctx) -> dict:
    import jax

    import graphmine_tpu as gm
    from graphmine_tpu.obs.schema import SCHEMAS
    from graphmine_tpu.pipeline.metrics import MetricsSink

    traffic = ctx["traffic"]
    if traffic["algorithm"] != "cdlp":
        raise ValueError(f"kernel_job_large has no algorithm {traffic['algorithm']!r}")
    for name, needed in _NEEDS.items():
        # before any input is made: in seconds, not after a draw of 260 M edges
        if not hasattr(gm, name):
            raise SystemExit(f"kernel_job_large: this program has no {name}; "
                             "it cannot run this cell")
        have = inspect.signature(getattr(gm, name)).parameters
        missing = [p for p in needed if p not in have]
        if missing:
            raise SystemExit(f"kernel_job_large: this program's {name} takes no "
                             f"{missing}; it cannot run this cell")
    if "device_residency" not in SCHEMAS:
        raise SystemExit("kernel_job_large: this program registers no "
                         "device_residency record: it sizes nothing against "
                         "the device; it cannot run this cell")
    device = jax.devices()[0]
    generator_args = ctx["sizes"]["generator_args"]
    scratch = ctx["scratch"]
    num_vertices = 1 << generator_args["scale"]
    iterations = traffic["iterations"]
    gen_s = _on_one_heap("generate", scratch,
                         generator=ctx["config"]["generator"],
                         generator_args=generator_args,
                         dataset_seed=ctx["config"]["dataset_seed"])
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
               generate_s=gen_s, build_graph_s=build_s, count_vertices_s=count_s,
               memory=_memory(device))
    state = {
        "ctx": ctx, "num_edges": num_edges, "num_vertices": num_vertices,
        "graph": graph, "devices": [device], "iterations": iterations,
        "labels": None, "reference": None,
        "edges_plus_vertices": with_edge + num_edges,
    }
    sink = MetricsSink()
    _, warm_s = _run(state, sink)  # builds the plan and its index, compiles or loads
    after_warmup = _memory(device)
    _hand_back_the_freed_heap()
    by_phase = {r["phase"]: r for r in sink.records}
    plan_s = sum(r.get("seconds", 0.0) for r in sink.records
                 if r["phase"] == "plan_build")
    state["program_facts"] = _program_facts(sink.records)
    state["warmup_records"] = handover.warmup(sink.records)  # the program's, whole
    state["setup_records"] = handover.stages(
        build_graph=build_s, plan_build=plan_s, generate=gen_s,
        count_vertices=count_s, warmup_job=warm_s)
    selected = by_phase.get("impl_selected", {})
    ctx["say"](family=selected.get("impl"), scan=selected.get("scan"),
               scan_reason=selected.get("scan_reason"), plan_build_s=plan_s,
               warmup_job_s=warm_s, memory_after_warmup=after_warmup,
               memory=_memory(device),
               device_residency={k: v for k, v in
                                 by_phase.get("device_residency", {}).items()
                                 if k not in ("phase", "t", "reason")},
               superstep_delta={k: v for k, v in
                                by_phase.get("superstep_delta", {}).items()
                                if k not in ("phase", "t")})
    return state


def job(state, index: int) -> dict:
    state["labels"], seconds = _run(state)
    return {"seconds": seconds}


end_to_end = _mesh_driver.end_to_end  # Graphalytics' EVPS over the window
records = _mesh_driver.records


def facts(state) -> dict:
    return dict(state["program_facts"], num_vertices=state["num_vertices"],
                num_messages=2 * state["num_edges"],
                iterations=state["iterations"])


def check(state, jobs, control: bool) -> list:
    """``kernel_job_mesh``'s comparison: every label of the window's last
    job against ``threaded_lpa``'s in a one-heap child, limit 0; the control
    is the reference with the graph taken as directed."""
    state["ctx"]["say"](memory=_memory(state["devices"][0]))
    return _mesh_driver.check(state, jobs, control)
