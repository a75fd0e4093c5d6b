"""Driver ``kernel_job_mesh``: ``kernel_job``'s contract on a mesh. One job
is one whole run of a graph algorithm through the program's public entry
point, on a graph that is built once in set-up, stays on the host, and is
partitioned over the cell's chips by the program.

Today's one algorithm is ``cdlp`` (``label_propagation(graph, max_iter=N,
plan="auto", mesh=mesh)``): the superstep family and the exchange are
whatever ``auto`` resolves on that mesh, the driver pins none. The
partition, its plan and the placement are made by the warm-up job and
cached by the program per (graph, mesh), so the timed jobs hold processing
only, as LDBC Graphalytics separates loading from processing time. What
``records()`` hands on is ``kernel_job``'s (``benchmark/handover.py``), with
the ``partition`` seconds among set-up's; ``facts()`` states the ``exchange``
record's counts and the carried-rows job's facts.
"""

from __future__ import annotations

import inspect
import json
import os
import subprocess
import sys
import time

import numpy as np

_BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCHMARK not in sys.path:  # this file is also its children's __main__
    sys.path.insert(0, _BENCHMARK)
import handover  # noqa: E402

# glibc reads these at start-up only. One arena that serves every size from
# one growing heap and never gives it back: freed blocks are reused, not
# unmapped and mapped anew.
_ONE_HEAP = {"MALLOC_ARENA_MAX": "1", "MALLOC_MMAP_MAX_": "0",
             "MALLOC_TRIM_THRESHOLD_": str(1 << 40),
             "MALLOC_TOP_PAD_": str(1 << 28)}


def _on_one_heap(task: str, scratch: str, **args) -> float:
    """Run ``task`` (``_TASKS``) in an interpreter of its own that was
    started with ``_ONE_HEAP``; arrays pass as ``.npy`` files in ``scratch``.

    At 1.05 G messages every NumPy temporary of the generator and of the
    reference is past glibc's 32 MiB mmap threshold, so each is mapped and
    unmapped on its own: 240 GB of that in the generator alone, more in the
    reference. The chip tool's machine (gVisor, ``Linux runsc``) takes
    unmapped pages back at 1-4 GB/s and counts them until it has: the run's
    memory read +1.4 GB/s through ``generators.make`` with 44 GB resident
    and was killed at the 140 GiB limit (PERF.md, PR 27). With ``_ONE_HEAP``
    the same churn holds the count at the working set and runs 17x faster
    there. The process that holds the chips must not run on one arena,
    though: the compiler's threads queue for it, and the superstep program,
    which every run compiles, takes 1,106 s there against 251.5 s (30
    cores; a run may last 1,200 s). So the two NumPy-heavy steps, which
    touch neither the program nor a chip, get a process each."""
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), task, scratch, json.dumps(args)],
        env={**os.environ, **_ONE_HEAP}, check=True)
    return time.perf_counter() - t0


def _task_generate(scratch, generator, generator_args, dataset_seed):
    import generators

    u, v = generators.make(generator, generator_args, dataset_seed)
    np.save(os.path.join(scratch, "u.npy"), u)
    np.save(os.path.join(scratch, "v.npy"), v)


def _task_reference(scratch, num_vertices, iterations, one_way, out):
    import references

    # every core the host has: at 1.05 G messages a superstep the default 8
    # workers take minutes, and the chips are held while they do
    workers = min(32, os.cpu_count() or 8)
    labels = references.threaded_lpa(
        np.load(os.path.join(scratch, "u.npy")),
        np.load(os.path.join(scratch, "v.npy")), num_vertices, iterations,
        one_way=one_way, slices=min(128, 4 * workers), workers=workers)
    np.save(os.path.join(scratch, out), labels)


_TASKS = {"generate": _task_generate, "reference": _task_reference}

def _run(state, sink=None):
    import graphmine_tpu as gm

    t0 = time.perf_counter()
    labels = gm.label_propagation(state["graph"], max_iter=state["iterations"],
                                  plan="auto", mesh=state["mesh"], sink=sink)
    labels.block_until_ready()
    return labels, time.perf_counter() - t0


def _peaks(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def setup(ctx) -> dict:
    import jax

    import graphmine_tpu as gm
    from graphmine_tpu.pipeline.metrics import MetricsSink

    traffic = ctx["traffic"]
    if traffic["algorithm"] != "cdlp":
        raise ValueError(f"kernel_job_mesh has no algorithm {traffic['algorithm']!r}")
    if "mesh" not in inspect.signature(gm.label_propagation).parameters:
        # before any input is made: a program without the mesh entry cannot
        # run this cell, and says so in seconds, not after set-up
        raise SystemExit("kernel_job_mesh: this program's label_propagation "
                         "takes no mesh=; it cannot run a cell across chips")
    devices = jax.devices()[:ctx["chips"]]  # a rehearsal may see fewer
    mesh = gm.make_mesh(devices=devices)
    generator_args = ctx["sizes"]["generator_args"]
    gen_s = _on_one_heap("generate", ctx["scratch"],
                         generator=ctx["config"]["generator"],
                         generator_args=generator_args,
                         dataset_seed=ctx["config"]["dataset_seed"])
    t0 = time.perf_counter()
    u = np.load(os.path.join(ctx["scratch"], "u.npy"))
    v = np.load(os.path.join(ctx["scratch"], "v.npy"))
    num_vertices = 1 << generator_args["scale"]
    gen_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    # the whole graph never sits on one device: the program slices the host
    # copy onto the mesh
    graph = gm.build_graph(u, v, num_vertices=num_vertices, to_device=False)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    touched = np.zeros(num_vertices, bool)
    touched[u] = True
    touched[v] = True
    num_edges, with_edge = len(u), int(touched.sum())
    del u, v, touched  # the reference reads its own copy, in its own process
    count_s = time.perf_counter() - t0
    ctx["say"](vertices=num_vertices, vertices_with_edge=with_edge, edges=num_edges,
               generate_s=gen_s, build_graph_s=build_s, count_vertices_s=count_s)
    state = {
        "ctx": ctx, "num_edges": num_edges, "num_vertices": num_vertices,
        "graph": graph, "mesh": mesh, "devices": devices,
        "iterations": traffic["iterations"], "labels": None, "reference": None,
        "edges_plus_vertices": with_edge + num_edges,
    }
    sink = MetricsSink()
    _, warm_s = _run(state, sink)  # partitions, plans and places too
    by_phase = {r["phase"]: r for r in sink.records}
    exchange = by_phase.get("exchange", {})
    state["exchange"] = {k: exchange[k] for k in (
        "bytes_per_superstep", "messages_per_shard_max",
        "messages_per_shard_mean", "padded_slots_per_shard") if k in exchange}
    state["shards"] = exchange.get("shards", 1)
    plan_s = by_phase.get("plan_build", {}).get("seconds", 0.0)
    partition_s = by_phase.get("partition", {}).get("seconds", 0.0)
    state["setup_records"] = handover.stages(
        build_graph=build_s, plan_build=plan_s, partition=partition_s,
        generate=gen_s, count_vertices=count_s, warmup_job=warm_s)
    # the warm-up job's records whole, and the carried-rows job's facts
    state["warmup_records"] = handover.warmup(sink.records)
    state["program_facts"] = handover.program_facts(sink.records)
    selected = by_phase.get("impl_selected", {})
    ctx["say"](shards=state["shards"], family=selected.get("impl"),
               reason=selected.get("reason"), partition_s=partition_s,
               plan_build_s=plan_s, warmup_job_s=warm_s,
               per_device_peak_bytes=_peaks(devices), **state["exchange"])
    return state


def job(state, index: int) -> dict:
    state["labels"], seconds = _run(state)
    return {"seconds": seconds}


def end_to_end(state, jobs, window_s: float) -> dict:
    # Graphalytics' EVPS, over all the jobs and all the time of the window
    return {"evps": state["edges_plus_vertices"] * len(jobs) / window_s}


records = handover.records


def facts(state) -> dict:
    return dict(state["program_facts"], **state["exchange"],
                num_vertices=state["num_vertices"],
                num_messages=2 * state["num_edges"],
                iterations=state["iterations"], chips=state["shards"])


# -- correctness --------------------------------------------------------------


def _reference(state, one_way: bool = False) -> np.ndarray:
    out = "control.npy" if one_way else "reference.npy"
    scratch = state["ctx"]["scratch"]
    _on_one_heap("reference", scratch, num_vertices=state["num_vertices"],
                 iterations=state["iterations"], one_way=one_way, out=out)
    return np.load(os.path.join(scratch, out))


def check(state, jobs, control: bool) -> list:
    """Every label the window's last job produced against the plain
    reference's, on the whole graph at the timed size and iteration count.
    Labels are integers and the output is stated exact: the limit is 0. The
    control is the reference with one stated guarantee broken: the graph
    taken as directed, a message flowing one way along each edge."""
    n, iters = state["num_vertices"], state["iterations"]
    if state["reference"] is None:
        state["reference"] = _reference(state)
    want = state["reference"]
    got = (_reference(state, one_way=True) if control
           else np.asarray(state["labels"]))
    bad = int((got != want).sum())
    state["ctx"]["say"](per_device_peak_bytes=_peaks(state["devices"]))
    return [{"check": f"labels_after_{iters}_supersteps_mismatches", "value": bad,
             "limit": 0, "ok": bad == 0, "compared": n,
             "communities": int(len(np.unique(want)))}]


if __name__ == "__main__":  # a child of _on_one_heap: no program, no chip
    _TASKS[sys.argv[1]](sys.argv[2], **json.loads(sys.argv[3]))
