"""One job of a kernel cell (a carried-rows CDLP job, or an algorithm file's run) under a profiler capture,
reduced by scope and by program (ISSUE 36, PERF.md §5).

    python _proof/profile_job.py cdlp-g500-24 [out.json]
    python _proof/profile_job.py cdlp-g500-25-x4 [out.json]   # a mesh cell: on its four chips

Set-up is the cell's own driver's (draw, build_graph, the warm-up job that
builds plan and index and compiles or loads the programs); then one job
through the public entry with a sink, inside a capture. The capture does
NOT put op metadata into the compile-cache key (``maybe_profile`` does, and
every program would compile anew): run it in a call whose cache this
checkout alone has filled. Prints JSON lines; the last holds the scopes,
the scopes of each program (``by_program_scope``: ``jit__tail_table_class:credit``),
and the rows' passes by width class (``by_width``: ``row_mode/w33``,
``dirty_rows/w4096``)."""
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def say(**record):
    print(json.dumps(record, default=str), flush=True)


def main():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell = run.load_cell(ROOT, sys.argv[1])
    driver = run.load_module("drivers", cell["traffic"]["driver"])

    import jax

    import graphmine_tpu as gm
    from graphmine_tpu.compile_cache import enable_compile_cache
    from graphmine_tpu.obs import devtrace
    from graphmine_tpu.obs.schema import DEVICE_SCOPES
    from graphmine_tpu.pipeline.metrics import MetricsSink

    say(cache_dir=enable_compile_cache(), device=str(jax.devices()[0]))
    scratch = tempfile.mkdtemp(prefix="prof_")
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "sizes": cell["config"]["rehearsal"] if os.environ.get("REHEARSE") else cell["config"],
           "seed": 1, "scratch": scratch,
           "chips": cell["chips"], "say": say, "load_module": run.load_module}
    state = driver.setup(ctx)
    graph, iters = state["graph"], cell["traffic"].get("iterations")  # an algorithm file may state none
    # a mesh cell's driver keeps its mesh: the job goes through the same entry
    on_mesh = {"mesh": state["mesh"]} if "mesh" in state else {}
    if "algorithm" in state:  # an algorithm-file driver (ISSUE 41): the job is the file's run()
        job = lambda sink=None: state["algorithm"].run(graph, sink, cell["traffic"])[0]
    else:
        job = lambda sink=None: gm.label_propagation(
            graph, max_iter=iters, plan="auto", sink=sink, **on_mesh)
    device = jax.devices()[0]
    memory = lambda: [d.memory_stats() for d in state.get("devices", [device])]

    # an untraced job first, with a sink: the record and the job's seconds
    sink = MetricsSink()
    t0 = time.perf_counter()
    job(sink).block_until_ready()
    say(plain_job_s=time.perf_counter() - t0,
        records=[{k: v for k, v in r.items() if k not in ("t", "cost", "thresholds")}
                 for r in sink.records
                 if r["phase"] in ("superstep_delta", "impl_selected", "device_residency",
                                   "plan_build", "partition", "superstep_timing")],
        memory=memory())

    trace_dir = os.path.join(scratch, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    job().block_until_ready()
    job_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    planes = devtrace.read_xplane(devtrace.newest_xplane(trace_dir), "run")
    reduced = devtrace.reduce_capture(*planes, DEVICE_SCOPES)
    by_scope, by_program, by_program_scope = {}, {}, {}
    for row in reduced["scopes"]:
        by_scope[row["scope"]] = by_scope.get(row["scope"], 0.0) + row["device_seconds"]
        name = row["module"]
        by_program[name] = by_program.get(name, 0.0) + row["device_seconds"]
        # one scope name in two programs (LCC's `credit` in both stages) read apart
        both = f"{name}:{row['scope']}"
        by_program_scope[both] = by_program_scope.get(both, 0.0) + row["device_seconds"]
    # the third scope level: the rows' passes by width class (`w<width>`)
    passes = frozenset(("row_gather", "row_mode", "row_sum", "dirty_rows"))
    by_width = {}
    for row in devtrace.reduce_capture(
            *planes, passes | {f"w{w}" for w in range(1, 1 << 15)})["scopes"]:
        if row["scope"].split("/")[0] in passes:
            by_width[row["scope"]] = by_width.get(row["scope"], 0.0) + row["device_seconds"]
    out = {"cell": sys.argv[1], "job_s": job_s, "busy_s": reduced["busy_seconds"],
           "idle_s": job_s - reduced["busy_seconds"],
           "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
           "by_program": dict(sorted(by_program.items(), key=lambda kv: -kv[1])),
           "by_program_scope": dict(sorted(by_program_scope.items(), key=lambda kv: -kv[1])),
           "by_width": dict(sorted(by_width.items(), key=lambda kv: -kv[1])),
           "memory": memory()}
    say(**out)
    if len(sys.argv) > 2:
        os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])), exist_ok=True)
        with open(sys.argv[2], "w") as f:
            json.dump(dict(out, rows=reduced["scopes"]), f)


if __name__ == "__main__":
    main()
