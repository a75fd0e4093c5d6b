"""ISSUE 43: what a sparse superstep costs with the dirty reduce up to each place
of ``delta_rungs`` (-1: never, the parent's job), one process, the cell's own set-up.

    python _proof/dirty_place.py cdlp-g500-22 [-1 0 1]

The constant ``ops/superstep_policy.DIRTY_REDUCE_TOP_PLACE`` is set from these
seconds. Prints one JSON line a place: the job's seconds (a second job, programs
loaded) and the ``superstep_delta`` record's per-superstep fields."""
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
    places = [int(p) for p in sys.argv[2:]] or [-1, 0, 1]
    driver = run.load_module("drivers", cell["traffic"]["driver"])

    import jax

    import graphmine_tpu as gm
    from graphmine_tpu.compile_cache import enable_compile_cache
    from graphmine_tpu.ops import superstep_policy
    from graphmine_tpu.pipeline.metrics import MetricsSink

    say(cache_dir=enable_compile_cache(), device=str(jax.devices()[0]))
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "sizes": cell["config"]["rehearsal"] if os.environ.get("REHEARSE") else cell["config"],
           "seed": 1, "scratch": tempfile.mkdtemp(prefix="place_"),
           "chips": cell["chips"], "say": say, "load_module": run.load_module}
    state = driver.setup(ctx)
    graph, iters = state["graph"], cell["traffic"]["iterations"]
    want = None
    for place in places:
        superstep_policy.DIRTY_REDUCE_TOP_PLACE = place
        for turn in ("first", "second"):  # the first job of a place loads or compiles
            sink = MetricsSink()
            t0 = time.perf_counter()
            labels = gm.label_propagation(graph, max_iter=iters, plan="auto", sink=sink)
            labels.block_until_ready()
            secs = time.perf_counter() - t0
        (delta,) = [r for r in sink.records if r["phase"] == "superstep_delta"]
        want = labels if want is None else want
        say(place=place, job_s=secs, equal=bool((labels == want).all()),
            **{k: delta[k] for k in ("branch", "reduce", "dirty_rows", "dirty_slots",
                                     "changed_messages", "seconds")},
            memory=jax.devices()[0].memory_stats())


if __name__ == "__main__":
    main()
