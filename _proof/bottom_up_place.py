"""What a bottom-up place costs when it reads the graph's own message CSR
(ISSUE 53, PERF.md §6): the terms of a place by program, and the loop over
fixed chunks of places against a static cap, on a cell's own graph before
the level that turns.

    python _proof/bottom_up_place.py bfs-g500-24 [levels before the turn = 3 [terms]]

Draws the cell's graph as its driver does, takes the search ``levels``
levels down with the ``while_loop`` (no plan, no rows), compacts the
unreached vertices once, and times (a), with ``terms``, four programs at a
cap fitted to U, each holding one more term of a place (the spreads, the
read of ``msg_send`` along the spans, the neighbours' depths, the
scatter-min), the read also with its places issued in transposed order and
with the sorted hint; (b) the loop form the job runs, by chunk and by the
stride of its read's issues, on that U and on a U of a few thousand edges,
and once under a capture, by scope. Prints JSON lines."""
import json
import os
import sys
import tempfile
import time
from functools import partial

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
    levels = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    driver = run.load_module("drivers", cell["traffic"]["driver"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    import graphmine_tpu as gm
    from graphmine_tpu.compile_cache import enable_compile_cache
    bm = importlib.import_module("graphmine_tpu.ops.bucketed_mode")  # ops exports a function of that name

    say(cache_dir=enable_compile_cache(), device=str(jax.devices()[0]))
    config = cell["config"]
    sizes = config["rehearsal"] if os.environ.get("REHEARSE") else config
    scratch = tempfile.mkdtemp(prefix="place_")
    args = sizes["generator_args"]
    v = 1 << args["scale"]
    driver._mesh_driver._on_one_heap(
        "generate", scratch, generator=config["generator"], generator_args=args,
        dataset_seed=config["dataset_seed"])
    graph = gm.build_graph(np.load(os.path.join(scratch, "u.npy")),
                           np.load(os.path.join(scratch, "v.npy")), num_vertices=v)
    jax.block_until_ready(graph)
    sources = jnp.argmax(graph.degrees() > 0)[None]
    t0 = time.perf_counter()
    want = gm.bfs_distances(graph, sources, direction="both", max_depth=levels + 1, plan=None)
    depth = jnp.where(want > levels, bm._SENTINEL, want)  # as the level before left them
    depth.block_until_ready()
    msg_ptr, msg_send = graph.msg_ptr, graph.msg_send
    m = int(msg_send.shape[0])
    deg = msg_ptr[1:] - msg_ptr[:-1]
    unreached = (depth == bm._SENTINEL) & (deg > 0)
    u = int(jnp.sum(jnp.where(unreached, deg, 0)))
    say(loop_levels_s=time.perf_counter() - t0, vertices=v, messages=m, unreached_edges=u,
        unreached_vertices=int(unreached.sum()),
        next_level_reaches=int((want != depth).sum()), memory=jax.devices()[0].memory_stats())

    def timed(name, fn, *a, reps=3, **more):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        first = time.perf_counter() - t0
        runs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a))
            runs.append(time.perf_counter() - t0)
        say(program=name, first_s=round(first, 3), seconds=[round(s, 4) for s in runs], **more)
        return out, min(runs)

    compact = jax.jit(lambda depth, ptr: bm._compact_spans(
        (depth == bm._SENTINEL) & (ptr[1:] > ptr[:-1]), ptr, ptr[1:] - ptr[:-1]))
    (owner, start, count), compact_s = timed("compact", compact, depth, msg_ptr)

    if "terms" in sys.argv[3:]:
        # (a) the terms, at a cap fitted to U (a multiple of 2^20, so of 1,024)
        cap = -(-u // (1 << 20)) << 20
        senders = min(cap, v)

        @partial(jax.jit, static_argnames=("upto", "order", "is_sorted"))
        def static(depth, owner, start, count, msg_send, upto, order="spans", is_sorted=False):
            owner, start, count = (x[:senders] for x in (owner, start, count))
            place, source, spread, end = bm._expand_spans(start, count, cap)
            vertex = jnp.where(place < end[-1], spread(owner), v)
            source = jnp.clip(source, 0, m - 1)
            if order == "transposed":  # neighbouring issues lie cap / 1,024 places apart
                source, vertex = (x.reshape(1024, -1).T.reshape(-1) for x in (source, vertex))
            if upto == "spreads":
                return jnp.sum(source) + jnp.sum(vertex)
            nb = msg_send.at[source].get(indices_are_sorted=is_sorted, mode="promise_in_bounds")
            if upto == "read":
                return jnp.sum(nb) + jnp.sum(vertex)
            near = depth[nb]
            if upto == "depths":
                return jnp.sum(near) + jnp.sum(vertex)
            # ascending in span order: the scatter then compiles with no sort of its indices
            return depth.at[vertex].min(
                bm._one_past(near), indices_are_sorted=order == "spans", mode="drop")

        spans = (depth, owner, start, count, msg_send)
        seconds, before = {}, 0.0
        for upto in ("spreads", "read", "depths", "scatter"):
            out, s = timed(f"static:{upto}", partial(static, upto=upto), *spans, cap=cap)
            seconds[upto], before = s - before, s
        say(cap=cap, ns_a_place_by_term={k: round(1e9 * s / cap, 2) for k, s in seconds.items()},
            static_level_s=before, equals_the_loop=bool((out == want).all()))
        for name, more in (("read:sorted_hint", dict(upto="read", is_sorted=True)),
                           ("read:transposed", dict(upto="read", order="transposed"))):
            got, s = timed(f"static:{name}", partial(static, **more), *spans,
                           ns_a_place_of="whole program")
            say(program=f"static:{name}", ns_a_place=round(1e9 * s / cap, 2))

    # (b) the loop form: one program, the trip count read from U on the device;
    # `stride` is the places between neighbouring issues of the read of msg_send
    spans = (depth, owner, start, count, msg_send)
    def jitted(chunk, stride):  # the stride is the module's constant, read at the trace
        def level(*arrays):
            bm._BOTTOM_UP_ISSUE_STRIDE = stride
            return bm.bfs_level_bottom_up(*arrays, chunk)
        return jax.jit(level)

    few = jnp.where(unreached & (jnp.arange(v) % 1400 != 0), 0, depth)  # a late level's U
    few_spans = compact(few, msg_ptr)
    forms = [(1 << 20, 1), (1 << 20, 16), (1 << 20, 128), (1 << 20, 1024), (1 << 20, 8192),
             (1 << 20, 65536), (1 << 22, 2048), (1 << 22, 65536), (1 << 19, 1024)]
    for chunk, stride in forms:
        form = dict(chunk=chunk, stride=stride)
        (got, trips), s = timed(f"loop:{chunk}:{stride}", jitted(**form), *spans)
        say(**form, trips=int(trips), level_s=s, ns_a_place=round(1e9 * s / u, 2),
            equals_the_loop=bool((got == want).all()))
        if stride == 1024:
            _, s = timed(f"loop:{chunk}:{stride}:few", jitted(**form), few, *few_spans, msg_send)
            say(**form, few_edges=int(few_spans[2].sum()), level_s=s)

    # by scope: one level of the form the job runs, under a capture
    from graphmine_tpu.obs import devtrace
    from graphmine_tpu.obs.schema import DEVICE_SCOPES

    trace_dir = os.path.join(scratch, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    jax.block_until_ready(jitted(chunk=1 << 19, stride=1024)(*spans))
    jax.profiler.stop_trace()
    reduced = devtrace.reduce_capture(
        *devtrace.read_xplane(devtrace.newest_xplane(trace_dir), "run"), DEVICE_SCOPES)
    by_scope = {}
    for row in reduced["scopes"]:
        by_scope[row["scope"]] = by_scope.get(row["scope"], 0.0) + row["device_seconds"]
    say(by_scope=dict(sorted(by_scope.items(), key=lambda kv: -kv[1])), busy_s=reduced["busy_seconds"])
    say(compact_s=compact_s, memory=jax.devices()[0].memory_stats())


if __name__ == "__main__":
    main()
