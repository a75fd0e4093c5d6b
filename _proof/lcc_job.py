"""Dev run of the exact LCC kernel on the chip at a configuration's size
(PR 46): plan seconds, job seconds by stage and by class, the allocator's
peak, and the answer written to chiprun_out/ for a comparison off the chip.
    python _proof/lcc_job.py [scale]"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
import generators  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import graphmine_tpu as gm  # noqa: E402
from graphmine_tpu.compile_cache import enable_compile_cache  # noqa: E402
from graphmine_tpu.obs.spans import Tracer  # noqa: E402
from graphmine_tpu.ops import triangles as T  # noqa: E402
from graphmine_tpu.pipeline.metrics import MetricsSink  # noqa: E402

enable_compile_cache()
scale = int(sys.argv[1]) if len(sys.argv) > 1 else 22
say = lambda **r: print(json.dumps(r, default=str), flush=True)
t0 = time.perf_counter()
u, v = generators.make("rmat_undirected", {"scale": scale, "edge_factor": 16, "a": 0.57,
                                           "b": 0.19, "c": 0.19}, 2147483659)
n = 1 << scale
say(draw_s=time.perf_counter() - t0, edges=len(u))
t0 = time.perf_counter()
g = gm.build_graph(u, v, num_vertices=n)
jax.block_until_ready(g)
say(build_s=time.perf_counter() - t0)
dev = jax.devices()[0]
for i in range(3):
    sink = MetricsSink(tracer=Tracer())
    t0 = time.perf_counter()
    out = gm.clustering_coefficient(g, sink=sink)
    out.block_until_ready()
    say(job=i, seconds=time.perf_counter() - t0,
        records=[{k: r[k] for k in r if k not in ("t", "run_id", "trace_id", "span_id", "parent_id", "path")}
                 for r in sink.records if r["phase"] in ("span", "plan_build")],
        memory={k: (dev.memory_stats() or {}).get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
os.makedirs("chiprun_out", exist_ok=True)
np.save(f"chiprun_out/lcc{scale}.npy", np.asarray(out))
# by class
plan, _, _ = T._lcc_plan(g)
lo, hi = jnp.zeros((n,), jnp.uint32), jnp.zeros((n,), jnp.uint32)
for w, nb, blocks, *arrays in plan.core_classes:
    t0 = time.perf_counter()
    lo, hi = T._core_class(lo, hi, plan.bits, blocks, *arrays, w=w, nb=nb, core_start=plan.core_start)
    lo.block_until_ready()
    say(core_w=w, nb=nb, blocks=blocks, seconds=time.perf_counter() - t0)
for w, ne, blocks, *arrays in plan.tail_classes:
    t0 = time.perf_counter()
    if plan.tail_table is not None:
        lo, hi = T._tail_table_class(lo, hi, plan.tail_table, blocks, *arrays, w=w, ne=ne)
    else:
        lo, hi = T._tail_class(lo, hi, plan.col, blocks, *arrays, w=w, ne=ne)
    lo.block_until_ready()
    say(tail_w=w, ne=ne, blocks=blocks, seconds=time.perf_counter() - t0)
