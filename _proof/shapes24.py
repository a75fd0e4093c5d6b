"""Step 0a (ISSUE 36, ISSUE 38): the class shapes of a configuration's fused
plan, from a host-only build of the configuration's own draw.

    python _proof/shapes24.py 24                             # graphalytics-g500-24 -> g500_24_shapes.json
    python _proof/shapes24.py 24 gap-urand-24 urand          # -> urand_24_shapes.json

Writes _proof/<prefix>_<scale>_shapes.json: [[n, w], ...] per class, hubs,
hist_send length, V, M, the degree distribution's marks. No device array is
made of the graph."""
import json, os, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import numpy as np
import generators
scale = int(sys.argv[1]) if len(sys.argv) > 1 else 24
config = sys.argv[2] if len(sys.argv) > 2 else "graphalytics-g500-24"
prefix = sys.argv[3] if len(sys.argv) > 3 else "g500"
cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                                  config + ".json")))
args = dict(cfg["generator_args"], scale=scale)
t0 = time.time()
u, v = generators.make(cfg["generator"], args, cfg["dataset_seed"])
print("draw", len(u), time.time() - t0, flush=True)
import jax
jax.config.update("jax_platforms", "cpu")
import graphmine_tpu as gm
from graphmine_tpu.ops.bucketed_mode import BucketedModePlan
t0 = time.time()
g = gm.build_graph(u, v, num_vertices=1 << scale, to_device=False)
print("graph", g.num_messages, time.time() - t0, flush=True)
t0 = time.time()
import unittest.mock as mock
import jax.numpy as jnp
# host arrays only: keep the plan's matrices NumPy (shapes are all we want)
with mock.patch.object(jnp, "asarray", lambda x, *a, **k: np.asarray(x)):
    plan = BucketedModePlan.from_graph(g, with_send=True)
print("plan", time.time() - t0, flush=True)
out = {
    "scale": scale, "num_vertices": int(plan.num_vertices), "num_messages": int(plan.num_messages),
    "classes": [[int(i.shape[0]), int(i.shape[1])] for i in plan.send_idx],
    "vertex_ids": [int(i.shape[0]) for i in plan.vertex_ids],
    "hubs": 0 if plan.hist_vertex_ids is None else int(plan.hist_vertex_ids.shape[0]),
    "hist_send": 0 if plan.hist_send is None else int(plan.hist_send.shape[0]),
    "hist_row_offset": 0 if plan.hist_row_offset is None else int(plan.hist_row_offset.shape[0]),
    "weighted": plan.weight_mat is not None,
}
out["slots"] = sum(n * w for n, w in out["classes"])
deg = np.diff(np.asarray(g.msg_ptr).astype(np.int64))
touched = deg > 0
out["edges"] = int(len(u))
out["vertices_with_edge"] = int(touched.sum())
out["degree"] = {"mean": float(deg.mean()), "max": int(deg.max()),
                 "p01": int(np.percentile(deg, 1)), "p50": int(np.percentile(deg, 50)),
                 "p99": int(np.percentile(deg, 99))}
json.dump(out, open(os.path.join(os.path.dirname(__file__), f"{prefix}_{scale}_shapes.json"), "w"))
print(json.dumps(out))
