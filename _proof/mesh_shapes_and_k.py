"""Step 0 (ISSUE 39): the shapes of a mesh configuration's stacked plan and a
replay of K, from a host-only build of the configuration's own draw.

    python _proof/mesh_shapes_and_k.py graphalytics-g500-25 4   # -> _proof/g500_25_x4_shapes.json

Writes the per-shard class shapes ``[[n, w], ...]``, the chunk size, the
shards' message counts, and, from the benchmark's reference
(``references.threaded_lpa``, stepped: its source with one line hooked),
per superstep the changed vertices and each shard's K (the messages the
changed vertices send into the shard's vertex range). No device array."""
import inspect, json, os, sys, time
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import generators, references

config, d = sys.argv[1], int(sys.argv[2])
cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", config + ".json")))
args = cfg["rehearsal"]["generator_args"] if os.environ.get("REHEARSE") else cfg["generator_args"]
scale, iters = args["scale"], 10
t0 = time.time()
u, v = generators.make(cfg["generator"], args, cfg["dataset_seed"])
print("draw", len(u), round(time.time() - t0, 1), flush=True)
import graphmine_tpu as gm
from graphmine_tpu.parallel.sharded import _shard_message_offsets, partition_graph, shard_row_slots
t0 = time.time()
g = gm.build_graph(u, v, num_vertices=1 << scale, to_device=False)
sg = partition_graph(g, num_shards=d, lpa_only=True, build_bucket_plan=True)
offsets = _shard_message_offsets(np.asarray(g.msg_ptr), d, sg.chunk_size)
out = {
    "config": config, "shards": d, "num_vertices": g.num_vertices, "chunk_size": sg.chunk_size,
    "num_messages": g.num_messages, "edges": int(len(u)),
    "messages_per_shard": np.diff(offsets).tolist(),
    "classes": [[int(b.shape[1]), int(b.shape[2])] for b in sg.bucket_send],
    "slots": shard_row_slots(sg),
}
print("partition", round(time.time() - t0, 1), json.dumps({k: out[k] for k in out if k != "classes"}), flush=True)
send = np.asarray(g.msg_send)
del sg, g
history = []

def hook(labels):
    history.append(labels.astype(np.int32))
    print("superstep", len(history), round(time.time() - t0, 1), flush=True)

src = inspect.getsource(references.threaded_lpa)
assert src.count("            labels = new\n") == 1
scope = dict(vars(references), HOOK=hook)
exec(src.replace("            labels = new\n", "            labels = new\n            HOOK(labels)\n"), scope)
t0 = time.time()
workers = min(32, os.cpu_count() or 8)
scope["threaded_lpa"](u, v, 1 << scale, iters, slices=min(128, 4 * workers), workers=workers)
prev = np.arange(1 << scale, dtype=np.int32)
out["changed_vertices"], out["k_per_shard"] = [], []
for labels in history:
    changed = labels != prev
    out["changed_vertices"].append(int(changed.sum()))
    out["k_per_shard"].append(
        [int(changed[send[offsets[s]:offsets[s + 1]]].sum()) for s in range(d)])
    prev = labels
name = "g500_%d_x%d_shapes.json" % (scale, d)
json.dump(out, open(os.path.join(ROOT, "_proof", name), "w"))
print(json.dumps({k: out[k] for k in ("changed_vertices", "k_per_shard")}))
