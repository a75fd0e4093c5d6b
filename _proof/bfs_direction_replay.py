"""ISSUE 50, before a chip-minute is spent: K and U by level of the BFS cell's
search at a smaller scale, and the update the job's own rule picks for each
(host only: the benchmark's generator, its plain reference's depths, and
``ops/paths.py:_next_update`` over ``delta_rungs``, the plan's slots taken as
M: the padding is 4 %). K is the messages the vertices a level reached send,
U the edges of the vertices still unreached.

    python _proof/bfs_direction_replay.py 20 22      # one JSON line a scale

At scale 24 the chip run's own record holds both (U = M less the K's so far)."""
import json, os, sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "benchmark", "algorithms")]
import numpy as np

import bfs, generators
from graphmine_tpu.ops.paths import _next_update
from graphmine_tpu.ops.superstep_policy import bottom_up_chunk, delta_rungs

cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", "graphalytics-g500-24-bfs.json")))
for scale in (int(a) for a in sys.argv[1:]):
    args = dict(cfg["generator_args"], scale=scale)
    u, v = generators.make(cfg["generator"], args, cfg["dataset_seed"])
    n = 1 << scale
    depth = bfs.reference(u, v, n, {"source": "lowest_id_with_an_edge"})[0]
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    m = int(deg.sum())
    rungs = delta_rungs(m)
    names = [*(f"M/{m // r}" for r in rungs), "full"]
    deepest = int(depth[depth != bfs.UNREACHED].max())
    k, unreached, stale, levels = int(deg[depth == 0].sum()), m, False, []
    unreached -= k
    for level in range(1, deepest + 2):  # the last reaches nothing
        chunk = bottom_up_chunk(m)
        places = -(-unreached // chunk) * chunk
        place, bottom_up = _next_update(k, places, rungs, m, stale)
        stale = bottom_up
        at = depth == level
        levels.append({"level": level, "picked_by_K": k, "picked_by_U": unreached,
                       "branch": places if bottom_up else names[place], "direction": "bottom_up" if bottom_up else "top_down",
                       "reached": int(at.sum())})
        k = int(deg[at].sum())
        unreached -= k
    print(json.dumps({"scale": scale, "num_messages": m, "rungs": list(rungs),
                      "unreached_for_good": unreached, "levels": levels}), flush=True)
