"""Step 0 (ISSUE 43): how many rows of a CDLP job's plan are DIRTY in each
superstep, replayed off the chip on a configuration's own draw.

    python _proof/dirty_rows_replay.py                       # graphalytics-g500-22
    python _proof/dirty_rows_replay.py graphalytics-g500-24  # an hour of host time
    python _proof/dirty_rows_replay.py graphalytics-g500-22 12   # another scale of the draw

Host only: the draw of ``benchmark/generators.py`` at the configuration's
``dataset_seed``, ten supersteps by ``benchmark/references.py:mode_smallest``,
the plan's classes by ``np.searchsorted`` on the package's width ladder
(``_extend_widths``; the histogram hubs the ``_HIST_BUDGET // V`` vertices of
largest degree past ``_HIST_MIN_DEG``, as ``BucketedModePlan.from_ptr`` picks
them). No device array is made.

A superstep's rows are brought up to the labels it starts from by rewriting
the slots behind the senders its predecessor moved; a row is dirty when it
holds such a slot, that is when its vertex receives a message from a moved
sender. Every other row is the row it was, and its mode the label its vertex
holds. One JSON line a superstep: K (the messages the moved vertices send,
which picks the rung), the rung, the dirty rows (histogram hubs apart) and
their slots as a share of S, the same in groups of 8 rows of one class (what a
reduce that takes 8 rows at a time runs), the share by sort cost (a group's
8 x w slots x log2(w)^2 against every row's), the groups, the classes that hold
a dirty row, the dirty hubs, and the dirty rows by the coarse width the dirty
reduce runs them at (``_dirty_groups``: 32, then powers of two). The first line
sums the plan up."""
import json, os, sys, time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import generators
import references

config = sys.argv[1] if len(sys.argv) > 1 else "graphalytics-g500-22"
cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                                  config + ".json")))
args = dict(cfg["generator_args"])
if len(sys.argv) > 2:
    args["scale"] = int(sys.argv[2])
iterations = 10
t0 = time.time()
u, v = generators.make(cfg["generator"], args, cfg["dataset_seed"])
nv = 1 << args["scale"]
recv = np.concatenate([v, u]).astype(np.int64)
send = np.concatenate([u, v]).astype(np.int64)
del u, v
deg = np.bincount(recv, minlength=nv)
m = int(deg.sum())

from graphmine_tpu.ops.bucketed_mode import (
    _HIST_BUDGET, _HIST_MIN_DEG, _PAIRWISE_MAX_W, _extend_widths)
from graphmine_tpu.ops.superstep_policy import DELTA_RUNG_DIVISORS

hub = np.zeros(nv, bool)
cand = np.nonzero(deg > _HIST_MIN_DEG)[0]
cand = cand[np.argsort(deg[cand], kind="stable")[::-1][:_HIST_BUDGET // nv]]
hub[cand] = True
row = (deg > 0) & ~hub
widths = _extend_widths(int(deg[row].max(initial=1)))
cls = np.minimum(np.searchsorted(widths, np.maximum(deg, 1)), len(widths) - 1)  # a hub is in no class
w_of = widths[cls]
s = int(w_of[row].sum())
rows_in_class = np.bincount(cls[row], minlength=len(widths))
groups_all = -(-rows_in_class // 8)
cost_all = float((8 * groups_all * widths * np.log2(np.maximum(widths, 2)) ** 2).sum())
coarse_of = np.asarray([max(_PAIRWISE_MAX_W, 1 << (int(w) - 1).bit_length()) for w in widths])
rungs = sorted({m // d for d in DELTA_RUNG_DIVISORS} - {0})
rung_name = {m // d: f"M/{d}" for d in DELTA_RUNG_DIVISORS}
print(json.dumps({"config": config, "scale": args["scale"], "draw_s": round(time.time() - t0, 1),
                  "num_messages": m, "rows": int(row.sum()), "slots": s, "hubs": int(hub.sum()),
                  "classes": int((rows_in_class > 0).sum()), "rungs": rungs}), flush=True)

labels = np.arange(nv, dtype=np.int64)
moved = None
for step in range(1, iterations + 1):
    said = {"superstep": step}
    if moved is None:
        said["rung"] = "full"
    else:
        k = int(deg[moved].sum())
        under = [r for r in rungs if k <= r]
        said["rung"] = rung_name[under[0]] if under else "full"
        dirty = np.zeros(nv, bool)
        dirty[recv[moved[send]]] = True
        d = dirty & row
        in_class = np.bincount(cls[d], minlength=len(widths))
        groups = -(-in_class // 8)
        said.update(
            k=k, k_share=round(k / m, 6),
            dirty_rows=int(d.sum()), dirty_rows_share=round(float(d.sum()) / int(row.sum()), 5),
            dirty_slots_share=round(float(w_of[d].sum()) / s, 5),
            dirty_slots_share_in_groups=round(float((8 * groups * widths).sum()) / s, 5),
            sort_cost_share=round(float((8 * groups * widths * np.log2(np.maximum(widths, 2)) ** 2
                                         ).sum()) / cost_all, 5),
            groups=int(groups.sum()), classes_dirty=int((in_class > 0).sum()),
            hubs_dirty=int((dirty & hub).sum()),
            rows_by_coarse_width={int(c): int(in_class[coarse_of == c].sum())
                                  for c in np.unique(coarse_of) if in_class[coarse_of == c].sum()},
            slots_by_band={name: round(float(w_of[d & (deg > lo) & (deg <= hi)].sum()) / s, 6)
                           for name, lo, hi in (("w<=32", 0, 32), ("w33-2048", 32, 2048),
                                                ("past_2048", 2048, m))},
            widest_classes=[[int(widths[c]), int(in_class[c]), int(rows_in_class[c])]
                            for c in np.nonzero(rows_in_class)[0][-5:][::-1]],
        )
    r, lab = references.mode_smallest(recv, labels[send], nv)
    new = labels.copy()
    new[r] = lab
    moved = new != labels
    labels = new
    said.update(changed_vertices=int(moved.sum()), seconds=round(time.time() - t0, 1))
    print(json.dumps(said), flush=True)
