"""PR 49: which vertex the rule `lowest_id_with_an_edge` resolves to on a
configuration's draw, its degree and its neighbours' (host only, no chip).

    python _proof/source_vertex.py graphalytics-g500-24-bfs"""
import json, os, shutil, subprocess, sys, tempfile
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", sys.argv[1] + ".json")))
scratch = tempfile.mkdtemp(prefix="src_")
child = os.path.join(ROOT, "benchmark", "drivers", "kernel_job_mesh.py")
args = {"generator": cfg["generator"], "generator_args": cfg["generator_args"],
        "dataset_seed": cfg["dataset_seed"]}
env = {**os.environ, "MALLOC_ARENA_MAX": "1", "MALLOC_MMAP_MAX_": "0",
       "MALLOC_TRIM_THRESHOLD_": str(1 << 40), "MALLOC_TOP_PAD_": str(1 << 28)}
subprocess.run([sys.executable, child, "generate", scratch, json.dumps(args)], env=env, check=True)
u = np.load(os.path.join(scratch, "u.npy"), mmap_mode="r")
v = np.load(os.path.join(scratch, "v.npy"), mmap_mode="r")
source = int(min(u.min(), v.min()))
near = np.concatenate([np.asarray(v[u == source]), np.asarray(u[v == source])])
degrees = [int((u == w).sum() + (v == w).sum()) for w in near[:8]]
print(json.dumps({"config": sys.argv[1], "source_vertex": source, "degree": int(len(near)),
                  "neighbours": near[:8].tolist(), "neighbour_degrees": degrees,
                  "edges": int(len(u))}))
del u, v
shutil.rmtree(scratch, ignore_errors=True)
