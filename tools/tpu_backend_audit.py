"""Cross-backend numerical audit: default (TPU) vs CPU, same inputs.

CI forces 8 virtual CPU devices (tests/conftest.py), so a TPU-only
miscompile passes the suite silently — exactly what happened to the first
betweenness kernel: a ``[M, b]`` segment_sum chained across supersteps
compiled to zeros on the TPU backend while every test stayed green (see
``ops/centrality.py:_brandes_tile`` and docs/DESIGN.md). Run this on a
machine with the real accelerator after touching any lane-batched or
iterated segment-op kernel:

    python tools/tpu_backend_audit.py

Exits nonzero on any mismatch.
"""

import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # `python tools/...` puts tools/ on the path, not the repo
    sys.path.insert(0, _REPO)

REF_PATH = "/tmp/graphmine_cpu_ref.npz"

_COMPUTE = """
import numpy as np
import graphmine_tpu as gm

def compute():
    rng = np.random.default_rng(0)
    v, e = 300, 1500
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    g = gm.build_graph(src, dst, num_vertices=v)
    gd = gm.build_graph(src, dst, num_vertices=v, symmetric=False)
    # bucketed-min CC (r5): the fused-plan superstep path cell
    # `wcc-g500-22` runs — audited against CPU like every other kernel
    from graphmine_tpu.ops.bucketed_mode import build_graph_and_plan

    gp, plan = build_graph_and_plan(src, dst, num_vertices=v)
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    labels = gm.label_propagation(g, max_iter=5)
    h, a = gm.hits(gd)
    # kNN/LOF at k=8: impl="auto" resolves to the fused Pallas kernel on
    # TPU and the XLA path on CPU *only for k <= 8* (the r5-measured
    # policy, ops/knn.py), so both rows are real-hardware Pallas-vs-XLA
    # checks — at any larger k they would silently become vacuous
    # XLA-vs-XLA comparisons. (kNN indices are excluded: near-tie
    # orderings may legitimately differ across backends.)
    from graphmine_tpu.ops.knn import knn
    from graphmine_tpu.ops.lof import lof_scores

    pts = rng.normal(size=(512, 8)).astype(np.float32)
    knn_d2, _ = knn(pts, k=8, impl="auto")

    # One shard_map output (VERDICT r4 item 1): the distributed LPA body
    # on a 1-device mesh of whatever backend this process has — on the
    # real TPU this is the first-ever silicon execution class for the
    # shard_map programs, which CPU CI can never de-risk (the r4 Mosaic
    # compile blowup and MXU rounding bugs were both invisible there).
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    mesh = make_mesh(1)
    sg = shard_graph_arrays(
        partition_graph(g, mesh=mesh, build_bucket_plan=True), mesh
    )
    sharded_lpa = sharded_label_propagation(sg, mesh, max_iter=5)

    # IVF-LOF, fused AND mesh-sharded (r6): the deployed large-cloud LOF
    # path (ops/lof.py auto-policy) and its distributed twin. Blob data,
    # not gaussian: the k-means assignment step runs on device, and on a
    # near-tie cloud a backend's last-ulp rounding could flip a border
    # point's cluster — a DIFFERENT candidate set, not a numerics bug.
    # Well-separated blobs keep assignment margins far above float
    # jitter, so these rows compare numerics, not tie-breaks.
    from graphmine_tpu.parallel.knn import sharded_lof

    blob_c = rng.normal(size=(8, 8)).astype(np.float32) * 4
    blob_pts = (
        blob_c[rng.integers(0, 8, 2048)]
        + rng.normal(size=(2048, 8)).astype(np.float32)
    )
    ivf_lof_fused = lof_scores(blob_pts, k=8, impl="ivf")
    ivf_lof_sharded = sharded_lof(blob_pts, mesh, k=8, impl="ivf")
    return {
        "lpa": np.asarray(labels),
        "cc": np.asarray(gm.connected_components(g)),
        "cc_bucketed": np.asarray(
            gm.connected_components(gp, plan=plan)
        ),
        "sp": np.asarray(gm.shortest_paths(
            g, np.arange(16, dtype=np.int32), direction="both",
            landmark_batch=5)),
        "wsp": np.asarray(gm.weighted_shortest_paths(
            g, np.arange(4, dtype=np.int32), w, direction="both")),
        "ppr": np.asarray(gm.parallel_personalized_pagerank(
            gd, np.arange(6, dtype=np.int32))),
        "closeness": np.asarray(gm.closeness_centrality(
            g, vertices=np.arange(12, dtype=np.int32))),
        "bc": np.asarray(gm.betweenness_centrality(
            g, sources=np.arange(20, dtype=np.int32), source_batch=7)),
        "hits_h": np.asarray(h),
        "hits_a": np.asarray(a),
        "pagerank": np.asarray(gm.pagerank(gd, max_iter=50)),
        "knn_d2": np.asarray(knn_d2),
        "lof": np.asarray(lof_scores(pts, k=8)),
        "sharded_lpa": np.asarray(sharded_lpa),
        "ivf_lof_fused": np.asarray(ivf_lof_fused),
        "ivf_lof_sharded": np.asarray(ivf_lof_sharded),
    }
"""


def main() -> int:
    # CPU reference in a subprocess (JAX_PLATFORMS must be set pre-import)
    code = _COMPUTE + f"""
np.savez({REF_PATH!r}, **compute())
print("cpu reference written")
"""
    # The child's platform is fixed in its environment before jax starts:
    # a "CPU reference" that landed on the accelerator too would make the
    # audit vacuously compare the TPU against itself (and fight this
    # process for the chip).
    import __graft_entry__

    env = __graft_entry__._load_envscrub().virtual_cpu_env(1)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run([sys.executable, "-c", code], check=True, env=env)

    ns: dict = {}
    exec(_COMPUTE, ns)  # default backend (the accelerator) in this process
    got = ns["compute"]()
    ref = np.load(REF_PATH)
    bad = []
    for k, dev_val in got.items():
        ok = np.allclose(dev_val, ref[k], rtol=1e-4, atol=1e-5)
        print(f"{k:10s} TPU==CPU: {ok}")
        if not ok:
            diff = np.max(np.abs(dev_val.astype(np.float64) - ref[k].astype(np.float64)))
            print(f"           max abs diff: {diff}")
            bad.append(k)
    if bad:
        print(f"MISMATCH on: {bad}", file=sys.stderr)
        return 1
    print("all backends agree")
    return 0


if __name__ == "__main__":
    sys.exit(main())
