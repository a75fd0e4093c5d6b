"""Driver ``pipeline_job``: one job is one whole ``run_pipeline(...)`` from
the string-domain parquet on disk to the published snapshot, every chapter
on, in a warm process. Set-up writes the parquet and runs one untimed job.
No superstep family, kNN path or environment override is pinned here: the
program's planner chooses. ``records()`` hands on every record of every timed
job's JSONL (``scope: "job"``), set-up's three stages by the harness's clock
(``generate``, ``write_parquet``, ``warmup_job``) and every record of the
warm-up job's JSONL (``scope: "warmup"``; ``benchmark/handover.py``).
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import time

import numpy as np

import generators
import handover
import references

_FALLBACK_PHASES = ("degrade", "retry", "mesh_degrade", "ivf_fallback")


def job(state, index) -> dict:
    """One whole pipeline run into a fresh snapshot store (``index`` names it)."""
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline

    work = state["ctx"]["scratch"]
    store = os.path.join(work, f"store_{index}")
    metrics = os.path.join(work, f"metrics_{index}.jsonl")
    state["result"] = None  # the harness lets go of the last job's arrays
    gc.collect()
    t0 = time.perf_counter()
    result = run_pipeline(PipelineConfig(
        data_path=state["parquet"], snapshot_out=store, metrics_out=metrics,
        num_devices=state["ctx"]["chips"], **state["ctx"]["sizes"]["pipeline"],
    ))
    seconds = time.perf_counter() - t0
    with open(metrics) as f:
        records = [json.loads(line) for line in f if line.strip()]
    if state.get("store"):
        shutil.rmtree(state["store"], ignore_errors=True)
    state["result"], state["store"] = result, store
    return {"seconds": seconds, "records": records}


def setup(ctx) -> dict:
    sizes = ctx["sizes"]
    t0 = time.perf_counter()
    src, dst, is_anomaly, _ = generators.make(
        ctx["config"]["generator"],
        dict(sizes.get("generator_args", {}), num_vertices=sizes["num_vertices"],
             num_edges=sizes["num_edges"]),
        ctx["config"]["dataset_seed"],
    )
    t1 = time.perf_counter()
    parquet = os.path.join(ctx["scratch"], "outlinks.parquet")
    generators.write_parquet(src, dst, sizes["num_vertices"], parquet)
    t2 = time.perf_counter()
    state = {"ctx": ctx, "src": src, "dst": dst, "is_anomaly": is_anomaly,
             "parquet": parquet, "result": None, "store": None}
    warm = job(state, "warmup")
    state["setup_records"] = handover.stages(
        generate=t1 - t0, write_parquet=t2 - t1, warmup_job=warm["seconds"])
    state["warmup_records"] = handover.warmup(warm["records"])  # the program's, whole
    ctx["say"](rows=len(src), anomalies=int(is_anomaly.sum()), generate_s=t1 - t0,
               write_parquet_s=t2 - t1, warmup_job_s=warm["seconds"])
    return state


def end_to_end(state, jobs, window_s: float) -> dict:
    # seconds per whole job, over all the jobs and all the time of the window
    return {"makespan_s": window_s / len(jobs)}


def records(state, jobs) -> list:
    return ([dict(r, scope="job", job=i)
             for i, j in enumerate(jobs) for r in j["records"]]
            + state.get("setup_records", []) + state.get("warmup_records", []))


def facts(state) -> dict:
    return {}


# -- correctness --------------------------------------------------------------


def _bfloat16(x):
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def check(state, jobs, control: bool) -> list:
    """The last job's answers against the plain references, on the graph as
    generated. The program numbers vertices in its own order of first
    appearance; the rows it ingested are first checked, one by one, to be
    the generated rows under that numbering, and every reference then runs
    on the generated rows in that numbering."""
    from graphmine_tpu.serve.snapshot import SnapshotStore

    ctx, result = state["ctx"], state["result"]
    limits = ctx["sizes"].get("check", ctx["traffic"]["check"])
    k = ctx["sizes"]["pipeline"]["lof_k"]
    max_iter = ctx["sizes"]["pipeline"]["max_iter"]
    out = []

    def compare(name, value, limit, **more):
        out.append(dict({"check": name, "value": value, "limit": limit,
                         "ok": bool(value <= limit)}, **more))

    table = result.edge_table
    n = int(table.num_vertices)
    original = np.array([generators.domain_id(name) for name in table.names])
    position = np.full(ctx["sizes"]["num_vertices"], -1, np.int64)
    position[original] = np.arange(n)
    src, dst = position[state["src"]], position[state["dst"]]
    t_src, t_dst = np.asarray(table.src), np.asarray(table.dst)
    bad_rows = (len(src) != len(t_src)) or int(
        (src != t_src).sum() + (dst != t_dst).sum())
    compare("ingested_row_mismatches", int(bad_rows), 0, compared=len(src))

    labels = np.asarray(result.labels)
    want_labels = references.numpy_lpa(src, dst, n, max_iter)
    compare("lpa_label_mismatches", int((labels != want_labels).sum()), 0,
            compared=n)

    snap = SnapshotStore(state["store"]).load()
    compare("snapshot_label_mismatches",
            int((np.asarray(snap["labels"]) != labels).sum()), 0, compared=n)
    compare("snapshot_cc_partition_mismatches",
            references.partition_mismatches(
                np.asarray(snap["cc_labels"]), references.scipy_cc(src, dst, n)),
            0, compared=n)

    fallbacks = [r["phase"] for j in jobs for r in j["records"]
                 if r.get("phase") in _FALLBACK_PHASES]
    compare("fallback_records", len(fallbacks), 0, found=sorted(set(fallbacks)))

    feats = references.structural_features(src, dst, want_labels, n)
    want_lof = references.lof_from_knn(*references.exact_knn(feats, k))
    lof = np.asarray(result.lof, np.float64)
    if control:
        # the control: the same reference with features and distances in
        # bfloat16, the next precision below the float32 the chapter states
        dist, idx = references.exact_knn(_bfloat16(feats), k)
        lof = references.lof_from_knn(_bfloat16(dist), idx)
    compare("lof_nonfinite", int((~np.isfinite(lof)).sum()), 0, compared=n)
    gap = np.abs(lof - want_lof) / want_lof
    compare("lof_median_relative_gap", float(np.median(gap)),
            limits["lof_median_relative_gap"], compared=n,
            p90=float(np.quantile(gap, 0.9)), worst=float(gap.max()))
    anomalous = state["is_anomaly"][original]
    auroc = references.rank_auroc(lof, anomalous)
    want_auroc = references.rank_auroc(want_lof, anomalous)
    compare("lof_auroc_shortfall", want_auroc - auroc, limits["lof_auroc_shortfall"],
            auroc=auroc, reference_auroc=want_auroc,
            anomalies=int(anomalous.sum()))
    return out
