#!/usr/bin/env python3
"""Chip smoke: pipeline -> snapshot -> serve on one TPU, from tracked files.

The quickest proof that the system still starts on the chip. One process
(the only one that touches JAX) drives the user entry points once —
``run_pipeline`` on a generated 262,144-vertex / 25 M-edge string-domain
parquet, the published snapshot, and an in-process ``SnapshotServer``
answering HTTP reads and one delta — and checks every answer against
plain references (NumPy synchronous LPA written here; SciPy connected
components and rank-statistic AUROC from ``benchmark/references.py``).
Everything is generated from ``--seed``.

Each phase prints one JSON line. The LAST line is exactly
``{"ok": true, "device": {...}}`` and exit code 0 — only when the default
backend is a TPU and every check passed. Anything else (no accelerator, a
failed check, an exception) exits non-zero with no ``ok`` line.

``--chips 4`` runs ONLY the sharded path (the com-livejournal R-MAT rung
through ``run_pipeline`` over a 4-device mesh) and its 1-device twin.

``--rehearse`` lets the same code run on a non-TPU backend at small sizes
(the no-chip rehearsal); a passed rehearsal exits 4 and prints no ``ok``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request

import numpy as np

from benchmark.references import rank_auroc, scipy_cc

_REPO = os.path.dirname(os.path.abspath(__file__))
_FAILURES: list[str] = []
_COMPILE = {"seconds": 0.0, "count": 0, "cache_hits": 0}


def say(**record) -> None:
    print(json.dumps(record, default=str), flush=True)


def check(name: str, ok, **detail) -> bool:
    ok = bool(ok)
    say(check=name, ok=ok, **detail)
    if not ok:
        _FAILURES.append(name)
    return ok


def _listen_for_compiles() -> None:
    import jax

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["seconds"] += seconds
            _COMPILE["count"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _device_bytes() -> list[dict]:
    import jax

    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append({
            "device": dev.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for f in os.listdir(cache_dir) if not f.endswith("-atime"))
    except FileNotFoundError:
        return 0


def phase(name: str, fn, *args):
    """Run one phase: time it, print its JSON line, record an exception as
    a failure (printed, never swallowed into a pass) and carry on so one
    chip call shows every independent fault."""
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    detail, out = {}, None
    try:
        out = fn(detail, *args)
    except Exception:
        traceback.print_exc()
        _FAILURES.append(f"{name}: exception")
        detail["exception"] = traceback.format_exc(limit=1).splitlines()[-1]
    dev = _device_bytes()
    say(
        phase=name,
        seconds=round(time.perf_counter() - t0, 3),
        compile_seconds=round(_COMPILE["seconds"] - before["seconds"], 3),
        compiles=_COMPILE["count"] - before["count"],
        cache_hits=_COMPILE["cache_hits"] - before["cache_hits"],
        bytes_in_use=dev[0]["bytes_in_use"],
        peak_bytes_in_use=dev[0]["peak_bytes_in_use"],
        **detail,
    )
    return out


# -- references, independent of the code under test: ``scipy_cc`` and
# ``rank_auroc`` are the benchmark's own (benchmark/references.py) ---------


def numpy_lpa(src, dst, num_vertices: int, max_iter: int) -> np.ndarray:
    """Plain synchronous label propagation: messages flow both ways along
    every edge, duplicates counted, initial label = vertex id, the most
    frequent incoming label wins and the smallest label wins a tie; a
    vertex that receives nothing keeps its label."""
    recv = np.concatenate([dst, src]).astype(np.int64)
    send = np.concatenate([src, dst]).astype(np.int64)
    labels = np.arange(num_vertices, dtype=np.int64)
    for _ in range(max_iter):
        pair, count = np.unique(
            recv * num_vertices + labels[send], return_counts=True
        )
        r, lab = pair // num_vertices, pair % num_vertices
        # per receiver: highest count first, then smallest label
        order = np.lexsort((lab, -count, r))
        first = np.ones(len(order), bool)
        first[1:] = r[order][1:] != r[order][:-1]
        labels = labels.copy()
        labels[r[order][first]] = lab[order][first]
    return labels


def same_partition(a, b) -> bool:
    from graphmine_tpu.oracle import canonical_partition

    return bool(np.array_equal(canonical_partition(a), canonical_partition(b)))


# -- phases ---------------------------------------------------------------


def build_phase(detail) -> None:
    proc = subprocess.run(
        ["make", "-C", os.path.join(_REPO, "native")],
        capture_output=True, text=True,
    )
    detail["make_rc"] = proc.returncode
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
    from graphmine_tpu.io import native

    lib = native._lib()
    check(
        "native_library_loaded",
        proc.returncode == 0 and lib is not None
        and hasattr(lib, "gb_build_message_csr"),
        lib=getattr(lib, "_name", None),
    )


def exact_phase(detail, args) -> None:
    import graphmine_tpu as gm

    v, e = args.exact_vertices, args.exact_edges
    src, dst, _, _ = gm.datasets.planted_anomaly_graph(v, e, seed=args.seed)
    g = gm.build_graph(src, dst, num_vertices=v)
    labels = np.asarray(gm.label_propagation(g, max_iter=5))
    cc = np.asarray(gm.connected_components(g))
    want = numpy_lpa(src, dst, v, 5)
    mismatch = int((labels != want).sum())
    detail.update(vertices=v, edges=len(src),
                  communities=int(len(np.unique(labels))))
    check("lpa_labels_equal_numpy", mismatch == 0, mismatched=mismatch)
    check(
        "cc_equals_scipy", same_partition(cc, scipy_cc(src, dst, v)),
        components=int(len(np.unique(cc))),
    )


def write_parquet(src, dst, num_vertices: int, path: str) -> None:
    """The reference's ingestion format: domain-string columns
    ``_c1``/``_c2``, one row per outlink, duplicates kept."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    names = pa.array([f"d{i:07d}.example" for i in range(num_vertices)])

    def col(ids):
        return pa.DictionaryArray.from_arrays(
            pa.array(ids, pa.int32()), names
        ).cast(pa.string())

    pq.write_table(pa.table({"_c1": col(src), "_c2": col(dst)}), path)


def read_metrics(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def check_clean_run(records, prefix: str = "") -> None:
    """No rung of the resilience ladder and no loud fallback was taken:
    what ran is what the plan and impl_selected records name."""
    bad = [
        {k: r.get(k) for k in ("phase", "stage", "to", "guard", "detail", "error")
         if r.get(k) is not None}
        for r in records
        if r.get("phase") in ("degrade", "retry", "mesh_degrade", "ivf_fallback")
    ]
    check(prefix + "no_degrade_retry_mesh_degrade", not bad, found=bad)


def pipeline_phase(detail, args, work: str):
    import jax

    from graphmine_tpu.datasets import planted_anomaly_graph
    from graphmine_tpu.io import native
    from graphmine_tpu.ops.lof import select_lof_impl
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.pipeline.planner import _HBM_HEADROOM
    from graphmine_tpu.serve.snapshot import SnapshotStore

    v, e = args.vertices, args.edges
    t0 = time.perf_counter()
    src, dst, is_anomaly, _ = planted_anomaly_graph(v, e, seed=args.seed)
    data = os.path.join(work, "edges.parquet")
    write_parquet(src, dst, v, data)
    detail["dataset_seconds"] = round(time.perf_counter() - t0, 3)

    store_dir = os.path.join(work, "store")
    metrics_path = os.path.join(work, "metrics.jsonl")
    t0 = time.perf_counter()
    res = run_pipeline(PipelineConfig(
        data_path=data, batch_rows=args.batch_rows, max_iter=5,
        outlier_method="both", lof_k=args.lof_k,
        snapshot_out=store_dir, metrics_out=metrics_path,
    ))
    detail["run_pipeline_seconds"] = round(time.perf_counter() - t0, 3)
    records = read_metrics(metrics_path)
    seconds = {}
    for r in records:
        if "seconds" in r and r["phase"] != "span":
            seconds[r["phase"]] = round(
                seconds.get(r["phase"], 0.0) + r["seconds"], 3
            )
    detail.update(
        vertices=int(res.edge_table.num_vertices),
        edges=int(res.edge_table.num_edges),
        communities=int(res.num_communities),
        phase_seconds=seconds,
        native_csr=native.available(),
    )
    check("vertex_and_edge_counts", detail["edges"] == len(src)
          and detail["vertices"] <= v, expected_edges=len(src))
    check_clean_run(records)

    plan = [r for r in records if r.get("phase") == "plan"]
    limits = [
        (d.memory_stats() or {}).get("bytes_limit") for d in jax.local_devices()
    ]
    if all(limits):
        check(
            "hbm_budget_from_device",
            len(plan) == 1
            and plan[0]["hbm_budget"] == int(min(limits) * _HBM_HEADROOM),
            hbm_budget=plan[0]["hbm_budget"] if plan else None,
            bytes_limit=min(limits),
        )
    else:
        # only a non-TPU rehearsal gets here: a TPU that reports no
        # bytes_limit already raised inside run_pipeline
        check("hbm_budget_from_device", jax.default_backend() != "tpu",
              note="backend reports no bytes_limit")

    # the scorer's own record (the publish-time canary probe emits one
    # too, for its small cloud)
    lof_impl = [r["impl"] for r in records if r.get("phase") == "impl_selected"
                and r.get("op") == "lof_knn" and r.get("n") == detail["vertices"]]
    want_impl = select_lof_impl(detail["vertices"], args.lof_k)[0]
    check("lof_impl_selected", lof_impl == [want_impl],
          impl=lof_impl, expected=want_impl)
    detail["memory_watermarks"] = {
        r["op"]: r.get("peak_bytes_in_use", r.get("achieved_bytes"))
        for r in records if r.get("phase") == "memory_watermark"
    }

    snap = SnapshotStore(store_dir).load()
    s_src, s_dst = np.asarray(snap["src"]), np.asarray(snap["dst"])
    check(
        "snapshot_cc_equals_scipy",
        same_partition(
            np.asarray(snap["cc_labels"]),
            scipy_cc(s_src, s_dst, len(snap["labels"])),
        ),
        components=int(len(np.unique(snap["cc_labels"]))),
    )
    check("snapshot_labels_equal_result",
          np.array_equal(snap["labels"], res.labels))

    # ingestion re-factorizes ids in name-appearance order; the original
    # id is in the name ("d%07d.example")
    orig = np.array([int(n[1:8]) for n in res.edge_table.names], np.int64)
    lof = np.asarray(res.lof)
    auc = rank_auroc(lof, is_anomaly[orig])
    check("lof_finite", bool(np.isfinite(lof).all()))
    check("lof_auroc_at_least_0.8", auc >= 0.8, auroc=auc,
          anomalies=int(is_anomaly.sum()))
    return res, store_dir


def _http(base: str, path: str, body=None):
    req = urllib.request.Request(
        base + path,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, json.loads(resp.read())


def serve_phase(detail, args, res, store_dir: str) -> None:
    from graphmine_tpu.pipeline.metrics import MetricsSink
    from graphmine_tpu.serve.server import SnapshotServer
    from graphmine_tpu.serve.snapshot import SnapshotStore

    labels = np.asarray(res.labels)
    lof = np.asarray(res.lof, np.float32)
    v = len(labels)
    rng = np.random.default_rng(args.seed)
    sink = MetricsSink()
    server = SnapshotServer(SnapshotStore(store_dir), port=0, sink=sink, wal=True)
    host, port = server.start()
    base = f"http://{host}:{port}"
    try:
        _, health = _http(base, "/healthz")
        v0 = health["version"]
        detail["version"] = v0

        for n in (1, 37, 1024):  # batched gather, three jit buckets
            ids = rng.integers(0, v, n)
            _, out = _http(base, "/query", {"vertices": ids.tolist()})
            check(
                f"query_batch_{n}",
                out["version"] == v0
                and np.array_equal(out["label"], labels[ids])
                and np.array_equal(np.asarray(out["lof"], np.float32), lof[ids]),
            )
        one = int(rng.integers(0, v))
        _, row = _http(base, f"/vertex?v={one}")
        check("vertex_row", row["label"] == int(labels[one])
              and np.float32(row["lof"]) == lof[one])
        comm = int(np.bincount(labels).argmax())  # the largest community
        members = np.flatnonzero(labels == comm)
        _, top = _http(base, f"/topk?community={comm}&k=5")
        got = [(t["vertex"], np.float32(t["lof"])) for t in top["top"]]
        want_scores = np.sort(lof[members])[::-1][:5]
        check(
            "community_topk",
            [s for _, s in got] == want_scores.tolist()
            and all(labels[u] == comm and lof[u] == s for u, s in got),
            community=comm, size=int(len(members)),
        )

        # one delta: insert an edge between two existing vertices that
        # are not adjacent, delete one existing edge
        src, dst = np.asarray(res.edge_table.src), np.asarray(res.edge_table.dst)
        a = int(src[0])
        nbrs = set(_http(base, f"/neighbors?v={a}")[1]["neighbors"])
        b = next(int(u) for u in rng.permutation(v) if u != a and u not in nbrs)
        gone = [int(src[1]), int(dst[1])]
        t0 = time.perf_counter()
        status, ack = _http(base, "/delta", {"insert": [[a, b]], "delete": [gone]})
        detail.update(
            delta_seconds=round(time.perf_counter() - t0, 3),
            delta_status=status,
        )
        check("delta_acknowledged", status in (200, 202), response=ack)
        server.wait_applied(timeout=600)
        # reported, not asserted: warm repair or the full-recompute rung
        for r in sink.records:
            if r.get("phase") == "delta_apply":
                detail.update(repair=r["method"], repair_iterations=r["iterations"],
                              repair_budget=r["budget"])
            elif r.get("phase") == "repair_fallback":
                detail["repair_fallback_reason"] = r.get("reason")
        _, health = _http(base, "/healthz")
        check("version_bumped", health["version"] > v0,
              before=v0, after=health["version"])
        _, out = _http(base, "/query", {"vertices": [a, b]})
        _, nb = _http(base, f"/neighbors?v={a}")
        check(
            "inserted_edge_read_back",
            out["version"] == health["version"] and out["vertex"] == [a, b]
            and b in nb["neighbors"],
            version=out["version"],
        )
    finally:
        server.stop()


def four_chip_phase(detail, args, work: str) -> None:
    """The sharded path and its 1-device twin, nothing else."""
    import jax

    from graphmine_tpu.datasets import LADDER, rmat
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.serve.snapshot import SnapshotStore

    rung = LADDER["com-livejournal"]
    scale = min(rung.scale, args.rung_scale)
    src, dst = rmat(scale, rung.edge_factor, seed=args.seed)
    data = os.path.join(work, "lj.parquet")
    write_parquet(src, dst, 1 << scale, data)
    detail.update(vertices=1 << scale, edges=len(src))

    def run(tag, num_devices):
        store_dir = os.path.join(work, f"store_{tag}")
        metrics_path = os.path.join(work, f"metrics_{tag}.jsonl")
        t0 = time.perf_counter()
        res = run_pipeline(PipelineConfig(
            data_path=data, batch_rows=args.batch_rows, max_iter=5,
            outlier_method="none", schedule="auto", num_devices=num_devices,
            snapshot_out=store_dir, metrics_out=metrics_path,
        ))
        detail[f"{tag}_seconds"] = round(time.perf_counter() - t0, 3)
        records = read_metrics(metrics_path)
        check_clean_run(records, prefix=f"{tag}_")
        plan = [r for r in records if r.get("phase") == "plan"]
        detail[f"{tag}_schedule"] = plan[0]["schedule"] if plan else None
        cc = np.asarray(SnapshotStore(store_dir).load()["cc_labels"])
        return np.asarray(res.labels), cc

    labels4, cc4 = run("mesh", None)
    check("sharded_schedule", detail["mesh_schedule"] in ("replicated", "ring"),
          schedule=detail["mesh_schedule"])
    per_device = _device_bytes()
    detail["per_device"] = per_device
    peaks = [d["peak_bytes_in_use"] for d in per_device]
    if all(p is not None for p in peaks):
        check("every_device_held_a_shard",
              min(peaks) > 0 and min(peaks) >= 0.1 * max(peaks), peaks=peaks)
    else:
        check("every_device_held_a_shard", jax.default_backend() != "tpu",
              note="backend reports no memory stats")
    labels1, cc1 = run("single", 1)
    check("single_schedule", detail["single_schedule"] == "single")
    check("labels_bit_equal", np.array_equal(labels4, labels1),
          mismatched=int((labels4 != labels1).sum()))
    check("cc_bit_equal", np.array_equal(cc4, cc1),
          mismatched=int((cc4 != cc1).sum()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--vertices", type=int, default=1 << 18)
    ap.add_argument("--edges", type=int, default=25_000_000)
    ap.add_argument("--batch-rows", type=int, default=4_000_000)
    ap.add_argument("--lof-k", type=int, default=128)
    ap.add_argument("--exact-vertices", type=int, default=4096)
    ap.add_argument("--exact-edges", type=int, default=200_000)
    ap.add_argument("--rung-scale", type=int, default=22,
                    help="cap on the 4-chip R-MAT rung's scale")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU backend; never prints ok")
    args = ap.parse_args()

    import jax

    platform = jax.default_backend()
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: default backend is {platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    from graphmine_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _listen_for_compiles()
    say(device=device, seed=args.seed, cache_dir=cache_dir,
        cache_entries_before=_cache_entries(cache_dir))

    t_all = time.perf_counter()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase("four_chips", four_chip_phase, args, work)
        else:
            phase("build", build_phase)
            phase("exact", exact_phase, args)
            out = phase("pipeline", pipeline_phase, args, work)
            if out is not None:
                phase("serve", serve_phase, args, *out)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(cache_dir=cache_dir, cache_entries_after=_cache_entries(cache_dir),
        compile_seconds=round(_COMPILE["seconds"], 3),
        compiles=_COMPILE["count"], cache_hits=_COMPILE["cache_hits"],
        total_seconds=round(time.perf_counter() - t_all, 3))

    if _FAILURES:
        say(failed=_FAILURES)
        return 1
    if platform != "tpu":
        say(rehearsal="passed", device=device)
        return 4
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
