"""End-to-end pipeline driver: load → build → LPA → census → outliers.

Reproduces the five phases of the reference script
(``CommunityDetection/Graphframes.py``) with the TPU-native engine:

  CS-1 ingestion (:12-32)      → parquet/edge-list load, null filter, counts
  CS-2 graph construction (:53-78) → dense factorize + message CSR
  CS-3 label propagation (:81-85)  → jit/shard_map LPA supersteps
  CS-4 census (:92-120)            → segment-sum community table
  CS-5 outliers (:121-137, dead)   → recursive LPA decile + kNN/LOF scores

plus the subsystems the reference lacked: structured metrics (edges/sec/
chip), profiling, checkpoint/resume, multi-device execution.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

import numpy as np

from graphmine_tpu.graph.container import Graph, graph_from_edge_table
from graphmine_tpu.io.edges import EdgeTable, load_edge_list, load_parquet_edges
from graphmine_tpu.obs.spans import stage_span
from graphmine_tpu.pipeline import checkpoint as ckpt
from graphmine_tpu.pipeline import resilience
from graphmine_tpu.pipeline.config import PipelineConfig
from graphmine_tpu.pipeline.metrics import MetricsSink, maybe_profile


def _visible_devices() -> int:
    import jax

    return len(jax.devices())


def device_hbm_bytes(devices=None) -> int | None:
    """Real per-device HBM via ``memory_stats()``: the MIN of
    ``bytes_limit`` across all local devices (ISSUE 14 satellite) — a
    heterogeneous or partially-occupied mesh must plan against its
    smallest chip, and trusting ``jax.devices()[0]`` alone budgeted
    against whichever part happened to enumerate first.

    A CPU device reports none and is skipped; with no report at all the
    answer is None and the planner budgets against its 16 GiB default
    (the CPU test meshes). A TPU must report: one without a
    ``bytes_limit``, or whose ``memory_stats()`` raises, is an error —
    never a silent 16 GiB assumption about an unknown part. Queried
    here, not in the planner, so host-side planning paths never import
    jax (planner.hbm_bytes_per_device). ``devices`` overrides the
    enumeration (tests)."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    limits = []
    for dev in devices:
        on_tpu = getattr(dev, "platform", None) == "tpu"
        try:
            stats = dev.memory_stats()
        except Exception:
            if on_tpu:
                raise
            continue
        limit = (stats or {}).get("bytes_limit")
        if limit and limit > 0:
            limits.append(int(limit))
        elif on_tpu:
            raise RuntimeError(
                f"{dev} reports no bytes_limit: cannot size the memory "
                "plan for it (set GRAPHMINE_HBM_BYTES to pin a budget)"
            )
    return min(limits) if limits else None


def _memory_sample() -> dict | None:
    """Measured memory for ``memory_watermark`` records (ISSUE 14):
    per-device ``bytes_in_use``/``peak_bytes_in_use`` when the backend's
    allocator exposes them (also cached for the heartbeat thread, which
    must never probe the runtime itself — obs/heartbeat.py), host RSS
    otherwise (``source: "rss"``). ``memory_stats`` is a host-side
    allocator query — sampling at the telemetry cadence adds zero
    device syncs."""
    per = []
    try:
        import jax

        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats() or {}
            except Exception:
                continue
            if stats.get("bytes_in_use") is None:
                continue
            in_use = int(stats["bytes_in_use"])
            per.append({
                "device": int(getattr(dev, "id", len(per))),
                "bytes_in_use": in_use,
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use") or in_use
                ),
                "bytes_limit": int(stats.get("bytes_limit") or 0) or None,
            })
    except Exception:
        per = []
    if per:
        from graphmine_tpu.obs.heartbeat import note_device_memory

        note_device_memory(per)
        # achieved is the CURRENT fleet-wide max (phase-attributable);
        # the lifetime peak and the smallest limit ride as context — the
        # device holding an old allocator peak may be near-idle NOW,
        # and reporting its current bytes would understate the phase.
        limits = [s["bytes_limit"] for s in per if s["bytes_limit"]]
        return {
            "bytes_in_use": max(s["bytes_in_use"] for s in per),
            "peak_bytes_in_use": max(
                s["peak_bytes_in_use"] for s in per
            ),
            "bytes_limit": min(limits) if limits else None,
            "source": "device",
        }
    from graphmine_tpu.obs.memmodel import rss_sample

    return rss_sample()


@dataclass
class PipelineResult:
    edge_table: EdgeTable
    graph: Graph
    labels: np.ndarray                 # community label per vertex
    num_communities: int
    community_table: tuple             # (labels present, sizes, intra-edge counts)
    outliers: object | None = None     # OutlierReport (recursive_lpa)
    lof: np.ndarray | None = None      # LOF score per vertex
    metrics: MetricsSink = field(default_factory=MetricsSink)


def run_pipeline(config: PipelineConfig) -> PipelineResult:
    config.validate()
    from graphmine_tpu.compile_cache import enable_compile_cache
    from graphmine_tpu.obs.spans import Tracer

    enable_compile_cache()

    # Records stream to --metrics-out AS EMITTED (MetricsSink.emit), not
    # only at exit: a preemption or OOM-kill skips every finally block,
    # and those are exactly the runs whose retry/degrade/rollback trail
    # the operator needs for offline triage. Every record carries the
    # tracer's run/trace/span identity (docs/OBSERVABILITY.md), and the
    # stream begins with a run_start header delimiting this run's segment
    # of a (possibly reused, append-mode) metrics file.
    tracer = Tracer(run_id=config.run_id)
    m = MetricsSink(stream_path=config.metrics_out, tracer=tracer)
    m.emit(
        "run_start", pid=os.getpid(), data_path=config.data_path,
        backend=config.backend, schedule=config.schedule,
        community_method=config.community_method, max_iter=config.max_iter,
    )
    hb = None
    if config.heartbeat_every_s:
        from graphmine_tpu.obs.heartbeat import Heartbeat

        hb = Heartbeat(
            m, every_s=config.heartbeat_every_s, prom_path=config.prom_out
        ).start()
    run_err: BaseException | None = None
    try:
        # profile_dir: one capture around every chapter of the run,
        # reduced into device_scope / device_idle records when it stops
        with maybe_profile(config.profile_dir, sink=m):
            result = _run_pipeline(config, m)
            if config.snapshot_out:
                # Serving hand-off (r7, docs/SERVING.md): the run's final
                # phase publishes labels/CC/LOF/census + edges as a
                # versioned snapshot generation the serve/ subsystem
                # queries and delta-repairs against.
                _publish_snapshot(config, result, m)
        return result
    except BaseException as e:
        run_err = e
        raise
    finally:
        # Finalized on EVERY exit, not just success: stop the heartbeat,
        # close the run with a run_end record (so offline triage can tell
        # a finished run from a killed one), publish the registry, close
        # the live stream or append what it never persisted. A failed
        # flush must not mask the pipeline's own outcome.
        if hb is not None:
            hb.stop()
        # The root span closes here and is written like every other
        # span, so the seconds of a run that no chapter span holds can
        # be read (root minus its children) and need not be inferred.
        root = tracer.close()
        m.emit(
            "span", _span=root, name=root.name,
            seconds=round(root.seconds, 4),
            status="ok" if run_err is None else "error",
        )
        if run_err is None:
            m.emit("run_end", ok=True)
        else:
            m.emit(
                "run_end", ok=False, error=resilience.classify_error(run_err),
                error_detail=repr(run_err),
            )
        import logging

        if config.prom_out:
            try:
                m.registry.write_textfile(
                    config.prom_out, labels={"run_id": tracer.run_id}
                )
            except OSError as prom_err:
                logging.getLogger("graphmine_tpu").warning(
                    "could not write --prom-out %s: %r",
                    config.prom_out, prom_err,
                )
        if config.metrics_out:
            try:
                m.finalize(config.metrics_out)
            except OSError as flush_err:
                logging.getLogger("graphmine_tpu").warning(
                    "could not write --metrics-out %s: %r",
                    config.metrics_out, flush_err,
                )


def _run_pipeline(config: PipelineConfig, m: MetricsSink) -> PipelineResult:
    # ---- CS-1 ingestion -------------------------------------------------
    def _load():
        resilience.fault_point("load", path=config.data_path)
        if config.data_format == "parquet":
            return load_parquet_edges(
                config.data_path, batch_rows=config.batch_rows, sink=m
            )
        return load_edge_list(
            config.data_path, weight_col=config.edge_weight_col,
            quarantine=config.quarantine_inputs,
        )

    with m.span("load"), m.timed(
        "load", path=config.data_path, format=config.data_format
    ):
        table = resilience.run_phase("load", _load, config.resilience, m)
    m.emit(
        "counts",  # parity with the prints at Graphframes.py:18 and :54
        rows_raw=table.num_rows_raw,
        edges=table.num_edges,
        vertices=table.num_vertices,
    )
    if table.quarantine and config.quarantine_inputs:
        # rows set aside instead of crashing ingestion (docs/RESILIENCE.md).
        # Gated on the flag: parquet loaders always count their null filter,
        # but --no-quarantine-inputs promises a strict-parsing run whose
        # metrics stream carries no quarantine records.
        m.emit("quarantine", **table.quarantine)

    # ---- CS-2 graph construction ---------------------------------------
    # Schedule resolution happens HERE, before any device allocation: the
    # memory planner (pipeline/planner.py) models per-device HBM for each
    # schedule and either picks one ("auto") or validates the requested
    # one — an impossible config raises PlanError with the numbers now,
    # instead of OOMing deep inside XLA after minutes of graph build.
    n_dev = config.num_devices or _visible_devices()
    run_plan = None
    if config.community_method == "lpa" and config.backend != "graphframes":
        from graphmine_tpu.pipeline.planner import (
            hbm_bytes_per_device,
            plan_run,
        )

        # Budget chain: env override → what THIS device actually reports
        # (a v4/v5p part has 2-6x the v5e default) → 16 GiB. The callable
        # keeps the device query lazy: an env-pinned budget never touches
        # memory_stats.
        run_plan = plan_run(
            table.num_vertices,
            table.num_edges,
            n_dev,
            weighted=table.weights is not None,
            requested=config.schedule,
            hbm=hbm_bytes_per_device(device_hbm_bytes),
        )
        from graphmine_tpu.obs.memmodel import schedule_footprint

        m.emit(
            "plan",
            schedule=run_plan.schedule,
            bytes_per_device=run_plan.bytes_per_device,
            hbm_budget=run_plan.hbm_bytes,
            reason=run_plan.reason,
            # the memory plane's named inventory behind bytes_per_device
            # (ISSUE 14): the same seeds the planner's accept/reject used,
            # decomposed — obs_report's recalibration suggestion compares
            # measured watermarks against exactly these components
            mem=schedule_footprint(
                run_plan.schedule, table.num_vertices, table.num_edges,
                n_dev, weighted=table.weights is not None,
            ).record(),
        )
    # The fused LPA plan is only consumed by the single-device jax LPA
    # path; build it (from the same message-CSR pass as the Graph) only
    # when that path will run — it is pure HBM/host waste for louvain,
    # graphframes, and sharded runs.
    wants_plan = run_plan is not None and run_plan.schedule == "single"
    # Which plan FAMILY that single-device path runs: bucketed at every
    # size, degrading to sort. The policy's crossover is the plan-build
    # cost, and here the plan is built in the SAME message-CSR pass as the
    # Graph, so it is cheap exactly where `auto` would say "sort".
    sstep_plan = None
    if wants_plan:
        from graphmine_tpu.pipeline.planner import SuperstepPlan

        sstep_plan = SuperstepPlan(
            family="bucketed",
            reason="single-device pipeline path: the plan is built in the "
            "graph's own message-CSR pass, so bucketed runs at every size",
        )
        # Plan-time memory pre-degrade (ISSUE 14): a family whose MODELED
        # footprint already exceeds the planning budget cannot survive
        # the build — consume its rung NOW, with the oversized inventory
        # in the degrade record, instead of letting XLA OOM after the
        # plan materializes. Honors degradation="off" (an operator who
        # sized the run wants the OOM, not a silently leaner family).
        if config.resilience.degradation == "auto":
            from graphmine_tpu.obs.memmodel import predegrade_superstep

            fam, _fit, steps = predegrade_superstep(
                sstep_plan.family, table.num_vertices, 2 * table.num_edges,
                table.num_edges, table.weights is not None,
                run_plan.hbm_bytes,
            )
            for depth, (frm, to, oversized) in enumerate(steps, 1):
                m.emit(
                    "degrade", stage="plan_superstep", to=to, depth=depth,
                    kind="mem_plan",
                    error=(
                        f"plan-time memory pre-degrade: modeled {frm!r} "
                        f"footprint {oversized.total_bytes:,} B exceeds "
                        f"the {run_plan.hbm_bytes:,} B budget"
                    ),
                    mem=oversized.record(),
                )
            if steps:
                sstep_plan = SuperstepPlan(
                    family=fam,
                    reason=sstep_plan.reason
                    + f" — pre-degraded to {fam!r}: modeled footprint of "
                    f"{steps[0][0]!r} exceeds the memory budget",
                )
        from graphmine_tpu.obs.costmodel import superstep_cost
        from graphmine_tpu.ops.superstep_policy import crossover_thresholds

        m.emit(
            "impl_selected", op="lpa_superstep", impl=sstep_plan.family,
            n=2 * table.num_edges, reason=sstep_plan.reason,
            # the deciding crossover constants + the model's pre-build
            # estimate (ISSUE 12; the plan_build record below carries the
            # exact padded counts once the plan exists)
            thresholds=crossover_thresholds(),
            cost=superstep_cost(
                "lpa_superstep", sstep_plan.family, table.num_vertices,
                2 * table.num_edges, table.num_edges,
                weighted=table.weights is not None,
            ).record(),
        )
    # Scale-out mode (r3): when the planner chose a distributed schedule
    # AND the whole graph cannot also fit one device, the full Graph stays
    # HOST-side NumPy — partitioning slices it onto the mesh, and the
    # census/modularity phases dispatch to their NumPy twins. Building it
    # device-resident here would OOM device 0 before LPA ever ran.
    scale_out = (
        run_plan is not None
        and run_plan.schedule != "single"
        and run_plan.estimates.get("single", 0) > run_plan.hbm_bytes
    )
    if scale_out:
        m.emit("scale_out", message="full graph exceeds one device: host-"
               "resident graph; outlier phases run distributed (recursive "
               "LPA over the intra-community subgraph, sharded kNN/LOF)")
    def _build():
        resilience.fault_point("build_graph")
        if wants_plan and sstep_plan.family != "sort":
            from graphmine_tpu.ops.bucketed_mode import build_graph_and_plan
            from graphmine_tpu.ops.superstep_policy import plan_build_stats

            t0 = time.perf_counter()
            g, plan = build_graph_and_plan(
                table.src, table.dst, num_vertices=table.num_vertices,
                edge_weights=table.weights,
            )
            # plan_build: the host plan cost, visible in obs_report
            # instead of hiding inside first-call latency (the
            # impl_selected record above already carries the rationale).
            from graphmine_tpu.obs.costmodel import superstep_cost

            m.emit(
                "plan_build", op="lpa_superstep",
                seconds=round(time.perf_counter() - t0, 6), cached=False,
                cost=superstep_cost(
                    "lpa_superstep", sstep_plan.family, table.num_vertices,
                    2 * table.num_edges, table.num_edges, plan=plan,
                ).record(),
                **plan_build_stats(plan, table.num_edges),
            )
            # single-element holder, not the bare plan: the LPA loop can
            # release the fused plan's padded device matrices when the
            # degradation ladder leaves the fused kernel, with no caller
            # frame still pinning a reference
            return g, [plan]
        return graph_from_edge_table(table, to_device=not scale_out), [None]

    with m.span("build_graph"), m.timed("build_graph"):
        graph, plan_holder = resilience.run_phase(
            "build_graph", _build, config.resilience, m
        )

    # ---- CS-3 community detection --------------------------------------
    if config.community_method in ("louvain", "leiden"):
        from graphmine_tpu.ops.louvain import leiden, louvain

        if config.checkpoint_dir:
            m.emit("warning", message="checkpoint/resume applies to LPA only; "
                   f"{config.community_method} runs are not checkpointed")
        algo = leiden if config.community_method == "leiden" else louvain
        with m.span(config.community_method), m.timed(
            config.community_method, gamma=config.gamma
        ):
            labels, q = algo(graph, gamma=config.gamma)
    else:
        with m.span("lpa"):
            labels = _run_lpa(
                config, table, graph, m, plan_holder, n_dev, run_plan,
                sstep_plan,
            )
        q = None

    # ---- CS-4 census ----------------------------------------------------
    from graphmine_tpu.ops.census import census_table
    from graphmine_tpu.ops.lpa import num_communities
    from graphmine_tpu.ops.modularity import modularity

    def _census():
        resilience.fault_point("census")
        n = int(num_communities(labels))
        table_ = census_table(labels, graph)
        qq = q if q is not None else float(
            modularity(labels, graph, gamma=config.gamma)
        )
        return n, table_, qq

    with m.span("census"), m.timed("census"):
        n_comm, (present, sizes, edge_counts), q = resilience.run_phase(
            "census", _census, config.resilience, m
        )
    # parity with "There are N Communities in the Dataset." (:85)
    m.emit("communities", count=n_comm, largest=int(sizes.max(initial=0)), modularity=round(q, 6))

    result = PipelineResult(
        edge_table=table,
        graph=graph,
        labels=np.asarray(labels),
        num_communities=n_comm,
        community_table=(present, sizes, edge_counts),
        metrics=m,
    )

    # ---- CS-5 outliers --------------------------------------------------
    if config.outlier_method in ("recursive_lpa", "both"):
        if scale_out:
            # The device-resident masked pass would materialize the full
            # graph on one device, which the planner just ruled out.
            # Run the distributed composition instead: host-side
            # intra-community edge filter → planner-resolved distributed
            # LPA schedule → host decile (VERDICT r3 item 2). scale_out
            # implies a multi-device plan (plan_run maps any request on
            # one device to "single"), so a mesh always exists here.
            from graphmine_tpu.ops.outliers import recursive_lpa_outliers_sharded
            from graphmine_tpu.parallel.mesh import make_mesh

            scorer = lambda: recursive_lpa_outliers_sharded(
                graph, labels, make_mesh(n_dev),
                max_iter=config.sub_max_iter, decile=config.decile,
                schedule=run_plan.schedule,
            )
            timing_kv = dict(schedule=run_plan.schedule, devices=n_dev)
        else:
            from graphmine_tpu.ops.outliers import recursive_lpa_outliers

            # the LPA chapter's fused plan, where it is still held (None
            # after a degrade to sort: the masked pass then sorts too)
            scorer = lambda: recursive_lpa_outliers(
                graph, labels, max_iter=config.sub_max_iter,
                decile=config.decile, sink=m, plan=plan_holder[0],
            )
            timing_kv = {}

        def _outliers():
            resilience.fault_point("outliers_recursive")
            return scorer()

        with m.span("outliers_recursive_lpa"), m.timed(
            "outliers_recursive_lpa", **timing_kv
        ):
            result.outliers = resilience.run_phase(
                "outliers_recursive", _outliers, config.resilience, m
            )
        m.emit(
            "outlier_summary",
            method="recursive_lpa",
            flagged_vertices=int(result.outliers.outlier_vertices.sum()),
            sub_communities=len(result.outliers.sub_sizes),
        )
    if config.outlier_method in ("lof", "both"):
        from graphmine_tpu.ops.features import (
            standardize,
            vertex_features,
            vertex_features_host,
        )
        from graphmine_tpu.ops.lof import lof_scores

        from graphmine_tpu.parallel.knn import can_shard
        from graphmine_tpu.pipeline.planner import plan_lof

        k = min(config.lof_k, graph.num_vertices - 1)
        use_sharded_lof = n_dev > 1 and can_shard(graph.num_vertices, n_dev, k)
        # Plan-time impl resolution (r6): the measured IVF crossover
        # (ops/lof.py provenance table) decides here, BEFORE any scorer
        # runs, so the degradation ladder below is built in the right
        # direction — exact primary gets the leaner IVF index as its OOM
        # rung; IVF primary gets the roofline-bounded exact tiles as its
        # rescue rung. The scorers re-apply the same policy function and
        # emit the impl_selected record through the sink.
        lof_plan = plan_lof(graph.num_vertices, k, requested=config.lof_impl)
        # Memory plane (ISSUE 14): the planned impl's workspace inventory
        # (exact [rows, n] distance/top-k tiles vs the IVF cluster-batched
        # model) — watermarked after scoring, attached to any OOM degrade.
        from graphmine_tpu.obs.memmodel import (
            emit_memory_watermark,
            lof_footprint,
        )

        lof_mem_holder = [lof_footprint(
            lof_plan.impl, graph.num_vertices, k, features=8,
            devices=n_dev if use_sharded_lof else 1,
        )]

        def _lof_degrade_context() -> dict:
            return {"mem": lof_mem_holder[0].record()}

        def _lof_rung_entered() -> None:
            # The ladder rung runs the OPPOSITE impl: re-point the holder
            # so the post-phase watermark pairs the surviving rung's
            # model with its measured peak (the failed primary's model
            # already rode the degrade record via _lof_degrade_context).
            lof_mem_holder[0] = lof_footprint(
                lof_plan.degrade_to, graph.num_vertices, k, features=8,
                devices=n_dev if use_sharded_lof else 1,
            )
        if use_sharded_lof and config.lof_impl in ("xla", "pallas"):
            m.emit(
                "warning",
                message=f"lof_impl={config.lof_impl!r} forces an exact "
                "single-device kernel; the multi-device path runs the "
                "exact ring-sharded kNN/LOF instead (auto/ivf DO apply "
                "to the sharded scorer)",
            )
        if scale_out and not use_sharded_lof:
            m.emit(
                "warning",
                message="lof skipped in scale-out mode: the all-pairs "
                "single-device scorer cannot hold a graph this size; add "
                "devices so the sharded kNN/LOF path can run",
            )
            return result
        # Wedge-budget guard (r5): the exact clustering pipeline used to
        # list every oriented wedge on the host (~28 B each) — a mega-hub
        # power-law graph at 25M edges has ~10^10 of them, and the first
        # e2e bench run was OOM-killed at 130 GB RSS before this guard
        # existed. Since PR 46 the exact kernel lists none (ops/triangles.py:
        # a plan, bit rows, row pairs; 1.4e10 wedges in 9.04 s on a v5e),
        # so the budget guards a host cost that is gone; it stays this PR so
        # that the pipeline's answers do not move (ROADMAP Queue 3,
        # Q3-wedge-budget). The probe is O(E log E) host work; past the
        # budget the clustering column comes from the sampled estimator
        # (stderr <= 1/(2*sqrt(64)) per vertex), same as scale-out mode.
        feature_mode = "device-8"
        simple_edges = None
        if not scale_out:
            from graphmine_tpu.graph.container import simple_undirected_edges
            from graphmine_tpu.ops.triangles import oriented_wedge_count

            wedge_budget = int(float(os.environ.get(
                "GRAPHMINE_WEDGE_BUDGET", "2.5e8"
            )))
            # One O(E log E) dedup, shared with the clustering column
            # below (exact or sampled) — the probe must not double the
            # host prep it exists to bound (code-review r5).
            simple_edges = simple_undirected_edges(graph)
            wedges = oriented_wedge_count(graph, simple_edges=simple_edges)
            if wedges > wedge_budget:
                feature_mode = "device-8-sampled"
                m.emit(
                    "warning",
                    message=f"exact clustering infeasible: {wedges:,} "
                    f"oriented wedges exceed GRAPHMINE_WEDGE_BUDGET="
                    f"{wedge_budget:,}; using the wedge-sampled estimator",
                )
        with m.span("outliers_lof"), m.timed(
                     "outliers_lof", k=config.lof_k,
                     devices=n_dev if use_sharded_lof else 1,
                     features="host-8-sampled" if scale_out else feature_mode):
            with stage_span(
                m, "lof_features", n=graph.num_vertices
            ) as stage:
                if scale_out:
                    # Host feature twin (no O(E) device transfer). The
                    # exact wedge pipeline is infeasible exactly when the
                    # graph exceeds one device, so the clustering column
                    # comes from the wedge-SAMPLED estimator (r4): the
                    # full 8-feature set survives at scale with a bounded
                    # per-vertex error
                    # (ops/triangles.sampled_clustering_coefficient).
                    feats = standardize(vertex_features_host(
                        graph, labels, include_clustering="sampled"
                    ))
                else:
                    feats = stage.sync(standardize(vertex_features(
                        graph, labels,
                        include_clustering=(
                            "sampled" if feature_mode == "device-8-sampled"
                            else True
                        ),
                        simple_edges=simple_edges, sink=m,
                    )))
            if use_sharded_lof:
                # Multi-device (parallel/knn.py): the planner-resolved
                # family — IVF candidate reduction with the search stage
                # sharded over the mesh at crossover scale (r6), else the
                # exact ring-sharded kNN — plus the opposite family as
                # the degradation rung.
                from graphmine_tpu.parallel.knn import sharded_lof
                from graphmine_tpu.parallel.mesh import make_mesh

                impl_sharded = (
                    "ivf" if lof_plan.impl == "ivf" else "exact"
                )

                def _score():
                    resilience.fault_point("outliers_lof")
                    return sharded_lof(
                        feats, make_mesh(n_dev), k=k, impl=impl_sharded,
                        sink=m,
                    )

                def _rung_sharded():
                    _lof_rung_entered()
                    return sharded_lof(
                        feats, make_mesh(n_dev), k=k,
                        impl=lof_plan.degrade_to, sink=m,
                    )

                ladder = ((
                    f"lof_sharded_{lof_plan.degrade_to}", _rung_sharded,
                ),)
            else:
                # Planner-selected family (r6): impl="auto" deploys the
                # IVF index at the measured crossover scale (~3.1x at
                # 262K points for ~0.001 AUROC — ops/lof.py provenance);
                # config.lof_impl passes through so explicit choices
                # stay honored, and lof_scores re-applies the same
                # policy + emits the impl_selected record.
                def _score():
                    resilience.fault_point("outliers_lof")
                    return lof_scores(feats, k=k, impl=config.lof_impl, sink=m)

                # Degradation rung, direction from the plan: the exact
                # scorer's [V, V] distance tiles OOM -> the IVF index's
                # bounded candidate set; the IVF scorer's data-dependent
                # pair tables blow up -> the roofline-bounded exact path.
                rung_impl = (
                    "xla" if lof_plan.degrade_to == "exact" else "ivf"
                )

                def _rung_fused():
                    _lof_rung_entered()
                    return lof_scores(feats, k=k, impl=rung_impl, sink=m)

                ladder = ((
                    f"lof_{lof_plan.degrade_to}", _rung_fused,
                ),)
            scores = resilience.run_phase(
                "outliers_lof", _score, config.resilience, m, ladder=ladder,
                degrade_context=_lof_degrade_context,
            )
            result.lof = np.asarray(scores)
            # Phase-cadence watermark (ISSUE 14): the workspace model of
            # the impl that actually SCORED (the holder re-points on a
            # rung entry) vs the bytes peaked while scoring.
            emit_memory_watermark(
                m, "lof_knn", lof_mem_holder[0], _memory_sample(),
                budget_bytes=run_plan.hbm_bytes if run_plan is not None
                else None,
                impl=lof_mem_holder[0].family,
            )
        m.emit(
            "outlier_summary",
            method="lof",
            max_score=float(result.lof.max()),
            over_1_5=int((result.lof > 1.5).sum()),
        )
    return result


def _publish_snapshot(config: PipelineConfig, result: PipelineResult, m: MetricsSink) -> None:
    """Publish the pipeline's outputs as one snapshot generation.

    CC labels are computed here (the pipeline itself has no CC phase):
    device-resident graphs run the fused single-device fixpoint; host-
    resident graphs (scale-out mode) shard over the mesh — the planner
    just ruled out materializing them on one device. Wrapped in
    ``run_phase`` so transient publish weather retries like any phase.
    """
    from graphmine_tpu.serve.snapshot import SnapshotStore, publish_result

    table, graph = result.edge_table, result.graph
    n_dev = config.num_devices or _visible_devices()

    def _publish():
        resilience.fault_point("snapshot_publish")
        # Stage spans (docs/OBSERVABILITY.md "Stage spans"): the CC and
        # the fetch of its labels are `publish_cc`; the five stages of
        # the shared tail (serve/snapshot.publish_result) follow it.
        with stage_span(m, "publish_cc") as stage:
            if isinstance(graph.src, np.ndarray):
                from graphmine_tpu.parallel.mesh import make_mesh
                from graphmine_tpu.parallel.sharded import (
                    partition_graph,
                    shard_graph_arrays,
                    sharded_connected_components,
                )

                from graphmine_tpu.obs.costmodel import (
                    emit_superstep_timing,
                    sharded_superstep_cost,
                    timed_fixpoint,
                )

                mesh = make_mesh(n_dev)
                sg = shard_graph_arrays(
                    partition_graph(graph, mesh=mesh), mesh
                )
                # telemetry=True returns the real supersteps-to-fixpoint
                # on the existing while-loop carry (no extra device
                # syncs) — the CC phase's achieved-vs-model window
                # (ISSUE 12).
                (cc_labels, tele), secs, cold = timed_fixpoint(
                    lambda: sharded_connected_components(
                        sg, mesh, telemetry=True
                    )
                )
                supersteps = tele.iterations
                emit_superstep_timing(
                    m, "cc_superstep",
                    sharded_superstep_cost(
                        "cc_superstep", sg, graph.num_edges,
                        num_messages=graph.num_messages, weighted=False,
                    ),
                    supersteps, supersteps, secs, graph.num_edges,
                    variant="sharded", cold_compile=cold,
                )
            else:
                from graphmine_tpu.ops.cc import connected_components

                # sink=m: the auto seam emits impl_selected/plan_build
                # AND the CC phase's superstep_timing record (ops/cc.py);
                # with a sink the program counts its supersteps anyway.
                cc_labels, supersteps = connected_components(
                    graph, return_iterations=True, sink=m
                )
            cc = np.asarray(cc_labels)
            stage.note(supersteps=int(supersteps))
        present, sizes, edge_counts = result.community_table
        columns = {
            "src": (table.src, np.int32),
            "dst": (table.dst, np.int32),
            "labels": (result.labels, np.int32),
            "cc_labels": (cc, np.int32),
            "census_present": present,
            "census_sizes": sizes,
            "census_edges": edge_counts,
        }
        if result.lof is not None:
            columns["lof"] = (result.lof, np.float32)
        if table.weights is not None:
            # Preserved so queries/provenance keep the real graph; the
            # delta-repair path refuses weighted snapshots loudly (its
            # propagations are unweighted — repairing weighted-LPA labels
            # with unweighted supersteps would silently change semantics).
            columns["weights"] = (table.weights, np.float32)
        store = SnapshotStore(config.snapshot_out)
        # Result-quality plane (ISSUE 13, docs/OBSERVABILITY.md "Result
        # quality"): a driver publish is the version chain's first link —
        # seed/readopt the canary probe so the serving writer scores the
        # SAME frozen probe, read the parent's result columns for drift,
        # and emit quality_snapshot/quality_drift/canary_score in the
        # publishing trace. GRAPHMINE_QUALITY=0 disables; failures are
        # telemetry-only and never fail the publish phase (publish_result).
        parent: dict = {"arrays": {}, "meta": {}}

        def _canary():
            from graphmine_tpu.obs.quality import CanaryProbe

            peeked = store.peek_arrays(
                ("labels", "lof", "canary_features", "canary_is_anomaly")
            )
            if peeked is not None:
                parent["arrays"], parent["meta"] = peeked
            return CanaryProbe.from_arrays(
                parent["arrays"], parent["meta"]
            ) or CanaryProbe.generate(
                seed=int(os.environ.get("GRAPHMINE_CANARY_SEED", "0"))
            )

        def _quality(snap, arrays, canary):
            from graphmine_tpu.obs.quality import run_quality_pass

            run_quality_pass(
                arrays["labels"], arrays.get("lof"), snap.version,
                parent_labels=parent["arrays"].get("labels"),
                parent_lof=parent["arrays"].get("lof"),
                parent_version=parent["meta"].get("version"),
                canary=canary, sink=m, registry=m.registry,
            )

        quality_on = os.environ.get("GRAPHMINE_QUALITY", "1") != "0"
        return publish_result(
            store, columns, sink=m,
            canary=_canary if quality_on else None,
            quality=_quality if quality_on else None,
            run_id=m.tracer.run_id if m.tracer is not None else "",
            mesh_shape=[n_dev],
        )

    with m.span("snapshot_publish"):
        resilience.run_phase(
            "snapshot_publish", _publish, config.resilience, m
        )


def _emit_superstep_telemetry(
    m: MetricsSink, new, old, chunk: int, ndev: int, variant: str,
    iteration: int,
) -> int:
    """``superstep_telemetry`` record: per-shard active counts and the
    load-imbalance ratio for one superstep. Called only at the existing
    tripwire/checkpoint cadence boundaries, where the driver already
    syncs per superstep — the reduction runs on device and only a
    [D]-int vector crosses to the host. Shards are the REAL partition
    chunks (``chunk`` is partition_graph's padded size); shard count is
    clamped to the chunks that actually cover real vertices, so the
    per-shard counts sum to exactly the labels-changed total — which is
    returned, sparing the caller a second full-vertex diff pass."""
    import jax.numpy as jnp

    d = max(1, min(int(ndev), -(-int(new.shape[0]) // max(chunk, 1))))
    diff = new != old
    pad = d * chunk - int(diff.shape[0])
    if pad > 0:
        diff = jnp.concatenate([diff, jnp.zeros((pad,), diff.dtype)])
    per = np.asarray(
        jnp.sum(jnp.reshape(diff, (d, chunk)), axis=1, dtype=jnp.int32)
    )
    changed = int(per.sum())
    mean = changed / d
    imbalance = float(per.max()) / mean if mean > 0 else 1.0
    m.emit(
        "superstep_telemetry",
        iteration=iteration,
        labels_changed=changed,
        # synchronous LPA's frontier IS the changed set: exactly the
        # vertices whose neighbors must re-reduce next superstep
        frontier=changed,
        shard_changed=per.tolist(),
        shard_max=int(per.max()),  # per is never empty: d >= 1
        shard_min=int(per.min()),
        imbalance=round(imbalance, 3),
        devices=int(ndev),
        variant=variant,
    )
    return changed


def _run_lpa(
    config: PipelineConfig, table: EdgeTable, graph: Graph, m: MetricsSink,
    plan_holder: list, n_dev: int, run_plan, sstep_plan=None,
):
    """Community detection with backend dispatch, checkpointing and
    per-iteration metrics. Runs iterations one jit call at a time so the
    labels-changed counter and edges/sec are observable (the whole loop is
    still device-resident; only the scalar counter syncs)."""
    if config.backend == "graphframes":
        from graphmine_tpu.pipeline.backends import lpa_graphframes

        with m.timed("lpa", backend="graphframes"):
            return lpa_graphframes(table, config.max_iter)

    import jax
    import jax.numpy as jnp

    from graphmine_tpu.obs.costmodel import (
        WindowTimer,
        sharded_superstep_cost,
        superstep_cost,
    )
    from graphmine_tpu.obs.memmodel import (
        emit_memory_watermark,
        sharded_superstep_footprint,
        superstep_footprint,
    )
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_label_propagation,
    )

    # Achieved-vs-model window timing (ISSUE 12): per-superstep wall
    # durations accumulate here and flush as `superstep_timing` records
    # at the EXISTING telemetry cadence — the driver already syncs every
    # superstep for the labels-changed counter, so this adds zero device
    # syncs. Each operating point (make_superstep) installs its own cost
    # estimate in current["cost"].
    wtimer = WindowTimer()
    chips = max(n_dev, 1)
    start_iter = 0
    labels = jnp.arange(graph.num_vertices, dtype=jnp.int32)

    # One O(E) hash per run; ties every checkpoint to this exact graph,
    # id assignment (bulk vs batch_rows ingestion assign different ids),
    # and edge weights (weighted/unweighted trajectories differ).
    fingerprint = (
        ckpt.graph_fingerprint(table.src, table.dst, table.weights)
        if config.checkpoint_dir else None
    )

    def _reload_checkpoint():
        """Newest recoverable state across BOTH checkpoint formats
        (sharded manifest + npz; the higher iteration wins, one corrupt
        format does not veto the other) — see checkpoint.load_newest."""
        return ckpt.load_newest(
            config.checkpoint_dir, fingerprint=fingerprint, sink=m
        )

    if config.resume and config.checkpoint_dir:
        loaded = _reload_checkpoint()
        if loaded is not None:
            saved_labels, start_iter = loaded
            if start_iter > config.max_iter:
                raise ValueError(
                    f"checkpoint at iteration {start_iter} exceeds "
                    f"max_iter={config.max_iter}; delete the checkpoint or "
                    f"raise max_iter"
                )
            labels = jnp.asarray(saved_labels, dtype=jnp.int32)
            m.emit("resume", iteration=start_iter)

    # Dispatch on the planner-resolved schedule (plan_run maps an explicit
    # "ring"/"replicated" request on one device to "single").
    if config.schedule == "ring" and run_plan.schedule == "single":
        m.emit("warning", message="schedule='ring' needs >1 device; "
               "running the single-device fused kernel instead")

    policy = config.resilience
    # Mutable loop state shared by every ladder rung: a retry re-enters
    # and a degradation steps down FROM THE LAST GOOD SUPERSTEP, never
    # from iteration 0 — supersteps are deterministic, so a resumed
    # trajectory is byte-identical to an uninterrupted one.
    state = {"labels": labels, "it": start_iter}
    # The ACTIVE operating point: the elastic device rungs shrink "ndev"
    # below the starting mesh, and the sharded-checkpoint writer splits
    # by whatever is current (a checkpoint's shard count is metadata, not
    # a restore constraint — load_sharded re-shards).
    current = {"ndev": n_dev, "variant": run_plan.schedule}
    # The last memory_watermark record emitted (ISSUE 14): a reactive
    # OOM's degrade record attaches it (plus the active operating
    # point's modeled inventory) via run_phase's degrade_context, so
    # model-miss vs fragmentation is triageable from the JSONL alone —
    # joinable back to the full watermark by span path.
    last_watermark: dict = {"rec": None}

    def _mem_watermark(op_iteration: int, variant: str, ndev: int) -> None:
        rec = emit_memory_watermark(
            m, "lpa_superstep", current.get("mem"), _memory_sample(),
            budget_bytes=run_plan.hbm_bytes, iteration=int(op_iteration),
            variant=variant, devices=int(ndev),
        )
        if rec is not None:
            last_watermark["rec"] = rec

    def _lpa_degrade_context() -> dict:
        ctx = {}
        est = current.get("mem")
        if est is not None:
            ctx["mem"] = est.record()
        w = last_watermark["rec"]
        if w is not None:
            ctx["last_watermark"] = {
                k: w.get(k)
                for k in (
                    "t", "op", "iteration", "predicted_bytes",
                    "achieved_bytes", "headroom_frac", "source",
                    "span_path",
                )
            }
        return ctx
    # Device indices implicated in a device-loss error (parsed best-effort
    # from its message): the runtime usually still LISTS a chip that just
    # failed a collective, and a rung mesh built from the first N visible
    # devices would re-enroll it — every halved rung would then die the
    # same death, exhausting the elastic ladder without ever routing
    # around the loss.
    dead_devices: set = set()

    def _rung_mesh(ndev: int):
        from graphmine_tpu.parallel.mesh import surviving_mesh

        if dead_devices:
            try:
                return surviving_mesh(ndev, exclude=sorted(dead_devices))
            except ValueError:
                # exclusions leave too few survivors: better to try the
                # first-N mesh (maybe the parse over-matched) than abort
                pass
        return make_mesh(ndev)

    def _note_dead_devices() -> None:
        """Harvest chip indices from the device-loss error that triggered
        this descent (run_phase records it in the degrade event just
        before invoking the rung). Message parsing is best-effort — an
        unattributed loss still degrades, just without the exclusion."""
        import re

        device_degrades = [
            r for r in m.of_phase("degrade") if r.get("kind") == "device"
        ]
        if device_degrades:
            for tok in re.findall(
                r"(?:chip|device)\s+#?(\d+)",
                device_degrades[-1].get("error", ""),
            ):
                dead_devices.add(int(tok))

    def make_superstep(variant: str, ndev: int):
        """Build the per-superstep callable for one operating point
        (schedule x device count: the planner's memory rungs keep the
        mesh and lean the schedule; the elastic device rungs keep the
        schedule and shrink the mesh)."""
        if variant == "ring":
            # Memory-scalable schedule: labels stay sharded, chunks rotate
            # over ICI (parallel/ring.py). Uses the sort-body message CSR.
            from graphmine_tpu.parallel.ring import ring_label_propagation

            mesh = _rung_mesh(ndev)
            with m.timed("partition", shards=ndev, schedule="ring"):
                sg = shard_graph_arrays(partition_graph(graph, mesh=mesh), mesh)
            current["chunk_size"] = sg.chunk_size
            current["cost"] = sharded_superstep_cost(
                "lpa_superstep", sg, graph.num_edges,
                num_messages=graph.num_messages,
            )
            current["mem"] = sharded_superstep_footprint(
                "lpa_superstep", sg, schedule="ring",
            )
            return lambda lbl: ring_label_propagation(
                sg, mesh, max_iter=1, init_labels=lbl
            )
        if variant == "replicated":
            mesh = _rung_mesh(ndev)
            # the family is the mesh policy owner's (plan_run asked
            # select_superstep_family with num_devices=D)
            lpa_only = run_plan.lpa_only and run_plan.family != "sort"
            with m.timed("partition", shards=ndev, schedule="replicated"):
                sg = shard_graph_arrays(
                    partition_graph(
                        graph, mesh=mesh, lpa_only=lpa_only,
                        build_bucket_plan=run_plan.family != "sort",
                    ),
                    mesh,
                    lpa_only=lpa_only,
                )
            current["chunk_size"] = sg.chunk_size
            current["cost"] = sharded_superstep_cost(
                "lpa_superstep", sg, graph.num_edges,
                num_messages=graph.num_messages,
            )
            current["mem"] = sharded_superstep_footprint(
                "lpa_superstep", sg, schedule="replicated",
            )
            return lambda lbl: sharded_label_propagation(
                sg, mesh, max_iter=1, init_labels=lbl
            )
        if variant == "single_sort":
            # Degradation rung: the plain sort-based superstep over the
            # bare message CSR — no padded bucket matrices, ~identical
            # labels by construction (tests/test_lpa.py pins parity).
            from graphmine_tpu.ops.lpa import lpa_superstep

            current["chunk_size"] = graph.num_vertices
            current["cost"] = superstep_cost(
                "lpa_superstep", "sort", graph.num_vertices,
                graph.num_messages, graph.num_edges,
                weighted=graph.msg_weight is not None,
            )
            current["mem"] = superstep_footprint(
                "lpa_superstep", "sort", graph.num_vertices,
                graph.num_messages, num_edges=graph.num_edges,
                weighted=graph.msg_weight is not None,
            )
            step = jax.jit(lpa_superstep)
            return lambda lbl: step(lbl, graph)
        # "single": the degree-bucketed kernel (ops/bucketed_mode.py,
        # ~3x the sort superstep). The plan was built alongside the Graph
        # from one shared message-CSR pass (wants_plan in run_pipeline is
        # true exactly for this branch).
        from graphmine_tpu.ops.bucketed_mode import lpa_superstep_bucketed

        if plan_holder[0] is None:
            raise ValueError("single-device LPA requires the fused plan "
                             "built by run_pipeline (wants_plan)")
        current["chunk_size"] = graph.num_vertices
        plan = plan_holder[0]
        current["cost"] = superstep_cost(
            "lpa_superstep", "auto", graph.num_vertices,
            graph.num_messages, graph.num_edges, plan=plan,
        )
        current["mem"] = superstep_footprint(
            "lpa_superstep", "auto", graph.num_vertices,
            graph.num_messages, num_edges=graph.num_edges, plan=plan,
        )
        step = jax.jit(lpa_superstep_bucketed)
        return lambda lbl: step(lbl, graph, plan)

    def save_ck(iteration: int) -> None:
        if not config.checkpoint_dir:
            return
        if current["ndev"] > 1:
            # Distributed rungs write the shard-aware manifest format:
            # per-shard files + sha256 manifest, re-shardable on restore
            # (the elastic path after a chip loss resumes on D' != D).
            ckpt.save_sharded(
                config.checkpoint_dir, np.asarray(state["labels"]),
                iteration, fingerprint=fingerprint,
                num_shards=current["ndev"], sink=m,
            )
        else:
            ckpt.save_labels(
                config.checkpoint_dir, state["labels"], iteration,
                fingerprint=fingerprint, sink=m,
            )

    # Built supersteps survive retry re-entry: a transient failure at
    # superstep N must not repartition/reshard the whole graph (minutes
    # of host+device work at scale) nor emit a duplicate "partition"
    # record before resuming at N. Keyed (variant, ndev): the elastic
    # rungs rebuild the same schedule on a smaller mesh.
    superstep_cache: dict = {}
    # Operating points that have completed >=1 superstep in THIS build:
    # the first superstep of a freshly built point includes its XLA
    # compile, which can dwarf the steady-state bound the operator sized
    # the watchdog for — arming it there would kill the very rung a
    # degradation just rescued the run with. The watchdog arms from the
    # second superstep.
    warmed: set = set()
    # Operating points whose entry preamble (cache purge, device-loss
    # state salvage, mesh_degrade record) already ran: transient-retry
    # re-entries must not re-salvage or re-emit.
    entered: set = set()
    trip_k = policy.tripwire_every_k

    def check_tripwire(new, it: int, variant: str) -> None:
        """Host-side divergence tripwire at the superstep boundary (the
        driver already syncs each superstep for the labels-changed
        counter, so the guard costs one more reduction every K steps).
        Real vertices can only ever carry real vertex ids — the mode /
        min of incoming real labels, or their own id — so anything
        outside [0, V) means corrupted state. The in-memory iterate is
        untrusted after a trip: roll back to the last checkpoint before
        raising the (retryable) error, so the retry resumes from trusted
        bytes instead of re-propagating the garbage."""
        bad = (new < 0) | (new >= graph.num_vertices)
        n_bad = int(bad.sum())
        if not n_bad:
            return
        # The REAL per-device chunk (partition_graph's padded size,
        # recorded by make_superstep) — a ceil(V/D) approximation would
        # attribute boundary vertices to the wrong shard.
        chunk = current.get("chunk_size") or graph.num_vertices
        shard = int(jnp.argmax(bad)) // chunk
        err = resilience.DivergenceError(
            "label_out_of_range", shard, it + 1
        )
        m.tripwire(
            err.kind, err.shard, err.iteration,
            stage="lpa", bad_vertices=n_bad, variant=variant,
        )
        restored = (
            _reload_checkpoint() if config.checkpoint_dir else None
        )
        if restored is not None:
            state["labels"] = jnp.asarray(restored[0], dtype=jnp.int32)
            state["it"] = restored[1]
            m.emit("resume", iteration=restored[1], reason="tripwire")
        raise err

    def make_runner(variant: str | None, ndev: int | None = None):
        """The remaining-supersteps loop at one operating point. Runs
        iterations one jit call at a time so the labels-changed counter
        and edges/sec stay observable (the loop is device-resident; only
        the scalar counter syncs) and every superstep is a watchdog +
        checkpoint + tripwire boundary. ``ndev=None`` inherits the mesh
        size current at entry (memory rungs lean the schedule wherever
        the elastic ladder already moved the run); an explicit ``ndev``
        is an elastic device rung. ``variant=None`` inherits the variant
        current at entry: a device rung must rebuild the schedule the run
        was ACTUALLY using — re-running the planner's original choice
        would undo a memory degradation whose rung was already consumed
        (replicated OOMs -> ring rescues -> chip dies -> the smaller mesh
        must run ring, not replicated again)."""

        def run():
            nd = current["ndev"] if ndev is None else ndev
            var = current["variant"] if variant is None else variant
            key = (var, nd)
            if key not in entered:
                entered.add(key)
                if nd < current["ndev"]:
                    # Elastic descent: route the rung mesh around the
                    # implicated chip(s), and salvage the loop state —
                    # the failed mesh's device arrays may be GONE with
                    # the lost chip. In-memory labels when the host
                    # transfer still works, else the last sharded
                    # checkpoint (re-shard on restore handles the new
                    # device count).
                    _note_dead_devices()
                    try:
                        host_labels = np.asarray(state["labels"])
                        resumed_from = "memory"
                    except Exception as salvage_err:
                        restored = (
                            _reload_checkpoint()
                            if config.checkpoint_dir else None
                        )
                        if restored is None:
                            raise RuntimeError(
                                "device loss with no recoverable state: "
                                "the in-memory labels died with the mesh "
                                f"({salvage_err!r}) and no checkpoint "
                                "exists — set checkpoint_dir to make "
                                "device loss survivable"
                            ) from salvage_err
                        host_labels, state["it"] = restored
                        resumed_from = "checkpoint"
                    state["labels"] = jnp.asarray(
                        host_labels, dtype=jnp.int32
                    )
                    m.emit(
                        "mesh_degrade", from_devices=current["ndev"],
                        to_devices=nd, schedule=var,
                        iteration=state["it"], resumed_from=resumed_from,
                        dead_devices=sorted(dead_devices),
                    )
            current["ndev"], current["variant"] = nd, var
            # The ladder degrades BECAUSE device memory ran out (or a
            # chip died): before building this rung's superstep, release
            # everything the failed rung held on device — its cached
            # superstep closure (sharded label/bucket arrays) and, once
            # the fused kernel is abandoned, the plan's padded bucket
            # matrices. Retries re-enter the SAME operating point, so its
            # cache entry survives.
            for stale in [k for k in superstep_cache if k != key]:
                del superstep_cache[stale]
                warmed.discard(stale)  # re-entry would recompile
            if var != "single":
                plan_holder[0] = None
            if key not in superstep_cache:
                superstep_cache[key] = make_superstep(var, nd)
            one_iter = superstep_cache[key]
            m.registry.gauge(
                "graphmine_devices_alive",
                "devices in the active LPA mesh",
            ).set(nd)
            # A rung entry (or retry re-entry) starts a fresh timing
            # window: a window must never mix supersteps from two
            # operating points — the cost model it is judged against is
            # per-point.
            wtimer.reset()
            # Rung-entry watermark (ISSUE 14): predicted footprint of the
            # operating point just built vs the bytes actually resident —
            # the baseline an OOM later in this rung is triaged against
            # (memory_stats is a host query; no device sync).
            _mem_watermark(state["it"], var, nd)
            while state["it"] < config.max_iter:
                it = state["it"]

                def step_sync():
                    resilience.fault_point(
                        "lpa_superstep", iteration=it + 1, variant=var,
                        state=state, num_shards=nd,
                    )
                    new = one_iter(state["labels"])
                    new.block_until_ready()
                    return new

                # Superstep span (emit=False: lpa_iter IS the superstep
                # record, already carrying this span's identity — a span
                # record per superstep would double the stream). The
                # TraceAnnotation names the XLA profiler slice after the
                # span path, lining device traces up with the span tree.
                with m.span("superstep", emit=False, iteration=it + 1):
                    was_warm = key in warmed
                    t0 = time.perf_counter()
                    # Watchdog contract: checkpoint-then-abort. On a hung
                    # superstep the LAST GOOD labels (iteration `it`) are
                    # saved before SuperstepTimeout surfaces, so the run
                    # resumes exactly where it hung. Unarmed (None) for an
                    # operating point's compile-bearing first superstep —
                    # see ``warmed`` above.
                    new = resilience.run_with_watchdog(
                        "lpa_superstep", step_sync,
                        policy.superstep_timeout_s if was_warm else None,
                        m,
                        # no hook at all without a checkpoint_dir: the
                        # timeout message/record must not claim a
                        # checkpoint was saved
                        on_timeout=(
                            (lambda it=it: save_ck(it))
                            if config.checkpoint_dir else None
                        ),
                    )
                    dt = time.perf_counter() - t0
                    warmed.add(key)
                    if was_warm:
                        # the compile-bearing first superstep of an
                        # operating point is excluded from the timing
                        # window, exactly like the watchdog above — a
                        # compile-dominated window would read far below
                        # model on healthy hardware, the false positive
                        # the roofline flag exists to avoid
                        wtimer.add(dt)
                    # Cadence (r3): every Nth superstep, plus always the
                    # final one so a completed run's checkpoint is never
                    # stale.
                    will_save = config.checkpoint_dir and (
                        (it + 1) % config.checkpoint_every == 0
                        or it + 1 == config.max_iter
                    )
                    # A superstep that will CHECKPOINT is always guarded
                    # too (when tripwires are armed): persisting
                    # unverified labels would rotate the last
                    # tripwire-validated generation away, and the rollback
                    # the tripwire promises would restore
                    # intact-but-garbage bytes.
                    if trip_k and ((it + 1) % trip_k == 0 or will_save):
                        check_tripwire(new, it, var)
                    # Superstep telemetry piggybacks on the EXISTING
                    # cadence (tripwire / checkpoint boundaries, plus the
                    # final superstep): the driver already syncs each
                    # superstep for the labels-changed counter, so the
                    # per-shard [D] fetch adds no sync point — and
                    # off-cadence supersteps pay nothing. At a telemetry
                    # boundary the changed count comes from the per-shard
                    # sums (one diff pass, not two).
                    if will_save or it + 1 == config.max_iter or (
                        trip_k and (it + 1) % trip_k == 0
                    ):
                        changed = _emit_superstep_telemetry(
                            m, new, state["labels"],
                            current.get("chunk_size") or graph.num_vertices,
                            nd, var, it + 1,
                        )
                        # superstep_timing rides the same cadence: the
                        # window since the last boundary, judged against
                        # this operating point's cost model (ISSUE 12).
                        wtimer.flush(
                            m, "lpa_superstep", current.get("cost"),
                            it + 1, graph.num_edges, variant=var,
                        )
                        # memory_watermark rides the same boundary
                        # (ISSUE 14): predicted vs measured peak for
                        # this operating point, zero extra syncs.
                        _mem_watermark(it + 1, var, nd)
                    else:
                        changed = int((new != state["labels"]).sum())
                    state["labels"] = new
                    state["it"] = it + 1
                    reg = m.registry
                    reg.gauge(
                        "graphmine_superstep", "last completed LPA superstep"
                    ).set(it + 1)
                    reg.gauge(
                        "graphmine_labels_changed",
                        "labels changed in the last superstep",
                    ).set(changed)
                    reg.counter(
                        "graphmine_supersteps_total",
                        "LPA supersteps completed this run",
                    ).inc()
                    m.lpa_iteration(it + 1, changed, graph.num_edges, dt, chips)
                    if will_save:
                        save_ck(it + 1)
            return state["labels"]

        return run

    from graphmine_tpu.pipeline.planner import (
        degradation_ladder,
        elastic_device_ladder,
    )

    rungs = degradation_ladder(
        run_plan.schedule, n_dev,
        family=sstep_plan.family if sstep_plan is not None else "bucketed",
    )
    # Elastic device rungs (DEGRADABLE_DEVICE failures): halved mesh,
    # resumed from salvage/checkpoint, running the variant CURRENT at
    # descent time (variant=None) — a memory degradation that already
    # moved the run off the planner's original schedule must survive the
    # descent (replicated OOMs -> ring rescues -> chip dies -> ring@2dev,
    # never replicated again). The 1-device floor runs the sort-based
    # single kernel — only when the full graph fits one device (in
    # scale-out mode there is no such floor).
    device_rungs = []
    for d2 in elastic_device_ladder(run_plan.schedule, n_dev):
        if d2 > 1:
            device_rungs.append(
                (f"elastic@{d2}dev", make_runner(None, d2))
            )
        elif run_plan.estimates.get("single", 0) <= run_plan.hbm_bytes:
            device_rungs.append(
                ("single_sort@1dev", make_runner("single_sort", 1))
            )
    # A run pre-degraded to the "sort" family runs the sort superstep as
    # its primary — no plan was built, and "single" would demand one.
    primary = (
        "single_sort"
        if (
            run_plan.schedule == "single"
            and sstep_plan is not None and sstep_plan.family == "sort"
        )
        else run_plan.schedule
    )
    return resilience.run_phase(
        "lpa", make_runner(primary), policy, m,
        ladder=tuple((v, make_runner(v)) for v in rungs),
        device_ladder=tuple(device_rungs),
        # supersteps advanced since the last failure => a NEW incident:
        # the retry budget bounds attempts per incident, not per run
        progress=lambda: state["it"],
        # a reactive OOM's degrade record carries the failed point's
        # modeled inventory + the last watermark (ISSUE 14)
        degrade_context=_lpa_degrade_context,
    )


def main(argv=None) -> None:
    import logging

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    from graphmine_tpu.pipeline.config import parse_args

    config = parse_args(argv)  # --help / bad flags exit before jax loads
    result = run_pipeline(config)
    _show(result, config.show)


def _show(result: PipelineResult, n: int) -> None:
    """Terminal summary (parity with the reference's .show(10) calls)."""
    present, sizes, edges = result.community_table
    order = np.argsort(sizes)[::-1][:n]
    print(f"\nVertices: {result.edge_table.num_vertices}  "
          f"Edges: {result.edge_table.num_edges}")
    print(f"There are {result.num_communities} Communities in the Dataset.")
    print(f"\nTop {len(order)} communities (label, vertices, intra-edges):")
    for i in order:
        name = result.edge_table.names[present[i]]
        print(f"  {present[i]:>8}  {sizes[i]:>8}  {edges[i]:>8}   ({name})")
    if result.outliers is not None:
        print(f"\nRecursive-LPA outliers: {int(result.outliers.outlier_vertices.sum())} "
              f"vertices in bottom-decile sub-communities")
    if result.lof is not None:
        top = np.argsort(result.lof)[::-1][:n]
        print(f"\nTop {len(top)} LOF outliers (vertex, score, name):")
        for v in top:
            print(f"  {v:>8}  {result.lof[v]:>7.3f}   ({result.edge_table.names[v]})")


if __name__ == "__main__":
    main()
