"""Record-schema registry: every emitted phase name is declared here.

The metrics stream is an append-only JSONL of heterogeneous records; its
consumers (``tools/obs_report.py``, dashboards, the tests) key on phase
names and required fields. Nothing stops a new call site from emitting a
typo'd phase or dropping a key — except this registry, validated over
every e2e run's records in ``tests/test_obs.py`` (marker ``obs``):
an **unknown phase name fails loudly** instead of rotting the JSONL, and
a registered phase missing a required key does too.

Required keys are the *always-present* set; optional keys are free-form
(records routinely carry extra context). Two cross-cutting rules:

- every record needs ``phase`` (str) and ``t`` (epoch seconds);
- trace identity is all-or-nothing: a record carrying any of
  ``run_id`` / ``trace_id`` / ``span_id`` / ``span_path`` must carry all
  four (a half-stamped record would silently fall out of timeline joins).

Extend with :func:`register` (e.g. from tools that emit their own
records) — registration is the contract, not a fixed builtin list.
"""

from __future__ import annotations

import re

_TRACE_KEYS = ("run_id", "trace_id", "span_id", "span_path")

# phase name -> frozenset of required keys (beyond phase/t).
SCHEMAS: dict = {}


def register(phase: str, *required: str) -> None:
    """Declare a phase and its always-present keys (idempotent; a
    re-registration unions the key sets so split declarations merge)."""
    SCHEMAS[phase] = frozenset(required) | SCHEMAS.get(phase, frozenset())


# ---- run lifecycle --------------------------------------------------------
register("run_start", "pid")
register("run_end", "ok")
register("span", "name", "seconds", "status")
register("heartbeat", "uptime_s")
register("profile_capture", "dir", "ok")
# compile: three per compiled program (its outermost trace, its
# lowering, its backend stage) from the process-wide jax.monitoring
# listener of pipeline/metrics.py, stamped with the span open on the
# compiling thread; `cache_hit` is
# the persistent-cache verdict of a backend stage (None for the other
# two, which the cache never serves).
register("compile", "stage", "fun_name", "seconds", "cache_hit")
# device_scope / device_idle: the reduction of one profile_dir capture
# (obs/devtrace.py), emitted by maybe_profile before profile_capture.
# device_scope: device seconds of the leaf operations grouped by the
# first two DEVICE_SCOPES levels of their op_name, by compiled program
# (`module`) and by the innermost program span that was open when the
# operation started. device_idle: busy/idle device seconds inside one
# chapter span (a direct child of the run's root span).
register("device_scope", "module", "scope", "device_seconds", "events")
register("device_idle", "busy_seconds", "idle_seconds")

# ---- pipeline phases (timed records carry `seconds`) ----------------------
register("load", "seconds")
register("counts", "rows_raw", "edges", "vertices")
register("quarantine")
register("plan", "schedule", "bytes_per_device", "hbm_budget", "reason")
register("scale_out", "message")
register("warning", "message")
register("build_graph", "seconds")
register("partition", "seconds", "shards", "schedule")
register("lpa", "seconds")            # timed record (graphframes backend)
register("louvain", "seconds", "gamma")
register("leiden", "seconds", "gamma")
register("lpa_iter", "iteration", "labels_changed", "seconds",
         "edges_per_sec", "edges_per_sec_per_chip")
register("superstep_telemetry", "iteration", "labels_changed", "frontier",
         "shard_changed", "imbalance", "devices", "variant")
register("census", "seconds")
register("communities", "count", "largest", "modularity")
register("outliers_recursive_lpa", "seconds")
register("outliers_lof", "seconds", "k", "devices", "features")
register("outlier_summary", "method")
register("ivf_fallback", "guard", "detail")
# impl_selected: one per resolution of a superstep family or LOF impl. Where
# `label_propagation` asked whether the carried rows go on the device (one
# chip: once per plan; a mesh: once per (graph, mesh), of the fullest chip)
# it says `scan` (`carried` | `plain`) and `scan_reason`, the admission's
# arithmetic or why nothing was asked (the sort family, a caller's trace, a
# mesh that spans processes). `pagerank(..., directed=False, plan="auto")`
# writes one too (`op: pagerank_inflow`), with no `scan`: it asks nothing.
register("impl_selected", "op", "impl", "n", "reason")
# plan_build: one per superstep-plan materialization
# (ops/superstep_policy.emit_plan_records and the driver's single-device
# build): host build seconds, family, width classes, padded gather slots per
# edge. Host plan cost grows with the tighter ladders; this record keeps
# it visible in obs_report instead of hiding inside first-call latency.
# A one-device plan's record (ops/superstep_policy.plan_build_stats; the
# mesh entry's has none of these) also says how the plan's rows reduce:
# `padded_slots_per_message`, `rows_pairwise` (vertices in classes up to
# the pairwise width: copy, min, pairwise count), `rows_sorted` (wider:
# the row sort), `rows_hist` (hubs), `max_width`. Benchmark metric
# `plan_slots_per_message` reads the first. The mesh entry's record
# (ops/lpa.py:_mesh_label_propagation) says `index_seconds`: the part of
# `seconds` spent on the carried-rows job's admission, the shards' slot
# index (built in threads from the host partition) and its placement; 0.0
# where the rows were not admitted and on a cache hit. PageRank's message
# reading (`op: pagerank_inflow`) reads the plan the other two ops cache:
# on a graph one of them planned its record says `cached: true`, 0.0 s.
# The exact triangle counts' plan (`op: lcc`, ops/triangles.py:_lcc_plan,
# once per graph, `cached: true` after) says `core_vertices`, `core_edges`,
# `classes`, `wedges_core` and `wedges_tail` (neighbour pairs to close, in
# the core's bit rows and outside them), `core_rows`, `core_slots` (slots
# of the centres' neighbour ranks the plan lays out for the core classes,
# padding in: `blocks x nb x w` over the classes, four bytes each on the
# device; a job cuts no window of the CSR for them), `tail_edges`,
# `tail_compares`, `tail_middles` (distinct middle vertices of the tail's
# edges: a triangle's third corner is credited through its middle's row
# once a middle a block, not once an edge), `tail_credit_slots` (slots a
# job's tail programs scatter through those rows, padding in: `blocks x ns
# x w` over the classes, `ns` the runs of one middle a block may hold; the
# `lcc_tail` stage span says it again as `credit_slots`) and
# `resident_bytes` (what the plan keeps on the device).
# Benchmark metric `lcc_core_wedge_share` reads the two wedge counts.
register("plan_build", "op", "family", "seconds", "padded_slots_per_edge")
# superstep_timing (ISSUE 12): achieved-vs-model throughput for one
# window of supersteps, emitted at the existing tripwire/telemetry
# cadence (zero extra device syncs — the driver already blocks per
# superstep) and by the ops-layer fixpoint seams (cc/pagerank/LPA with a
# sink). Carries BOTH sides of the roofline argument: achieved
# edges/s/chip and the cost model's prediction, plus the full `cost`
# sub-record (see COST_KEYS below). obs_report's roofline section
# renders these; windows below a configurable fraction of model are the
# RUNBOOKS §12 triage signal.
register("superstep_timing", "op", "family", "variant", "iteration",
         "window", "seconds", "edges_per_sec_per_chip",
         "predicted_edges_per_sec_per_chip", "achieved_fraction",
         "devices", "cost")

# fixpoint: one per `connected_components(..., sink=)` call (ops/cc.py):
# the supersteps the `lax.while_loop` took (the confirming pass included)
# and the labels each one moved, from a fixed-size vector in the loop's
# carry; `changed` is cut to `supersteps` (to 64 entries on a longer run).
# Every pass reads all M messages whatever moved: these counts size a
# frontier. Benchmark metric `wcc_quiet_pass_share` reads it.
# `bfs_distances(..., direction="both", sink=)` writes one too for every
# job it steps from the host (`op: bfs_level`, PR 49: ops/paths.py): the
# levels of the search, the last, which reaches nothing, among them, and
# the vertices each reached, which ARE the frontier there.
register("fixpoint", "op", "supersteps", "changed", "num_vertices", "family")

# superstep_delta: one per `label_propagation(..., sink=)` call over a
# fused plan's dense rows on one chip (ops/lpa.py). Per superstep, in
# order: `branch`, how the superstep brought its rows up to date ("full":
# every class gathered anew; a number: the rung, the static cap of the
# slots rewritten through the index), and what it left for the next one:
# `changed_vertices`, the labels it moved, and `changed_messages` (K), the
# messages those vertices send, which picks the next branch. `rungs` are
# `ops/superstep_policy.delta_rungs(M)`. The host's own counts: the job
# steps from the host and reads K and the labels moved once a superstep to
# pick the next update (PR 36). A job whose rows were not admitted to the
# device (`device_residency` says `scan: plain`) runs the stateless scan,
# which keeps no rows and counts no K (its int32[max_iter] of labels moved
# comes back with the labels): every `branch` is "full",
# `changed_messages` and `rungs` are empty. `seconds`: the host's clock
# from one superstep's fetch of K to the next (the first from the job's
# start), one a superstep, read where the host waits for K anyway: no
# sync of its own; empty for the stateless scan, one program that the
# host does not step. Benchmark metrics `cdlp_sparse_superstep_share`
# (`branch`) and `full_superstep_ms` (`seconds` where `branch` is "full")
# read it. On a mesh (`label_propagation(..., mesh=)`, PR 39:
# parallel/sharded.carried_label_propagation) the record also says
# `shards`, and `num_messages`, `rungs` and `changed_messages` are the
# LARGEST shard's: the messages it receives, the rungs cut from them, and
# K as the most messages the changed vertices send into any one shard (one
# `pmax`), which picks one branch for every shard; `changed_vertices` is
# the whole graph's. The mesh entry writes no record where the one
# compiled program runs (`impl_selected` says `scan: plain`): that program
# counts nothing a superstep. `reduce`, `dirty_rows`, `dirty_slots` (PR 43),
# one a superstep: after a rewrite on the lowest rung
# (ops/superstep_policy.DIRTY_REDUCE_TOP_PLACE) the one-chip job reduces
# only the rows the rewrite wrote to (`"dirty"`: a row that was not
# rewritten keeps its mode), and says how many of the plan's rows those
# were and how many slots they hold, the histogram hubs apart (they run in
# every superstep); where every row was reduced (`"full"`: after a full
# gather, on the rungs above, in every superstep of the stateless scan, of
# a weighted plan's job and of the mesh job) the two are the plan's rows
# and S (a shard's, on a mesh).
# The BFS job (`op: bfs_level`, PR 49: ops/paths.py, the same rows, index
# and stepping loop with a row min for its reduce) writes the same record,
# one a job, a level a superstep: `changed_vertices` the vertices the level
# reached, `changed_messages` the messages they send, which picks the next
# level's `branch`; the first level's is "fill" where it wrote the sources'
# slots into rows that a fill, not a gather, had laid out (`source_messages`
# is the K that picked its rung), the search stops at the first level that
# reaches nothing, and every reduce is "full". Where the rows were not
# admitted one compiled full-width level is stepped from the host: every
# `branch` "full", no K, no rungs, and `seconds` all the same. Benchmark
# metrics `bfs_sparse_level_share` and `bfs_full_level_ms` read it.
# Since PR 50 the job over carried rows also says `direction` and
# `unreached_messages`, one a level: `"top_down"` (the rows brought up to
# date behind the K messages of the vertices the level before reached, then
# the row min) or `"bottom_up"` (the vertices still unreached look their
# neighbours' depths up; no row is read or written, so `reduce` is "none"
# and `dirty_rows` / `dirty_slots` 0), and U, the edges of the vertices the
# level left unreached. Since PR 53 a bottom-up level reads its neighbours
# from the graph's message CSR in one loop over chunks of places, and the
# record says `places`, one a level: what that loop ran over, the U before
# the level rounded up to its trips (0 on a top-down level), so that
# `seconds` over `places` is a bottom-up place's cost. The next level is
# bottom-up where its places cost less than the top-down update (the rung
# K fits under, or the full gather: ops/paths._next_update); its `branch`
# is then its `places`, never "full". After a bottom-up level the rows are
# stale and a top-down level gathers in full.
register("superstep_delta", "op", "changed_vertices", "changed_messages",
         "branch", "rungs", "num_messages", "reduce", "dirty_rows",
         "dirty_slots")

# device_residency: one per plan materialisation of a one-device
# `label_propagation(..., plan="auto", sink=)` call on the bucketed family
# (ops/superstep_policy.emit_device_residency): what the device holds for
# this graph's supersteps, in bytes by array group, beside the allocator's
# `bytes_limit` and `bytes_in_use` at the time (None on a backend that
# keeps no statistics). `graph_bytes` and `plan_bytes` are the arrays' own
# `nbytes` (0 for a host-resident graph; the plan without its slot index),
# `slot_index_bytes` the index's, `rows_bytes` the carried rows' (one
# buffer a job, updated in place; 0 under `plain`), `labels_bytes` labels
# in and out. Arrays only: what a compiled
# program takes beside them while it runs (its temporaries, which the
# allocator's `peak_bytes_in_use` leaves out too) is in no field; under
# `carried` the `reason` holds the admission's count of it, and the job's
# `program_memory` records the compiler's, beside the programs' code (this
# record is written before anything is compiled and has no field for it).
# `scan` is the admission's answer (`carried` |
# `plain`, ops/superstep_policy.admit_carried_rows) and `reason` its
# arithmetic, of the device's memory alone. Benchmark metric
# `plan_resident_gb` reads it. The mesh entry's record (PR 39:
# ops/superstep_policy.emit_shard_residency) says `shards` and counts what
# ONE chip holds: its shard of the stacked plan, of the rows and of the
# slot index, the padded label vector (replicated) in and out;
# `graph_bytes` 0 (the graph stays on the host), `bytes_limit` and
# `bytes_in_use` the fullest chip's, which the admission was asked of.
# `pagerank(..., directed=False, plan="auto", sink=)` writes one on the
# bucketed family too (`op: pagerank_inflow`, PR 41:
# ops/superstep_policy.stepped_residency): `scan: plain` (every iteration
# gathers every row anew), `rows_bytes` and `slot_index_bytes` 0 (nothing
# is carried, no index is built), `labels_bytes` the ranks in and out, and
# in `reason` the reckoned temporaries of the one compiled iteration the
# host steps, beside the device's free bytes, and whether they fit.
register("device_residency", "op", "scan", "reason", "bytes_limit",
         "graph_bytes", "plan_bytes", "rows_bytes", "slot_index_bytes",
         "labels_bytes")

# program_memory (PR 52): one for each compiled program a job under a sink
# ran (ops/superstep_policy.emit_program_memory, the one writer; the
# entries of ops/lpa.py, ops/paths.py, ops/pagerank.py, ops/cc.py and
# ops/triangles.py call it when the job ends), in the order the job first
# ran them: what the program's own executable says it takes of the chip
# (`memory_analysis()`), integers: `code_bytes` (the generated code,
# resident from the load on), `temp_bytes` (what it holds beside its
# arguments and results while it runs: in no allocator statistic),
# `argument_bytes`, `output_bytes`, `alias_bytes` (arguments it writes its
# results into: a donated buffer). On a mesh they are ONE chip's and the
# record says `shards`. `op` is `device_residency`'s; `program` the job's
# own word (`gather`, `rewrite`, `modes`, `dirty_modes`, `blank_rows`; the
# BFS job's `start`, `level`, `unreached`, `bottom_up`, `full_level`;
# PageRank's `start` and `iteration`; `loop` for a fixpoint in one program,
# `scan` for a stated count of supersteps in one; LCC's `core`, `tail`,
# `by_id`) and beside it the program's static arguments (`cap`, `marked`;
# `chunk`; `w`, `nb`, `ne`; `max_iter`), which tell two programs of one name apart.
# `reckoned_temp_bytes`, where the admission's model counts this very
# program (obs/memmodel.carried_job_transients by program, the rewrite at
# the one rung it is reckoned at, the bottom-up level at its loop's chunk,
# the hubs' histograms with the two programs that hold them;
# row_sum_transients for
# PageRank's iteration), is that count: the
# compiler's stands beside it in `temp_bytes`. The executable is asked once
# a (plan, program), after the program's first call, of the lowering and
# executable that call left in jit's caches (nothing compiles or loads
# again); a later job on the same plan copies the answers and says
# `cached: True`. The last record of a job says `asked_s`: the seconds the
# job spent asking and writing. Benchmark metrics `program_code_gb`,
# `program_temp_peak_gb` and `admission_temp_overcount_gb` read the warm-up
# job's.
register("program_memory", "op", "program", "code_bytes", "temp_bytes",
         "argument_bytes", "output_bytes", "alias_bytes", "cached")

# memory_watermark (ISSUE 14): predicted-vs-measured HBM/RSS for one
# operating point, emitted by obs/memmodel.emit_memory_watermark (the
# single builder) at the existing phase/rung/telemetry cadence — zero
# extra device syncs (memory_stats is a host-side allocator query).
# `headroom_frac` may be None when no budget is known; `source` says
# whether `achieved_bytes` is a device allocator peak ("device") or the
# host-RSS fallback ("rss"). The `mem` sub-record carries the full
# inventory (see MEM_KEYS below). obs_report's memory section renders
# the per-phase predicted-vs-peak waterfall from these.
register("memory_watermark", "op", "predicted_bytes", "achieved_bytes",
         "headroom_frac", "source", "mem")

# shard_exchange (ISSUE 15): modeled per-chip ICI bytes of one sharded
# superstep: the one-all_gather model 4·Vc·(D-1) under all three byte
# keys, frontier_frac 1.0 (a family that shipped less was deleted in
# PR 29). Single builder: obs/costmodel.emit_shard_exchange, emitted once
# per sharded repair apply (serve/delta.py).
register("shard_exchange", "op", "family", "devices", "peers",
         "exchange_bytes", "frontier_bytes", "ladder_bytes",
         "frontier_frac")

# exchange: one per `label_propagation(..., mesh=)` call (ops/lpa.py), exact
# from the placed partition: the bytes one chip receives per superstep
# (4·Vc·(D-1): one tiled all_gather of the label vector), how uneven the
# vertex-range shards are in messages, and the padded gather slots a shard
# streams per superstep.
register("exchange", "op", "family", "shards", "bytes_per_superstep",
         "messages_per_shard_max", "messages_per_shard_mean",
         "padded_slots_per_shard")

# ---- serving records (docs/SERVING.md) ------------------------------------
register("snapshot_publish", "version", "snapshot_id", "path", "bytes",
         "arrays", "seconds")
register("snapshot_load", "version", "path", "seconds")
register("delta_apply", "inserts", "deletes", "method", "iterations",
         "quarantine", "version", "seconds")
register("query_batch", "endpoint", "n", "seconds")
register("repair_fallback", "stage", "reason")

# ---- serving SLO records (docs/OBSERVABILITY.md "serving SLO") ------------
# access_log: one per HTTP request through the serve middleware (slow
# requests additionally carry slow/body_sha256/body_bytes); slo_rollup:
# one per /statusz read — a periodic checkpoint of the quantile/debt
# state so scrape-less runs still leave an SLO trail in the JSONL.
register("access_log", "method", "endpoint", "status", "seconds",
         "request_id")
register("slo_rollup", "uptime_s", "endpoints", "repair_debt")

# ---- serving admission control (docs/SERVING.md "admission control") ------
# admission: one per AdmissionController.resolve — the provenance trail
# of every accept/queue/coalesce/shed verdict with the debt state that
# decided it; delta_coalesce: one per merged apply group; delta_shed:
# one per refused/dropped batch (stage says where: admission front door,
# deadline expiry on the queue, shutdown drain).
register("admission", "verdict", "reason", "queue_depth", "rows",
         "repair_debt")
register("delta_coalesce", "batches", "inserts", "deletes", "rows_in",
         "rows_out")
register("delta_shed", "stage", "reason", "rows", "retry_after_s")

# ---- replicated serving fleet (docs/SERVING.md "Fleet") --------------------
# replica_health: one per replica state-machine transition (joining/
# healthy/degraded/draining/down) from the fleet prober or the rolling
# reload; breaker_transition: one per circuit-breaker state change
# (closed/open/half_open) with the deciding window stats; fleet_route:
# one per routed request — verdict served/no_replica/stale_pin/
# forwarded/read_only/writer_unreachable with the attempt count and the
# version the response was pinned at; fleet_degraded: the loud read-only
# flip when the writer is lost (and its restoration).
register("replica_health", "replica", "from_state", "to_state", "reason")
register("breaker_transition", "replica", "from_state", "to_state",
         "reason")
register("fleet_route", "endpoint", "verdict", "attempts")
register("fleet_degraded", "reason", "read_only")

# ---- durable write path / replicated writers (docs/SERVING.md
# "Replicated writers") --------------------------------------------------
# wal_append: one per fsync'd write-ahead-log append (the durability
# point every acknowledged delta passes through); wal_replay: one per
# startup/promotion replay of the accepted-but-unapplied tail;
# writer_promote: the standby-to-writer failover step (server- and
# fleet-side both emit it, keyed by epoch); publish_fenced: a deposed
# writer's publish refused at the snapshot store by the epoch fence —
# THE split-brain-impossibility record; ship_lag: the standby's
# replication lag while behind the primary's log (rate-limited).
register("wal_append", "seq", "rows", "bytes", "seconds")
register("wal_replay", "entries", "from_seq")
register("writer_promote", "epoch")
register("publish_fenced", "attempted_epoch", "store_epoch", "reason")
register("ship_lag", "lag_entries", "lag_s")

# ---- sharded write plane (r17, serve/shardplane.py; docs/SERVING.md
# "Sharded write plane") ----------------------------------------------------
# shard_publish: one per writer shard per epoch stage — the per-range
# array files written under epochs/epoch-<e>.stage before the commit;
# epoch_commit: the coordinator's durable two-phase commit point — the
# epoch → per-shard version vector mapping readers key off (a crash
# before this record leaves the previous epoch served); shard_degraded:
# a per-range availability transition (killed / read_only / recovered /
# promoted) — shard loss degrades ONE vertex range, and this record is
# the timeline line that says which. Single builder:
# serve/shardplane.emit_shard_record (tools/schema_lint.py flags inline
# emits elsewhere).
register("shard_publish", "epoch", "shard", "version", "arrays")
register("epoch_commit", "epoch", "version_vector", "shards")
register("shard_degraded", "shard", "status", "reason")

# ---- cross-process tracing / time-to-visible SLO (docs/OBSERVABILITY.md
# "Fleet tracing") ---------------------------------------------------------
# delta_stages: one per accepted delta batch at publish time, emitted in
# the BATCH's own trace (the propagated traceparent context) — the
# writer-side causal chain on the spans' clock: admission accept -> WAL
# fsync -> queued -> apply (the `delta_apply` span) -> commit (engine
# swap, WAL watermark, epoch), each stage in seconds under `stages`
# (DELTA_STAGES below; `total_s` is their sum); delta_visible: one
# per (delta, replica) from the fleet router when a replica first serves
# the version that absorbed the delta — the read-side tail of
# time-to-visible, feeding the router's merged histogram.
register("delta_stages", "version", "stages")
register("delta_visible", "replica", "version", "seconds")

# ---- result-quality observability (docs/OBSERVABILITY.md "Result
# quality") -----------------------------------------------------------------
# quality_snapshot: one per snapshot publish — the published result
# distributions (LOF score + community-size sketches, anomaly rate,
# census scalars) from the bounded host-side quality pass
# (obs/quality.run_quality_pass); quality_drift: the snapshot-over-
# parent comparison (partition-matched churn, PSI sketch drift, id-chain
# community births/deaths); canary_score: the frozen planted-anomaly
# probe re-scored through the production LOF scorer — recall@k dropping
# between publishes is a scorer regression by construction; alert: one
# per firing/resolved transition of an obs/alerts.py rule.
register("quality_snapshot", "version", "num_vertices", "num_communities",
         "anomaly_rate", "lof_threshold", "lof_sketch", "size_sketch",
         "seconds")
register("quality_drift", "version", "parent_version", "churn_frac",
         "new_communities", "dissolved_communities", "lof_psi",
         "size_psi", "anomaly_rate_delta")
register("canary_score", "version", "recall_at_k", "recall_k",
         "mean_rank_frac", "num_anomalies", "k")
register("alert", "name", "state", "severity", "metric", "value",
         "threshold")

# ---- recovery / resilience records (docs/RESILIENCE.md) -------------------
register("retry", "stage", "attempt", "backoff_s", "error")
register("retries_exhausted", "stage", "attempts", "error")
register("degrade", "stage", "to", "depth", "error")
register("mesh_degrade", "from_devices", "to_devices", "schedule",
         "iteration", "resumed_from", "dead_devices")
register("tripwire", "kind", "shard", "iteration")
register("watchdog_timeout", "stage", "timeout_s", "checkpointed")
register("resume", "iteration")
register("checkpoint_save", "iteration", "format", "path")
register("checkpoint_rollback", "path", "error")
register("checkpoint_rollback_ok", "path", "iteration")

# ---- multi-tenant serving (ISSUE 16, docs/SERVING.md "Multi-tenant
# serving") -----------------------------------------------------------------
# Records on these phases MAY carry an optional `tenant` key naming the
# owning tenant (serve/tenancy.py grammar). ABSENT means the default
# tenant — the back-compat contract that keeps every pre-tenancy record
# valid — so the key is never required; when present it must be a valid
# tenant id (a malformed value would leak into per-tenant groupings as a
# phantom tenant). obs_report groups admission/quality/alert timelines
# by it.
TENANT_PHASES = frozenset((
    "admission", "delta_coalesce", "delta_shed", "delta_apply",
    "delta_stages", "snapshot_publish", "snapshot_load", "access_log",
    "alert", "quality_snapshot", "quality_drift", "canary_score",
    "wal_append", "wal_replay", "repair_fallback",
    "shard_publish", "epoch_commit", "shard_degraded",
))

# Mirrors serve/tenancy.py TENANT_RE — duplicated by design: obs/ stays
# importable without serve/ (the JSONL consumers are stdlib-only tools).
_TENANT_VALUE_RE = re.compile(r"[a-z0-9_-]{1,64}")

# The recovery phases obs_report joins into the causal timeline.
RECOVERY_PHASES = frozenset((
    "retry", "retries_exhausted", "degrade", "mesh_degrade", "tripwire",
    "watchdog_timeout", "resume", "checkpoint_rollback",
    "checkpoint_rollback_ok", "ivf_fallback", "quarantine",
    "repair_fallback", "delta_shed", "breaker_transition",
    "fleet_degraded", "wal_replay", "writer_promote", "publish_fenced",
    "shard_degraded",
))


# Every ``jax.named_scope`` literal of the package, once: the names the
# device timeline carries in each operation's ``op_name``. Two levels are
# stable across graphs and refactors — an outer scope per algorithm x
# plan family, an inner scope per pass over memory — and the degree
# class ``w<width>`` (the one computed name) may follow as a third.
# ``obs/devtrace.py`` groups device seconds by the first two registered
# names of an op_name; what carries none is booked as ``unscoped``.
# ``tests/test_trace.py`` holds the package to this list both ways.
DEVICE_SCOPES = frozenset((
    # outer: algorithm x family
    "lpa_bucketed", "cc_bucketed", "pagerank_bucketed", "bfs_level",
    "lpa_sort", "cc_sort", "pagerank_sort", "lpa_sharded", "masked_lpa", "superstep", "census",
    "modularity", "features", "triangles", "ivf", "knn_exact",
    "knn_cross", "lof",
    # inner: superstep passes
    "row_gather", "row_mode", "row_min", "row_sum", "hub_sum",
    "segment_sum", "dangling_mass", "rank_update",
    "hist", "write_back", "pointer_jump", "msg_gather", "segment_mode",
    "segment_min", "sort", "run_reduce", "mask", "exchange",
    "changed_count", "converged",
    # under bfs_level (ops/bucketed_mode.py, PR 49): the hubs' segment_min
    # over their senders' depths, and the rows' rewrite behind the vertices
    # the last level reached (`delta` and its passes follow it)
    "hubs", "rewrite",
    # the level that asks the unreached vertices for a reached neighbour
    # (ops/bucketed_mode.bfs_level_bottom_up, PR 50; PR 53: through the
    # graph's message CSR): `compact`, `expand`, then a trip's `expand`,
    # `neighbours` (the neighbour, its depth) and `write_back`
    "bottom_up", "neighbours",
    # carried rows (ops/bucketed_mode.rewrite_rows): outer, then its passes
    # (`mark`: the marked rewrite's list of the rows it wrote to, PR 43)
    "delta", "compact", "expand", "scatter", "mark",
    # the reduce over those rows alone, under lpa_bucketed; a coarse width
    # `w<width>` may follow (ops/bucketed_mode.lpa_modes_from_dirty_rows)
    "dirty_rows",
    # inner: census / modularity
    "sizes", "edge_counts", "q",
    # inner: features / triangles
    "degrees", "neighbor_stats", "distinct_communities", "stack",
    # exact triangle counts (ops/triangles.py): the core's bit rows built and
    # a centre's own, the rows fetched and and-ed, the tail's row pairs
    # compared, a block's credits added to the two count words
    "core_bits", "bit_rows", "row_compare", "credit",
    # inner: kNN / IVF / LOF
    "distance", "topk", "assign", "lloyd_update", "search_gather",
    "search_distance", "search_topk", "merge_gather", "merge_topk",
    "lists_census", "lists_tables", "lists_take",
    "reach", "lrd", "score",
))


# Every stage span of the package, once: the literal names
# ``obs/spans.stage_span`` is called with. A stage is a ``span`` record
# at close and a ``TraceAnnotation`` on the profiler's clock, one level
# or more under a chapter span of the run (or under ``delta_apply`` on
# the served write path); the benchmark's readers and the idle-gap
# labels of a device trace go by these names, so they are registered
# like the named scopes above and ``tools/schema_lint.py`` holds the
# package to the list both ways (docs/OBSERVABILITY.md "Stage spans").
STAGE_SPANS = frozenset((
    # under `load`
    "ingest_file", "ingest_decode", "ingest_intern", "ingest_concat",
    # under `outliers_recursive_lpa`
    "masked_lpa", "decile_report",
    # under `outliers_lof` (and wherever else ivf_knn / lof_scores run)
    "lof_features", "triangles_host", "triangles_device",
    # under `triangles_device`: the two device stages of the exact counts
    "lcc_core", "lcc_tail",
    "features_device", "ivf_train", "ivf_probe", "ivf_lists", "ivf_search",
    "ivf_merge", "knn_exact", "lof_formula",
    # the write path to a snapshot. Under `snapshot_publish` (pipeline):
    # the CC; under it and under `delta_apply` alike, the five stages of
    # serve/snapshot.publish_result
    "publish_cc", "publish_fetch", "publish_canary", "publish_fingerprint",
    "publish_write", "publish_quality",
    # under `delta_apply` (serve/delta.DeltaIngestor.apply)
    "delta_splice", "delta_build_graph", "delta_repair", "delta_lof",
    "delta_census",
))

# The stages of one served delta's `delta_stages` record, in order; the
# last is the sum of the others to the microsecond. `wal_fsync_s` is
# absent on a server without a WAL.
DELTA_STAGES = ("wal_fsync_s", "queued_s", "apply_s", "commit_s", "total_s")


# The `cost` sub-record shape (obs/costmodel.CostEstimate.record — the
# single builder; tools/schema_lint.py flags inline cost={...} literals
# elsewhere in the package). Like trace identity, the sub-record is
# all-or-nothing: a record carrying `cost` must carry EVERY key below,
# or the roofline tooling would silently render holes — half-stamped
# cost records fail validation the same way half-stamped traces do.
COST_KEYS = frozenset((
    "family", "devices", "slots", "padded_slots", "bytes_gathered",
    "bytes_scattered", "padding_overhead", "exchange_bytes",
    "compute_seconds", "exchange_seconds", "predicted_seconds",
    "predicted_per_chip", "unit", "roofline",
))

# The `mem` sub-record shape (obs/memmodel.MemEstimate.record — the
# single builder; tools/schema_lint.py flags inline mem={...} literals
# elsewhere in the package). Same all-or-nothing rule as `cost`: a
# record carrying `mem` must carry EVERY key below, or the memory-plane
# tooling (obs_report's waterfall, the recalibration suggestion) would
# silently render holes.
MEM_KEYS = frozenset((
    "family", "devices", "weighted", "total_bytes", "inventory", "exact",
    "unit",
))

# The sketch sub-record shape (obs/sketch.QuantileSketch.to_state — the
# single builder; tools/schema_lint.py flags inline *_sketch={...}
# literals elsewhere). Same all-or-nothing rule as `cost`: a record
# carrying a `*_sketch` dict must carry every key below, or the quality
# tooling (obs_report's quality timeline, the router's counter-wise
# merge) would silently drop or mis-merge the distribution.
SKETCH_KEYS = frozenset(("bounds", "counts", "sum", "count"))


def validate_record(rec) -> list:
    """Problems with one record (empty list = valid)."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    phase = rec.get("phase")
    if not isinstance(phase, str) or not phase:
        return [f"missing/empty phase in {rec!r}"]
    if not isinstance(rec.get("t"), (int, float)):
        problems.append(f"{phase}: missing numeric t")
    required = SCHEMAS.get(phase)
    if required is None:
        problems.append(
            f"unknown phase {phase!r} — register it in "
            "graphmine_tpu/obs/schema.py with its required keys"
        )
    else:
        missing = sorted(k for k in required if k not in rec)
        if missing:
            problems.append(f"{phase}: missing required keys {missing}")
    present = [k for k in _TRACE_KEYS if k in rec]
    if present and len(present) != len(_TRACE_KEYS):
        absent = sorted(set(_TRACE_KEYS) - set(present))
        problems.append(
            f"{phase}: partial trace identity (has {present}, lacks {absent})"
        )
    if "tenant" in rec:
        tval = rec["tenant"]
        if not isinstance(tval, str) or not _TENANT_VALUE_RE.fullmatch(tval):
            problems.append(
                f"{phase}: tenant key {tval!r} does not match the tenant-id "
                "grammar [a-z0-9_-]{1,64} (serve/tenancy.py)"
            )
    if phase == "delta_stages" and isinstance(rec.get("stages"), dict):
        unknown = sorted(set(rec["stages"]) - set(DELTA_STAGES))
        if unknown:
            problems.append(
                f"{phase}: unknown stage(s) {unknown} — the stages of a "
                "served delta are schema.DELTA_STAGES"
            )
    for key in rec:
        if not key.endswith("_sketch"):
            continue
        sk = rec[key]
        if not isinstance(sk, dict):
            problems.append(
                f"{phase}: {key} sub-record is {type(sk).__name__}, not "
                "dict — build it with obs/sketch QuantileSketch.to_state()"
            )
        else:
            missing = sorted(k for k in SKETCH_KEYS if k not in sk)
            if missing:
                problems.append(
                    f"{phase}: half-stamped {key} sub-record (missing "
                    f"{missing}) — build it with obs/sketch "
                    "QuantileSketch.to_state()"
                )
    if "mem" in rec:
        mem = rec["mem"]
        if not isinstance(mem, dict):
            problems.append(
                f"{phase}: mem sub-record is {type(mem).__name__}, not "
                "dict — build it with obs/memmodel MemEstimate.record()"
            )
        else:
            missing = sorted(k for k in MEM_KEYS if k not in mem)
            if missing:
                problems.append(
                    f"{phase}: half-stamped mem sub-record (missing "
                    f"{missing}) — build it with obs/memmodel "
                    "MemEstimate.record()"
                )
    if "cost" in rec:
        cost = rec["cost"]
        if not isinstance(cost, dict):
            problems.append(
                f"{phase}: cost sub-record is {type(cost).__name__}, not "
                "dict — build it with obs/costmodel CostEstimate.record()"
            )
        else:
            missing = sorted(k for k in COST_KEYS if k not in cost)
            if missing:
                problems.append(
                    f"{phase}: half-stamped cost sub-record (missing "
                    f"{missing}) — build it with obs/costmodel "
                    "CostEstimate.record()"
                )
    return problems


def validate_records(records) -> list:
    """Flat problem list over a record iterable, each prefixed with its
    position — the loud-failure hook tests run over every e2e stream."""
    problems = []
    for i, rec in enumerate(records):
        problems.extend(f"record {i}: {p}" for p in validate_record(rec))
    return problems
