#!/usr/bin/env bash
# Tier-1 verification: the exact command pinned in ROADMAP.md.
#
# Runs the full CPU test suite (excluding @slow) with collection errors
# surfaced instead of aborting the run, and prints the passed-dot count
# the roadmap uses as its no-regression floor. The fault-injection suite
# (-m faults: tests/test_resilience.py + the tripwire/reshard cases in
# tests/test_sharded.py) is part of this default pass.
#
# Usage: tools/run_tier1.sh [--faults-only|--obs-only|--ann-only|--serve-only|--slo-only|--admission-only|--fleet-only|--wal-only|--trace-only|--perf-only|--quality-only|--mem-only|--tenancy-only|--shardplane-only] [extra pytest args...]
#   --shardplane-only run just the `shardplane`-marked sharded-write-
#                  plane suite (tests/test_shardplane.py: range plan
#                  ownership, deterministic delta splitter bit-parity,
#                  epoch stage/commit/recover incl. torn publish, and
#                  the 3-shard/2-tenant shard-kill chaos acceptance) —
#                  the fast slice when iterating on serve/shardplane.py
#   --tenancy-only run just the `tenancy`-marked multi-tenant serving
#                  suite (tests/test_tenancy.py: namespaced stores,
#                  hostile-id refusal, per-tenant bounds + fair apply,
#                  tenant-scoped WAL replay, per-tenant alerting and
#                  the noisy-neighbor chaos acceptance) — the fast
#                  slice when iterating on tenancy
#   --mem-only     run just the `mem`-marked memory-plane suite
#                  (tests/test_memmodel.py: the HBM footprint inventory
#                  exact against hand-computed tiny plans, the planner
#                  constant derivation, memory_watermark e2e + the
#                  fault-injected OOM degrade join, /statusz + /profilez
#                  memory surfaces and the obs_report memory section)
#                  — the fast slice when iterating on obs/memmodel.py
#   --quality-only run just the `quality`-marked result-quality suite
#                  (tests/test_quality.py: sketch merge associativity,
#                  PSI drift exactness, canary probe recall + injected
#                  scorer regression, alert firing/resolve/flap, the
#                  /alertz + fleet-merge e2e and the obs_report quality
#                  gate) — the fast slice when iterating on obs/sketch,
#                  obs/quality or obs/alerts
#   --perf-only    run just the `perf`-marked compute-plane performance-
#                  observability suite (tests/test_costmodel.py: the
#                  analytical cost model exact against hand-computed
#                  plans, superstep_timing achieved-vs-model e2e,
#                  the obs_report roofline section) — the fast slice
#                  when iterating on obs/costmodel.py
#   --faults-only  run just the `faults`-marked recovery suite — the fast
#                  pre-commit loop when iterating on resilience paths
#   --obs-only     run just the `obs`-marked tracing/telemetry suite
#                  (tests/test_obs.py: spans, schema validation, heartbeat,
#                  superstep telemetry, obs_report e2e)
#   --ann-only     run just the `ann`-marked approximate-kNN suite
#                  (tests/test_ann.py + tests/test_lof_policy.py: IVF
#                  contract/recall, the LOF auto-policy crossover, and the
#                  recall/AUROC regression gates) — the fast slice when
#                  iterating on the IVF index or its deployment policy
#   --serve-only   run just the `serve`-marked serving suite
#                  (tests/test_serve.py: snapshot round-trip/rollback,
#                  delta repair equivalence, query engine, live-swap
#                  server) — the fast slice when iterating on serve/
#   --slo-only     run just the `slo`-marked serving-SLO suite
#                  (tests/test_slo.py: histograms + merge associativity,
#                  live /metrics + /statusz under the query hammer,
#                  quantile agreement vs the access_log JSONL, repair
#                  debt, request tracing) — the fast slice when
#                  iterating on the SLO observability layer
#   --admission-only run just the `admission`-marked write-path
#                  overload suite (tests/test_admission.py: the
#                  accept/queue/coalesce/shed policy owner, order-exact
#                  coalescing parity, deadline shedding, LOF-defer rung,
#                  and the burst + slow-repair chaos acceptance test) —
#                  the fast slice when iterating on serve/admission.py
#   --fleet-only   run just the `fleet`-marked replicated-serving suite
#                  (tests/test_fleet.py: circuit breakers, quorum
#                  committed-version routing, writer loss = read-only,
#                  rolling reload, the reload-vs-inflight-delta rebase,
#                  serve_cli client retries, and the 3-replica
#                  kill+slow+roll chaos acceptance test) — the fast
#                  slice when iterating on serve/fleet.py
#   --wal-only     run just the `wal`-marked durable-write-path suite
#                  (tests/test_wal.py: WAL framing/torn-tail/rotation/
#                  compaction, epoch fencing, 202 + kill/restart replay,
#                  duplicate-submit idempotency, log-shipped standby +
#                  lag, fenced promotion, and the writer-SIGKILL chaos
#                  acceptance test) — the fast slice when iterating on
#                  serve/wal.py
set -o pipefail
cd "$(dirname "$0")/.."

MARKER='not slow'
if [ "${1:-}" = "--faults-only" ]; then
    shift
    MARKER='faults and not slow'
elif [ "${1:-}" = "--obs-only" ]; then
    shift
    MARKER='obs and not slow'
elif [ "${1:-}" = "--ann-only" ]; then
    shift
    MARKER='ann and not slow'
elif [ "${1:-}" = "--serve-only" ]; then
    shift
    MARKER='serve and not slow'
elif [ "${1:-}" = "--slo-only" ]; then
    shift
    MARKER='slo and not slow'
elif [ "${1:-}" = "--admission-only" ]; then
    shift
    MARKER='admission and not slow'
elif [ "${1:-}" = "--fleet-only" ]; then
    shift
    MARKER='fleet and not slow'
elif [ "${1:-}" = "--wal-only" ]; then
    shift
    MARKER='wal and not slow'
elif [ "${1:-}" = "--trace-only" ]; then
    shift
    MARKER='trace and not slow'
elif [ "${1:-}" = "--perf-only" ]; then
    shift
    MARKER='perf and not slow'
elif [ "${1:-}" = "--quality-only" ]; then
    shift
    MARKER='quality and not slow'
elif [ "${1:-}" = "--mem-only" ]; then
    shift
    MARKER='mem and not slow'
elif [ "${1:-}" = "--tenancy-only" ]; then
    shift
    MARKER='tenancy and not slow'
elif [ "${1:-}" = "--shardplane-only" ]; then
    shift
    MARKER='shardplane and not slow'
fi

LOG="${TIER1_LOG:-/tmp/_t1.log}"
rm -f "$LOG"
timeout -k 10 "${TIER1_TIMEOUT:-870}" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m "$MARKER" \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"
exit "$rc"
