"""Test harness: force an 8-device virtual CPU mesh *before* jax imports.

The TPU analog of the reference's ``SparkContext("local[*]")``
(``Graphframes.py:12``): run the real pjit/shard_map code paths on fake
devices on one host (SURVEY §4, "multi-chip-without-a-cluster").
"""

import os
import sys

# Tests need 8 virtual CPU devices, and jax reads the platform and the
# host device count once, at backend start-up: the environment is fixed
# below, before anything imports jax. Set GRAPHMINE_TEST_TPU=1 to run
# tests on the real device instead. The recipe itself is shared with
# __graft_entry__.dryrun_multichip via graphmine_tpu/_envscrub.py, loaded
# by file path so the jax-importing package __init__ never runs here.

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 pass"
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-injection resilience suite (tests/test_resilience.py "
        "plus the tripwire/reshard cases in tests/test_sharded.py); runs in "
        "the default CPU pass — select with -m faults or "
        "tools/run_tier1.sh --faults-only",
    )
    config.addinivalue_line(
        "markers",
        "obs: tracing/telemetry suite (tests/test_obs.py: spans, record "
        "schema, heartbeat, superstep telemetry, obs_report e2e); runs in "
        "the default CPU pass — select with -m obs or "
        "tools/run_tier1.sh --obs-only",
    )
    config.addinivalue_line(
        "markers",
        "ann: approximate-kNN suite (tests/test_ann.py + "
        "tests/test_lof_policy.py: IVF contract/recall, the LOF "
        "auto-policy crossover, recall/AUROC regression gates); runs in "
        "the default CPU pass — select with -m ann or "
        "tools/run_tier1.sh --ann-only",
    )
    config.addinivalue_line(
        "markers",
        "serve: serving-layer suite (tests/test_serve.py: versioned "
        "snapshots, delta ingest + warm-start repair equivalence, the "
        "batched query engine, live-swap HTTP server); runs in the "
        "default CPU pass — select with -m serve or "
        "tools/run_tier1.sh --serve-only",
    )
    config.addinivalue_line(
        "markers",
        "admission: write-path admission-control suite "
        "(tests/test_admission.py: the accept/queue/coalesce/shed policy "
        "owner, order-exact delta coalescing, deadline shedding, the "
        "LOF-defer rung, and the overload chaos acceptance test); runs "
        "in the default CPU pass — select with -m admission or "
        "tools/run_tier1.sh --admission-only",
    )
    config.addinivalue_line(
        "markers",
        "fleet: replicated-serving-fleet suite (tests/test_fleet.py: "
        "per-replica circuit breakers, quorum committed-version "
        "routing, writer loss = read-only, zero-downtime rolling "
        "reload, the reload-vs-inflight-delta rebase, serve_cli client "
        "retries, and the 3-replica kill+slow+roll chaos acceptance "
        "test); runs in the default CPU pass — select with -m fleet or "
        "tools/run_tier1.sh --fleet-only",
    )
    config.addinivalue_line(
        "markers",
        "wal: durable-write-path suite (tests/test_wal.py: write-ahead "
        "log framing/torn-tail/rotation/compaction, writer-epoch "
        "fencing, WAL-durable 202 acknowledgements + kill/restart "
        "replay, duplicate-submit idempotency, log-shipped standby + "
        "replication lag, fenced promotion, and the 2-writer/3-replica "
        "writer-SIGKILL chaos acceptance test); runs in the default "
        "CPU pass — select with -m wal or tools/run_tier1.sh "
        "--wal-only",
    )
    config.addinivalue_line(
        "markers",
        "trace: cross-process observability suite (tests/test_trace.py: "
        "traceparent propagation + span adoption, per-delta "
        "time-to-visible stages, the merged router histogram, "
        "trace_stitch/obs_report/schema_lint gates, POST /profilez, and "
        "the chaos-run shard-stitch acceptance test); runs in the "
        "default CPU pass — select with -m trace or tools/run_tier1.sh "
        "--trace-only",
    )
    config.addinivalue_line(
        "markers",
        "perf: compute-plane performance-observability suite "
        "(tests/test_costmodel.py: analytical cost model exact against "
        "hand-computed plans, superstep_timing achieved-vs-model "
        "attribution e2e, obs_report roofline section); runs in the "
        "default CPU "
        "pass — select with -m perf or tools/run_tier1.sh --perf-only",
    )
    config.addinivalue_line(
        "markers",
        "quality: result-quality observability suite "
        "(tests/test_quality.py: quantile-sketch merge associativity/"
        "commutativity, PSI drift hand-computed exactness, partition-"
        "matched churn, canary probe recall + injected scorer "
        "regression, alert firing/resolve/flap sequences, /alertz + "
        "fleet sketch-merge e2e, the obs_report quality timeline and "
        "its exit-4 canary gate); runs in the default CPU pass — "
        "select with -m quality or tools/run_tier1.sh --quality-only",
    )
    config.addinivalue_line(
        "markers",
        "mem: memory-plane observability suite (tests/test_memmodel.py: "
        "the analytical HBM footprint inventory exact against "
        "hand-computed tiny plans, the planner byte-constant "
        "derivation, memory_watermark emission e2e + the fault-injected "
        "OOM degrade join, serve /statusz + /profilez memory surfaces, "
        "the obs_report memory waterfall); runs in the default CPU "
        "pass — select with -m mem or "
        "tools/run_tier1.sh --mem-only",
    )
    config.addinivalue_line(
        "markers",
        "tenancy: multi-tenant serving suite (tests/test_tenancy.py: "
        "namespaced snapshot store round-trip, hostile tenant-id "
        "refusal, per-tenant admission bounds + weighted-fair apply, "
        "tenant-scoped WAL replay/dedupe, per-tenant alert planes and "
        "the noisy-neighbor chaos acceptance); runs in the default CPU "
        "pass — select with -m tenancy or tools/run_tier1.sh "
        "--tenancy-only",
    )
    config.addinivalue_line(
        "markers",
        "shardplane: sharded-write-plane suite (tests/test_shardplane.py: "
        "vertex-range plan ownership, deterministic delta-splitter "
        "bit-parity vs sequential whole-batch apply, epoch "
        "stage/commit/recover incl. the torn-publish drill, per-range "
        "failover and the 3-shard/2-tenant shard-kill chaos acceptance "
        "test); runs in the default CPU pass — select with -m shardplane "
        "or tools/run_tier1.sh --shardplane-only",
    )
    config.addinivalue_line(
        "markers",
        "slo: serving-SLO observability suite (tests/test_slo.py: "
        "bucket histograms + merge associativity, live /metrics and "
        "/statusz under the query hammer, quantile agreement vs the "
        "access_log JSONL, repair-debt accounting, request tracing); "
        "runs in the default CPU pass — select with -m slo or "
        "tools/run_tier1.sh --slo-only",
    )


if os.environ.get("GRAPHMINE_TEST_TPU") != "1":
    # The single loader in __graft_entry__ imports only numpy/stdlib, never
    # jax. An existing explicit device-count flag is respected.
    import __graft_entry__

    os.environ.update(
        __graft_entry__._load_envscrub().virtual_cpu_env(8, override_count=False)
    )
    # run_pipeline and SnapshotServer point jax at <checkout>/.jax_cache
    # (compile_cache.py); CPU test runs neither fill nor read it.
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REFERENCE_PARQUET = "/root/reference/CommunityDetection/data/outlinks_pq"


def cached_edgelist(prefix: str, text: str) -> str:
    """Persist generated test edge-list ``text`` at a content-addressed,
    per-user path in the shared tempdir and return the path.

    Reused across pytest runs instead of leaking one temp dir per
    invocation — but never trusted blindly: the digest in the name
    invalidates the cache whenever the generator changes, and the
    read-back check means a stale or foreign file (shared /tmp) can't be
    consumed. If the shared path isn't writable, falls back to a private
    directory.
    """
    import hashlib
    import tempfile

    digest = hashlib.sha1(text.encode()).hexdigest()[:12]
    p = os.path.join(
        tempfile.gettempdir(), f"{prefix}_{os.getuid()}_{digest}.txt"
    )
    try:
        with open(p) as f:
            cached_ok = f.read() == text
    except OSError:
        cached_ok = False
    if not cached_ok:
        try:
            tmp = f"{p}.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(text)
            os.replace(tmp, p)
        except OSError:
            p = os.path.join(
                tempfile.mkdtemp(prefix=f"{prefix}_"), "edges.txt"
            )
            with open(p, "w") as f:
                f.write(text)
    return p


@pytest.fixture(scope="session")
def bundled_edges():
    from graphmine_tpu.io.edges import load_parquet_edges

    if not os.path.isdir(REFERENCE_PARQUET):
        pytest.skip("bundled reference parquet not available")
    return load_parquet_edges(REFERENCE_PARQUET)


@pytest.fixture(scope="session")
def bundled_graph(bundled_edges):
    from graphmine_tpu.graph.container import graph_from_edge_table

    return graph_from_edge_table(bundled_edges)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
