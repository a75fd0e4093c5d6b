"""Serving-SLO observability suite (marker ``slo``;
``tools/run_tier1.sh --slo-only``): bucket histograms, the live
``/metrics`` + ``/statusz`` endpoints, request tracing, repair-debt
accounting, and the obs_report serving-SLO section.

The acceptance pins (ISSUE 6):
- concurrent histogram observes lose nothing, and merge is associative
  (bucket counts exactly; sums to float tolerance);
- ``GET /metrics`` and ``GET /statusz`` serve mid-flight under the
  live-query hammer, across a delta publish, with no torn exposition
  (every scrape parses; cumulative buckets monotone; ``+Inf`` ==
  ``_count``);
- the ``/statusz`` per-endpoint quantiles agree with quantiles computed
  offline from the ``access_log`` JSONL alone to within one histogram
  bucket;
- ``access_log`` / ``slo_rollup`` records are schema-registered and
  carry full trace identity.
"""

import bisect
import json
import math
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs.histogram import (
    DEFAULT_LATENCY_BUCKETS,
    Histogram,
)
from graphmine_tpu.obs.registry import Registry
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.obs.spans import Tracer
from graphmine_tpu.pipeline.checkpoint import graph_fingerprint
from graphmine_tpu.pipeline.metrics import MetricsSink
from graphmine_tpu.serve import (
    DeltaIngestor,
    EdgeDelta,
    QueryEngine,
    RepairDebt,
    SnapshotStore,
)
from graphmine_tpu.serve.delta import cold_recompute
from graphmine_tpu.serve.server import SnapshotServer

pytestmark = pytest.mark.slo


# ---- fixtures -------------------------------------------------------------


def _clique(lo, hi):
    ids = np.arange(lo, hi)
    s, d = np.meshgrid(ids, ids)
    m = s.ravel() < d.ravel()
    return s.ravel()[m], d.ravel()[m]


def _community_graph():
    parts = [_clique(0, 12), _clique(12, 26), _clique(26, 40)]
    src = np.concatenate([p[0] for p in parts]).astype(np.int32)
    dst = np.concatenate([p[1] for p in parts]).astype(np.int32)
    return src, dst, 40


def _publish_base(tmp_path, sink=None):
    src, dst, v = _community_graph()
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {
            "src": src, "dst": dst, "labels": labels, "cc_labels": cc,
            "lof": np.linspace(0.5, 2.5, v).astype(np.float32),
        },
        fingerprint=graph_fingerprint(src, dst),
        sink=sink,
    )
    return store


def _get(host, port, path, headers=None):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", headers=headers or {}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.read(), dict(r.headers)


def _get_json(host, port, path, headers=None):
    body, hdrs = _get(host, port, path, headers)
    return json.loads(body), hdrs


def _post(host, port, path, payload, headers=None):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read()), dict(r.headers)


# ---- histograms -----------------------------------------------------------


def test_histogram_observe_count_sum_quantile():
    h = Histogram("h", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap.count == 6
    assert snap.sum == pytest.approx(5.5605)
    # per-bucket: one <=1ms, two <=10ms, one <=100ms, one <=1s, one +Inf
    assert snap.counts == (1, 2, 1, 1, 1)
    assert snap.cumulative() == [1, 3, 4, 5, 6]
    # the median rank lands at the top of the (0.001, 0.01] bucket
    assert h.quantile(0.5) == pytest.approx(0.01)
    # a rank in the +Inf overflow reports the largest finite bound
    assert h.quantile(0.999) == 1.0
    # empty histogram: 0.0, never NaN (statusz must stay strict-JSON)
    assert Histogram("e").quantile(0.5) == 0.0
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)


def test_histogram_bucket_validation():
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram("h", buckets=(0.1, 0.1))
    with pytest.raises(ValueError, match="finite"):
        Histogram("h", buckets=(0.1, float("inf")))
    with pytest.raises(ValueError, match="at least one"):
        Histogram("h", buckets=())


def test_histogram_merge_associativity():
    """Merge over one bucket ladder is associative: bucket counts
    exactly (integer adds), sums to float tolerance — the property that
    lets per-replica histograms roll up into a fleet view in any
    grouping."""
    rng = np.random.default_rng(0)

    def mk(vals):
        h = Histogram("m")
        for v in vals:
            h.observe(float(v))
        return h

    a_vals = rng.exponential(0.001, 40)
    b_vals = rng.exponential(0.1, 30)
    c_vals = rng.exponential(2.0, 20)
    ab_c = mk([]).merge(mk(a_vals)).merge(mk(b_vals)).merge(mk(c_vals))
    bc = mk([]).merge(mk(b_vals)).merge(mk(c_vals))
    a_bc = mk([]).merge(mk(a_vals)).merge(bc)
    assert ab_c.snapshot().counts == a_bc.snapshot().counts
    assert ab_c.snapshot().sum == pytest.approx(a_bc.snapshot().sum)
    assert ab_c.count == 90
    # commutes too
    c_a_b = mk([]).merge(mk(c_vals)).merge(mk(a_vals)).merge(mk(b_vals))
    assert c_a_b.snapshot().counts == ab_c.snapshot().counts
    # mismatched ladders refuse instead of silently re-binning
    with pytest.raises(ValueError, match="different bucket ladders"):
        mk([]).merge(Histogram("x", buckets=(1.0, 2.0)))


def test_histogram_concurrent_observes_lose_nothing():
    h = Histogram("c")
    n_threads, per_thread = 8, 2000

    def work(seed):
        rng = np.random.default_rng(seed)
        for v in rng.exponential(0.01, per_thread):
            h.observe(float(v))

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    assert snap.count == n_threads * per_thread
    assert sum(snap.counts) == snap.count


def test_registry_histogram_family_and_conflicts():
    reg = Registry()
    h1 = reg.histogram("req_s", "latency", endpoint="query")
    assert reg.histogram("req_s", endpoint="query") is h1
    h2 = reg.histogram("req_s", endpoint="vertex")
    assert h2 is not h1
    fam = reg.histogram_family("req_s")
    assert [c.labels["endpoint"] for c in fam.children()] == [
        "query", "vertex"
    ]
    assert reg.histogram_family("nope") is None
    # one name, one kind / one ladder
    reg.counter("c_total").inc()
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("c_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_s")
    with pytest.raises(ValueError, match="bucket ladder"):
        reg.histogram("req_s", buckets=(1.0, 2.0))
    # values() folds a histogram family to its total observation count
    h1.observe(0.1)
    h2.observe(0.2)
    assert reg.values()["req_s"] == 2
    # an invalid ladder raises WITHOUT registering: the name is not
    # poisoned for the later, valid call
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("clean", buckets=(0.1, 0.1))
    assert reg.histogram_family("clean") is None
    reg.histogram("clean", buckets=(0.1, 0.2)).observe(0.15)
    assert reg.values()["clean"] == 1


def test_textfile_exposition_deterministic_help_type():
    """The satellite pin: # HELP/# TYPE lines, sorted metric ordering,
    sorted histogram children, byte-identical renders regardless of
    creation order — so successive scrapes diff cleanly."""

    def build(order):
        reg = Registry()
        for what in order:
            if what == "g":
                reg.gauge("aaa_gauge", "a gauge").set(2)
            elif what == "c":
                reg.counter("zzz_total", "a counter").inc(3)
            else:
                reg.histogram(
                    "mid_seconds", "latency", buckets=(0.01, 0.1),
                    endpoint=what,
                ).observe(0.05)
        return reg.render_textfile(labels={"run_id": "r1"})

    a = build(["g", "c", "vertex", "query"])
    b = build(["query", "c", "vertex", "g"])
    assert a == b
    lines = a.splitlines()
    # metric families in name order, children in label order
    assert lines.index("# TYPE aaa_gauge gauge") < lines.index(
        "# TYPE mid_seconds histogram"
    ) < lines.index("# TYPE zzz_total counter")
    assert "# HELP mid_seconds latency" in lines
    q = [ln for ln in lines if ln.startswith("mid_seconds_bucket")]
    assert q == [
        'mid_seconds_bucket{endpoint="query",run_id="r1",le="0.01"} 0',
        'mid_seconds_bucket{endpoint="query",run_id="r1",le="0.1"} 1',
        'mid_seconds_bucket{endpoint="query",run_id="r1",le="+Inf"} 1',
        'mid_seconds_bucket{endpoint="vertex",run_id="r1",le="0.01"} 0',
        'mid_seconds_bucket{endpoint="vertex",run_id="r1",le="0.1"} 1',
        'mid_seconds_bucket{endpoint="vertex",run_id="r1",le="+Inf"} 1',
    ]
    assert 'mid_seconds_count{endpoint="query",run_id="r1"} 1' in lines


# ---- repair debt ----------------------------------------------------------


def test_repair_debt_ledger():
    reg = Registry()
    debt = RepairDebt(registry=reg)
    debt.submitted(10, t=100.0)
    debt.submitted(5, t=200.0)
    snap = debt.snapshot()
    assert snap["pending_deltas"] == 2 and snap["pending_rows"] == 15
    assert debt.ingest_lag_s(now=103.0) == pytest.approx(3.0)
    assert reg.values()["graphmine_serve_repair_debt_rows"] == 15
    debt.applied(method="warm", iterations=6, budget=24)
    snap = debt.snapshot()
    assert snap["pending_rows"] == 5 and snap["applies_warm"] == 1
    assert snap["last_budget_frac"] == pytest.approx(0.25)
    assert snap["rows_applied_total"] == 10
    debt.applied(method="full_recompute", iterations=12, budget=24)
    snap = debt.snapshot()
    assert snap["applies_cold"] == 1 and snap["warm_ratio"] == 0.5
    assert snap["pending_rows"] == 0 and snap["ingest_lag_s"] == 0.0
    assert reg.values()["graphmine_serve_repairs_cold_total"] == 1
    # an abandoned submission (validation refused) drains without
    # counting an apply
    debt.submitted(7)
    debt.abandoned()
    snap = debt.snapshot()
    assert snap["pending_rows"] == 0
    assert snap["applies_warm"] + snap["applies_cold"] == 2


def test_delta_apply_record_carries_budget_and_debt(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store = _publish_base(tmp_path, sink=sink)
    ing = DeltaIngestor(store, sink=sink, lof_k=4, check_samples=8)
    ing.apply(EdgeDelta.from_pairs(insert=[(40, 12), (40, 13)]))
    rec = [r for r in sink.records if r["phase"] == "delta_apply"][-1]
    assert rec["budget"] > 0 and rec["iterations"] <= rec["budget"]
    debt = rec["repair_debt"]
    assert debt["applies_warm"] == 1 and debt["pending_rows"] == 0
    assert validate_records(sink.records) == []


# ---- query stage split ----------------------------------------------------


def test_query_engine_stage_split(tmp_path):
    store = _publish_base(tmp_path)
    eng = QueryEngine(store.load())
    assert eng.stage_snapshot()["batches"] == 0
    for n in (3, 7, 30):
        eng.query_batch(np.arange(n))
    stages = eng.stage_snapshot()
    assert stages["batches"] == 3 and stages["ids"] == 40
    assert stages["gather_seconds"] > 0.0
    assert stages["pad_seconds"] >= 0.0 and stages["host_seconds"] >= 0.0
    # host-table twin accounts too
    eng_h = QueryEngine(store.load(), device=False)
    eng_h.query_batch([1, 2, 3])
    assert eng_h.stage_snapshot()["batches"] == 1


# ---- HTTP SLO surfaces ----------------------------------------------------


def _parse_exposition(text):
    """Parse histogram bucket/count lines into
    {labels-string-without-le: {"buckets": [(le, v), ...], "count": n}}."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, _, rest = line.partition("{")
        if name == "graphmine_serve_request_seconds_bucket":
            labels, _, val = rest.partition("} ")
            le = [p for p in labels.split(",") if p.startswith('le="')][0]
            key = ",".join(p for p in labels.split(",") if not p.startswith('le="'))
            out.setdefault(key, {"buckets": [], "count": None})
            out[key]["buckets"].append((le[4:-1], int(val)))
        elif name == "graphmine_serve_request_seconds_count":
            labels, _, val = rest.partition("} ")
            out.setdefault(labels, {"buckets": [], "count": None})
            out[labels]["count"] = int(val)
    return out


def _assert_untorn(text):
    """A scrape is internally consistent: cumulative buckets monotone,
    the +Inf bucket equals _count, every family's sample set complete."""
    for key, fam in _parse_exposition(text).items():
        values = [v for _, v in fam["buckets"]]
        assert values == sorted(values), f"non-monotone buckets for {key}"
        assert fam["buckets"][-1][0] == "+Inf"
        assert fam["count"] == fam["buckets"][-1][1], f"torn family {key}"


def _bucket_index(value, bounds=DEFAULT_LATENCY_BUCKETS):
    return bisect.bisect_left(bounds, value)


def test_live_metrics_statusz_under_query_hammer(tmp_path):
    """The acceptance pin: /metrics and /statusz serve mid-flight while
    the query hammer runs and a delta publishes; no dropped queries, no
    torn exposition, and the statusz quantiles agree with offline
    quantiles from the access_log JSONL to within one histogram
    bucket."""
    stream = tmp_path / "metrics.jsonl"
    sink = MetricsSink(stream_path=str(stream), tracer=Tracer())
    sink.emit("run_start", pid=os.getpid())
    store = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    try:
        errors, versions, scrapes = [], set(), []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    out, _ = _post(
                        host, port, "/query", {"vertices": [0, 13, 27]}
                    )
                    versions.add(out["version"])
                    if len(out["label"]) != 3:
                        raise AssertionError(f"short response: {out}")
                except Exception as e:  # noqa: BLE001 — collect, assert later
                    errors.append(e)

        def scraper():
            while not stop.is_set():
                try:
                    body, _ = _get(host, port, "/metrics")
                    scrapes.append(body.decode())
                    sz, _ = _get_json(host, port, "/statusz")
                    if "endpoints" not in sz or "repair_debt" not in sz:
                        raise AssertionError(f"bad statusz: {sz}")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        threads.append(threading.Thread(target=scraper))
        for t in threads:
            t.start()
        # the delta publish swaps the engine mid-hammer, mid-scrape
        out, _ = _post(
            host, port, "/delta",
            {"insert": [[40, 12], [40, 13], [40, 14]], "delete": [[0, 1]]},
        )
        assert out["version"] == 2
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert errors == []
        assert versions <= {1, 2} and versions
        assert len(scrapes) >= 2
        for text in scrapes:
            _assert_untorn(text)

        # quantile agreement: statusz (live bucket estimate) vs offline
        # exact quantiles over the access_log JSONL, within one bucket
        statusz, _ = _get_json(host, port, "/statusz")
        assert statusz["inflight"] >= 1  # the statusz request itself
        q_live = statusz["endpoints"]["query"]
        assert q_live["count"] >= 3 and q_live["error_rate"] == 0.0
    finally:
        server.stop()
    sink.emit("run_end", ok=True)
    sink.finalize(str(stream))
    assert validate_records(sink.records) == []

    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import obs_report

    records, bad = obs_report.load_records(str(stream))
    assert bad == 0
    offline = sorted(
        float(r["seconds"]) for r in records
        if r.get("phase") == "access_log" and r.get("endpoint") == "query"
    )
    assert len(offline) >= q_live["count"]
    for q, key in ((0.5, "p50_s"), (0.95, "p95_s"), (0.99, "p99_s")):
        rank = max(1, math.ceil(q * len(offline)))
        exact = offline[rank - 1]
        live = q_live[key]
        assert abs(_bucket_index(live) - _bucket_index(exact)) <= 1, (
            f"{key}: live {live} vs offline {exact} differ by more than "
            "one bucket"
        )

    # and the JSONL alone renders the serving-SLO section
    report = obs_report.build_report(records)
    assert "-- serving SLO (latency / errors / repair debt) --" in report
    assert "repair-debt timeline:" in report
    assert "query" in report


def test_healthz_reports_staleness_and_debt(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    try:
        hz, _ = _get_json(host, port, "/healthz")
        assert hz["ok"] is True and hz["version"] == 1
        assert hz["snapshot_age_s"] >= 0.0
        assert hz["repair_debt_rows"] == 0 and hz["ingest_lag_s"] == 0.0
        _post(host, port, "/delta", {"insert": [[40, 12], [40, 13]]})
        hz, _ = _get_json(host, port, "/healthz")
        assert hz["version"] == 2
        # debt drained after the apply; age restarts from the publish
        assert hz["repair_debt_rows"] == 0
        assert hz["snapshot_age_s"] < 60.0
    finally:
        server.stop()
    assert validate_records(sink.records) == []


def test_request_id_propagated_and_generated(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    try:
        # client-supplied id echoes back and lands in the access_log
        _, hdrs = _get_json(
            host, port, "/healthz", headers={"X-Request-Id": "lb-42.az1"}
        )
        assert hdrs["X-Request-Id"] == "lb-42.az1"
        # absent or hostile ids get a generated one
        _, hdrs2 = _get_json(host, port, "/healthz")
        assert hdrs2["X-Request-Id"] and hdrs2["X-Request-Id"] != "lb-42.az1"
        _, hdrs3 = _get_json(
            host, port, "/healthz",
            headers={"X-Request-Id": "x" * 200},
        )
        assert len(hdrs3["X-Request-Id"]) <= 64
    finally:
        server.stop()
    logs = [r for r in sink.records if r["phase"] == "access_log"]
    assert [r["request_id"] for r in logs][0] == "lb-42.az1"
    # trace identity rides along: access_log joins the span timeline
    assert {"run_id", "trace_id", "span_id", "span_path"} <= set(logs[0])
    assert validate_records(sink.records) == []


def test_slow_request_digest_and_error_accounting(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store = _publish_base(tmp_path, sink=sink)
    # slow_request_s=0: EVERY request is "slow", so POST bodies digest
    server = SnapshotServer(store, sink=sink, slow_request_s=0.0)
    host, port = server.start()
    try:
        _post(host, port, "/query", {"vertices": [1, 2]})
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(host, port, "/query", {"vertices": [1.5]})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(host, port, "/nope")
        assert e.value.code == 404
        # a request is accounted after its reply is sent: a reader that
        # arrives at once may be early, so read until the tail has run
        deadline = time.monotonic() + 5.0
        while True:
            statusz, _ = _get_json(host, port, "/statusz")
            eps = statusz["endpoints"]
            settled = (eps.get("query", {}).get("count") == 2
                       and "unknown" in eps)
            if settled or time.monotonic() > deadline:
                break
            time.sleep(0.01)
    finally:
        server.stop()
    assert eps["query"]["count"] == 2 and eps["query"]["errors"] == 1
    assert eps["query"]["error_rate"] == 0.5
    # unknown paths share ONE bucket — no unbounded label cardinality
    assert eps["unknown"]["errors"] == 1
    logs = [r for r in sink.records if r["phase"] == "access_log"]
    post_logs = [r for r in logs if r["method"] == "POST"]
    assert all(r.get("slow") for r in logs)
    assert all(
        r.get("body_sha256") and r.get("body_bytes") for r in post_logs
    )
    import hashlib

    want = hashlib.sha256(
        json.dumps({"vertices": [1, 2]}).encode()
    ).hexdigest()
    # the 200 reply's record, picked by status: the access-log line is
    # written after the reply is sent, so two handler threads may append
    # theirs in either order
    (ok_log,) = [r for r in post_logs if r["status"] == 200]
    assert ok_log["body_sha256"] == want
    assert validate_records(sink.records) == []


def test_statusz_emits_schema_valid_slo_rollup(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    try:
        _get_json(host, port, "/healthz")
        _get_json(host, port, "/statusz")
    finally:
        server.stop()
    rollups = [r for r in sink.records if r["phase"] == "slo_rollup"]
    assert len(rollups) == 1
    assert {"uptime_s", "endpoints", "repair_debt"} <= set(rollups[0])
    assert "healthz" in rollups[0]["endpoints"]
    assert validate_records(sink.records) == []


def test_refused_delta_abandons_debt_without_double_drain(tmp_path):
    """A delta the ingestor refuses (a snapshot whose weights column is
    misaligned with its edge arrays — the loud damaged-store refusal)
    must drain its OWN pending entry and nothing else — /healthz on a
    drained queue reports zero backlog, and no phantom apply is
    counted."""
    src, dst, v = _community_graph()
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {
            "src": src, "dst": dst, "labels": labels, "cc_labels": cc,
            "weights": np.ones(len(src) - 3, np.float32),
        },
        fingerprint=graph_fingerprint(src, dst),
    )
    server = SnapshotServer(store)
    host, port = server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(host, port, "/delta", {"insert": [[1, 2]]})
        assert e.value.code == 400
        hz, _ = _get_json(host, port, "/healthz")
        assert hz["repair_debt_rows"] == 0 and hz["ingest_lag_s"] == 0.0
    finally:
        server.stop()
    snap = server.debt.snapshot()
    assert snap["pending_deltas"] == 0
    assert snap["applies_warm"] + snap["applies_cold"] == 0


def test_client_disconnect_records_499_not_success(tmp_path, monkeypatch):
    """A reply the client never received must not count as a served
    2xx: a dead-socket write (BrokenPipeError) records as 499 and shows
    up in the endpoint's error rate — impatient clients are exactly the
    tail signal the SLO page exists to surface."""
    from graphmine_tpu.serve import server as server_mod

    def dead_socket(self, url):
        self._status = 200  # the write "succeeded" right up to the pipe
        raise BrokenPipeError("client went away")

    monkeypatch.setattr(server_mod._Handler, "_ep_snapshot", dead_socket)
    store = _publish_base(tmp_path)
    server = SnapshotServer(store)
    host, port = server.start()
    try:
        with pytest.raises(Exception):  # noqa: B017 — empty reply, any client error
            _get(host, port, "/snapshot")
        # the server-side ledger saw the failure, and stayed up
        _get_json(host, port, "/healthz")
    finally:
        server.stop()
    eps = server.endpoint_latency()
    assert eps["snapshot"]["count"] == 1
    assert eps["snapshot"]["errors"] == 1
    assert eps["healthz"]["errors"] == 0


def test_sink_max_records_bounds_memory_without_losing_stream(tmp_path):
    """The long-lived-server memory bound: with max_records set, the
    in-memory list stays capped while the JSONL stream keeps every
    record, and finalize neither re-appends survivors nor duplicates
    streamed records."""
    stream = tmp_path / "m.jsonl"
    sink = MetricsSink(
        stream_path=str(stream), tracer=Tracer(), max_records=10
    )
    for i in range(50):
        sink.emit("heartbeat", uptime_s=float(i))
    assert len(sink.records) == 10
    assert sink.records[0]["uptime_s"] == 40.0  # oldest were dropped
    sink.finalize(str(stream))
    lines = [
        json.loads(ln) for ln in stream.read_text().splitlines() if ln
    ]
    assert len(lines) == 50  # disk kept everything, exactly once
    assert [r["uptime_s"] for r in lines] == [float(i) for i in range(50)]


def test_sinkless_server_still_serves_metrics(tmp_path):
    """A server with no record sink still has the full metric surface:
    /metrics and /statusz work off its private registry."""
    store = _publish_base(tmp_path)
    server = SnapshotServer(store)
    host, port = server.start()
    try:
        _get_json(host, port, "/healthz")
        body, _ = _get(host, port, "/metrics")
        text = body.decode()
        assert "# TYPE graphmine_serve_request_seconds histogram" in text
        assert "# TYPE graphmine_serve_snapshot_version gauge" in text
        _assert_untorn(text)
        sz, _ = _get_json(host, port, "/statusz")
        assert sz["endpoints"]["healthz"]["count"] == 1
    finally:
        server.stop()
