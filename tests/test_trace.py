"""Cross-process observability plane (ISSUE 11, docs/OBSERVABILITY.md
"Fleet tracing"): trace-context propagation, per-delta time-to-visible,
the federated metrics plane, and the stitching/gating tools.

Marker ``trace`` (``tools/run_tier1.sh --trace-only``). The acceptance
pin is :func:`test_fleet_chaos_trace_stitch_acceptance`: a 3-replica
chaos run (kill + roll + writer failover) whose per-process JSONL shards
alone reconstruct at least one COMPLETE per-delta timeline (admission →
WAL fsync → apply → publish → replica visible) and the failover
epoch-fence sequence, with zero half-stamped trace records.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs.histogram import Histogram
from graphmine_tpu.obs.schema import DELTA_STAGES, validate_records
from graphmine_tpu.obs.spans import TRACE_HEADER, TraceContext, Tracer
from graphmine_tpu.pipeline.checkpoint import graph_fingerprint
from graphmine_tpu.pipeline.metrics import MetricsSink, shard_sink
from graphmine_tpu.serve.delta import cold_recompute
from graphmine_tpu.serve.fleet import FleetConfig, FleetRouter, ReplicaSpec
from graphmine_tpu.serve.server import SnapshotServer
from graphmine_tpu.serve.snapshot import SnapshotStore
from graphmine_tpu.testing import faults

pytestmark = pytest.mark.trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(_REPO, "tools") not in sys.path:
    sys.path.insert(0, os.path.join(_REPO, "tools"))


# ---- helpers (the test_fleet.py idioms) -----------------------------------


def _clique(lo, hi):
    ids = np.arange(lo, hi)
    s, d = np.meshgrid(ids, ids)
    m = s.ravel() < d.ravel()
    return s.ravel()[m], d.ravel()[m]


def _publish_base(tmp_path):
    parts = [_clique(0, 12), _clique(12, 26), _clique(26, 40)]
    src = np.concatenate([p[0] for p in parts]).astype(np.int32)
    dst = np.concatenate([p[1] for p in parts]).astype(np.int32)
    v = 40
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {
            "src": src, "dst": dst, "labels": labels, "cc_labels": cc,
            "lof": np.zeros(v, np.float32),
        },
        fingerprint=graph_fingerprint(src, dst),
    )
    return store, v


def _post(host, port, path, payload, timeout=60, headers=None):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(host, port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=timeout
    ) as r:
        body = r.read()
        ct = r.headers.get("Content-Type", "")
    return json.loads(body) if "json" in ct else body.decode()


def _fast_config(**overrides):
    kv = dict(
        probe_interval_s=0.08,
        probe_timeout_s=4.0,
        read_timeout_s=1.0,
        down_after_probes=2,
        reload_cadence_s=0.1,
        rejoin_timeout_s=15.0,
        breaker_backoff_base_s=0.3,
        breaker_backoff_max_s=1.0,
        retry_after_s=1.0,
        default_deadline_ms=8000,
        promote_timeout_s=120.0,
    )
    kv.update(overrides)
    return FleetConfig(**kv)


# ---- TraceContext wire format ---------------------------------------------


def test_trace_context_header_roundtrip():
    ctx = TraceContext("ab" * 8, "cd" * 4)
    header = ctx.to_header()
    assert header == f"00-{'ab' * 8}-{'cd' * 4}-01"
    assert TraceContext.from_header(header) == ctx
    off = TraceContext("ab" * 8, "cd" * 4, sampled=False)
    assert TraceContext.from_header(off.to_header()) == off


@pytest.mark.parametrize("bad", [
    "", "garbage", "00-xyz-abc-01", "00-abcd1234-ef-01",
    "zz-" + "ab" * 8 + "-" + "cd" * 4 + "-01",
    "00-" + "ab" * 8 + "-" + "cd" * 4,          # 3 parts
    "00-" + "ab" * 40 + "-" + "cd" * 4 + "-01",  # trace_id too long
    "00-" + "AB" * 8 + "-" + "cd" * 4 + "-0\n",  # hostile flags
    None, 7,
])
def test_trace_context_malformed_headers_parse_to_none(bad):
    assert TraceContext.from_header(bad) is None


def test_trace_context_header_is_case_normalized():
    header = "00-" + "AB" * 8 + "-" + "CD" * 4 + "-01"
    ctx = TraceContext.from_header(header)
    assert ctx is not None and ctx.trace_id == "ab" * 8


# ---- span adoption / per-record trace identity ----------------------------


def test_span_adoption_new_trace_and_inheritance():
    sink = MetricsSink(tracer=Tracer())
    run_trace = sink.tracer.trace_id
    # default: records ride the run trace
    assert sink.emit("warning", message="x")["trace_id"] == run_trace
    # new_trace: the subtree is its own trace, nested spans inherit
    with sink.span("req", emit=False, new_trace=True) as sp:
        assert sp.trace_id != run_trace
        assert sink.emit("warning", message="x")["trace_id"] == sp.trace_id
        with sink.tracer.span("child") as child:
            assert child.trace_id == sp.trace_id
            assert child.path == "req/child"
    # remote: adopts the sender's identity, parents under its span
    ctx = TraceContext("12" * 8, "34" * 4)
    with sink.span("adopt", emit=False, remote=ctx) as sp:
        assert sp.trace_id == ctx.trace_id
        assert sp.parent_id == ctx.span_id
        rec = sink.emit("warning", message="y")
        assert rec["trace_id"] == ctx.trace_id
        assert validate_records([rec]) == []
    # back out of the span: the run trace again
    assert sink.emit("warning", message="z")["trace_id"] == run_trace
    with pytest.raises(ValueError):
        with sink.tracer.span("both", remote=ctx, new_trace=True):
            pass


def test_span_context_roundtrips_through_header():
    tracer = Tracer()
    with tracer.span("a") as sp:
        ctx = TraceContext.from_header(sp.context().to_header())
        assert ctx == TraceContext(sp.trace_id, sp.span_id)


# ---- Histogram.merge property tests (ISSUE 11 satellite) ------------------


def _hist(vals, buckets=(0.001, 0.01, 0.1, 1.0)):
    h = Histogram("h", buckets=buckets)
    for v in vals:
        h.observe(v)
    return h


def test_histogram_merge_commutative_and_associative_random():
    rng = np.random.default_rng(7)
    for _ in range(10):
        a, b, c = (
            rng.gamma(1.0, 0.05, size=rng.integers(0, 40)).tolist()
            for _ in range(3)
        )
        ab_c = _hist(a).merge(_hist(b)).merge(_hist(c)).snapshot()
        a_bc = _hist(a).merge(_hist(b).merge(_hist(c))).snapshot()
        ba = _hist(b).merge(_hist(a)).snapshot()
        ab = _hist(a).merge(_hist(b)).snapshot()
        assert ab_c.counts == a_bc.counts          # associative
        assert ab_c.count == len(a) + len(b) + len(c)
        assert ab.counts == ba.counts              # commutative
        assert ab.sum == pytest.approx(ba.sum)
        # merge == observing the union directly
        union = _hist(a + b + c).snapshot()
        assert ab_c.counts == union.counts
        assert ab_c.sum == pytest.approx(union.sum)


def test_histogram_merge_mismatched_ladder_raises():
    a = _hist([0.5], buckets=(0.1, 1.0))
    b = _hist([0.5], buckets=(0.2, 1.0))
    with pytest.raises(ValueError, match="bucket ladders"):
        a.merge(b)
    c = _hist([0.5], buckets=(0.1, 1.0, 10.0))
    with pytest.raises(ValueError, match="bucket ladders"):
        a.merge(c)


def test_histogram_merge_of_labeled_children():
    from graphmine_tpu.obs.histogram import HistogramFamily

    fam = HistogramFamily("ttv", buckets=(0.01, 0.1, 1.0))
    fam.labels(replica="r0").observe(0.05)
    fam.labels(replica="r0").observe(0.5)
    fam.labels(replica="r1").observe(0.005)
    fam.labels(replica="r2")  # zero observations merges as identity
    merged = Histogram("m", buckets=fam.bounds)
    for child in fam.children():
        merged.merge(child)
    snap = merged.snapshot()
    assert snap.count == 3
    # counter-wise equality against the children's summed buckets
    summed = [0] * (len(fam.bounds) + 1)
    for child in fam.children():
        for i, cnt in enumerate(child.snapshot().counts):
            summed[i] += cnt
    assert list(snap.counts) == summed


# ---- schema lint (ISSUE 11 satellite) -------------------------------------


def test_schema_lint_package_is_clean():
    import schema_lint

    assert schema_lint.violations() == []
    found = schema_lint.scan()
    # sanity: the scan actually sees the well-known emit sites
    phases = {p for p, _, _ in found}
    assert {"wal_append", "delta_stages", "admission", "lpa_iter"} <= phases


def test_schema_lint_catches_unregistered_phase(tmp_path):
    import schema_lint

    bad = tmp_path / "mod.py"
    bad.write_text(
        'def f(sink):\n'
        '    sink.emit(\n'
        '        "definitely_not_registered_phase", x=1)\n'
        '    sink.emit("wal_append", seq=1)\n'
    )
    out = schema_lint.violations(str(tmp_path))
    assert len(out) == 1
    assert "definitely_not_registered_phase" in out[0]
    assert "mod.py:2" in out[0]


# ---- obs_report strict gate (ISSUE 11 satellite) --------------------------


def test_obs_report_fails_on_half_stamped_records(tmp_path, capsys):
    from tools.obs_report import main as report_main

    mo = str(tmp_path / "m.jsonl")
    sink = MetricsSink(stream_path=mo, tracer=Tracer())
    sink.emit("run_start", pid=1)
    sink.emit("warning", message="fine")
    # a half-stamped record: run_id without the rest of the identity
    with open(mo, "a") as f:
        f.write(json.dumps({
            "phase": "warning", "t": time.time(), "message": "rotted",
            "run_id": sink.tracer.run_id,
        }) + "\n")
    assert report_main([mo]) == 3
    err = capsys.readouterr().err
    assert "partial trace identity" in err
    assert report_main([mo, "--lenient"]) == 0
    # unknown phases fail the same gate
    mo2 = str(tmp_path / "m2.jsonl")
    sink2 = MetricsSink(stream_path=mo2, tracer=Tracer())
    sink2.emit("run_start", pid=1)
    with open(mo2, "a") as f:
        f.write(json.dumps(
            {"phase": "not_a_phase", "t": time.time()}
        ) + "\n")
    capsys.readouterr()
    assert report_main([mo2]) == 3
    # and a clean stream still exits 0
    mo3 = str(tmp_path / "m3.jsonl")
    sink3 = MetricsSink(stream_path=mo3, tracer=Tracer())
    sink3.emit("run_start", pid=1)
    sink3.emit("run_end", ok=True)
    capsys.readouterr()
    assert report_main([mo3]) == 0


# ---- trace_stitch units ---------------------------------------------------


def test_trace_stitch_joins_shards_and_gates_stamping(tmp_path, capsys):
    import trace_stitch

    obs = tmp_path / "obs"
    writer = shard_sink(str(obs), "writer")
    router = shard_sink(str(obs), "router")
    ctx = TraceContext("fe" * 8, "dc" * 4)
    with writer.span("http:delta", emit=False, remote=ctx):
        writer.emit("admission", verdict="accept", reason="", rows=2,
                    queue_depth=0, repair_debt={})
        writer.emit("wal_append", seq=1, rows=2, bytes=100, seconds=0.001)
        writer.emit("delta_stages", version=2, seq=1, stages={
            "wal_fsync_s": 0.001, "queued_s": 0.0, "apply_s": 0.1,
            "total_s": 0.101,
        })
        writer.emit("snapshot_publish", version=2, snapshot_id="x",
                    path="p", bytes=10, arrays=["labels"], seconds=0.01)
    with router.span("fleet:delta", emit=False, remote=ctx):
        router.emit("delta_visible", replica="r1", version=2,
                    seconds=0.2)
    records, bad, problems = trace_stitch.load_shards([str(obs)])
    assert bad == 0 and problems == []
    traces = trace_stitch.stitch(records)
    deltas = trace_stitch.delta_traces(traces)
    assert ctx.trace_id in deltas
    _, stages = deltas[ctx.trace_id]
    assert all(stages.values()), stages
    assert trace_stitch.main([str(obs)]) == 0
    out = capsys.readouterr().out
    assert "verdict: COMPLETE" in out
    assert "2 process(es)" in out
    # a half-stamped record fails the gate (exit 3), --lenient downgrades
    with open(obs / "rotten.jsonl", "w") as f:
        f.write(json.dumps({
            "phase": "warning", "t": time.time(), "message": "x",
            "trace_id": "aa" * 8,
        }) + "\n")
    assert trace_stitch.main([str(obs)]) == 3
    capsys.readouterr()
    assert trace_stitch.main([str(obs), "--lenient"]) == 0
    capsys.readouterr()
    assert trace_stitch.main([str(tmp_path / "empty")]) == 2


def test_obs_report_directory_mode_renders_fleet_traces(tmp_path, capsys):
    """obs_report accepts a fleet --obs-dir: shards merge into one view
    and the fleet-traces section renders the trace_stitch join inline,
    each line attributed to the emitting process."""
    from tools.obs_report import main as report_main

    obs = tmp_path / "obs"
    writer = shard_sink(str(obs), "writer")
    router = shard_sink(str(obs), "router")
    ctx = TraceContext("ab" * 8, "cd" * 4)
    with writer.span("http:delta", emit=False, remote=ctx):
        writer.emit("admission", verdict="accept", reason="", rows=2,
                    queue_depth=0, repair_debt={})
        writer.emit("wal_append", seq=1, rows=2, bytes=100, seconds=0.001)
        writer.emit("delta_stages", version=2, seq=1, stages={
            "wal_fsync_s": 0.001, "queued_s": 0.0, "apply_s": 0.1,
            "total_s": 0.101,
        })
        writer.emit("snapshot_publish", version=2, snapshot_id="x",
                    path="p", bytes=10, arrays=["labels"], seconds=0.01)
    with router.span("fleet:delta", emit=False, remote=ctx):
        router.emit("delta_visible", replica="r1", version=2,
                    seconds=0.2)
    assert report_main([str(obs)]) == 0
    out = capsys.readouterr().out
    assert "-- fleet traces (cross-process timelines) --" in out
    assert "verdict: COMPLETE" in out
    assert "complete per-delta timelines: 1/1" in out
    # shard attribution: the line for wal_append names the writer shard,
    # delta_visible the router shard
    assert any("writer-" in ln and "wal_append" in ln
               for ln in out.splitlines())
    assert any("router-" in ln and "delta_visible" in ln
               for ln in out.splitlines())


# ---- stdlib-only surface (acceptance) -------------------------------------


def test_obs_and_tools_import_without_jax():
    """obs/ and the triage tools must load on a machine with no jax at
    all — a meta-path blocker in a child process proves it (the lazy
    PEP 562 package __init__ is what makes this possible)."""
    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_module(self, name, path=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            return self\n"
        "    def load_module(self, name):\n"
        "        raise ImportError('jax blocked: ' + name)\n"
        "sys.meta_path.insert(0, Block())\n"
        f"sys.path.insert(0, {_REPO!r})\n"
        f"sys.path.insert(0, {os.path.join(_REPO, 'tools')!r})\n"
        "import graphmine_tpu\n"
        "import graphmine_tpu.obs.schema\n"
        "from graphmine_tpu.obs import Histogram, TraceContext, Tracer\n"
        "import obs_report, trace_stitch, schema_lint\n"
        "print('ok')\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "ok" in p.stdout


# ---- POST /profilez -------------------------------------------------------


def test_profilez_disabled_answers_403(tmp_path):
    store, _ = _publish_base(tmp_path)
    srv = SnapshotServer(store)
    host, port = srv.start()
    try:
        code, body, _ = _post(host, port, "/profilez", {"duration_ms": 10})
        assert code == 403
        assert "disabled" in body["error"]
    finally:
        srv.stop()


def test_profilez_degrades_501_when_profiler_unavailable(
    tmp_path, monkeypatch,
):
    import jax

    store, _ = _publish_base(tmp_path)
    srv = SnapshotServer(store, profilez_dir=str(tmp_path / "prof"))
    host, port = srv.start()

    def boom(*a, **kw):
        raise RuntimeError("no profiler on this build")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    try:
        code, body, _ = _post(host, port, "/profilez", {"duration_ms": 10})
        assert code == 501
        assert "unavailable" in body["error"]
    finally:
        srv.stop()


def test_profilez_captures_and_tags_with_trace_id(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store, _ = _publish_base(tmp_path)
    srv = SnapshotServer(
        store, sink=sink, profilez_dir=str(tmp_path / "prof"),
    )
    host, port = srv.start()
    ctx = TraceContext("ba" * 8, "dc" * 4)
    try:
        code, body, _ = _post(
            host, port, "/profilez", {"duration_ms": 30},
            headers={TRACE_HEADER: ctx.to_header()},
        )
        assert code == 200, body
        assert body["trace_id"] == ctx.trace_id
        assert ctx.trace_id in body["dir"]
        assert os.path.isdir(body["dir"])
        caps = [r for r in sink.records if r["phase"] == "profile_capture"]
        assert caps and caps[-1]["ok"] is True
        assert caps[-1]["trace_id"] == ctx.trace_id
    finally:
        srv.stop()


# ---- writer-side delta stages + trace adoption ----------------------------


def test_delta_stages_record_in_the_clients_trace(tmp_path):
    sink = MetricsSink(tracer=Tracer())
    store, _ = _publish_base(tmp_path)
    srv = SnapshotServer(store, sink=sink, wal=str(tmp_path / "wal"))
    host, port = srv.start()
    ctx = TraceContext("aa" * 8, "bb" * 4)
    try:
        code, body, _ = _post(
            host, port, "/delta", {"insert": [[1, 39]]},
            headers={TRACE_HEADER: ctx.to_header()},
        )
        assert code == 200 and body["version"] == 2
        by_phase = {}
        for r in sink.records:
            by_phase.setdefault(r["phase"], []).append(r)
        # the whole writer-side chain landed in the CLIENT's trace:
        # middleware adoption (access_log, admission, wal_append) plus
        # worker-side leader-span adoption (delta_apply,
        # snapshot_publish) plus the per-batch stage record
        for phase in ("access_log", "admission", "wal_append",
                      "delta_apply", "snapshot_publish", "delta_stages"):
            recs = [
                r for r in by_phase.get(phase, ())
                if r.get("trace_id") == ctx.trace_id
            ]
            assert recs, f"{phase} not in the client's trace"
        stages = [
            r for r in by_phase["delta_stages"]
            if r["trace_id"] == ctx.trace_id
        ][-1]["stages"]
        assert tuple(stages) == DELTA_STAGES == (
            "wal_fsync_s", "queued_s", "apply_s", "commit_s", "total_s"
        )
        assert all(seconds >= 0 for seconds in stages.values())
        # one clock, marks rounded before the differences are taken:
        # the four stages ARE the total, to the microsecond
        assert sum(
            stages[k] for k in DELTA_STAGES[:-1]
        ) == pytest.approx(stages["total_s"], abs=1e-6)
        # the WAL entry carries the header durably
        entry = srv.wal.entries(1)[0]
        assert TraceContext.from_header(
            entry["trace"]
        ).trace_id == ctx.trace_id
        # /statusz serves the per-stage breakdown
        statusz = _get(host, port, "/statusz")
        assert set(statusz["delta_stages"]) == {
            "wal_fsync", "queued", "apply", "commit", "total"
        }
        assert statusz["delta_stages"]["wal_fsync"]["count"] >= 1
        assert statusz["delta_stages"]["commit"]["count"] >= 1
        assert validate_records(sink.records) == []
    finally:
        srv.stop()


_DELTA_STAGE_SPANS = ("delta_splice", "delta_build_graph", "delta_repair",
                      "delta_lof", "delta_census")
_PUBLISH_STAGE_SPANS = ("publish_fetch", "publish_canary",
                        "publish_fingerprint", "publish_write",
                        "publish_quality")


def test_a_served_delta_nests_its_ten_stage_spans_under_delta_apply(tmp_path):
    """The five `delta_*` stages and the five `publish_*` stages of the
    shared tail, in order, under `delta_apply`, in the CLIENT's trace;
    `apply_s` of the request's `delta_stages` is that span, and the
    record's `repair_seconds` / `lof_seconds` are two of the stages."""
    import jax

    # "a first delta compiles" is asserted below: true of a fresh process,
    # not of an xdist worker that ran tests/test_serve.py before this file
    # and still holds the delta's programs. Make it true here.
    jax.clear_caches()
    sink = MetricsSink(tracer=Tracer())
    store, _ = _publish_base(tmp_path)
    srv = SnapshotServer(store, sink=sink, wal=str(tmp_path / "wal"))
    host, port = srv.start()
    ctx = TraceContext("cc" * 8, "dd" * 4)
    try:
        code, body, _ = _post(
            host, port, "/delta", {"insert": [[1, 39]], "delete": [[0, 1]]},
            headers={TRACE_HEADER: ctx.to_header()},
        )
        assert code == 200 and body["version"] == 2
        mine = [r for r in sink.records if r.get("trace_id") == ctx.trace_id]
        spans = [r for r in mine if r["phase"] == "span"]
        (apply_span,) = [r for r in spans if r["name"] == "delta_apply"]
        under = [r for r in spans
                 if r.get("parent_span_id") == apply_span["span_id"]]
        assert [r["name"] for r in under] == list(
            _DELTA_STAGE_SPANS + _PUBLISH_STAGE_SPANS
        )
        for r in under:
            assert r["span_path"] == f"{apply_span['span_path']}/{r['name']}"
            assert r["status"] == "ok"
        by_name = {r["name"]: r for r in under}
        # the counts that explain the seconds
        assert (by_name["delta_splice"]["inserted"],
                by_name["delta_splice"]["deleted"],
                by_name["delta_splice"]["quarantined"]) == (1, 1, 0)
        (record,) = [r for r in mine if r["phase"] == "delta_apply"]
        assert by_name["delta_build_graph"]["num_edges"] == record["num_edges"]
        assert by_name["delta_repair"]["method"] == record["method"]
        assert by_name["delta_repair"]["iterations"] == record["iterations"]
        assert by_name["delta_lof"]["mode"] == "refresh"
        assert by_name["delta_lof"]["stale"] is False
        assert by_name["publish_fetch"]["arrays"] >= 8
        assert by_name["publish_fingerprint"]["rows"] == record["num_edges"]
        assert by_name["publish_write"]["version"] == 2
        assert by_name["publish_write"]["arrays"] == len(
            srv.engine.snapshot.arrays
        )
        # the store's own timed record stays, inside its stage
        (written,) = [r for r in mine if r["phase"] == "snapshot_publish"]
        assert written["span_path"] == by_name["publish_write"]["span_path"]
        assert written["seconds"] <= by_name["publish_write"]["seconds"] + 1e-4
        # no second pair of clocks: the record's stage split IS the spans'
        assert record["repair_seconds"] == by_name["delta_repair"]["seconds"]
        assert record["lof_seconds"] == by_name["delta_lof"]["seconds"]
        # ... and the stages cover the apply (what none names stays small)
        assert sum(r["seconds"] for r in under) >= 0.9 * apply_span["seconds"]
        # one clock for the served delta: apply_s is the span
        (stages,) = [r["stages"] for r in mine if r["phase"] == "delta_stages"]
        assert stages["apply_s"] == pytest.approx(
            apply_span["seconds"], abs=1e-4  # the span record's rounding
        )
        # a first delta compiles; its compile records name their stage
        compiles = [r for r in mine if r["phase"] == "compile"]
        assert compiles and all(
            r["span_path"].startswith(apply_span["span_path"] + "/delta_")
            or r["span_path"].startswith(apply_span["span_path"] + "/publish_")
            for r in compiles
        ), sorted({r["span_path"] for r in compiles})
        assert validate_records(sink.records) == []
    finally:
        srv.stop()


def test_a_pipeline_job_closes_its_span_tree_and_stages_its_publish(tmp_path):
    """The root `run` span is a record beside `run_end`; the six
    `publish_*` stages lie under `snapshot_publish` and cover it."""
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline

    rng = np.random.default_rng(7)
    # big enough that the warm chapter is tens of milliseconds: its self
    # time is a few calls and seven records' rounding, well under 1 ms
    v, e = 20000, 200000
    src = rng.integers(0, v, e)
    dst = (src // 50 * 50 + rng.integers(0, 50, e)) % v  # 400 communities
    path = tmp_path / "edges.txt"
    path.write_text("".join(f"n{a} n{b}\n" for a, b in zip(src, dst)))
    res = run_pipeline(PipelineConfig(
        data_path=str(path), data_format="edgelist", max_iter=3,
        outlier_method="none", num_devices=1,
        snapshot_out=str(tmp_path / "snap"),
    ))
    recs = res.metrics.records
    assert validate_records(recs) == []
    spans = [r for r in recs if r["phase"] == "span"]
    # the tree closes: the root is written like any span, last of them,
    # just before run_end, and holds every chapter
    assert recs[-1]["phase"] == "run_end" and recs[-2] is spans[-1]
    root = spans[-1]
    assert (root["name"], root["span_path"], root["status"]) == (
        "run", "run", "ok"
    )
    assert "parent_span_id" not in root
    chapters = [r for r in spans if r.get("parent_span_id") == root["span_id"]]
    assert [r["name"] for r in chapters] == [
        "load", "build_graph", "lpa", "census", "snapshot_publish",
    ]
    outside = root["seconds"] - sum(r["seconds"] for r in chapters)
    assert 0 <= outside < 0.05 * root["seconds"] + 0.05
    # the publish chapter: six stages, in order, one level under the
    # phase's rung, and what none of them names under 5 % of the chapter
    chapter = chapters[-1]
    stages = [r for r in spans
              if r["span_path"].startswith(chapter["span_path"] + "/")
              and r["name"].startswith("publish_")]
    assert [r["name"] for r in stages] == ["publish_cc", *_PUBLISH_STAGE_SPANS]
    assert len({r["parent_span_id"] for r in stages}) == 1
    by_name = {r["name"]: r for r in stages}
    assert by_name["publish_cc"]["supersteps"] >= 2
    assert by_name["publish_fetch"]["arrays"] == 7
    assert by_name["publish_fingerprint"]["rows"] == e
    assert by_name["publish_write"]["version"] == 1
    self_s = chapter["seconds"] - sum(r["seconds"] for r in stages)
    assert -1e-3 <= self_s < 0.05 * chapter["seconds"], (self_s, chapter)
    # the records that were there land under their stage
    for phase, stage in (("superstep_timing", "publish_cc"),
                         ("fixpoint", "publish_cc"),
                         ("snapshot_publish", "publish_write"),
                         ("quality_snapshot", "publish_quality"),
                         ("canary_score", "publish_quality")):
        (rec,) = [r for r in recs if r["phase"] == phase
                  and r["span_path"].startswith(chapter["span_path"] + "/")]
        assert rec["span_path"] == by_name[stage]["span_path"], phase


# ---- router: time-to-visible merged histogram + statusz -------------------


def test_router_time_to_visible_merged_equals_counterwise_sum(tmp_path):
    """Acceptance: the router /metrics merged time_to_visible histogram's
    bucket counters equal the counter-wise sum of the per-replica
    snapshots, asserted via Histogram.merge."""
    sink = MetricsSink(tracer=Tracer())
    store, _ = _publish_base(tmp_path)
    servers = [SnapshotServer(store, sink=sink, wal=str(tmp_path / "wal"))]
    servers += [SnapshotServer(store) for _ in range(2)]
    addrs = [s.start() for s in servers]
    specs = [
        ReplicaSpec(f"r{i}", h, p) for i, (h, p) in enumerate(addrs)
    ]
    router = FleetRouter(
        specs, writer="r0", sink=sink, config=_fast_config(),
    )
    rh, rp = router.start()
    try:
        deadline = time.monotonic() + 10
        while (
            router.replica_set.committed_version() is None
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        for pair in ([1, 39], [2, 38]):
            code, body, _ = _post(rh, rp, "/delta", {"insert": [pair]})
            assert code == 200, body
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            with router._vis_lock:
                drained = not router._visibility
            if drained:
                break
            time.sleep(0.05)
        fam = router.registry.histogram_family(
            "graphmine_fleet_time_to_visible_seconds"
        )
        assert fam is not None
        children = fam.children()
        assert {c.labels["replica"] for c in children} == {"r0", "r1", "r2"}
        # every (delta, replica) leg observed: 2 deltas x 3 replicas
        assert sum(c.snapshot().count for c in children) == 6
        merged = router.time_to_visible_merged()
        reference = Histogram("ref", buckets=fam.bounds)
        for child in children:
            reference.merge(child)
        assert merged.snapshot().counts == reference.snapshot().counts
        assert merged.snapshot().count == 6
        # the merged series rides the /metrics exposition
        text = _get(rh, rp, "/metrics")
        assert "graphmine_fleet_time_to_visible_merged_seconds_count" in text
        assert "graphmine_fleet_time_to_visible_seconds" in text
        # /statusz: per-replica + merged quantiles, breaker last reasons,
        # writer epoch, WAL state — the gap-fill satellite
        statusz = _get(rh, rp, "/statusz")
        assert set(statusz["time_to_visible"]) == {
            "r0", "r1", "r2", "merged"
        }
        assert statusz["time_to_visible"]["merged"]["count"] == 6
        assert statusz["writer_epoch"] is not None
        assert statusz["wal"] is not None      # the writer runs a WAL
        for rep in statusz["replicas"]:
            assert "state_reason" in rep
            assert "last_transition_reason" in rep["breaker"]
        # delta_visible records emitted, schema-clean
        vis = [r for r in sink.records if r["phase"] == "delta_visible"]
        assert len(vis) == 6
        assert validate_records(sink.records) == []
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ---- THE acceptance: chaos run -> shards -> stitched timelines ------------


def test_fleet_chaos_trace_stitch_acceptance(tmp_path):
    """ISSUE 11 acceptance: 3-replica fleet chaos (kill + roll + writer
    failover) with per-process shards under one --obs-dir; the shards
    ALONE reconstruct at least one complete per-delta timeline and the
    failover epoch-fence sequence, with no half-stamped records."""
    import trace_stitch

    obs = str(tmp_path / "obs")
    store, _ = _publish_base(tmp_path)
    s_writer = shard_sink(obs, "writer")
    s_standby = shard_sink(obs, "standby")
    s_replica = shard_sink(obs, "replica-2")
    s_router = shard_sink(obs, "router")
    wal_p = str(tmp_path / "wal-r0")
    w0 = SnapshotServer(store, sink=s_writer, wal=wal_p)
    h0, p0 = w0.start()
    w1 = SnapshotServer(
        store, sink=s_standby, wal=str(tmp_path / "wal-r1"),
        standby_of=f"http://{h0}:{p0}", primary_wal=wal_p,
        ship_interval_s=0.05,
    )
    h1, p1 = w1.start()
    w2 = SnapshotServer(store, sink=s_replica)
    h2, p2 = w2.start()
    router = FleetRouter(
        [ReplicaSpec("r0", h0, p0), ReplicaSpec("r1", h1, p1),
         ReplicaSpec("r2", h2, p2)],
        writer="r0", standby="r1", sink=s_router, config=_fast_config(),
    )
    rh, rp = router.start()
    sinks = (s_writer, s_standby, s_replica, s_router)
    try:
        deadline = time.monotonic() + 10
        while (
            router.replica_set.committed_version() is None
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)

        # two synchronous deltas through the router: the per-delta
        # timelines under test
        for i, pair in enumerate(([1, 39], [2, 38])):
            code, body, _ = _post(
                rh, rp, "/delta", {"insert": [pair]},
                headers={"X-Delta-Id": f"acc-{i}"},
            )
            assert code == 200, body
        # let the prober close every replica's visibility leg
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            with router._vis_lock:
                if not router._visibility:
                    break
            time.sleep(0.05)

        # a read for trace variety
        _get(rh, rp, "/vertex?v=1")

        # CHAOS leg 1 — kill + restart a read replica (health churn)
        faults.replica_kill(w2)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.replica_set.replica("r2").state == "down":
                break
            router.probe_once()
            time.sleep(0.05)
        assert router.replica_set.replica("r2").state == "down"
        w2b = SnapshotServer(store, sink=s_replica, host=h2, port=p2)
        bind_deadline = time.monotonic() + 10
        while True:
            try:
                w2b.start()
                break
            except OSError:
                if time.monotonic() >= bind_deadline:
                    raise
                time.sleep(0.2)

        # CHAOS leg 2 — rolling reload (the roll walk in the stitch)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.replica_set.replica("r2").state == "healthy":
                break
            time.sleep(0.05)
        roll = router.rolling_reload()
        assert roll["ok"], roll

        # CHAOS leg 3 — writer kill, fenced failover onto the standby
        t_kill = time.monotonic()
        faults.writer_kill_mid_apply(w0)
        while time.monotonic() - t_kill < 20.0:
            rs = router.replica_set
            if rs.writer_id == "r1" and not rs.read_only:
                break
            time.sleep(0.05)
        assert router.replica_set.writer_id == "r1"

        # the deposed writer's comeback publish is fenced (loud record)
        try:
            out = w0.apply_delta({"insert": [[0, 13]]},
                                 delta_id="deposed-comeback")
        except Exception:  # noqa: BLE001 — PublishFencedError path
            pass
        else:
            assert out["verdict"] == "shed", out

        # one more delta through the promoted writer
        code, body, _ = _post(
            rh, rp, "/delta", {"insert": [[3, 37]]},
            headers={"X-Delta-Id": "acc-post-failover"},
        )
        assert code == 200, body
    finally:
        router.stop()
        for s in (w0, w1, w2):
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — killed replicas
                pass
        try:
            w2b.stop()
        except Exception:  # noqa: BLE001 — may not exist on early failure
            pass
        for s in sinks:
            s.finalize(s.stream_path)

    # ---- the stitch, from the shards alone ----------------------------
    records, bad, problems = trace_stitch.load_shards([obs])
    assert problems == [], problems[:10]       # zero half-stamped records
    traces = trace_stitch.stitch(records)
    deltas = trace_stitch.delta_traces(traces)
    complete = [
        tid for tid, (_, stages) in deltas.items() if all(stages.values())
    ]
    assert complete, {
        tid: stages for tid, (_, stages) in deltas.items()
    }
    # the complete timeline genuinely crosses processes
    recs, _ = deltas[complete[0]]
    assert len({r["_src"] for r in recs}) >= 2
    # the failover epoch-fence sequence is reconstructable
    phases = {r["phase"] for r in records}
    assert {"writer_promote", "publish_fenced", "fleet_degraded"} <= phases
    report = trace_stitch.build_report(records, bad, problems)
    assert "verdict: COMPLETE" in report
    assert "writer_promote" in report
    assert "publish_fenced" in report
    assert "== failover sequence" in report
    assert "== rolling reload walk" in report
    # and the CLI gate passes end-to-end
    assert trace_stitch.main([obs, "--out", str(tmp_path / "r.txt")]) == 0
