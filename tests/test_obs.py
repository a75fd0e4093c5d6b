"""Tracing & telemetry subsystem (ISSUE 3; marker ``obs``).

Covers the span tree (run -> phase -> rung -> superstep), record schema
validation, the counter/gauge registry + Prometheus textfile exporter,
heartbeats, on-device superstep telemetry (parity + no-extra-cadence),
the MetricsSink stream-append/finalize semantics, maybe_profile
hardening — and the acceptance e2e: a fault-injected CPU pipeline
(device loss + poisoned shard) whose JSONL alone lets
``tools/obs_report.py`` render a recovery timeline and per-superstep
throughput table.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from graphmine_tpu.obs import Registry, Tracer, schema
from graphmine_tpu.obs.heartbeat import Heartbeat
from graphmine_tpu.pipeline.config import PipelineConfig
from graphmine_tpu.pipeline.metrics import MetricsSink, maybe_profile
from graphmine_tpu.pipeline.resilience import ResilienceConfig

from conftest import cached_edgelist

pytestmark = pytest.mark.obs

_E2E: dict = {}


def _edgelist_path() -> str:
    if "path" not in _E2E:
        rng = np.random.default_rng(11)
        v, e = 160, 800
        src = rng.integers(0, v, e)
        dst = (src + rng.integers(1, v // 2, e)) % (v // 2) + (src // (v // 2)) * (v // 2)
        text = "".join(f"{s} {t}\n" for s, t in zip(src, dst))
        _E2E["path"] = cached_edgelist("graphmine_obs", text)
    return _E2E["path"]


def _cfg(**kw):
    base = dict(
        data_path=_edgelist_path(), data_format="edgelist",
        outlier_method="none", num_devices=1, max_iter=5,
        resilience=ResilienceConfig(backoff_base_s=0.001, backoff_max_s=0.01),
    )
    base.update(kw)
    return PipelineConfig(**base)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_tree_paths_and_ids():
    tr = Tracer(run_id="r1")
    assert tr.run_id == "r1" and tr.root.path == "run"
    with tr.span("lpa") as lpa:
        assert lpa.parent_id == tr.root.span_id
        assert lpa.path == "run/lpa"
        with tr.span("rung:primary") as rung:
            assert rung.parent_id == lpa.span_id
            assert rung.path == "run/lpa/rung:primary"
            assert tr.current() is rung
        assert tr.current() is lpa
    assert tr.current() is tr.root
    assert lpa.end_mono is not None and lpa.seconds >= 0


def test_span_error_status_and_monotonic_close():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom") as sp:
            raise ValueError("x")
    assert sp.status == "error" and sp.end_mono is not None
    # the stack unwound; the tracer is reusable
    with tr.span("after") as sp2:
        assert sp2.parent_id == tr.root.span_id


def test_tracer_other_thread_falls_back_to_root():
    tr = Tracer()
    seen = {}
    with tr.span("phase") as sp:
        def probe():
            seen["current"] = tr.current()
            seen["latest"] = tr.latest()
        t = threading.Thread(target=probe)
        t.start()
        t.join()
    # a threadless-span worker still gets run identity (root), while
    # latest() reports what the run was actually doing
    assert seen["current"] is tr.root
    assert seen["latest"] is sp


# ---------------------------------------------------------------------------
# MetricsSink integration: ids on records, span records, of_phase
# ---------------------------------------------------------------------------


def test_emit_stamps_trace_identity_and_of_phase_filters():
    m = MetricsSink(tracer=Tracer(run_id="rX"))
    with m.span("lpa"):
        m.emit("retry", stage="lpa", attempt=1, backoff_s=0.1, error="e")
    rec = m.of_phase("retry")[0]
    assert rec["run_id"] == "rX"
    assert rec["span_path"] == "run/lpa"
    assert rec["trace_id"] and rec["span_id"]
    # the span close emitted its own record, carrying its OWN identity
    sp = m.of_phase("span")[0]
    assert sp["name"] == "lpa" and sp["span_path"] == "run/lpa"
    assert sp["parent_span_id"]  # root
    # of_phase filtering is unaffected by the extra trace keys
    assert len(m.of_phase("retry")) == 1 and not m.of_phase("lpa")
    assert schema.validate_records(m.records) == []


def test_sink_without_tracer_is_unchanged():
    m = MetricsSink()
    rec = m.emit("resume", iteration=3)
    assert "run_id" not in rec and "span_id" not in rec
    with m.span("x") as sp:   # no tracer: yields None, no record
        assert sp is None
    assert not m.of_phase("span")


def test_timed_failure_identity():
    """Satellite: a raising body must leave ok=false + the classified
    error kind on the record (and re-raise) — not masquerade as success."""
    m = MetricsSink()
    with pytest.raises(ValueError, match="boom"):
        with m.timed("census"):
            raise ValueError("boom")
    rec = m.of_phase("census")[0]
    assert rec["ok"] is False and rec["error"] == "fatal"
    assert "boom" in rec["error_detail"] and rec["seconds"] >= 0

    with pytest.raises(ConnectionError):
        with m.timed("load", path="p"):
            raise ConnectionError("transport closed")
    rec = m.of_phase("load")[0]
    assert rec["ok"] is False and rec["error"] == "retryable"

    # success records carry no failure keys
    with m.timed("census"):
        pass
    assert "ok" not in m.of_phase("census")[1]


# ---------------------------------------------------------------------------
# stream append / run_start header / finalize fallbacks
# ---------------------------------------------------------------------------


def test_stream_appends_across_runs_with_run_start_headers(tmp_path):
    """Satellite: a resumed run reusing --metrics-out must append a new
    run_start-delimited segment, not clobber the prior run's records."""
    from graphmine_tpu.pipeline.driver import run_pipeline

    mo = str(tmp_path / "m.jsonl")
    run_pipeline(_cfg(max_iter=2, metrics_out=mo))
    run_pipeline(_cfg(max_iter=2, metrics_out=mo))
    recs = [json.loads(x) for x in open(mo)]
    starts = [r for r in recs if r["phase"] == "run_start"]
    ends = [r for r in recs if r["phase"] == "run_end"]
    assert len(starts) == 2 and len(ends) == 2
    assert starts[0]["run_id"] != starts[1]["run_id"]
    # both segments fully present (first run's records not clobbered)
    first = [r for r in recs if r["run_id"] == starts[0]["run_id"]]
    assert any(r["phase"] == "lpa_iter" for r in first)
    assert schema.validate_records(recs) == []


def test_finalize_append_tail_after_stream_failure(tmp_path):
    """Satellite: stream fails mid-run -> finalize appends exactly the
    records the stream never persisted (no loss, no duplicates)."""
    p = str(tmp_path / "m.jsonl")
    m = MetricsSink(stream_path=p)
    m.emit("resume", iteration=1)           # streams fine

    class _Broken:
        def write(self, _):
            raise OSError("disk full")
        def flush(self):
            pass
        def close(self):
            pass

    m._stream = _Broken()
    m.emit("resume", iteration=2)           # write fails -> streaming off
    assert m._stream_ok is False
    m.emit("resume", iteration=3)           # memory only
    out = m.finalize(p)
    assert out == p
    recs = [json.loads(x) for x in open(p)]
    assert [r["iteration"] for r in recs] == [1, 2, 3]


def test_finalize_repairs_torn_final_line(tmp_path):
    """A stream that died mid-write leaves a torn final line; finalize's
    append must not merge it with the first re-appended record."""
    p = str(tmp_path / "m.jsonl")
    m = MetricsSink(stream_path=p)
    m.emit("resume", iteration=1)
    # simulate a partial write that crashed before its newline
    m._stream.close()
    m._stream, m._stream_ok = None, False
    with open(p, "a") as f:
        f.write('{"phase": "resu')
    m.emit("resume", iteration=2)  # memory only (streaming disabled)
    m.finalize(p)
    from tools.obs_report import load_records

    recs, bad = load_records(p)
    assert bad == 1  # the torn line, counted, not merged
    assert [r["iteration"] for r in recs] == [1, 2]


def test_finalize_to_different_path_writes_all_records(tmp_path):
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    m = MetricsSink(stream_path=p1)
    m.emit("resume", iteration=1)
    m.emit("resume", iteration=2)
    m.finalize(p2)
    assert [json.loads(x)["iteration"] for x in open(p2)] == [1, 2]
    # the stream file keeps its own copy
    assert [json.loads(x)["iteration"] for x in open(p1)] == [1, 2]


def test_finalize_without_streaming_appends(tmp_path):
    p = str(tmp_path / "m.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"phase": "resume", "t": 0, "iteration": 0}) + "\n")
    m = MetricsSink()
    m.emit("resume", iteration=1)
    m.finalize(p)
    assert [json.loads(x)["iteration"] for x in open(p)] == [0, 1]


# ---------------------------------------------------------------------------
# maybe_profile hardening
# ---------------------------------------------------------------------------


def test_maybe_profile_stop_failure_does_not_mask_body_error(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)

    def bad_stop():
        raise RuntimeError("No profiler session active")

    monkeypatch.setattr(jax.profiler, "stop_trace", bad_stop)
    m = MetricsSink()
    with pytest.raises(ValueError, match="the real error"):
        with maybe_profile(str(tmp_path), sink=m):
            raise ValueError("the real error")
    rec = m.of_phase("profile_capture")[0]
    assert rec["ok"] is False and str(tmp_path) in rec["dir"]


def test_maybe_profile_start_failure_runs_unprofiled(tmp_path, monkeypatch):
    import jax

    def bad_start(d, **kw):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", bad_start)
    m = MetricsSink()
    ran = []
    with maybe_profile(str(tmp_path), sink=m):
        ran.append(1)
    assert ran == [1]
    assert m.of_phase("profile_capture")[0]["ok"] is False


def test_maybe_profile_success_records_trace_dir(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    m = MetricsSink()
    with maybe_profile(str(tmp_path), sink=m):
        pass
    rec = m.of_phase("profile_capture")[0]
    assert rec["ok"] is True and rec["dir"] == str(tmp_path)


# ---------------------------------------------------------------------------
# registry + Prometheus textfile + heartbeat
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_and_conflicts():
    reg = Registry()
    c = reg.counter("graphmine_retries_total", "retries")
    c.inc()
    c.inc(2)
    g = reg.gauge("graphmine_superstep")
    g.set(7)
    assert reg.values() == {"graphmine_retries_total": 3, "graphmine_superstep": 7}
    assert reg.counter("graphmine_retries_total") is c  # get-or-create
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("graphmine_retries_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name!")
    with pytest.raises(ValueError, match="only increase"):
        c.inc(-1)


def test_prometheus_textfile_format_and_labels(tmp_path):
    reg = Registry()
    reg.counter("graphmine_retries_total", "total retries").inc(4)
    reg.gauge("graphmine_superstep").set(2.5)
    p = str(tmp_path / "gm.prom")
    reg.write_textfile(p, labels={"run_id": 'r"1"'})
    text = open(p).read()
    assert "# HELP graphmine_retries_total total retries" in text
    assert "# TYPE graphmine_retries_total counter" in text
    assert 'graphmine_retries_total{run_id="r\\"1\\""} 4' in text
    assert "# TYPE graphmine_superstep gauge" in text
    assert "graphmine_superstep" in text and "2.5" in text
    # atomic: no tmp litter
    assert os.listdir(tmp_path) == ["gm.prom"]


def test_heartbeat_records_phase_gauges_rss(tmp_path):
    tr = Tracer()
    m = MetricsSink(tracer=tr)
    m.registry.gauge("graphmine_superstep").set(3)
    prom = str(tmp_path / "hb.prom")
    hb = Heartbeat(m, every_s=0.01, prom_path=prom)
    with tr.span("lpa"):
        hb.beat()
    rec = m.of_phase("heartbeat")[0]
    assert rec["uptime_s"] >= 0 and rec["busy"] == "run/lpa"
    assert rec["gauges"]["graphmine_superstep"] == 3
    assert rec.get("rss_mb", 1) > 0  # None is dropped off-Linux
    assert os.path.exists(prom)
    assert schema.validate_records(m.records) == []


def test_heartbeat_thread_beats_and_stops():
    m = MetricsSink(tracer=Tracer())
    hb = Heartbeat(m, every_s=0.01).start()
    deadline = time.time() + 2.0
    while not m.of_phase("heartbeat") and time.time() < deadline:
        time.sleep(0.01)
    hb.stop()
    n = len(m.of_phase("heartbeat"))
    assert n >= 1
    time.sleep(0.05)
    assert len(m.of_phase("heartbeat")) == n  # stopped means stopped


# ---------------------------------------------------------------------------
# schema validator
# ---------------------------------------------------------------------------


def test_schema_rejects_unknown_phase_and_missing_keys():
    ok = {"phase": "retry", "t": 1.0, "stage": "lpa", "attempt": 1,
          "backoff_s": 0.1, "error": "e"}
    assert schema.validate_record(ok) == []
    bad = dict(ok, phase="retyr")
    assert any("unknown phase" in p for p in schema.validate_record(bad))
    missing = {"phase": "retry", "t": 1.0}
    assert any("missing required keys" in p
               for p in schema.validate_record(missing))
    partial = dict(ok, run_id="r")
    assert any("partial trace identity" in p
               for p in schema.validate_record(partial))
    assert schema.validate_record({"t": 1.0}) == ["missing/empty phase in {'t': 1.0}"]


def test_schema_register_extends():
    schema.register("obs_test_phase", "k1")
    try:
        assert schema.validate_record(
            {"phase": "obs_test_phase", "t": 0.0, "k1": 1}
        ) == []
    finally:
        del schema.SCHEMAS["obs_test_phase"]


@pytest.mark.parametrize("scan", ["carried", "plain"])
def test_the_mesh_entry_s_records_say_what_each_field_holds_on_a_mesh(scan, monkeypatch):
    """One ``label_propagation(..., mesh=, sink=)`` call (ISSUE 39): every
    record registered and complete; ``impl_selected`` says which job ran,
    ``plan_build`` the index's seconds inside its own, ``device_residency``
    one chip's share, and the carried job's ``superstep_delta`` the shards
    and the LARGEST shard's messages; the one compiled program, which
    counts nothing a superstep, writes none."""
    import graphmine_tpu as gm
    from graphmine_tpu.ops import superstep_policy

    if scan == "plain":
        monkeypatch.setattr(superstep_policy, "mesh_memory_stats",
                            lambda mesh: {"bytes_limit": 4096, "bytes_in_use": 0})
    rng = np.random.default_rng(39)
    u, v = rng.integers(0, 2000, 30000), rng.integers(0, 2000, 30000)
    host = gm.build_graph(u, v, num_vertices=2000, to_device=False)
    m = MetricsSink()
    gm.label_propagation(host, max_iter=4, mesh=gm.make_mesh(4), sink=m)
    assert schema.validate_records(m.records) == []
    by_phase = {r["phase"]: r for r in m.records}
    assert by_phase["impl_selected"]["scan"] == scan
    assert by_phase["impl_selected"]["scan_reason"].startswith("a shard of 4")
    build = by_phase["plan_build"]
    assert 0.0 <= build["index_seconds"] <= build["seconds"]
    held = by_phase["device_residency"]
    assert (held["shards"], held["scan"], held["graph_bytes"]) == (4, scan, 0)
    padded = 4 * 504  # four shards of 500 vertices, each up to a multiple of 8
    assert held["labels_bytes"] == 8 * padded and held["plan_bytes"] > 0
    assert held["bytes_limit"] == (4096 if scan == "plain" else None)
    largest = by_phase["exchange"]["messages_per_shard_max"]
    if scan == "plain":
        assert held["rows_bytes"] == held["slot_index_bytes"] == 0
        assert "superstep_delta" not in by_phase
        return
    assert held["slot_index_bytes"] == 4 * (padded + 1 + largest)
    assert held["rows_bytes"] == 4 * by_phase["exchange"]["padded_slots_per_shard"]
    delta = by_phase["superstep_delta"]
    assert delta["shards"] == 4 and delta["num_messages"] == largest
    assert delta["rungs"] == sorted({largest // d for d in (4096, 256, 16, 6)} - {0})
    assert len(delta["branch"]) == len(delta["seconds"]) == 4
    assert max(delta["changed_messages"]) <= largest  # one shard's K, not the sum


# ---------------------------------------------------------------------------
# on-device superstep telemetry (sharded API)
# ---------------------------------------------------------------------------


def _mesh_graph(num_devices=4, symmetric=True):
    import jax

    if len(jax.devices()) < num_devices:
        pytest.skip(f"needs {num_devices} virtual devices")
    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
    )

    rng = np.random.default_rng(3)
    v, e = 96, 500
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    mesh = make_mesh(num_devices)
    g = build_graph(src, dst, num_vertices=v, symmetric=symmetric,
                    to_device=False)
    sg = shard_graph_arrays(partition_graph(g, mesh=mesh), mesh)
    return g, sg, mesh, (src, dst, v)


def test_sharded_lpa_telemetry_matches_manual_diffs():
    from graphmine_tpu.parallel.sharded import sharded_label_propagation

    _, sg, mesh, _ = _mesh_graph()
    plain = np.asarray(sharded_label_propagation(sg, mesh, max_iter=4))
    labels, tel = sharded_label_propagation(sg, mesh, max_iter=4,
                                            telemetry=True)
    np.testing.assert_array_equal(np.asarray(labels), plain)  # bit-identical
    assert tel.iterations == 4
    assert tel.labels_changed.shape == (4,)
    assert tel.shard_changed.shape == (4, sg.num_shards)
    # per-shard counts sum to the global count; frontier aliases it
    np.testing.assert_array_equal(tel.shard_changed.sum(1), tel.labels_changed)
    np.testing.assert_array_equal(tel.frontier, tel.labels_changed)
    # replay the supersteps one at a time: the counters must match the
    # actual per-iteration label diffs
    prev = np.arange(sg.num_vertices, dtype=np.int32)
    for t in range(4):
        cur = np.asarray(sharded_label_propagation(
            sg, mesh, max_iter=1, init_labels=prev
        ))
        assert int((cur != prev).sum()) == tel.labels_changed[t]
        prev = cur
    imb = tel.imbalance_ratio()
    assert imb.shape == (4,) and (imb >= 1.0 - 1e-6).all()


def test_sharded_cc_and_pagerank_telemetry():
    from graphmine_tpu.ops.degrees import out_degrees
    from graphmine_tpu.parallel.sharded import (
        sharded_connected_components,
        sharded_pagerank,
    )

    _, sg, mesh, _ = _mesh_graph()
    plain = np.asarray(sharded_connected_components(sg, mesh))
    labels, tel = sharded_connected_components(sg, mesh, telemetry=True)
    np.testing.assert_array_equal(np.asarray(labels), plain)
    assert tel.iterations >= 1
    assert len(tel.labels_changed) == tel.iterations
    assert tel.labels_changed[-1] == 0  # converged: final pass changed nothing

    g, sgd, mesh, _ = _mesh_graph(symmetric=False)
    od = out_degrees(g)
    plain = np.asarray(sharded_pagerank(sgd, mesh, od, max_iter=40))
    ranks, rtel = sharded_pagerank(sgd, mesh, od, max_iter=40, telemetry=True)
    np.testing.assert_allclose(np.asarray(ranks), plain, atol=1e-6)
    assert rtel.iterations >= 2
    assert rtel.residuals.shape == (rtel.iterations,)
    assert rtel.shard_residuals.shape == (rtel.iterations, sgd.num_shards)
    # the power iteration's residual trail is broadly decreasing
    assert rtel.residuals[-1] < rtel.residuals[0]
    # per-shard residuals sum to the global L1 delta
    np.testing.assert_allclose(
        rtel.shard_residuals.sum(1), rtel.residuals, rtol=1e-4
    )


def test_sharded_lpa_telemetry_with_tripwires_armed():
    from graphmine_tpu.parallel.sharded import sharded_label_propagation

    _, sg, mesh, _ = _mesh_graph()
    plain = np.asarray(sharded_label_propagation(sg, mesh, max_iter=3))
    labels, tel = sharded_label_propagation(
        sg, mesh, max_iter=3, telemetry=True, tripwire_every=2
    )
    np.testing.assert_array_equal(np.asarray(labels), plain)
    assert tel.labels_changed.shape == (3,)


# ---------------------------------------------------------------------------
# driver cadence: telemetry piggybacks on tripwire/checkpoint boundaries
# ---------------------------------------------------------------------------


def test_superstep_telemetry_cadence(tmp_path):
    from graphmine_tpu.pipeline.driver import run_pipeline

    # no tripwires, no checkpoints: only the final superstep reports
    res = run_pipeline(_cfg(max_iter=4))
    tele = res.metrics.of_phase("superstep_telemetry")
    assert [r["iteration"] for r in tele] == [4]
    rec = tele[0]
    assert rec["frontier"] == rec["labels_changed"]
    assert sum(rec["shard_changed"]) == rec["labels_changed"]
    assert rec["imbalance"] >= 1.0

    # checkpoint cadence 2: boundaries 2, 4 and the final 5
    res = run_pipeline(_cfg(
        max_iter=5, checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2
    ))
    tele = res.metrics.of_phase("superstep_telemetry")
    assert [r["iteration"] for r in tele] == [2, 4, 5]
    # checkpoint saves joined the stream too, span-tagged
    saves = res.metrics.of_phase("checkpoint_save")
    assert [r["iteration"] for r in saves] == [2, 4, 5]
    assert all(r["span_path"].endswith("/superstep") for r in saves)


# ---------------------------------------------------------------------------
# obs_report units
# ---------------------------------------------------------------------------


def _rec(phase, t, **kv):
    return {"phase": phase, "t": t, **kv}


def test_split_runs_and_liveness_verdicts():
    from tools.obs_report import _liveness, split_runs

    recs = (
        [_rec("run_start", 0.0, run_id="a", pid=1),
         _rec("run_end", 1.0, run_id="a", ok=True)]
        + [_rec("run_start", 2.0, run_id="b", pid=2)]
    )
    runs, order = split_runs(recs)
    assert order == ["a", "b"] and len(runs["a"]) == 2

    ok = _liveness(runs["a"], 0.0)
    assert ok[0] == "ok"
    # no run_end, no trailing heartbeats -> DEAD
    dead = _liveness([_rec("run_start", 0.0, pid=1),
                      _rec("lpa_iter", 1.0)], 0.0)
    assert dead[0] == "DEAD"
    # heartbeats continued past the last phase record -> HUNG
    hung = _liveness(
        [_rec("run_start", 0.0, pid=1), _rec("lpa_iter", 1.0),
         _rec("heartbeat", 5.0, uptime_s=5.0, busy="run/lpa/superstep")],
        0.0,
    )
    assert hung[0] == "HUNG" and "run/lpa/superstep" in hung[1]


def test_obs_report_tolerates_torn_lines(tmp_path):
    from tools.obs_report import load_records

    p = tmp_path / "m.jsonl"
    p.write_text(
        json.dumps(_rec("run_start", 0.0, run_id="a", pid=1)) + "\n"
        + '{"phase": "lpa_iter", "t": 1.0, "itera'  # torn final line
    )
    recs, bad = load_records(str(p))
    assert len(recs) == 1 and bad == 1


# ---------------------------------------------------------------------------
# acceptance e2e: fault-injected pipeline -> JSONL -> triage report
# ---------------------------------------------------------------------------


def test_recovery_records_and_report_e2e(tmp_path, capsys):
    """Acceptance: device loss + poisoned shard (testing/faults.py) on a
    4-device CPU run; every recovery record carries run/trace/span
    identity, and obs_report renders a recovery timeline + per-superstep
    throughput table from the JSONL alone."""
    import jax

    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.testing import faults
    from tools.obs_report import main as report_main

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mo = str(tmp_path / "metrics.jsonl")
    cfg = _cfg(
        num_devices=4, metrics_out=mo,
        checkpoint_dir=str(tmp_path / "ck"), heartbeat_every_s=0.05,
        resilience=ResilienceConfig(
            backoff_base_s=0.001, backoff_max_s=0.01, tripwire_every_k=1,
        ),
    )
    inj = faults.FaultInjector()
    inj.add("lpa_superstep", faults.device_loss, at=3)
    inj.add("lpa_superstep", faults.poison_labels(shard=1, num_shards=2), at=6)
    with inj.installed():
        res = run_pipeline(cfg)
    assert inj.fired() == 2

    # -- every recovery record joinable: run/trace/span identity --------
    recovery = [
        r for r in res.metrics.records
        if r["phase"] in ("retry", "degrade", "mesh_degrade", "tripwire",
                          "checkpoint_rollback", "resume")
    ]
    assert {r["phase"] for r in recovery} >= {
        "retry", "degrade", "mesh_degrade", "tripwire", "resume"
    }
    run_ids = set()
    for r in recovery:
        assert r["run_id"] and r["trace_id"] and r["span_id"], r
        assert r["span_path"].startswith("run/lpa"), r
        run_ids.add((r["run_id"], r["trace_id"]))
    assert len(run_ids) == 1  # one causal timeline
    # rung identity: the mesh_degrade landed on the elastic rung's span
    md = res.metrics.of_phase("mesh_degrade")[0]
    assert "rung:elastic@2dev" in md["span_path"]
    # the tripwire fired inside a superstep span of that rung
    tw = res.metrics.of_phase("tripwire")[0]
    assert tw["span_path"].endswith("/superstep")
    # the whole stream passes schema validation — unknown shapes fail loud
    assert schema.validate_records(res.metrics.records) == []

    # -- offline triage from the JSONL alone ----------------------------
    assert report_main([mo]) == 0
    report = capsys.readouterr().out
    assert "recovery timeline" in report
    assert "mesh_degrade" in report and "from_devices=4" in report
    assert "tripwire" in report and "label_out_of_range" in report
    assert "[lpa/rung:elastic@2dev" in report      # span path rendered
    # per-superstep throughput table: all 5 supersteps with the metric
    assert "edges/sec/chip" in report
    table = report.split("-- lpa supersteps --")[1].split("--")[0]
    rows = [ln for ln in table.splitlines() if ln.strip()]
    assert len(rows) == 1 + 5  # header + max_iter supersteps
    assert "status: ok" in report
    assert "beats" in report  # heartbeat section rendered


def test_report_flags_dead_run(tmp_path, capsys):
    """A preempted run (no run_end) must read as DEAD, with its partial
    superstep trail still rendered from the streamed records."""
    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.testing import faults
    from tools.obs_report import main as report_main

    mo = str(tmp_path / "metrics.jsonl")
    inj = faults.FaultInjector()
    inj.add("lpa_superstep", faults.preemption, at=3)
    with inj.installed():
        with pytest.raises(faults.SimulatedPreemption):
            run_pipeline(_cfg(metrics_out=mo, checkpoint_dir=str(tmp_path / "ck")))
    # simulate the kill: strip the orderly run_end/finalize tail the real
    # preemption would never have written
    lines = [ln for ln in open(mo)
             if json.loads(ln)["phase"] not in ("run_end",)]
    with open(mo, "w") as f:
        f.writelines(lines)
    assert report_main([mo]) == 0
    report = capsys.readouterr().out
    assert "DEAD" in report
    assert "lpa supersteps" in report


def test_report_missing_file_and_unknown_run(tmp_path, capsys):
    from tools.obs_report import main as report_main

    assert report_main([str(tmp_path / "nope.jsonl")]) == 2
    mo = str(tmp_path / "m.jsonl")
    with open(mo, "w") as f:
        f.write(json.dumps(_rec("run_start", 0.0, run_id="a", pid=1)) + "\n")
    assert report_main([mo, "--run-id", "zzz"]) == 2
    capsys.readouterr()
