"""Compute-plane performance observability (ISSUE 12, marker `perf`):

- the analytical cost model exact against HAND-COMPUTED tiny plans for
  both superstep families (fused + sharded, weighted) and both LOF
  impls — the derivation reads the plan objects, so these tests pin the
  byte/slot accounting to paper arithmetic;
- roofline anchor overrides (env / file) and provenance;
- superstep_timing achieved-vs-model attribution: ops seams, the driver
  e2e (every LPA/CC phase emits a schema-valid record joinable to its
  phase span — THE acceptance criterion), and the sharded driver path's
  exchange split;
- obs_report's roofline section + the waterfall threshold/model lines;
- schema: half-stamped cost sub-records fail validation; schema_lint
  flags inline cost=... literals outside the single builder.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs import costmodel
from graphmine_tpu.obs.schema import COST_KEYS, validate_record, validate_records
from graphmine_tpu.obs.spans import Tracer
from graphmine_tpu.pipeline.metrics import MetricsSink

from conftest import cached_edgelist

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

# Deterministic anchors for the hand-computed cases (the seeds are real
# measurements; tests want round numbers).
ANCHORS = {
    "gather_slots_per_sec": {"v": 100.0, "src": "test"},
    "exchange_bytes_per_sec": {"v": 400.0, "src": "test"},
    "lof_exact_pairs_per_sec": {"v": 1000.0, "src": "test"},
    "lof_ivf_points_per_sec": {"v": 50.0, "src": "test"},
}


def ring4():
    """Directed 4-ring; symmetric message CSR => M=8, every degree 2."""
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 0], np.int32)
    return build_graph(src, dst, num_vertices=4)


def star21(weights=None):
    """Hub of degree 21 (falls in the ladder's 20->22 gap): bucketed rows
    are 21x1 (leaves) + 1x22 (hub) = 43 padded slots over M=42."""
    src = np.zeros(21, np.int32)
    dst = np.arange(1, 22, dtype=np.int32)
    return build_graph(src, dst, num_vertices=22, edge_weights=weights)


# ---------------------------------------------------------------------------
# cost model: hand-computed exactness
# ---------------------------------------------------------------------------


def test_sort_cost_exact():
    c = costmodel.superstep_cost(
        "lpa_superstep", "sort", 4, 8, 4, anchors=ANCHORS
    )
    assert (c.slots, c.padded_slots) == (8, 8)
    assert c.bytes_gathered == 4 * 8          # one int32 label per slot
    assert c.bytes_scattered == 4 * 4         # V results
    assert c.padding_overhead == 1.0
    assert c.exchange_bytes == 0
    assert c.predicted_seconds == pytest.approx(8 / 100.0)
    assert c.predicted_per_chip == pytest.approx(4 / (8 / 100.0))
    assert c.unit == "edges/s/chip"


def test_weighted_sort_cost_doubles_gathered_bytes():
    c = costmodel.superstep_cost(
        "lpa_superstep", "sort", 4, 8, 4, weighted=True, anchors=ANCHORS
    )
    assert c.bytes_gathered == 2 * 4 * 8      # label + float32 weight
    assert c.predicted_seconds == pytest.approx(16 / 100.0)


def test_bucketed_cost_exact_ring_and_star():
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    plan = BucketedModePlan.from_graph(ring4(), with_send=True)
    c = costmodel.superstep_cost(
        "lpa_superstep", "bucketed", 4, 8, 4, plan=plan, anchors=ANCHORS
    )
    # 4 vertices x width-2 rows = 8 slots, zero padding on the ring
    assert (c.family, c.padded_slots, c.padding_overhead) == ("bucketed", 8, 1.0)
    assert c.predicted_seconds == pytest.approx(8 / 100.0)

    plan2 = BucketedModePlan.from_graph(star21(), with_send=True)
    c2 = costmodel.superstep_cost(
        "lpa_superstep", "bucketed", 22, 42, 21, plan=plan2, anchors=ANCHORS
    )
    # hand-computed: 21 leaves x w=1 + hub x w=22 (deg 21 pads 1 slot)
    assert c2.padded_slots == 21 * 1 + 1 * 22 == 43
    assert c2.padding_overhead == pytest.approx(43 / 42)
    assert c2.bytes_gathered == 4 * 43
    assert c2.predicted_seconds == pytest.approx(43 / 100.0)


def test_sharded_cost_exact_all_families():
    from graphmine_tpu.parallel.sharded import partition_graph

    src = np.arange(16, dtype=np.int32)
    dst = (src + 1) % 16
    g = build_graph(src, dst, num_vertices=16, to_device=False)

    # sort shard body: padded [2, 16] message arrays, Vc=8
    sg = partition_graph(g, num_shards=2)
    c = costmodel.sharded_superstep_cost(
        "lpa_superstep", sg, 16, num_messages=32, anchors=ANCHORS
    )
    assert (c.family, c.devices) == ("sort", 2)
    assert c.padded_slots == 16                 # Mp per shard
    assert c.exchange_bytes == 4 * 8 * (2 - 1)  # Vc to each of D-1 peers
    assert c.compute_seconds == pytest.approx(16 / 100.0)
    assert c.exchange_seconds == pytest.approx(32 / 400.0)
    assert c.predicted_seconds == pytest.approx(0.16 + 0.08)
    assert c.predicted_per_chip == pytest.approx(16 / (0.24 * 2))

    # stacked bucket plan: [2, 8, 2] rows -> 16 padded slots per chip
    sgb = partition_graph(g, num_shards=2, build_bucket_plan=True)
    cb = costmodel.sharded_superstep_cost(
        "lpa_superstep", sgb, 16, num_messages=32, anchors=ANCHORS
    )
    assert (cb.family, cb.padded_slots) == ("bucketed", 16)
    assert cb.compute_seconds == pytest.approx(16 / 100.0)
    # the record names the anchors the estimate used, and no other
    assert set(cb.roofline) == {
        "gather_slots_per_sec", "exchange_bytes_per_sec"
    }


def test_lof_cost_exact():
    ce = costmodel.lof_cost("exact", 100, 5, features=8, anchors=ANCHORS)
    assert ce.slots == 100 * 100
    assert ce.bytes_gathered == 4 * 8 * 100 * 100
    assert ce.predicted_seconds == pytest.approx(10000 / 1000.0)
    assert ce.predicted_per_chip == pytest.approx(10.0)
    assert ce.unit == "points/s/chip"
    ci = costmodel.lof_cost("ivf", 100, 5, features=8, anchors=ANCHORS)
    assert ci.predicted_seconds == pytest.approx(100 / 50.0)
    assert ci.slots == 100 * 5
    # the ring-sharded exact scorer splits the pair work
    c2 = costmodel.lof_cost("exact", 100, 5, devices=2, anchors=ANCHORS)
    assert c2.slots == 100 * 100 // 2
    with pytest.raises(ValueError):
        costmodel.lof_cost("pallas", 100, 5)


# ---------------------------------------------------------------------------
# roofline anchors: seeds, env/file overrides, provenance
# ---------------------------------------------------------------------------


def test_roofline_seeds_carry_provenance():
    a = costmodel.rooflines()
    assert a["gather_slots_per_sec"]["v"] == pytest.approx(1.32e8)
    assert "r4/r5 roofline capture" in a["gather_slots_per_sec"]["src"]
    assert costmodel.MODEL_DEVICE_KIND in a["gather_slots_per_sec"]["src"]
    # the unmeasured seeds SAY they are unmeasured
    assert "unmeasured" in a["exchange_bytes_per_sec"]["src"]
    assert "binned_slots_per_sec" not in a  # went with the blocked family


@pytest.mark.parametrize(
    "kind, roofline_file, has_fraction",
    [
        (costmodel.MODEL_DEVICE_KIND, False, True),  # the seeded kind
        ("TPU v4", False, False),   # a part nobody measured: no number
        ("cpu", False, False),
        ("TPU v4", True, True),     # the operator supplied its anchors
    ],
)
def test_achieved_fraction_only_on_anchored_device_kinds(
    monkeypatch, tmp_path, kind, roofline_file, has_fraction
):
    monkeypatch.setattr(costmodel, "_running_device_kind", lambda: kind)
    if roofline_file:
        p = tmp_path / "roof.json"
        p.write_text(json.dumps({"gather_slots_per_sec": 2e8}))
        monkeypatch.setenv("GRAPHMINE_ROOFLINE_FILE", str(p))
    m = MetricsSink(tracer=Tracer())
    cost = costmodel.superstep_cost("lpa_superstep", "sort", 4, 8, 4)
    rec = costmodel.emit_superstep_timing(
        m, "lpa_superstep", cost, 3, 3, 0.5, 4
    )
    assert validate_record(rec) == []
    if has_fraction:
        assert rec["achieved_fraction"] > 0
    else:
        assert rec["achieved_fraction"] is None


def test_roofline_env_and_file_overrides(monkeypatch, tmp_path):
    monkeypatch.setenv("GRAPHMINE_ROOFLINE_GATHER_SLOTS_PER_SEC", "5e8")
    a = costmodel.rooflines()
    assert a["gather_slots_per_sec"] == {"v": 5e8, "src": "env"}
    # file override: the re-seed path a fresh silicon capture uses
    p = tmp_path / "roof.json"
    p.write_text(json.dumps(
        {"exchange_bytes_per_sec": 2.5e8, "unknown_anchor": 1.0}
    ))
    monkeypatch.setenv("GRAPHMINE_ROOFLINE_FILE", str(p))
    a = costmodel.rooflines()
    assert a["exchange_bytes_per_sec"]["v"] == 2.5e8
    assert a["exchange_bytes_per_sec"]["src"].startswith("file:")
    # env still beats file for the anchor both set
    assert a["gather_slots_per_sec"]["src"] == "env"
    # malformed file raises instead of silently un-anchoring the model
    p.write_text("[1, 2]")
    with pytest.raises(ValueError):
        costmodel.rooflines()


# ---------------------------------------------------------------------------
# cost sub-record schema: all-or-nothing like trace identity
# ---------------------------------------------------------------------------


def test_cost_record_shape_matches_schema():
    c = costmodel.superstep_cost("lpa_superstep", "sort", 4, 8, 4)
    assert set(c.record().keys()) == set(COST_KEYS)


def test_half_stamped_cost_fails_validation():
    c = costmodel.superstep_cost("lpa_superstep", "sort", 4, 8, 4)
    rec = {"phase": "plan_build", "t": 1.0, "op": "x", "family": "sort",
           "seconds": 0.1, "padded_slots_per_edge": 2.0, "cost": c.record()}
    assert validate_record(rec) == []
    broken = dict(rec)
    broken["cost"] = {k: 1 for k in sorted(COST_KEYS)[:4]}
    problems = validate_record(broken)
    assert problems and "half-stamped cost" in problems[0]
    broken["cost"] = "not-a-dict"
    assert any("not dict" in p for p in validate_record(broken))


def test_schema_lint_flags_inline_cost_literals(tmp_path):
    import schema_lint

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        'sink.emit("plan_build", cost={"family": "sort"})\n'
        "# a comment mentioning cost={...} must NOT trip the lint\n"
        'sink.emit("plan_build", cost=dict(family="sort"))\n'
        'sink.emit("plan_build", cost=estimate.record())\n'
    )
    hits = schema_lint.scan_inline_costs(str(pkg))
    assert [line for _, line in hits] == [1, 3]
    # and the real package is clean (the builder lives in costmodel.py)
    assert schema_lint.scan_inline_costs() == []


# ---------------------------------------------------------------------------
# superstep_timing: ops seams
# ---------------------------------------------------------------------------


def _sink():
    return MetricsSink(tracer=Tracer())


def _timings(m, op=None):
    return [r for r in m.records if r["phase"] == "superstep_timing"
            and (op is None or r["op"] == op)]


def test_ops_seams_emit_schema_valid_timing():
    from graphmine_tpu.ops.cc import connected_components
    from graphmine_tpu.ops.lpa import label_propagation
    from graphmine_tpu.ops.pagerank import pagerank

    g = ring4()
    m = _sink()
    labels = label_propagation(g, max_iter=3, sink=m)
    assert labels.shape == (4,)
    (t,) = _timings(m, "lpa_superstep")
    assert t["window"] == 3 and t["family"] == "sort"
    assert t["edges_per_sec_per_chip"] > 0
    # the tests' CPU has no roofline anchors: no fraction, not a number
    assert t["achieved_fraction"] is None
    assert isinstance(t["cold_compile"], bool)
    # an identical warm call must NOT carry the cold-compile marker
    m_warm = _sink()
    label_propagation(g, max_iter=3, sink=m_warm)
    (tw,) = _timings(m_warm, "lpa_superstep")
    assert tw["cold_compile"] is False

    cc = connected_components(g, sink=m)
    assert int(np.asarray(cc).max()) == 0
    (tc,) = _timings(m, "cc_superstep")
    assert tc["window"] >= 1 and tc["iteration"] == tc["window"]

    gd = build_graph(
        np.array([0, 1, 2], np.int32), np.array([1, 2, 0], np.int32),
        num_vertices=3, symmetric=False,
    )
    pr = pagerank(gd, max_iter=30, sink=m)
    assert float(np.asarray(pr).sum()) == pytest.approx(1.0, abs=1e-4)
    (tp,) = _timings(m, "pagerank_inflow")
    assert 1 <= tp["window"] <= 30
    assert validate_records(m.records) == []


def test_timing_not_emitted_without_sink_or_under_jit():
    import jax

    from graphmine_tpu.ops.lpa import label_propagation

    g = ring4()
    m = _sink()
    # under jit the auto seam skips plan AND timing (tracer context)
    jitted = jax.jit(lambda graph: label_propagation(graph, max_iter=2, sink=m))
    jitted(g)
    assert _timings(m) == []


def test_lof_impl_selected_carries_threshold_and_cost():
    from graphmine_tpu.ops.lof import lof_scores

    m = _sink()
    pts = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    lof_scores(pts, k=5, sink=m)
    (sel,) = [r for r in m.records if r["phase"] == "impl_selected"]
    assert sel["thresholds"]["lof_ivf_min_points"] == 1 << 17
    assert sel["cost"]["unit"] == "points/s/chip"
    assert set(sel["cost"].keys()) == set(COST_KEYS)
    assert validate_records(m.records) == []


def test_superstep_auto_seam_impl_selected_carries_thresholds(monkeypatch):
    from graphmine_tpu.ops.lpa import label_propagation

    from graphmine_tpu.ops.superstep_policy import BUCKETED_MIN_MESSAGES

    m = _sink()
    label_propagation(ring4(), max_iter=1, sink=m)
    (sel,) = [r for r in m.records if r["phase"] == "impl_selected"]
    # the constants that decided ship with the record
    assert sel["thresholds"] == {"bucketed_min_messages": BUCKETED_MIN_MESSAGES}
    assert sel["cost"]["family"] == sel["impl"]


# ---------------------------------------------------------------------------
# driver e2e: the acceptance criterion
# ---------------------------------------------------------------------------

_E2E: dict = {}


def _edgelist_path() -> str:
    if "path" not in _E2E:
        rng = np.random.default_rng(7)
        v, e = 160, 800
        src = rng.integers(0, v, e)
        dst = (src + rng.integers(1, v // 2, e)) % v
        text = "".join(f"{s} {t}\n" for s, t in zip(src, dst))
        _E2E["path"] = cached_edgelist("graphmine_perf", text)
    return _E2E["path"]


def _run_driver(tmp_path, **kw):
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline

    base = dict(
        data_path=_edgelist_path(), data_format="edgelist",
        outlier_method="none", num_devices=1, max_iter=5,
        metrics_out=str(tmp_path / "metrics.jsonl"),
    )
    base.update(kw)
    return run_pipeline(PipelineConfig(**base))


def test_driver_e2e_timing_joinable_and_report_renders(tmp_path):
    """Acceptance: a CPU driver run emits >=1 schema-valid
    superstep_timing per LPA/CC phase, joinable to its phase span, and
    obs_report renders the roofline section with an achieved-fraction
    column from the JSONL alone."""
    res = _run_driver(
        tmp_path, snapshot_out=str(tmp_path / "snap"),
        outlier_method="lof",
    )
    recs = res.metrics.records
    assert validate_records(recs) == []
    run_id = recs[0]["run_id"]
    lpa = [r for r in recs if r["phase"] == "superstep_timing"
           and r["op"] == "lpa_superstep"]
    cc = [r for r in recs if r["phase"] == "superstep_timing"
          and r["op"] == "cc_superstep"]
    assert lpa and cc
    for r in lpa:
        # joinable: same run, span under the LPA phase span
        assert r["run_id"] == run_id
        assert r["span_path"].startswith("run/lpa")
        assert r["predicted_edges_per_sec_per_chip"] > 0
        assert r["edges_per_sec_per_chip"] > 0
        assert set(r["cost"].keys()) == set(COST_KEYS)
    assert all(
        r["span_path"].startswith("run/snapshot_publish") for r in cc
    )
    # the final superstep always closes a window: the last LPA timing
    # record covers through max_iter. The operating point's
    # compile-bearing FIRST superstep is excluded (the watchdog's
    # `warmed` discipline), so 5 supersteps time 4 window slots.
    assert lpa[-1]["iteration"] == 5
    assert sum(r["window"] for r in lpa) == 4

    # obs_report: roofline section from the JSONL alone, exit 0
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_report.py"),
         str(tmp_path / "metrics.jsonl")],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "-- roofline (achieved vs cost model) --" in out.stdout
    assert "frac" in out.stdout
    assert "model anchors:" in out.stdout
    # the waterfall small fix: thresholds + model under the auto lines
    assert "thresholds: " in out.stdout
    assert "model: " in out.stdout


def test_driver_sharded_timing_carries_exchange_split(tmp_path):
    res = _run_driver(tmp_path, num_devices=8, max_iter=3)
    lpa = [r for r in res.metrics.records
           if r["phase"] == "superstep_timing"]
    assert lpa, "sharded driver run emitted no superstep_timing"
    for r in lpa:
        assert r["devices"] == 8
        assert r["variant"] == "replicated"
        assert r["cost"]["exchange_bytes"] > 0
        assert r["cost"]["exchange_seconds"] >= 0
    assert validate_records(res.metrics.records) == []


def test_obs_report_flags_below_model_windows(tmp_path):
    sys.path.insert(0, TOOLS)
    import obs_report

    c = costmodel.superstep_cost("lpa_superstep", "sort", 4, 8, 4)
    base = dict(
        phase="superstep_timing", t=1.0, op="lpa_superstep",
        family="sort", variant="single", window=2, seconds=0.1,
        edges_per_sec_per_chip=100, devices=1, cost=c.record(),
    )
    records = [
        dict(base, iteration=2, achieved_fraction=0.95,
             predicted_edges_per_sec_per_chip=105),
        dict(base, iteration=4, achieved_fraction=0.2,
             predicted_edges_per_sec_per_chip=500),
        # a compile-bearing window below model must NOT raise the flag
        dict(base, iteration=6, achieved_fraction=0.05,
             predicted_edges_per_sec_per_chip=500, cold_compile=True),
    ]
    report = obs_report.build_report(records, roofline_min_frac=0.5)
    assert report.count("<< below 0.5x model") == 1
    assert "1 window(s) below 0.5x of model" in report
    assert "includes XLA compile" in report
    # configurable fraction: at 0.1 nothing is flagged
    assert "<< below" not in obs_report.build_report(
        records, roofline_min_frac=0.1
    )
