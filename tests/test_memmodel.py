"""Memory-plane observability (ISSUE 14, marker `mem`):

- the analytical HBM footprint inventory EXACT against HAND-COMPUTED
  tiny plans (ring-4 / star-21, all three superstep families, fused +
  sharded, weighted payload doubling — the test_costmodel.py
  discipline) and both LOF impl workspaces;
- the planner byte-constant derivation: one inventory, two consumers
  (pipeline/planner.py delegates to obs/memmodel.py bit-identically);
- the `mem` sub-record: schema shape, half-stamped validation failure,
  the schema_lint inline-mem rule;
- memory_watermark emission: the builder contract, the driver e2e (every
  LPA/LOF phase emits schema-valid watermarks, obs_report renders the
  memory waterfall + a recalibration suggestion from the JSONL alone —
  THE acceptance criterion), and the fault-injected OOM e2e whose
  degrade record carries the inventory + last watermark joinable by
  span path;
- plan-time pre-degrade under a squeezed budget;
- satellites: device_hbm_bytes min-across-devices, /profilez
  device-memory capture, heartbeat device-memory cache, serve /statusz
  memory section + graphmine_memory_* gauges + the low-headroom alert
  rule.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs import memmodel
from graphmine_tpu.obs.schema import (
    MEM_KEYS,
    validate_record,
    validate_records,
)
from graphmine_tpu.obs.spans import Tracer
from graphmine_tpu.pipeline.metrics import MetricsSink

from conftest import cached_edgelist

pytestmark = pytest.mark.mem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def ring4(weights=None):
    """Directed 4-ring; symmetric message CSR => M=8, every degree 2."""
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 0], np.int32)
    return build_graph(src, dst, num_vertices=4, edge_weights=weights)


def star21(weights=None):
    """Hub of degree 21: bucketed rows 21x1 (leaves) + 1x22 (hub) = 43
    padded slots over M=42 (the test_costmodel.py fixture)."""
    src = np.zeros(21, np.int32)
    dst = np.arange(1, 22, dtype=np.int32)
    return build_graph(src, dst, num_vertices=22, edge_weights=weights)


# ---------------------------------------------------------------------------
# one inventory, two consumers: the planner derives from memmodel
# ---------------------------------------------------------------------------


def test_planner_constants_derive_from_memmodel():
    from graphmine_tpu.pipeline import planner

    assert planner._BYTES_PER_EDGE == memmodel.BYTES_PER_EDGE
    assert planner._BYTES_PER_EDGE_WEIGHTED == memmodel.BYTES_PER_EDGE_WEIGHTED
    assert planner._SINGLE_BYTES_PER_VERTEX == memmodel.SINGLE_BYTES_PER_VERTEX
    assert (planner._REPLICATED_BYTES_PER_VERTEX
            == memmodel.REPLICATED_BYTES_PER_VERTEX)
    assert planner._RING_BYTES_PER_VERTEX == memmodel.RING_BYTES_PER_VERTEX
    # bit-identical accept/reject arithmetic across the whole grid
    for sched in ("single", "replicated", "ring"):
        for w in (False, True):
            for d in (1, 4, 7):
                assert planner.estimate_bytes_per_device(
                    sched, 100_000, 2_000_000, d, w
                ) == memmodel.schedule_bytes_per_device(
                    sched, 100_000, 2_000_000, d, w
                )
    with pytest.raises(ValueError):
        memmodel.schedule_bytes_per_device("mesh2d", 10, 10, 1)


def test_schedule_inventory_decomposes_the_seeds():
    # single, unweighted: 36 B/edge + 8 B/vertex, component-exact
    inv = memmodel.schedule_inventory("single", 1000, 5000, 1)
    assert inv == {
        "edge_endpoints": 40_000,   # 8 B/edge
        "message_csr": 80_000,      # 16 B/edge
        "plan_mats": 30_000,        # 6 B/edge
        "gather_transient": 30_000, # 6 B/edge
        "labels": 8_000,            # 8 B/vertex
    }
    assert sum(inv.values()) == memmodel.schedule_bytes_per_device(
        "single", 1000, 5000, 1
    )
    # weighted adds 8+8 B/edge; replicated/ring carry their vertex terms
    invw = memmodel.schedule_inventory("single", 1000, 5000, 1, weighted=True)
    assert invw["msg_weights"] == 40_000 and invw["weight_mats"] == 40_000
    invr = memmodel.schedule_inventory("replicated", 1000, 5000, 4)
    assert invr["labels_replicated"] == 8_000
    assert invr["exchange_buffer"] == 8_000
    invg = memmodel.schedule_inventory("ring", 1000, 5000, 4)
    assert invg["labels_sharded"] == 2_000 and invg["ring_chunks"] == 4_000
    est = memmodel.schedule_footprint("single", 1000, 5000, 1)
    assert est.total_bytes == 188_000 and est.exact is False


# ---------------------------------------------------------------------------
# fused footprints: hand-computed exactness
# ---------------------------------------------------------------------------


def test_prebuild_footprints_anchor_to_the_planner_seeds():
    """Without a plan, the fused bucketed estimate IS the schedule model
    the planner accepted the run with (an admitted run can never
    spuriously pre-degrade off its own family); sort drops the
    plan-mats term."""
    bu = memmodel.superstep_footprint("lpa_superstep", "bucketed", 4, 8,
                                      num_edges=4)
    assert bu.inventory == memmodel.schedule_inventory("single", 4, 4, 1)
    assert bu.total_bytes == memmodel.schedule_bytes_per_device(
        "single", 4, 4, 1
    )
    so = memmodel.superstep_footprint("lpa_superstep", "sort", 4, 8,
                                      num_edges=4)
    assert "plan_mats" not in so.inventory
    assert so.total_bytes == bu.total_bytes - 4 * 6  # 6 B/edge plan term
    assert not any(e.exact for e in (bu, so))
    # weighted adds the seed's 16 B/edge payload terms
    ew = memmodel.superstep_footprint("lpa_superstep", "sort", 4, 8,
                                      num_edges=4, weighted=True)
    assert ew.inventory["msg_weights"] == 4 * 8
    assert ew.inventory["weight_mats"] == 4 * 8
    for gone in ("mesh2d", "blocked", "sharded_2d"):
        with pytest.raises(ValueError, match="unknown superstep family"):
            memmodel.superstep_footprint("x", gone, 4, 8)


def test_bucketed_footprint_exact_ring_and_star():
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    plan = BucketedModePlan.from_graph(ring4(), with_send=True)
    e = memmodel.superstep_footprint(
        "lpa_superstep", "bucketed", 4, 8, num_edges=4, plan=plan
    )
    # 4 vertices x width-2 rows = 8 padded slots, 4 vertex ids
    assert e.inventory["plan_mats"] == 4 * 8
    assert e.inventory["plan_vertex_ids"] == 4 * 4
    assert e.inventory["gather_transient"] == 4 * 8
    assert (e.family, e.exact) == ("bucketed", True)
    assert e.total_bytes == 32 + 84 + 32 + 32 + 16 + 32 == 228

    plan2 = BucketedModePlan.from_graph(star21(), with_send=True)
    e2 = memmodel.superstep_footprint(
        "lpa_superstep", "bucketed", 22, 42, num_edges=21, plan=plan2
    )
    # hand-computed: 21 leaves x w=1 + hub x w=22 = 43 padded slots,
    # 22 owning vertex ids; csr = 4*(2*42 + 23) = 428
    assert e2.inventory["plan_mats"] == 4 * 43
    assert e2.inventory["plan_vertex_ids"] == 4 * 22
    assert e2.inventory["message_csr"] == 428
    assert e2.total_bytes == 168 + 428 + 176 + 172 + 88 + 172

    # weighted star: slot-aligned weight mats ride the same 43 slots
    gw = star21(weights=np.ones(21, np.float32) * 2.0)
    planw = BucketedModePlan.from_graph(gw, with_send=True)
    ew = memmodel.superstep_footprint(
        "lpa_superstep", "bucketed", 22, 42, num_edges=21, plan=planw
    )
    assert ew.weighted is True
    assert ew.inventory["weight_mats"] == 4 * 43
    assert ew.inventory["msg_weights"] == 4 * 42


def test_sharded_footprint_exact_all_families():
    from graphmine_tpu.parallel.sharded import partition_graph

    src = np.arange(16, dtype=np.int32)
    dst = (src + 1) % 16
    g = build_graph(src, dst, num_vertices=16, to_device=False)

    # sort shard body: [2, 16] message arrays, Vc=8, D=2
    sg = partition_graph(g, num_shards=2)
    e = memmodel.sharded_superstep_footprint("lpa_superstep", sg)
    assert (e.family, e.devices, e.exact) == ("sort", 2, True)
    assert e.inventory["shard_messages"] == 2 * 4 * 16  # recv + send
    assert e.inventory["degrees"] == 4 * 8
    assert e.inventory["labels_replicated"] == 2 * 4 * 16
    assert e.inventory["exchange_buffer"] == 2 * 4 * 8 * 2
    assert e.inventory["gather_transient"] == 4 * 16
    assert e.total_bytes == 480

    # the ring schedule drops the replicated V-term entirely
    er = memmodel.sharded_superstep_footprint(
        "lpa_superstep", sg, schedule="ring"
    )
    assert "labels_replicated" not in er.inventory
    assert er.inventory["labels_sharded"] == 2 * 4 * 8
    assert er.inventory["ring_chunks"] == 2 * 4 * 8
    assert er.inventory["exchange_staging"] == 2 * 4 * 8
    assert er.total_bytes == 480 - 256 + 192 == 416
    assert er.total_bytes < e.total_bytes

    # stacked bucket plan: [2, 8, 2] mats -> 64 B/chip + [2, 8] targets
    sgb = partition_graph(g, num_shards=2, build_bucket_plan=True)
    eb = memmodel.sharded_superstep_footprint("lpa_superstep", sgb)
    assert eb.family == "bucketed"
    assert eb.inventory["plan_mats"] == 4 * 8 * 2
    assert eb.inventory["plan_vertex_ids"] == 4 * 8
    assert eb.total_bytes == 576 > e.total_bytes


def test_lof_footprint_exact_and_ivf_workspace():
    e = memmodel.lof_footprint("exact", 100, 5, features=8)
    assert e.inventory == {
        "features": 4 * 100 * 8,
        "scores": 4 * 100,
        "distance_tile": 4 * 100 * 100,
        "topk_workspace": 2 * 4 * 100 * 5,
    }
    assert e.total_bytes == 47_600
    # the ring-sharded exact scorer splits the distance rows 1/D
    e2 = memmodel.lof_footprint("exact", 100, 5, features=8, devices=2)
    assert e2.inventory["distance_tile"] == 4 * 50 * 100
    assert e2.inventory["topk_workspace"] == 2 * 4 * 50 * 5

    # IVF: C = max(8, round(sqrt(64)/8)*8) = 8, batch b = 2*64/8+1 = 17
    i = memmodel.lof_footprint("ivf", 64, 5, features=8)
    assert memmodel.ivf_model_clusters(64) == 8
    b = 17
    assert i.inventory["centers"] == 4 * 8 * 8
    assert i.inventory["assignments"] == 2 * 4 * 64
    assert i.inventory["cluster_batch"] == 4 * (b * 8 + b * b + 2 * b * 5)
    # the bounded-candidate index is the exact scorer's OOM rescue rung:
    # strictly leaner at equal n
    assert (memmodel.lof_footprint("ivf", 100, 5).total_bytes
            < memmodel.lof_footprint("exact", 100, 5).total_bytes)
    with pytest.raises(ValueError):
        memmodel.lof_footprint("pallas", 100, 5)


# ---------------------------------------------------------------------------
# mem sub-record: schema + lint
# ---------------------------------------------------------------------------


def test_mem_record_shape_matches_schema_and_half_stamped_fails():
    est = memmodel.superstep_footprint("lpa_superstep", "sort", 4, 8,
                                       num_edges=4)
    assert set(est.record().keys()) == set(MEM_KEYS)
    rec = {"phase": "memory_watermark", "t": 1.0, "op": "lpa_superstep",
           "predicted_bytes": est.total_bytes, "achieved_bytes": 10,
           "headroom_frac": None, "source": "rss", "mem": est.record()}
    assert validate_record(rec) == []
    broken = dict(rec)
    broken["mem"] = {"family": "sort"}
    problems = validate_record(broken)
    assert problems and "half-stamped mem" in problems[0]
    broken["mem"] = "not-a-dict"
    assert any("not dict" in p for p in validate_record(broken))


def test_schema_lint_flags_inline_mem_literals(tmp_path):
    import schema_lint

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        'sink.emit("memory_watermark", mem={"family": "sort"})\n'
        "# a comment mentioning mem={...} must NOT trip the lint\n"
        'sink.emit("memory_watermark", mem=dict(family="sort"))\n'
        'sink.emit("memory_watermark", mem=estimate.record())\n'
        "mem = list(ladder)  # assignment, not a kwarg literal\n"
        'emit("x", memory={"a": 1})  # different kwarg\n'
    )
    hits = schema_lint.scan_inline_mems(str(pkg))
    assert [line for _, line in hits] == [1, 3]
    # and the real package is clean (the builder lives in memmodel.py)
    assert schema_lint.scan_inline_mems() == []


# ---------------------------------------------------------------------------
# watermark emission + pre-degrade units
# ---------------------------------------------------------------------------


def _sink():
    return MetricsSink(tracer=Tracer())


def test_emit_memory_watermark_contract():
    est = memmodel.superstep_footprint("lpa_superstep", "sort", 4, 8,
                                       num_edges=4)
    m = _sink()
    rec = memmodel.emit_memory_watermark(
        m, "lpa_superstep", est,
        {"bytes_in_use": 700, "peak_bytes_in_use": 1000,
         "bytes_limit": 4000, "source": "device"},
        budget_bytes=4000, iteration=3,
    )
    assert rec["predicted_bytes"] == est.total_bytes
    # achieved is the phase-attributable CURRENT in-use; the lifetime
    # allocator peak rides as context and drives the headroom forecast
    assert rec["achieved_bytes"] == 700
    assert rec["peak_bytes_in_use"] == 1000
    assert rec["headroom_frac"] == pytest.approx(0.75)  # (4000-1000)/4000
    assert rec["source"] == "device" and rec["iteration"] == 3
    assert validate_record(rec) == []
    # no sink / no estimate / no measurement => no record claiming one
    assert memmodel.emit_memory_watermark(None, "x", est, {"a": 1}) is None
    assert memmodel.emit_memory_watermark(m, "x", None, {"a": 1}) is None
    assert memmodel.emit_memory_watermark(m, "x", est, None) is None
    assert memmodel.emit_memory_watermark(m, "x", est, {"source": "d"}) is None
    # rss fallback exists on Linux and is schema-valid
    s = memmodel.rss_sample()
    if s is not None:
        rec2 = memmodel.emit_memory_watermark(m, "x", est, s)
        assert rec2["source"] == "rss"
    assert validate_records(m.records) == []


def test_predegrade_walks_to_fit():
    v, mcount, e = 160, 1600, 800
    bu = memmodel.superstep_footprint(
        "lpa_superstep", "bucketed", v, mcount, num_edges=e
    ).total_bytes
    so = memmodel.superstep_footprint(
        "lpa_superstep", "sort", v, mcount, num_edges=e
    ).total_bytes
    # generous budget: the requested family fits, no steps
    fam, fit, steps = memmodel.predegrade_superstep(
        "bucketed", v, mcount, e, False, 1 << 30
    )
    assert (fam, steps) == ("bucketed", []) and fit.total_bytes == bu
    # budget between sort and bucketed: bucketed steps down exactly once
    fam, fit, steps = memmodel.predegrade_superstep(
        "bucketed", v, mcount, e, False, (bu + so) // 2
    )
    assert fam == "sort" and fit.total_bytes == so
    assert [(a, b) for a, b, _ in steps] == [("bucketed", "sort")]
    assert steps[0][2].total_bytes == bu
    # below even the sort floor: the floor is returned (there is nothing
    # leaner; the reactive ladder owns what happens next)
    fam, fit, steps = memmodel.predegrade_superstep(
        "bucketed", v, mcount, e, False, 16
    )
    assert fam == "sort" and len(steps) == 1
    # the walk is the one degrade order, read by the planner too
    assert memmodel.FAMILY_DEGRADE == {"bucketed": "sort", "sort": None}


# ---------------------------------------------------------------------------
# satellites: device_hbm_bytes min, heartbeat cache
# ---------------------------------------------------------------------------


class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_device_hbm_bytes_takes_min_across_devices():
    from graphmine_tpu.pipeline.driver import device_hbm_bytes

    devs = [
        _FakeDev({"bytes_limit": 32 << 30}),
        _FakeDev({"bytes_limit": 16 << 30}),   # the smallest chip governs
        _FakeDev({"bytes_limit": 95 << 30}),
    ]
    assert device_hbm_bytes(devs) == 16 << 30
    # unreporting / raising devices are skipped, not fatal
    devs2 = [
        _FakeDev(None),
        _FakeDev(RuntimeError("memory_stats unavailable")),
        _FakeDev({"bytes_limit": 8 << 30}),
    ]
    assert device_hbm_bytes(devs2) == 8 << 30
    assert device_hbm_bytes([_FakeDev(None)]) is None
    assert device_hbm_bytes([]) is None


def test_heartbeat_carries_cached_device_memory():
    from graphmine_tpu.obs import heartbeat as hb

    sample = [{"device": 0, "bytes_in_use": 100,
               "peak_bytes_in_use": 200, "bytes_limit": 1000}]
    hb.note_device_memory(sample)
    try:
        beat = hb.Heartbeat(_sink()).beat()
        assert beat["device_memory"]["per_device"] == sample
        assert beat["device_memory"]["age_s"] >= 0
        assert validate_record(beat) == []
    finally:
        hb._DEV_MEM = None  # don't leak the cache into other tests
    # without a cache the key is absent (RSS-only, the pre-ISSUE-14 shape)
    beat2 = hb.Heartbeat(_sink()).beat()
    assert "device_memory" not in beat2


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
        _E2E["path"] = cached_edgelist("graphmine_mem", text)
    return _E2E["path"]


def _run_driver(tmp_path, **kw):
    from graphmine_tpu.pipeline.config import PipelineConfig
    from graphmine_tpu.pipeline.driver import run_pipeline
    from graphmine_tpu.pipeline.resilience import ResilienceConfig

    base = dict(
        data_path=_edgelist_path(), data_format="edgelist",
        outlier_method="none", num_devices=1, max_iter=5,
        metrics_out=str(tmp_path / "metrics.jsonl"),
        resilience=ResilienceConfig(backoff_base_s=0.001, backoff_max_s=0.01),
    )
    base.update(kw)
    return run_pipeline(PipelineConfig(**base))


def test_driver_e2e_watermarks_and_report_renders(tmp_path):
    """Acceptance: a CPU driver run emits schema-valid memory_watermark
    records for the LPA and LOF phases, the plan record carries the full
    inventory, and obs_report renders the memory section (waterfall +
    recalibration suggestion) from the JSONL alone."""
    res = _run_driver(tmp_path, outlier_method="lof")
    recs = res.metrics.records
    assert validate_records(recs) == []
    marks = [r for r in recs if r["phase"] == "memory_watermark"]
    assert {r["op"] for r in marks} >= {"lpa_superstep", "lof_knn"}
    for r in marks:
        assert r["predicted_bytes"] > 0
        assert r["achieved_bytes"] > 0
        assert r["source"] in ("device", "rss")
        assert set(r["mem"].keys()) == set(MEM_KEYS)
        assert r["span_path"].startswith("run/")
    (plan,) = [r for r in recs if r["phase"] == "plan"]
    # one inventory, two consumers: the plan record's mem total IS the
    # planner's accept/reject number on the single-device path
    assert plan["mem"]["total_bytes"] == plan["bytes_per_device"]

    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_report.py"),
         str(tmp_path / "metrics.jsonl")],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "-- memory (predicted vs peak) --" in out.stdout
    assert "lpa_superstep" in out.stdout and "lof_knn" in out.stdout
    assert "recalibration:" in out.stdout


def test_oom_degrade_carries_watermark_and_inventory(tmp_path):
    """Acceptance: a fault-injected OOM's degrade record carries the
    failed operating point's modeled inventory AND the last
    memory_watermark, joinable back to the full record by span path —
    model-miss vs fragmentation is triageable from the JSONL alone."""
    from graphmine_tpu.pipeline.driver import run_pipeline  # noqa: F401
    from graphmine_tpu.testing import faults

    inj = faults.FaultInjector()
    inj.add("lpa_superstep", faults.oom_error, at=2)
    with inj.installed():
        res = _run_driver(tmp_path)
    recs = res.metrics.records
    assert validate_records(recs) == []
    deg = [r for r in recs if r["phase"] == "degrade"]
    assert deg and deg[0]["to"] == "single_sort"
    # the failed point's modeled inventory rides the record
    assert deg[0]["mem"]["family"] == "bucketed"
    assert deg[0]["mem"]["total_bytes"] > 0
    assert "inventory" in deg[0]["mem"]
    # ... and its last watermark, joinable by span path
    w = deg[0]["last_watermark"]
    marks = [r for r in recs if r["phase"] == "memory_watermark"]
    assert w["span_path"] in {r["span_path"] for r in marks}
    assert w["achieved_bytes"] > 0 and w["source"] in ("device", "rss")
    # the report renders the OOM join
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_report.py"),
         str(tmp_path / "metrics.jsonl")],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "OOM DEGRADE" in out.stdout
    assert "last watermark:" in out.stdout


def test_plan_time_predegrade_e2e(tmp_path, monkeypatch):
    """A family whose MODELED footprint exceeds the budget is consumed at
    PLAN time: a degrade record with kind=mem_plan and the oversized
    inventory, the sort superstep actually deployed as the run's primary
    — and degradation='off' keeps the family. The bucketed pre-build
    estimate IS the planner's accepted model, so an admitted run never
    pre-degrades on its own; the oversized term is injected here (a
    scratch the seed does not know), as a leaner-than-modeled family
    would bring one."""
    v, e = 160, 800
    floor = memmodel.schedule_bytes_per_device("single", v, e, 1)
    scratch = 4096
    real = memmodel.superstep_footprint

    def with_scratch(op, family, *a, **kw):
        est = real(op, family, *a, **kw)
        if family != "bucketed":
            return est
        return dataclasses.replace(
            est, inventory={**est.inventory, "scratch": scratch}
        )

    budget = floor + scratch // 2
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(int(budget / 0.9) + 1))
    # an admitted bucketed run NEVER pre-degrades: the pre-build model
    # is the planner's own arithmetic (the one-owner guarantee)
    res0 = _run_driver(tmp_path, max_iter=3,
                       metrics_out=str(tmp_path / "m0.jsonl"))
    assert not [r for r in res0.metrics.records
                if r["phase"] == "degrade" and r.get("kind") == "mem_plan"]
    monkeypatch.setattr(memmodel, "superstep_footprint", with_scratch)
    res = _run_driver(tmp_path, max_iter=3)
    recs = res.metrics.records
    pre = [r for r in recs if r["phase"] == "degrade"
           and r.get("kind") == "mem_plan"]
    assert len(pre) == 1 and pre[0]["to"] == "sort"
    assert pre[0]["stage"] == "plan_superstep"
    assert pre[0]["mem"]["family"] == "bucketed"
    assert pre[0]["mem"]["total_bytes"] == floor + scratch > budget
    (sel,) = [r for r in recs if r["phase"] == "impl_selected"]
    assert sel["impl"] == "sort" and "pre-degraded" in sel["reason"]
    # the sort superstep really ran: no plan was built
    assert not [r for r in recs if r["phase"] == "plan_build"]
    assert validate_records(recs) == []
    # labels match the unsqueezed (bucketed) run: the rung trades memory,
    # not results
    np.testing.assert_array_equal(res.labels, res0.labels)
    # degradation="off": the operator wants the OOM, not a leaner family
    from graphmine_tpu.pipeline.resilience import ResilienceConfig

    res3 = _run_driver(
        tmp_path, max_iter=1, metrics_out=str(tmp_path / "m3.jsonl"),
        resilience=ResilienceConfig(degradation="off"),
    )
    assert not [r for r in res3.metrics.records
                if r["phase"] == "degrade" and r.get("kind") == "mem_plan"]


# ---------------------------------------------------------------------------
# serve: /statusz memory section, gauges, alert rule, /profilez memory
# ---------------------------------------------------------------------------


def _serve_store(tmp_path):
    from graphmine_tpu.serve.snapshot import SnapshotStore

    store = SnapshotStore(str(tmp_path / "snap"))
    v = 50
    src = np.arange(v, dtype=np.int32)
    dst = (src + 1) % v
    store.publish({
        "src": src, "dst": dst, "labels": np.zeros(v, np.int32),
        "cc_labels": np.zeros(v, np.int32),
        "lof": np.ones(v, np.float32),
    })
    return store


def test_serve_memory_section_and_gauges(tmp_path):
    from graphmine_tpu.serve.server import SnapshotServer

    srv = SnapshotServer(_serve_store(tmp_path), wal=True)
    st = srv.statusz()
    mem = st["memory"]
    # byte accounting decomposes: snapshot arrays (50 vertices x 5
    # arrays x 4 B) vs the derived index, WAL retained bytes, RSS
    assert mem["snapshot_bytes"] == 5 * 50 * 4
    assert mem["index_bytes"] > 0
    assert mem["wal_segment_bytes"] >= 0
    assert mem["rss_bytes"] is None or mem["rss_bytes"] > 0
    text = srv.metrics_text()
    assert "graphmine_memory_rss_bytes" in text
    assert "graphmine_memory_snapshot_bytes" in text
    assert "graphmine_memory_wal_segment_bytes" in text
    # the low-headroom rule reads the same metric the section serves
    values = srv._alert_values()
    if mem["headroom_frac"] is not None:
        assert values["memory_headroom_frac"] == pytest.approx(
            mem["headroom_frac"], abs=0.05
        )


def test_serve_mem_budget_env_and_alert_rule(tmp_path, monkeypatch):
    from graphmine_tpu.obs.alerts import AlertManager, default_rules
    from graphmine_tpu.serve.server import SnapshotServer

    rules = {r.name: r for r in default_rules()}
    assert rules["mem_headroom_low"].op == "<"
    assert rules["mem_headroom_low"].threshold == pytest.approx(0.1)
    monkeypatch.setenv("GRAPHMINE_ALERT_MEM_HEADROOM", "0.5")
    assert {r.name: r for r in default_rules()}[
        "mem_headroom_low"].threshold == 0.5
    m = _sink()
    mgr = AlertManager(sink=m)
    mgr.evaluate({"memory_headroom_frac": 0.4})
    assert "mem_headroom_low" in mgr.firing()
    recs = [r for r in m.records if r.get("phase") == "alert"]
    assert recs and recs[0]["name"] == "mem_headroom_low"
    # an env budget drives headroom deterministically; malformed raises
    monkeypatch.setenv("GRAPHMINE_SERVE_MEM_BUDGET_BYTES", "1e12")
    srv = SnapshotServer(_serve_store(tmp_path))
    mem = srv.memory_payload()
    assert mem["budget_bytes"] == 10 ** 12
    if mem["rss_bytes"] is not None:
        assert 0 < mem["headroom_frac"] <= 1
    monkeypatch.setenv("GRAPHMINE_SERVE_MEM_BUDGET_BYTES", "plenty")
    with pytest.raises(ValueError, match="GRAPHMINE_SERVE_MEM_BUDGET"):
        SnapshotServer(_serve_store(tmp_path / "b"))


def test_profilez_memory_capture(tmp_path, monkeypatch):
    """/profilez kind=memory (satellite): 200 + a capture file under the
    single-flight lock, 501 when the profiler is unavailable, 403
    without a capture dir, 400-class on an unknown kind (HTTP layer)."""
    import jax

    from graphmine_tpu.serve.server import SnapshotServer

    srv = SnapshotServer(
        _serve_store(tmp_path), sink=_sink(),
        profilez_dir=str(tmp_path / "prof"),
    )
    monkeypatch.setattr(
        jax.profiler, "device_memory_profile", lambda: b"fake-pprof"
    )
    status, body = srv.profilez(kind="memory")
    assert status == 200 and body["kind"] == "memory"
    assert os.path.exists(body["path"]) and body["bytes"] == 10
    caps = [r for r in srv.sink.records if r["phase"] == "profile_capture"]
    assert caps and caps[-1]["ok"] and caps[-1]["kind"] == "memory"
    # single-flight: a concurrent capture answers 409
    assert srv._profilez_lock.acquire(blocking=False)
    try:
        assert srv.profilez(kind="memory")[0] == 409
    finally:
        srv._profilez_lock.release()

    def _boom():
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "device_memory_profile", _boom)
    status, body = srv.profilez(kind="memory")
    assert status == 501 and "unavailable" in body["error"]
    assert SnapshotServer(_serve_store(tmp_path / "n")).profilez(
        kind="memory"
    )[0] == 403


# ---------------------------------------------------------------------------
# obs_report: under-estimate flag + suggestion directions
# ---------------------------------------------------------------------------


def _wm(op, predicted, achieved, source="device", **kv):
    est = memmodel.superstep_footprint("lpa_superstep", "sort", 4, 8,
                                       num_edges=4)
    rec = {"phase": "memory_watermark", "t": 1.0, "op": op,
           "predicted_bytes": predicted, "achieved_bytes": achieved,
           "headroom_frac": 0.5, "source": source, "mem": est.record()}
    rec.update(kv)
    return rec


def test_obs_report_memory_flags_and_suggestions():
    import obs_report

    # device-measured peak 1.5x model: flagged + "raise the seeds"
    report = obs_report.build_report(
        [_wm("lpa_superstep", 1000, 1500)]
    )
    assert "<< model under-estimates" in report
    assert "recalibration: measured peak is 1.50x" in report
    assert "BYTES_PER_EDGE 36 -> 54" in report
    # conservative model: the seeds-can-come-down direction
    low = obs_report.build_report([_wm("lpa_superstep", 1000, 500)])
    assert "conservative" in low
    # within noise: keep the seeds
    ok = obs_report.build_report([_wm("lpa_superstep", 1000, 1000)])
    assert "keep the" in ok and "<< model under-estimates" not in ok
    # rss-only streams never flag against the HBM model
    rss = obs_report.build_report(
        [_wm("lpa_superstep", 1000, 99_000_000, source="rss")]
    )
    assert "<< model under-estimates" not in rss
    assert "host-RSS only" in rss
