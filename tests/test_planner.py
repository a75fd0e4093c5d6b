"""Memory planner + automatic schedule selection (VERDICT r2 item 3).

Pins the replicated↔ring crossover, the loud pre-allocation reject path,
and the driver wiring (--schedule auto default; explicit schedules still
planner-checked; checkpoint cadence rides the same loop).
"""

import os

import numpy as np
import pytest

from graphmine_tpu.pipeline.planner import (
    PlanError,
    estimate_bytes_per_device,
    plan_run,
)

GIB = 1 << 30


def test_plan_lof_applies_measured_crossover():
    """r6: the planner's LOF plan is the ops-layer policy (one owner)
    with the ladder direction derived from it — IVF primary degrades to
    exact, exact primary degrades to IVF."""
    from graphmine_tpu.ops.lof import LOF_IVF_MIN_POINTS
    from graphmine_tpu.pipeline.planner import plan_lof

    small = plan_lof(10_000, 128)
    assert small.impl == "exact" and small.degrade_to == "ivf"
    big = plan_lof(LOF_IVF_MIN_POINTS, 128)
    assert big.impl == "ivf" and big.degrade_to == "exact"
    assert "3.1x" in big.reason  # measured provenance rides the plan
    forced = plan_lof(10**8, 128, requested="xla")
    assert forced.impl == "exact"
    assert plan_lof(100, 16, requested="ivf").impl == "ivf"
    assert plan_lof(10_000, 128, ivf_min_points=1000).impl == "ivf"


def test_single_device_selects_fused_kernel():
    p = plan_run(1 << 20, 1 << 23, num_devices=1)
    assert p.schedule == "single" and not p.lpa_only
    # DESIGN.md: the north-star config (~100M edges) uses ~3.6 GB
    ns = plan_run(1 << 24, 100_000_000, num_devices=1)
    assert ns.schedule == "single"
    assert 3.3 * GIB < ns.bytes_per_device < 4.2 * GIB


def test_small_multi_device_selects_replicated():
    p = plan_run(1 << 20, 1 << 23, num_devices=8)
    assert p.schedule == "replicated" and p.lpa_only
    # speed-preference order: replicated wins when it fits, even though
    # ring models *smaller* here (no replicated V-term)
    assert p.estimates["ring"] < p.estimates["replicated"]
    assert "fastest" in p.reason


def test_crossover_300m_vertices_selects_ring():
    """The VERDICT scenario: 300M vertices (with a natural ~2.5B-edge
    graph) on 8 devices must route to ring without user knowledge —
    replicated's V-terms don't fit next to the sharded edge arrays."""
    v, e, d = 300_000_000, 2_500_000_000, 8
    assert estimate_bytes_per_device("replicated", v, e, d) > 0.9 * 16 * GIB
    p = plan_run(v, e, num_devices=d)
    assert p.schedule == "ring" and not p.lpa_only
    assert "sharded" in p.reason
    assert p.bytes_per_device <= p.hbm_bytes


def test_reject_path_is_loud_and_numeric():
    with pytest.raises(PlanError) as ei:
        plan_run(2_000_000_000, 40_000_000_000, num_devices=2)
    msg = str(ei.value)
    assert "no LPA schedule fits" in msg
    assert "GiB" in msg and "Add devices" in msg
    # numbers for every candidate schedule appear
    assert "replicated=" in msg and "ring=" in msg


def test_int32_message_overflow_rejected_at_plan_time(monkeypatch):
    """VERDICT r4 item 6: a per-device message count past 2^31-1 must fail
    LOUDLY at plan time — not rely on HBM byte budgets coincidentally
    rejecting it first, and never wrap silently at gather time."""
    # Single device, E such that M = 2E > int32 range, with an HBM
    # override huge enough that bytes alone would accept the config —
    # isolating the index bound as the thing that rejects it.
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(1 << 46))  # 64 TiB part
    e = 1_200_000_000  # M = 2.4B messages
    with pytest.raises(PlanError) as ei:
        plan_run(1 << 26, e, num_devices=1)
    msg = str(ei.value)
    assert "int32" in msg and "2,147,483,647" in msg
    assert "SILENTLY" in msg and "devices" in msg

    # explicit request for an overflowing sharded schedule: same wall
    with pytest.raises(PlanError, match="int32"):
        plan_run(1 << 26, 4_000_000_000, num_devices=2, requested="replicated")

    # enough devices: the same edge count plans fine (auto path)
    p = plan_run(1 << 26, e, num_devices=4)
    assert p.schedule in ("replicated", "ring")

    # the error's minimum-device hint is itself sufficient
    from graphmine_tpu.pipeline.planner import (
        _INT32_MAX,
        messages_per_device,
    )

    for s in ("replicated", "ring"):
        assert messages_per_device(s, e, 4) <= _INT32_MAX


def test_host_graph_int64_ptr_and_device_guard():
    """Companion container guards: a host CSR past int32 keeps an int64
    ptr (it exists to be partitioned), while DEVICE assembly of such a
    CSR raises with the remedy. Exercised with a fabricated ptr — 2^31
    real messages would need ~16 GB of host RAM in a unit test."""
    from graphmine_tpu.graph.container import _graph_from_csr

    ptr = np.array([0, (1 << 31) + 5], dtype=np.int64)
    tiny = np.zeros(4, np.int32)
    with pytest.raises(ValueError, match="int32 gather-index"):
        _graph_from_csr(tiny, tiny, ptr, tiny, tiny, 1, True)


def test_explicit_schedule_that_cannot_fit_names_the_one_that_would():
    v, e, d = 300_000_000, 2_500_000_000, 8
    with pytest.raises(PlanError, match="'ring' would fit"):
        plan_run(v, e, num_devices=d, requested="replicated")


def test_explicit_ring_on_one_device_maps_to_single():
    p = plan_run(1 << 16, 1 << 18, num_devices=1, requested="ring")
    assert p.schedule == "single"


def test_weighted_raises_estimates():
    kw = dict(num_vertices=1 << 20, num_edges=1 << 24, num_devices=4)
    for s in ("replicated", "ring"):
        assert estimate_bytes_per_device(s, weighted=True, **kw) > \
            estimate_bytes_per_device(s, weighted=False, **kw)


def test_hbm_env_override(monkeypatch):
    """A tiny budget forces ring early; a huge one keeps replicated."""
    v, e, d = 100_000_000, 200_000_000, 8
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(2 * GIB))
    assert plan_run(v, e, num_devices=d).schedule == "ring"
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(64 * GIB))
    assert plan_run(v, e, num_devices=d).schedule == "replicated"


def test_hbm_precedence_env_device_default(monkeypatch):
    """VERDICT r3 item 3: env var → device-reported bytes → 16 GiB."""
    from graphmine_tpu.pipeline.planner import hbm_bytes_per_device

    monkeypatch.delenv("GRAPHMINE_HBM_BYTES", raising=False)
    assert hbm_bytes_per_device() == 16 * GIB
    # device-reported value (a v4 part) wins over the default
    assert hbm_bytes_per_device(device_bytes=32 * GIB) == 32 * GIB
    # env var wins over both
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(2 * GIB))
    assert hbm_bytes_per_device(device_bytes=32 * GIB) == 2 * GIB
    # a zero/None device report falls through to the default
    monkeypatch.delenv("GRAPHMINE_HBM_BYTES")
    assert hbm_bytes_per_device(device_bytes=0) == 16 * GIB
    assert hbm_bytes_per_device(device_bytes=None) == 16 * GIB
    # lazy callable form: evaluated when env did not win...
    assert hbm_bytes_per_device(device_bytes=lambda: 32 * GIB) == 32 * GIB
    # ...and NEVER evaluated when it did (an env-pinned budget must not
    # touch a flaky runtime's memory query — code-review r4)
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", str(2 * GIB))

    def boom():
        raise AssertionError("device queried despite env override")

    assert hbm_bytes_per_device(device_bytes=boom) == 2 * GIB


def test_device_hbm_bytes_memory_stats_chain(monkeypatch):
    """The driver's device query: bytes_limit when reported — the MIN
    across all local devices since ISSUE 14 — None on CPU
    (memory_stats() -> None), None when a non-TPU runtime raises."""
    import jax

    from graphmine_tpu.pipeline import driver

    class _Dev:
        def __init__(self, stats=None, raise_=False):
            self._stats, self._raise = stats, raise_

        def memory_stats(self):
            if self._raise:
                raise RuntimeError("memory_stats unavailable")
            return self._stats

    def fake_devices(*devs):
        return lambda *a, **k: list(devs)

    # a v5p part reporting ~95 GiB
    monkeypatch.setattr(
        jax, "local_devices", fake_devices(_Dev({"bytes_limit": 95 * GIB}))
    )
    assert driver.device_hbm_bytes() == 95 * GIB
    # heterogeneous mesh: the smallest chip governs the budget
    monkeypatch.setattr(
        jax, "local_devices",
        fake_devices(_Dev({"bytes_limit": 95 * GIB}),
                     _Dev({"bytes_limit": 16 * GIB})),
    )
    assert driver.device_hbm_bytes() == 16 * GIB
    # CPU backend: memory_stats() is None (measured on this jax build)
    monkeypatch.setattr(jax, "local_devices", fake_devices(_Dev(None)))
    assert driver.device_hbm_bytes() is None
    # stats dict without the key, or a raising runtime -> None
    monkeypatch.setattr(
        jax, "local_devices", fake_devices(_Dev({"other": 1}))
    )
    assert driver.device_hbm_bytes() is None
    monkeypatch.setattr(
        jax, "local_devices", fake_devices(_Dev(raise_=True))
    )
    assert driver.device_hbm_bytes() is None


@pytest.mark.parametrize("stats", [None, {"other": 1}, RuntimeError("boom")])
def test_device_hbm_bytes_tpu_must_report(stats):
    """A TPU that reports no bytes_limit (or whose memory_stats raises)
    is an error — never a silent 16 GiB assumption about an unknown
    part; the default is left to the CPU backend of the tests."""
    from graphmine_tpu.pipeline.driver import device_hbm_bytes

    class _Tpu:
        platform = "tpu"

        def memory_stats(self):
            if isinstance(stats, Exception):
                raise stats
            return stats

    with pytest.raises(RuntimeError):
        device_hbm_bytes([_Tpu()])


def test_pipeline_plan_uses_device_reported_hbm(monkeypatch, tmp_path):
    """End-to-end chain: with no env override, the driver budgets against
    what the device reports — a mocked 1 MiB part forces the planner to
    reject a graph the 16 GiB default would happily accept."""
    import jax

    from graphmine_tpu.pipeline import driver

    rng = np.random.default_rng(0)
    path = tmp_path / "edges.txt"
    src = rng.integers(0, 2000, 30000)
    dst = rng.integers(0, 2000, 30000)
    path.write_text(
        "\n".join(f"a{a} b{b}" for a, b in zip(src, dst)) + "\n"
    )
    monkeypatch.delenv("GRAPHMINE_HBM_BYTES", raising=False)

    class _Tiny:
        def memory_stats(self):
            return {"bytes_limit": 1 << 20}

    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [_Tiny()])
    with pytest.raises(PlanError, match="no LPA schedule fits"):
        driver.run_pipeline(_tiny_config(
            data_path=str(path), data_format="edgelist", num_devices=1,
        ))


# ---------------------------------------------------------------------------
# driver wiring
# ---------------------------------------------------------------------------


_SYNTH = {}


def _synthetic_edgelist() -> str:
    """Deterministic stand-in for the bundled reference parquet (absent in
    some containers): same V/E scale (V=4613, E=18399), so every byte
    threshold in these tests — the 300 KB scale-out budget, the wedge
    budget, the replicated-fits/single-doesn't split — models identically.
    A chain over all V vertices guarantees full id coverage; the remaining
    edges are uniform random."""
    if "path" not in _SYNTH:
        from conftest import cached_edgelist

        v, e = 4613, 18399
        rng = np.random.default_rng(20260802)
        chain = np.arange(v, dtype=np.int64)
        src = np.concatenate([chain, rng.integers(0, v, e - v)])
        dst = np.concatenate([(chain + 1) % v, rng.integers(0, v, e - v)])
        text = "".join(f"{s} {t}\n" for s, t in zip(src, dst))
        _SYNTH["path"] = cached_edgelist("graphmine_synth", text)
    return _SYNTH["path"]


def _tiny_config(**kw):
    from graphmine_tpu.pipeline.config import PipelineConfig

    defaults = dict(
        outlier_method="none", max_iter=3,
        data_path=_synthetic_edgelist(), data_format="edgelist",
    )
    defaults.update(kw)
    return PipelineConfig(**defaults)


def test_pipeline_auto_schedule_emits_plan_and_runs(tmp_path):
    """Default --schedule auto: the plan event lands in metrics and the
    run completes; on 8 virtual devices with a small graph the planner
    picks replicated."""
    from graphmine_tpu.pipeline.driver import run_pipeline

    res = run_pipeline(_tiny_config(num_devices=8))
    plans = [r for r in res.metrics.records if r.get("phase") == "plan"]
    assert plans and plans[0]["schedule"] == "replicated"
    assert plans[0]["bytes_per_device"] > 0
    assert res.num_communities > 0


def test_pipeline_auto_schedule_single_device():
    from graphmine_tpu.pipeline.driver import run_pipeline

    res = run_pipeline(_tiny_config(num_devices=1))
    plans = [r for r in res.metrics.records if r.get("phase") == "plan"]
    assert plans and plans[0]["schedule"] == "single"
    assert res.num_communities > 0


def test_pipeline_wedge_budget_reroutes_lof_features(monkeypatch):
    """r5 OOM fix: past GRAPHMINE_WEDGE_BUDGET the LOF phase must use the
    wedge-sampled clustering column instead of the exact expansion (the
    exact pipeline materializes ~28 B/wedge on the host and was OOM-
    killed at 130 GB on the first e2e capture). A budget of 1 forces the
    reroute on the bundled data; the phase event and warning say so."""
    from graphmine_tpu.pipeline.driver import run_pipeline

    monkeypatch.setenv("GRAPHMINE_WEDGE_BUDGET", "1")
    res = run_pipeline(
        _tiny_config(num_devices=1, outlier_method="lof", lof_k=16)
    )
    lof_events = [r for r in res.metrics.records
                  if r.get("phase") == "outliers_lof"]
    assert lof_events and lof_events[0]["features"] == "device-8-sampled"
    warns = [r for r in res.metrics.records if r.get("phase") == "warning"]
    assert any("wedge" in w["message"].lower() for w in warns)
    assert res.lof is not None and len(res.lof) == res.graph.num_vertices

    # default budget: bundled data is far below it -> exact features
    monkeypatch.delenv("GRAPHMINE_WEDGE_BUDGET")
    res2 = run_pipeline(
        _tiny_config(num_devices=1, outlier_method="lof", lof_k=16)
    )
    lof_events = [r for r in res2.metrics.records
                  if r.get("phase") == "outliers_lof"]
    assert lof_events and lof_events[0]["features"] == "device-8"


def test_pipeline_impossible_config_fails_before_allocation(monkeypatch):
    """The loud plan-time error: a budget no schedule fits under raises
    PlanError during run_pipeline, before any partition/device work."""
    from graphmine_tpu.pipeline.driver import run_pipeline

    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", "1000")  # ~1 KB budget
    with pytest.raises(PlanError, match="no LPA schedule fits"):
        run_pipeline(_tiny_config(num_devices=8))


def test_checkpoint_cadence(tmp_path, monkeypatch):
    """checkpoint_every=2 with max_iter=5 saves supersteps 2, 4 and the
    final 5 (never stale at completion); default 1 saves every step."""
    from graphmine_tpu.pipeline import driver as drv

    saved = []
    real = drv.ckpt.save_labels

    def spy(d, labels, iteration, **kw):
        saved.append(iteration)
        return real(d, labels, iteration, **kw)

    monkeypatch.setattr(drv.ckpt, "save_labels", spy)
    drv.run_pipeline(_tiny_config(
        num_devices=1, max_iter=5,
        checkpoint_dir=str(tmp_path), checkpoint_every=2,
    ))
    assert saved == [2, 4, 5]

    saved.clear()
    drv.run_pipeline(_tiny_config(
        num_devices=1, max_iter=3,
        checkpoint_dir=str(tmp_path / "b"), checkpoint_every=1,
    ))
    assert saved == [1, 2, 3]


def test_checkpoint_every_validation():
    with pytest.raises(ValueError, match="checkpoint_every"):
        _tiny_config(checkpoint_every=0).validate()


def test_scale_out_mode_host_graph_pipeline(monkeypatch):
    """r3 scale-out: when the planner picks a distributed schedule AND the
    full graph cannot also fit one device, the pipeline keeps the graph
    host-side (census/modularity via NumPy twins) and produces identical
    labels/census to the device path. r4 (VERDICT r3 item 2): the
    recursive-LPA outlier pass now RUNS in scale-out mode — distributed
    over the planner-resolved schedule — and must match the single-device
    masked pass exactly, as must the sharded LOF scores."""
    import numpy as np

    from graphmine_tpu.pipeline.driver import run_pipeline

    # reference run: plenty of budget, device graph, same 8-device mesh
    ref = run_pipeline(_tiny_config(
        num_devices=8, max_iter=3, outlier_method="both",
    ))
    assert ref.outliers is not None

    # bundled graph models: single ~699 KB, replicated ~157 KB/device,
    # ring ~97 KB/device. 0.9 * 300000 = 270 KB -> replicated fits,
    # single does not => scale-out with the replicated schedule.
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", "300000")
    res = run_pipeline(_tiny_config(
        num_devices=8, max_iter=3, outlier_method="both",
    ))
    plans = [r for r in res.metrics.records if r.get("phase") == "plan"]
    assert plans[0]["schedule"] == "replicated"
    assert any(r.get("phase") == "scale_out" for r in res.metrics.records)
    np.testing.assert_array_equal(res.labels, ref.labels)
    p0, s0, e0 = ref.community_table
    p1, s1, e1 = res.community_table
    np.testing.assert_array_equal(p0, p1)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(e0, e1)
    # host graph really is host-resident numpy
    assert isinstance(res.graph.src, np.ndarray)
    # recursive-LPA outliers run distributed and match the single-device
    # masked pass bit-for-bit (VERDICT r3 item 2)
    assert res.outliers is not None
    np.testing.assert_array_equal(
        res.outliers.sub_labels, ref.outliers.sub_labels
    )
    np.testing.assert_array_equal(
        res.outliers.outlier_vertices, ref.outliers.outlier_vertices
    )
    np.testing.assert_array_equal(res.outliers.sub_sizes, ref.outliers.sub_sizes)
    assert res.outliers.thresholds == ref.outliers.thresholds
    out_rec = [r for r in res.metrics.records
               if r.get("phase") == "outliers_recursive_lpa"]
    assert out_rec and out_rec[0]["schedule"] == "replicated"
    # LOF still runs via the host feature twin + sharded scorer
    assert res.lof is not None and res.lof.shape == (res.graph.num_vertices,)
    lof_rec = [r for r in res.metrics.records if r.get("phase") == "outliers_lof"]
    assert lof_rec and lof_rec[0]["features"] == "host-8-sampled"
    # modularity host twin agrees with the device value
    comm = [r for r in res.metrics.records if r.get("phase") == "communities"][0]
    ref_comm = [r for r in ref.metrics.records if r.get("phase") == "communities"][0]
    assert abs(comm["modularity"] - ref_comm["modularity"]) < 1e-4

    # 0.9 * 120000 = 108 KB -> only ring fits; same labels, and the
    # outlier pass rides the ring schedule with the same result
    monkeypatch.setenv("GRAPHMINE_HBM_BYTES", "120000")
    res_ring = run_pipeline(_tiny_config(
        num_devices=8, max_iter=3, outlier_method="recursive_lpa",
    ))
    plans = [r for r in res_ring.metrics.records if r.get("phase") == "plan"]
    assert plans[0]["schedule"] == "ring"
    np.testing.assert_array_equal(res_ring.labels, ref.labels)
    assert res_ring.outliers is not None
    np.testing.assert_array_equal(
        res_ring.outliers.outlier_vertices, ref.outliers.outlier_vertices
    )
    out_rec = [r for r in res_ring.metrics.records
               if r.get("phase") == "outliers_recursive_lpa"]
    assert out_rec and out_rec[0]["schedule"] == "ring"


def test_vertex_features_host_parity(bundled_graph):
    """The NumPy feature twin matches the device feature matrix within
    float32 rounding when the clustering column is included."""
    import numpy as np

    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.ops.features import vertex_features, vertex_features_host
    from graphmine_tpu.ops.lpa import label_propagation

    g = bundled_graph
    labels = np.asarray(label_propagation(g, max_iter=3))
    want = np.asarray(vertex_features(g, labels))
    host_g = build_graph(
        np.asarray(g.src), np.asarray(g.dst),
        num_vertices=g.num_vertices, to_device=False,
    )
    got = vertex_features_host(host_g, labels, include_clustering=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    # clustering omitted -> same first 7 columns, zero last column
    got7 = vertex_features_host(host_g, labels, include_clustering=False)
    np.testing.assert_allclose(got7[:, :7], want[:, :7], rtol=2e-5, atol=2e-6)
    assert not got7[:, 7].any()

    # sampled clustering (the r4 scale-out default): same first 7 columns,
    # last column tracks the exact coefficient within the binomial bound
    gots = vertex_features_host(
        host_g, labels, include_clustering="sampled", clustering_samples=256
    )
    np.testing.assert_allclose(gots[:, :7], want[:, :7], rtol=2e-5, atol=2e-6)
    err = np.abs(gots[:, 7] - want[:, 7])
    assert err.max() <= 4.5 * 0.5 / np.sqrt(256) + 1e-6
    assert err.mean() <= 1.5 * 0.5 / np.sqrt(256)
