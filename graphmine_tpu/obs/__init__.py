"""Run-correlated tracing & telemetry (docs/OBSERVABILITY.md).

Stdlib-only leaf package — safe to import from anywhere in the pipeline
(nothing here imports jax, and :mod:`graphmine_tpu.pipeline.metrics`
builds on it, not the other way around):

- :mod:`graphmine_tpu.obs.spans`      hierarchical span context
  (run_id -> phase -> rung -> stage / superstep) with monotonic timings;
- :mod:`graphmine_tpu.obs.registry`   counter/gauge/histogram registry
  with a Prometheus exporter (textfile or the serve layer's live
  ``GET /metrics``);
- :mod:`graphmine_tpu.obs.histogram`  thread-safe, mergeable bucket
  histograms with ``histogram_quantile``-style estimation — the
  latency-distribution surface the serving SLO endpoints read;
- :mod:`graphmine_tpu.obs.heartbeat`  periodic liveness records (a hung
  run is distinguishable from a dead one);
- :mod:`graphmine_tpu.obs.schema`     the record-schema registry every
  emitted phase name must be declared in (validated in tests and by
  ``tools/obs_report.py``);
- :mod:`graphmine_tpu.obs.costmodel`  the analytical compute-plane cost
  model (r13): per-plan bytes/slots/exchange derivation, measured
  roofline anchors, the ``cost`` sub-record builder and the
  ``superstep_timing`` achieved-vs-model emission;
- :mod:`graphmine_tpu.obs.memmodel`   the analytical memory-plane model
  (ISSUE 14): per-plan HBM footprint inventories, the byte seeds the
  pipeline planner derives its schedule model from, the ``mem``
  sub-record builder and the ``memory_watermark`` emission;
- :mod:`graphmine_tpu.obs.devtrace`   the reduction of one
  ``profile_dir`` capture: device seconds by named scope, program and
  program span, busy/idle per chapter (a wire reader of its own for the
  profiler's file; no jax);
- :mod:`graphmine_tpu.obs.sketch`     mergeable quantile sketches over
  fixed log ladders (the ``Histogram.merge`` contract applied to LOF
  scores and community sizes) + the PSI drift distance;
- :mod:`graphmine_tpu.obs.quality`    the result-quality plane (r14):
  per-publish quality state, snapshot-diff drift, the planted-anomaly
  canary probe and the ``quality_*``/``canary_score`` record emission;
- :mod:`graphmine_tpu.obs.alerts`     the declarative threshold +
  for-duration alert rule engine behind ``/alertz``.
"""

from graphmine_tpu.obs.alerts import AlertManager, AlertRule, default_rules
from graphmine_tpu.obs.costmodel import (
    CostEstimate,
    lof_cost,
    rooflines,
    sharded_superstep_cost,
    superstep_cost,
)
from graphmine_tpu.obs.histogram import Histogram, HistogramFamily
from graphmine_tpu.obs.memmodel import (
    MemEstimate,
    emit_memory_watermark,
    lof_footprint,
    schedule_footprint,
    sharded_superstep_footprint,
    superstep_footprint,
)
from graphmine_tpu.obs.quality import (
    CanaryProbe,
    QualityState,
    run_quality_pass,
)
from graphmine_tpu.obs.registry import Registry
from graphmine_tpu.obs.sketch import QuantileSketch, log_ladder, psi_distance
from graphmine_tpu.obs.spans import (
    TRACE_HEADER,
    Span,
    TraceContext,
    Tracer,
    new_run_id,
)

__all__ = [
    "AlertManager",
    "AlertRule",
    "CanaryProbe",
    "CostEstimate",
    "Histogram",
    "HistogramFamily",
    "MemEstimate",
    "QualityState",
    "QuantileSketch",
    "Registry",
    "Span",
    "TRACE_HEADER",
    "TraceContext",
    "Tracer",
    "default_rules",
    "emit_memory_watermark",
    "lof_cost",
    "lof_footprint",
    "log_ladder",
    "new_run_id",
    "psi_distance",
    "rooflines",
    "run_quality_pass",
    "schedule_footprint",
    "sharded_superstep_cost",
    "sharded_superstep_footprint",
    "superstep_cost",
    "superstep_footprint",
]
