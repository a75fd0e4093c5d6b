"""Pipeline configuration — one dataclass + CLI.

Replaces the reference's scattered hardcoded constants (SURVEY §5 config):
the GraphFrames package pin env var (``Graphframes.py:3``), ``local[*]``
(``:12``), the data glob (``:16``), ``maxIter=5`` (``:81``, ``:126``),
``show(10)``, and the bottom-decile outlier threshold (``:136``).
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field

from graphmine_tpu.pipeline.resilience import ResilienceConfig


@dataclass
class PipelineConfig:
    # data
    data_path: str = "/root/reference/CommunityDetection/data/outlinks_pq"
    data_format: str = "parquet"  # parquet | edgelist
    batch_rows: int | None = None  # parquet only: stream in bounded batches
    # edgelist only: 0-based column holding a per-edge float weight
    # (weighted LPA: mode = argmax of incoming weight sums).
    edge_weight_col: int | None = None
    # engine (the plugin boundary from BASELINE.json)
    backend: str = "jax"  # jax | graphframes
    num_devices: int | None = None  # None = all visible (local[*] parity, :12)
    # Multi-device LPA schedule: "auto" (default, r3) consults the memory
    # planner (pipeline/planner.py) and picks the fastest schedule that
    # fits per-device HBM — single-device fused kernel, else "replicated"
    # (gathers the full V-length label vector per superstep; fastest to
    # ~100M vertices), else "ring" (labels stay sharded, chunks rotate
    # over ICI via ppermute — O(V/D + M/D) per device). Explicit
    # "replicated"/"ring" are honored but still planner-checked: an
    # impossible config fails loudly at plan time, not inside XLA.
    schedule: str = "auto"  # auto | replicated | ring
    # community detection
    community_method: str = "lpa"  # lpa (Graphframes.py:81 parity) | louvain | leiden
    max_iter: int = 5  # Graphframes.py:81
    gamma: float = 1.0  # louvain resolution
    # outlier detection
    outlier_method: str = "both"  # recursive_lpa | lof | both | none
    sub_max_iter: int = 5  # Graphframes.py:126
    decile: float = 0.1  # Graphframes.py:136
    # LOF neighborhood size. Must exceed the size of any *clustered*
    # anomaly group or the group's members score each other as inliers:
    # measured AUROC 0.49 at k=20 vs 0.91-0.93 at k>=100 on 64 injected
    # hubs (docs/DESIGN.md; r-series, no chip record). 128 is the measured
    # best; the driver clamps it to num_vertices - 1 on small graphs.
    lof_k: int = 128
    # LOF kNN implementation. "auto" (r6) is SCALE-AWARE: the planner
    # deploys the approximate IVF-flat index at the measured crossover
    # (>= 131K points — 3.1x over exact at 262K for recall 0.9999 /
    # AUROC -0.001; docs/DESIGN.md "LOF impl auto-policy"), the exact
    # path below it (whose own XLA/Pallas choice is ops/knn.py's
    # measured policy). The resolved family is emitted as an
    # impl_selected metrics record, and the degradation ladder runs the
    # opposite family as its rung. Explicit values force a path;
    # GRAPHMINE_LOF_IVF_MIN_N moves the crossover.
    lof_impl: str = "auto"  # auto | xla | pallas | ivf
    # observability (docs/OBSERVABILITY.md)
    show: int = 10  # .show(10) parity
    # one jax.profiler capture around the whole run, reduced at its end
    # into device_scope / device_idle records (docs/OBSERVABILITY.md)
    profile_dir: str | None = None
    # write every metrics record (incl. retry/degrade/quarantine/rollback
    # recovery events, docs/RESILIENCE.md) as JSON lines to this path at
    # the end of the run — the on-disk twin of the logging stream. Opened
    # in APPEND mode: a resumed run reusing the path adds a new
    # run_start-delimited segment instead of clobbering the prior trail.
    metrics_out: str | None = None
    # run identity stamped on every record/span (tools/obs_report.py joins
    # on it); None autogenerates a sortable UTC id. Set it explicitly to
    # correlate with an external scheduler's job id.
    run_id: str | None = None
    # emit a `heartbeat` record every N seconds (phase, gauges, RSS) so a
    # hung run is distinguishable from a dead one; None/0 = off.
    heartbeat_every_s: float | None = None
    # publish the counter/gauge registry as a Prometheus textfile at this
    # path (atomically, each heartbeat + once at exit) — the node_exporter
    # textfile-collector hand-off for runs with no scrape endpoint.
    prom_out: str | None = None
    # serving (docs/SERVING.md): publish the run's results — community
    # labels, CC labels, LOF scores, census, edge arrays, provenance —
    # as a versioned snapshot generation at this store directory, as the
    # pipeline's final phase. The serving layer (graphmine_tpu/serve/,
    # tools/serve_cli.py) queries it and ingests edge deltas against it
    # with warm-start repair instead of cold full recomputes.
    snapshot_out: str | None = None
    # checkpoint / resume
    checkpoint_dir: str | None = None
    # Save every N supersteps (plus always the final one). 1 = every
    # superstep — right for maxIter=5 parity runs; long billion-edge runs
    # (the case checkpointing exists for, SURVEY §5) should raise it: at
    # north-star scale each save is a ~64 MB npz. Multi-device rungs
    # write the sharded MANIFEST format (per-shard files + sha256,
    # re-shardable on restore — docs/RESILIENCE.md); single-device rungs
    # write the npz. --resume reads both and takes the newer iteration.
    checkpoint_every: int = 1
    resume: bool = False
    # resilience (docs/RESILIENCE.md): retry/backoff budget, superstep
    # watchdog, memory + elastic-device degradation policy, and the
    # in-loop divergence tripwires for every pipeline phase. CLI flags
    # are flattened (--max-retries, --superstep-timeout-s,
    # --tripwire-every-k, ...).
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    # Count-and-set-aside malformed rows / NaN weights at ingestion
    # (emitted as a "quarantine" metrics record) instead of crashing.
    # --no-quarantine-inputs restores strict parsing.
    quarantine_inputs: bool = True

    def validate(self) -> "PipelineConfig":
        self.resilience.validate()
        if self.data_format not in ("parquet", "edgelist"):
            raise ValueError(f"unknown data_format {self.data_format!r}")
        if self.backend not in ("jax", "graphframes"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.schedule not in ("auto", "replicated", "ring"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.outlier_method not in ("recursive_lpa", "lof", "both", "none"):
            raise ValueError(f"unknown outlier_method {self.outlier_method!r}")
        if self.lof_impl not in ("auto", "xla", "pallas", "ivf"):
            raise ValueError(f"unknown lof_impl {self.lof_impl!r}")
        if self.community_method not in ("lpa", "louvain", "leiden"):
            raise ValueError(f"unknown community_method {self.community_method!r}")
        if self.backend == "graphframes" and self.community_method != "lpa":
            raise ValueError(
                "backend='graphframes' only provides labelPropagation; "
                "use community_method='lpa' or backend='jax'"
            )
        if self.max_iter < 0 or self.sub_max_iter < 0:
            raise ValueError("max_iter must be >= 0")
        if self.batch_rows is not None and self.batch_rows <= 0:
            raise ValueError("batch_rows must be positive")
        if self.batch_rows is not None and self.data_format != "parquet":
            raise ValueError("batch_rows applies to parquet input only")
        if self.edge_weight_col is not None and self.data_format != "edgelist":
            raise ValueError("edge_weight_col applies to edgelist input only")
        if self.edge_weight_col is not None and self.backend == "graphframes":
            raise ValueError(
                "backend='graphframes' runs unweighted labelPropagation; "
                "use backend='jax' for weighted LPA"
            )
        if not 0 < self.decile < 1:
            raise ValueError("decile must be in (0, 1)")
        if self.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        if self.heartbeat_every_s is not None and self.heartbeat_every_s <= 0:
            raise ValueError("heartbeat_every_s must be positive (or unset)")
        return self


def parse_args(argv=None) -> PipelineConfig:
    parser = argparse.ArgumentParser(
        prog="graphmine_tpu.pipeline",
        description="TPU-native community + outlier detection pipeline",
    )
    def add_field(f):
        name = "--" + f.name.replace("_", "-")
        default = f.default
        if f.type in ("bool", bool):
            # BooleanOptionalAction so default-True flags (e.g.
            # quarantine_inputs) stay switchable: --no-quarantine-inputs
            parser.add_argument(
                name, action=argparse.BooleanOptionalAction, default=default
            )
        else:
            typ = str
            if f.type in ("int", int):
                typ = int
            elif f.type in ("float", float):
                typ = float
            elif f.type in ("int | None",):
                typ = int
            elif f.type in ("float | None",):
                typ = float
            parser.add_argument(name, type=typ, default=default)

    for f in dataclasses.fields(PipelineConfig):
        if f.name == "resilience":
            continue  # nested config: its fields flatten onto the CLI
        add_field(f)
    res_fields = dataclasses.fields(ResilienceConfig)
    for f in res_fields:
        add_field(f)
    ns = vars(parser.parse_args(argv))
    resilience = ResilienceConfig(
        **{f.name: ns.pop(f.name) for f in res_fields}
    )
    return PipelineConfig(**ns, resilience=resilience).validate()
