"""Result-quality observability: snapshot quality state, drift, canary.

Every observability layer before this one watches the *infrastructure*
(latency histograms, repair debt, WAL lag, rooflines) — none of them can
see a scorer that silently degrades while serving perfect p99s: an IVF
recall collapse after a bad retrain, a repair-path bias, a drifting
anomaly rate. This module watches the *product* — community labels and
LOF outlier scores — at every snapshot publish:

- :class:`QualityState`: one snapshot's result distributions — the LOF
  score sketch and community-size sketch (``obs/sketch.py`` log
  ladders), anomaly rate (share of scores above the threshold),
  community census scalars. Bounded host work: a handful of O(V)
  vectorized passes.
- :func:`quality_drift`: snapshot-over-parent drift — churned-vertex
  fraction (partition-matched, so a cold recompute's label renumbering
  does not read as churn), new/dissolved community counts, PSI drift of
  both sketches, anomaly-rate delta.
- :class:`CanaryProbe`: a seeded planted-anomaly probe set (generated
  once from the ``datasets.planted_anomaly_graph`` machinery, persisted
  as snapshot arrays + manifest metadata) re-scored through the
  production LOF scorer on every publish. Planted-anomaly recall@k is a
  production tripwire for scorer regressions that infra metrics cannot
  see — the probe's features are frozen, so any recall drop is the
  SCORER moving, never the data.
- :func:`run_quality_pass`: the publish-time orchestrator — computes
  state (+ drift vs parent, + canary score), emits the schema-registered
  ``quality_snapshot`` / ``quality_drift`` / ``canary_score`` records in
  the publishing trace, and mirrors the headline numbers into gauges.

numpy is imported inside functions (the ``serve/delta.py`` discipline)
so the ``obs`` package stays an import-clean stdlib leaf; the quality
pass itself always runs where numpy already is (the serving write path,
the driver's publish phase).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from graphmine_tpu.obs.sketch import (
    DEFAULT_SCORE_LADDER,
    DEFAULT_SIZE_LADDER,
    QuantileSketch,
    env_float,
    psi_distance,
)

__all__ = [
    "CanaryProbe",
    "DEFAULT_LOF_THRESHOLD",
    "QualityReport",
    "QualityState",
    "export_gauges",
    "lof_threshold",
    "partition_churn",
    "quality_drift",
    "run_quality_pass",
    "sketch_of",
]

# The anomaly-rate threshold: share of vertices with LOF above this is
# the "how many outliers are we serving" gauge (the r6 e2e reports
# LOF > 1.5 as its flagged count — same convention).
DEFAULT_LOF_THRESHOLD = 1.5

# Snapshot array names the canary probe persists (ride publishes the way
# lof_centers does) and the manifest key for its scoring parameters.
CANARY_ARRAYS = ("canary_features", "canary_is_anomaly")
CANARY_META_KEY = "canary"


def lof_threshold() -> float:
    """The env-resolved anomaly threshold (one owner for every caller:
    the quality pass, /statusz). Malformed env raises."""
    return env_float("GRAPHMINE_QUALITY_LOF_THRESHOLD",
                     DEFAULT_LOF_THRESHOLD)


def sketch_of(values, ladder, name: str = "sketch") -> QuantileSketch:
    """Bin a host value array into a fresh sketch with ONE vectorized
    pass (searchsorted + bincount) — the bounded-cost ingestion path the
    per-publish quality pass uses instead of V python-side observes."""
    import numpy as np

    sk = QuantileSketch(name=name, buckets=ladder)
    vals = np.asarray(values, np.float64).reshape(-1)
    if not len(vals):
        return sk
    bounds = np.asarray(sk.bounds, np.float64)
    idx = np.searchsorted(bounds, vals, side="left")
    counts = np.bincount(idx, minlength=len(bounds) + 1)
    sk.add_counts(counts.tolist(), total=float(vals.sum()))
    return sk


@dataclass
class QualityState:
    """The result-quality observables of one published snapshot."""

    version: int = 0
    num_vertices: int = 0
    num_communities: int = 0
    largest_community: int = 0
    anomaly_count: int = 0
    anomaly_rate: float = 0.0
    threshold: float = DEFAULT_LOF_THRESHOLD
    lof_sketch: QuantileSketch = field(
        default_factory=lambda: QuantileSketch(
            "lof_score", buckets=DEFAULT_SCORE_LADDER)
    )
    size_sketch: QuantileSketch = field(
        default_factory=lambda: QuantileSketch(
            "community_size", buckets=DEFAULT_SIZE_LADDER)
    )

    @classmethod
    def from_arrays(
        cls, labels, lof=None, version: int = 0, threshold: float | None = None,
    ) -> "QualityState":
        """Compute the state from host label/score columns: one bincount
        for the census, one binning pass per sketch. O(V) host work
        (its seconds ride the ``quality_snapshot`` record)."""
        import numpy as np

        labels = np.asarray(labels).reshape(-1)
        thr = lof_threshold() if threshold is None else float(threshold)
        sizes = np.bincount(labels.astype(np.int64))
        sizes = sizes[sizes > 0]
        lof_arr = (
            np.zeros(0, np.float32) if lof is None
            else np.asarray(lof, np.float32).reshape(-1)
        )
        n_anom = int((lof_arr > thr).sum())
        return cls(
            version=int(version),
            num_vertices=int(len(labels)),
            num_communities=int(len(sizes)),
            largest_community=int(sizes.max()) if len(sizes) else 0,
            anomaly_count=n_anom,
            anomaly_rate=round(n_anom / len(lof_arr), 6) if len(lof_arr) else 0.0,
            threshold=thr,
            lof_sketch=sketch_of(lof_arr, DEFAULT_SCORE_LADDER, "lof_score"),
            size_sketch=sketch_of(
                sizes, DEFAULT_SIZE_LADDER, "community_size"
            ),
        )

    def payload(self) -> dict:
        """The JSON body /statusz and /alertz serve (and the
        ``quality_snapshot`` record carries): scalars + both sketch
        states — the shape the fleet router's counter-wise merge and
        ``obs_report`` both read."""
        return {
            "version": self.version,
            "num_vertices": self.num_vertices,
            "num_communities": self.num_communities,
            "largest_community": self.largest_community,
            "anomaly_count": self.anomaly_count,
            "anomaly_rate": self.anomaly_rate,
            "lof_threshold": self.threshold,
            "lof_sketch": self.lof_sketch.to_state(),
            "size_sketch": self.size_sketch.to_state(),
        }


def partition_churn(parent_labels, labels) -> float:
    """Churned-vertex fraction between two community partitions over the
    common vertex prefix, ROBUST to label renumbering.

    Raw label comparison would read a cold recompute — which renumbers
    every community id while possibly changing nothing — as 100% churn.
    Instead each CHILD community is matched to the parent community it
    overlaps most; a vertex churned iff it is not in its child
    community's majority parent group:
    ``churn = 1 - (sum of per-child-community max overlaps) / V``.
    Exactly 0.0 when the partitions are identical up to renaming;
    hand-computable (the ``tests/test_quality.py`` pin).
    """
    import numpy as np

    parent = np.asarray(parent_labels).reshape(-1)
    child = np.asarray(labels).reshape(-1)
    n = min(len(parent), len(child))
    if n == 0:
        return 0.0
    parent, child = parent[:n].astype(np.int64), child[:n].astype(np.int64)
    # overlap counts per (child, parent) label pair, then the max
    # overlap per child community
    pair = np.stack([child, parent], axis=1)
    uniq, counts = np.unique(pair, axis=0, return_counts=True)
    order = np.lexsort((-counts, uniq[:, 0]))
    uniq, counts = uniq[order], counts[order]
    first = np.ones(len(uniq), bool)
    first[1:] = uniq[1:, 0] != uniq[:-1, 0]
    matched = int(counts[first].sum())
    return round(1.0 - matched / n, 6)


def _label_sets(parent_labels, labels):
    """(new, dissolved) community-id counts by raw id set difference —
    meaningful along warm-repair chains (labels persist), noisy across a
    cold recompute's renumbering; ``churn_frac`` is the renumbering-
    robust signal, these are the cheap id-chain diagnostics."""
    import numpy as np

    p = np.unique(np.asarray(parent_labels).reshape(-1))
    c = np.unique(np.asarray(labels).reshape(-1))
    new = int(len(np.setdiff1d(c, p, assume_unique=True)))
    dissolved = int(len(np.setdiff1d(p, c, assume_unique=True)))
    return new, dissolved


def quality_drift(
    parent: QualityState, state: QualityState, parent_labels, labels,
) -> dict:
    """Snapshot-over-parent drift: the ``quality_drift`` record body."""
    new, dissolved = _label_sets(parent_labels, labels)
    return {
        "version": state.version,
        "parent_version": parent.version,
        "churn_frac": partition_churn(parent_labels, labels),
        "new_communities": new,
        "dissolved_communities": dissolved,
        "lof_psi": round(
            psi_distance(parent.lof_sketch, state.lof_sketch), 6
        ),
        "size_psi": round(
            psi_distance(parent.size_sketch, state.size_sketch), 6
        ),
        "anomaly_rate": state.anomaly_rate,
        "anomaly_rate_delta": round(
            state.anomaly_rate - parent.anomaly_rate, 6
        ),
    }


# ---- canary probe ----------------------------------------------------------


def _probe_features(src, dst, comm, num_vertices: int):
    """Structural per-vertex features of the probe graph, computed ONCE
    at probe creation with plain numpy (no jax — probe generation must
    work anywhere, including the driver's publish phase before any
    device work): degree, distinct-partner count, mean partner degree,
    cross-block partner fraction — the same signal family the production
    feature pass scores, standardized column-wise."""
    import numpy as np

    es = np.concatenate([src, dst]).astype(np.int64)
    ed = np.concatenate([dst, src]).astype(np.int64)
    deg = np.bincount(es, minlength=num_vertices).astype(np.float64)
    pair = es * num_vertices + ed
    uniq = np.unique(pair)
    distinct = np.bincount(
        (uniq // num_vertices), minlength=num_vertices
    ).astype(np.float64)
    nbr_deg_sum = np.bincount(es, weights=deg[ed], minlength=num_vertices)
    mean_nbr_deg = nbr_deg_sum / np.maximum(deg, 1.0)
    cross = np.bincount(
        es, weights=(comm[es] != comm[ed]).astype(np.float64),
        minlength=num_vertices,
    ) / np.maximum(deg, 1.0)
    feats = np.stack([
        np.log1p(deg), np.log1p(distinct), np.log1p(mean_nbr_deg), cross,
    ], axis=1)
    mu = feats.mean(axis=0)
    sd = feats.std(axis=0)
    sd[sd == 0] = 1.0
    return ((feats - mu) / sd).astype(np.float32)


@dataclass
class CanaryProbe:
    """A frozen planted-anomaly probe set, re-scored on every publish.

    ``features`` [N, d] and ``is_anomaly`` [N] are generated once (seeded
    ``datasets.planted_anomaly_graph`` + the numpy structural-feature
    pass above) and persisted in the snapshot (arrays
    :data:`CANARY_ARRAYS`, parameters under manifest key
    :data:`CANARY_META_KEY`), so every publish in a store's lifetime —
    across restarts, failovers and standby promotions — scores the SAME
    probe. :meth:`score` runs the probe through the production scorer
    (``ops.lof.lof_scores``); planted-anomaly recall@k dropping between
    two publishes means the SCORER regressed, because nothing else in
    the comparison moved.
    """

    features: object          # np.ndarray [N, d] float32
    is_anomaly: object        # np.ndarray [N] bool
    k: int = 16
    recall_k: int = 0         # 0 = resolved to 2 * num planted anomalies
    seed: int = 0

    @property
    def num_anomalies(self) -> int:
        import numpy as np

        return int(np.asarray(self.is_anomaly).sum())

    def _recall_k(self) -> int:
        return int(self.recall_k) if self.recall_k else 2 * self.num_anomalies

    @classmethod
    def generate(
        cls, seed: int = 0, num_vertices: int = 384, num_anomalies: int = 6,
        edges_per_vertex: int = 8, edges_per_anomaly: int = 48,
        k: int = 16, recall_k: int = 0,
    ) -> "CanaryProbe":
        """Seeded probe construction: a small planted-community graph
        with injected structural anomalies (uniform cross-graph hubs —
        exactly the signature the production LOF pipeline scores),
        reduced to a frozen feature matrix. Deterministic per seed."""
        from graphmine_tpu.datasets import planted_anomaly_graph

        src, dst, is_anomaly, comm = planted_anomaly_graph(
            num_vertices, num_vertices * edges_per_vertex,
            n_communities=max(8, num_vertices // 48),
            num_anomalies=num_anomalies,
            edges_per_anomaly=edges_per_anomaly,
            seed=seed,
        )
        feats = _probe_features(src, dst, comm, num_vertices)
        return cls(
            features=feats, is_anomaly=is_anomaly, k=k,
            recall_k=recall_k, seed=seed,
        )

    # -- snapshot persistence ---------------------------------------------
    def arrays(self) -> dict:
        """The snapshot arrays a publish attaches (the ``lof_centers``
        pattern: probe identity rides the store, not process memory)."""
        import numpy as np

        return {
            "canary_features": np.asarray(self.features, np.float32),
            "canary_is_anomaly": np.asarray(self.is_anomaly, np.uint8),
        }

    def meta(self) -> dict:
        """The manifest entry (under :data:`CANARY_META_KEY`)."""
        return {
            "seed": int(self.seed),
            "k": int(self.k),
            "recall_k": self._recall_k(),
        }

    @classmethod
    def from_snapshot(cls, snapshot) -> "CanaryProbe | None":
        """Rebuild the probe a snapshot carries (None when it carries
        none — pre-quality stores bootstrap by generating a fresh one)."""
        return cls.from_arrays(snapshot.arrays, snapshot.meta)

    @classmethod
    def from_arrays(cls, arrays: dict, meta: dict) -> "CanaryProbe | None":
        """Rebuild from a raw array dict + manifest meta (the
        ``SnapshotStore.peek_arrays`` shape the driver's publish phase
        reads without a full load)."""
        feats = arrays.get("canary_features")
        mask = arrays.get("canary_is_anomaly")
        if feats is None or mask is None:
            return None
        import numpy as np

        probe_meta = (meta or {}).get(CANARY_META_KEY) or {}
        return cls(
            features=np.asarray(feats, np.float32),
            is_anomaly=np.asarray(mask).astype(bool),
            k=int(probe_meta.get("k", 16)),
            recall_k=int(probe_meta.get("recall_k", 0)),
            seed=int(probe_meta.get("seed", 0)),
        )

    # -- scoring -----------------------------------------------------------
    def score(self, sink=None) -> dict:
        """Re-score the frozen probe through the production LOF scorer
        and rank the planted anomalies: the ``canary_score`` record body.

        ``recall_at_k``: fraction of planted anomalies inside the top
        ``recall_k`` scores (1.0 on a healthy scorer — pinned at probe
        defaults by the tests); ``mean_rank_frac``: mean normalized rank
        of the planted anomalies (0.0 = all ranked first). The
        ``canary_probe`` fault seam between scoring and ranking is where
        the tests inject a scorer regression.
        """
        import numpy as np

        from graphmine_tpu.ops.lof import lof_scores
        from graphmine_tpu.pipeline import resilience

        t0 = time.perf_counter()
        feats = np.asarray(self.features, np.float32)
        scores = np.asarray(
            lof_scores(feats, k=min(self.k, len(feats) - 2), sink=sink)
        )
        # Fault seam (testing/faults.py mutators): corrupt the scores
        # HERE to prove a scorer regression trips the canary alert.
        state = {"scores": scores}
        resilience.fault_point("canary_probe", state=state)
        scores = np.asarray(state["scores"])

        mask = np.asarray(self.is_anomaly).astype(bool)
        n = len(scores)
        order = np.argsort(-scores, kind="stable")
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n)
        k_eff = min(self._recall_k(), n)
        anom_ranks = rank[mask]
        n_anom = int(mask.sum())
        recall = (
            round(float((anom_ranks < k_eff).sum()) / n_anom, 6)
            if n_anom else 1.0
        )
        return {
            "recall_at_k": recall,
            "recall_k": k_eff,
            "mean_rank_frac": (
                round(float(anom_ranks.mean()) / max(1, n - 1), 6)
                if n_anom else 0.0
            ),
            "num_anomalies": n_anom,
            "num_probe_vertices": n,
            "k": int(self.k),
            "seconds": round(time.perf_counter() - t0, 4),
        }


@dataclass
class QualityReport:
    """One publish's full quality pass: state + optional drift/canary."""

    state: QualityState
    drift: dict | None = None
    canary: dict | None = None
    seconds: float = 0.0

    def payload(self) -> dict:
        """The "quality" section body (/statusz, /alertz): the state
        under ``state`` plus ``drift``/``canary`` when computed."""
        out = {"state": self.state.payload(), "seconds": self.seconds}
        if self.drift is not None:
            out["drift"] = self.drift
        if self.canary is not None:
            out["canary"] = self.canary
        return out

    def values(self) -> dict:
        """The flat metric dict the alert rules evaluate over."""
        out = {
            "quality_anomaly_rate": self.state.anomaly_rate,
            "quality_num_communities": self.state.num_communities,
        }
        if self.drift is not None:
            out.update({
                "quality_lof_psi": self.drift["lof_psi"],
                "quality_size_psi": self.drift["size_psi"],
                "quality_churn_frac": self.drift["churn_frac"],
            })
        if self.canary is not None:
            out["canary_recall"] = self.canary["recall_at_k"]
        return out


def export_gauges(registry, state: QualityState, drift: dict | None = None,
                  canary: dict | None = None) -> None:
    """Mirror the quality headline numbers into scrapeable gauges — one
    owner for the metric names, shared by the publish pass and the
    serving layer's read-time state export."""
    g = registry.gauge
    g("graphmine_quality_anomaly_rate",
      "share of LOF scores above the anomaly threshold").set(
        state.anomaly_rate)
    g("graphmine_quality_num_communities",
      "present communities in the served snapshot").set(
        state.num_communities)
    if drift is not None:
        g("graphmine_quality_churn_frac",
          "partition-matched churned-vertex fraction vs parent").set(
            drift["churn_frac"])
        g("graphmine_quality_lof_psi",
          "PSI drift of the LOF score distribution vs parent").set(
            drift["lof_psi"])
        g("graphmine_quality_size_psi",
          "PSI drift of the community-size distribution vs parent").set(
            drift["size_psi"])
    if canary is not None:
        g("graphmine_quality_canary_recall",
          "planted-anomaly recall@k of the canary probe, last publish",
          ).set(canary["recall_at_k"])


def run_quality_pass(
    labels,
    lof,
    version: int,
    parent_labels=None,
    parent_lof=None,
    parent_version: int | None = None,
    parent_state: QualityState | None = None,
    canary: CanaryProbe | None = None,
    threshold: float | None = None,
    sink=None,
    registry=None,
) -> QualityReport:
    """The bounded publish-time quality pass, one owner for every
    publisher (delta ingestor, driver publish):

    1. compute :class:`QualityState` from the published columns;
    2. with a parent (``parent_labels`` [+ ``parent_state`` to reuse the
       already-computed sketches, or ``parent_lof`` to rebuild them]),
       compute :func:`quality_drift`;
    3. with a :class:`CanaryProbe`, re-score it;
    4. emit ``quality_snapshot`` / ``quality_drift`` / ``canary_score``
       records through ``sink`` (span-stamped by the sink, so they join
       the publishing trace) and mirror gauges into ``registry``.

    Never raises out of the record/gauge tail — result quality telemetry
    must not take a publish down (the caller owns harder failures like a
    malformed labels array, which IS a publish bug).
    """
    t0 = time.perf_counter()
    state = QualityState.from_arrays(
        labels, lof, version=version, threshold=threshold
    )
    drift = None
    if parent_labels is not None:
        if parent_state is None:
            parent_state = QualityState.from_arrays(
                parent_labels, parent_lof,
                version=version - 1 if parent_version is None else parent_version,
                threshold=threshold,
            )
        drift = quality_drift(parent_state, state, parent_labels, labels)
    canary_out = canary.score(sink=sink) if canary is not None else None
    seconds = round(time.perf_counter() - t0, 4)
    report = QualityReport(
        state=state, drift=drift, canary=canary_out, seconds=seconds
    )
    try:
        if sink is not None:
            sink.emit(
                "quality_snapshot", seconds=seconds, **state.payload()
            )
            if drift is not None:
                sink.emit("quality_drift", **drift)
            if canary_out is not None:
                sink.emit(
                    "canary_score", version=state.version, **canary_out
                )
        if registry is not None:
            export_gauges(registry, report.state, report.drift,
                          report.canary)
    except Exception:  # noqa: BLE001 — telemetry must not fail a publish
        pass
    return report
