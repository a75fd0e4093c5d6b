"""Analytical compute-plane cost model: what a superstep SHOULD cost.

Every compute-plane record so far says *what ran* (``impl_selected``,
``plan_build``, ``superstep_telemetry``) but not *how fast it should have
run* — the crossover constants in ``ops/superstep_policy.py`` and ``ops/lof.py``
encode measured walls, yet nothing at runtime judges achieved throughput
against them. This module closes that gap (ISSUE 12 tentpole), in the
tradition of the GraphBLAST / propagation-blocking line (PAPERS arXiv
1908.01407, 2011.08451) where bytes-moved / slots-per-second accounting
IS the performance argument:

1. **Per-plan cost derivation** — for every superstep family (sort /
   bucketed, fused and sharded) and LOF impl, derive message
   slots, padded gather slots, bytes gathered/scattered, padding overhead
   and exchanged ICI bytes **directly from the already-built plan/graph
   objects** (:func:`superstep_cost`, :func:`sharded_superstep_cost`,
   :func:`lof_cost`). No new measurement, no device work: the plans
   already hold the exact layout.

2. **Measured rooflines** — per-family achieved-rate anchors seeded from
   the r4/r5 silicon captures on one device kind (see
   :data:`ROOFLINE_SEEDS` for per-anchor provenance), overridable by a
   JSON file (``GRAPHMINE_ROOFLINE_FILE``) or per-anchor env vars
   (``GRAPHMINE_ROOFLINE_<NAME>``) so a fresh capture re-seeds the model
   without a code change (docs/OBSERVABILITY.md "Compute-plane
   roofline").

3. **Predicted time** — bytes/slots combined with the anchors into a
   predicted per-superstep time and a predicted work-rate per chip. The
   ``cost`` sub-record (:meth:`CostEstimate.record`) rides every
   ``plan_build`` / ``impl_selected`` / ``superstep_timing`` record, so
   every auto-policy decision ships the numbers that justified it, and
   ``tools/obs_report.py``'s roofline section can render achieved vs
   model from the JSONL alone.

The model is deliberately coarse — a per-superstep budget, not a
simulator. Its job is triage leverage: a window at 0.9x model is noise, a
window at 0.2x model is a real anomaly (imbalance, eviction, a degraded
part) worth reading the telemetry for *before* blaming the device
(docs/RUNBOOKS.md §12).

Import discipline: **stdlib only** — no jax, no numpy. Plan objects are
inspected by duck-typed attributes/shapes so this module loads on a
machine with no accelerator stack at all (the same contract as the rest
of ``obs/`` and both offline tools).
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass

_I32 = 4  # bytes per int32/float32 slot — the compute plane's one word size

# ---- measured roofline anchors (single owner) ------------------------------
#
# Values are work-units per second PER CHIP, keyed by the device kind
# (jax's ``device_kind``) they were measured on. Provenance discipline:
# each anchor names the capture that seeded it; anchors nobody has
# measured on silicon yet say so ("model seed") and are exactly the ones
# a chip record should replace. Cost estimates are always computed from
# ``MODEL_DEVICE_KIND``'s seeds (plus overrides); what a measurement on
# ANOTHER kind of device achieved against them is not a fraction of
# anything — see :func:`anchored`.
MODEL_DEVICE_KIND = "TPU v5 lite"
ROOFLINE_SEEDS: dict = {
    MODEL_DEVICE_KIND: {
        # Random-gather slots/s: the r4/r5 roofline microbenchmark
        # (131.8M / 132.6M slots/s; r-series, record deleted in PR 22).
        # Governs the sort gather and every bucketed row
        # reduce (136 M slots/s on a v5e at 128.3 M messages, PR 26).
        "gather_slots_per_sec": 1.32e8,
        # ICI exchange bytes/s per chip: no chip record measures this
        # — 4.5e10 B/s is a conservative v5e-interconnect model seed
        # (order of magnitude below the advertised peak; cell
        # `cdlp-g500-25-x4`'s `exchange` records are where to read it).
        "exchange_bytes_per_sec": 4.5e10,
        # Exact-kNN distance pairs/s: the r6 LOF crossover provenance
        # table (ops/lof.py): 65,536 points (=> 65,536^2 pairs) in 2.3 s.
        "lof_exact_pairs_per_sec": 1.87e9,
        # IVF-flat end-to-end points/s at crossover scale: same table,
        # 262,144 points in 9.0 s (candidate reduction included).
        "lof_ivf_points_per_sec": 2.9e4,
    },
}

_SEED_PROVENANCE = {
    "gather_slots_per_sec": (
        "r4/r5 roofline capture on TPU v5 lite (record deleted in PR 22)"
    ),
    "exchange_bytes_per_sec": "model seed (unmeasured; no chip record)",
    "lof_exact_pairs_per_sec": "ops/lof.py r6 crossover table (65K in 2.3s)",
    "lof_ivf_points_per_sec": "ops/lof.py r6 crossover table (262K in 9.0s)",
}

# Padding the r4 width ladder measures when no plan exists yet to count
# exactly (~10% — docs/DESIGN.md "bucket ladder"): pre-plan estimates
# (the driver's plan-time impl_selected fires before the build) use it.
_EST_PAD = 1.10


def rooflines(overrides: dict | None = None) -> dict:
    """The active anchor set: ``{name: {"v": rate, "src": provenance}}``.

    Precedence per anchor: ``overrides`` arg (tests, a caller holding a
    fresh capture) → ``GRAPHMINE_ROOFLINE_<NAME>`` env var →
    ``GRAPHMINE_ROOFLINE_FILE`` JSON (``{name: rate}`` — the re-seed
    path docs/OBSERVABILITY.md describes for a new silicon capture) →
    the committed seed. Unknown names in the file/overrides are ignored
    (a newer file must not break an older reader); a malformed file or
    env value raises — a silently-dropped override would un-anchor the
    model without anyone noticing.
    """
    out = {
        k: {"v": float(v), "src": _SEED_PROVENANCE[k]}
        for k, v in ROOFLINE_SEEDS[MODEL_DEVICE_KIND].items()
    }
    path = os.environ.get("GRAPHMINE_ROOFLINE_FILE")
    if path:
        with open(path) as f:
            loaded = json.load(f)
        if not isinstance(loaded, dict):
            raise ValueError(
                f"GRAPHMINE_ROOFLINE_FILE {path} must hold a JSON object "
                f"of anchor -> rate, got {type(loaded).__name__}"
            )
        for k, v in loaded.items():
            if k in out:
                out[k] = {"v": float(v), "src": f"file:{path}"}
    for k in out:
        env = os.environ.get(f"GRAPHMINE_ROOFLINE_{k.upper()}")
        if env:
            out[k] = {"v": float(env), "src": "env"}
    if overrides:
        for k, v in overrides.items():
            if k in out:
                out[k] = {"v": float(v), "src": "caller"}
    return out


def _running_device_kind() -> str | None:
    """``device_kind`` of the device this process computes on — read off
    jax only when the caller already imported it (this module stays
    importable on a machine with no accelerator stack)."""
    jax = sys.modules.get("jax")
    return jax.devices()[0].device_kind if jax is not None else None


def anchored(device_kind: str | None) -> bool:
    """Whether an achieved-vs-model fraction means anything on
    ``device_kind``: the kind has measured seeds, or the operator
    supplied anchors for this machine (``GRAPHMINE_ROOFLINE_FILE``)."""
    return device_kind in ROOFLINE_SEEDS or bool(
        os.environ.get("GRAPHMINE_ROOFLINE_FILE")
    )


@dataclass(frozen=True)
class CostEstimate:
    """Predicted per-superstep (or per-scoring-pass) cost for one
    operating point. All byte/slot figures are per superstep **per
    chip**; ``predicted_per_chip`` is the model's work rate in ``unit``
    (edges/s/chip for superstep families, points/s/chip for LOF)."""

    op: str
    family: str
    devices: int
    slots: int               # real message slots (no padding)
    padded_slots: int        # gathered slots incl. padding
    bytes_gathered: int
    bytes_scattered: int
    padding_overhead: float  # padded_slots / slots
    exchange_bytes: int      # ICI bytes per chip per superstep (0 fused)
    compute_seconds: float   # the model's compute share of one superstep
    exchange_seconds: float  # ... and its exchange share
    predicted_seconds: float  # compute + exchange
    predicted_per_chip: float
    unit: str
    roofline: dict           # the consulted anchors (+ provenance)

    def record(self) -> dict:
        """The ``cost`` sub-record (shape registered as
        ``obs.schema.COST_KEYS`` — a half-stamped copy fails validation
        like a half-stamped trace). This method is the SINGLE builder:
        ``tools/schema_lint.py`` flags inline ``cost={...}`` literals
        anywhere else in the package."""
        return {
            "family": self.family,
            "devices": self.devices,
            "slots": self.slots,
            "padded_slots": self.padded_slots,
            "bytes_gathered": self.bytes_gathered,
            "bytes_scattered": self.bytes_scattered,
            "padding_overhead": round(self.padding_overhead, 4),
            "exchange_bytes": self.exchange_bytes,
            "compute_seconds": _sig(self.compute_seconds),
            "exchange_seconds": _sig(self.exchange_seconds),
            "predicted_seconds": _sig(self.predicted_seconds),
            "predicted_per_chip": round(self.predicted_per_chip, 1),
            "unit": self.unit,
            "roofline": {k: a["v"] for k, a in self.roofline.items()}
            | {"provenance": "; ".join(
                f"{k}: {a['src']}" for k, a in sorted(self.roofline.items())
            )},
        }


def _sig(x: float, digits: int = 4) -> float:
    """Round to significant digits (predicted times span ns to minutes —
    fixed decimal places would zero the small ones)."""
    if x == 0:
        return 0.0
    from math import floor, log10

    return round(x, digits - 1 - floor(log10(abs(x))))


# ---- plan inspection (duck-typed: no jax import) ---------------------------


def _plan_family(plan) -> str:
    if plan is None:
        return "sort"
    if hasattr(plan, "vertex_ids"):        # ops.bucketed_mode.BucketedModePlan
        return "bucketed"
    raise TypeError(f"unknown plan type {type(plan).__name__}")


def _bucketed_padded_slots(plan) -> int:
    mats = plan.send_idx if plan.send_idx is not None else plan.msg_idx
    slots = sum(int(m.shape[0]) * int(m.shape[1]) for m in mats or ())
    if plan.hist_send is not None:
        slots += int(plan.hist_send.shape[0])
    return slots


def _plan_weighted(plan) -> bool:
    return getattr(plan, "weight_mat", None) not in (None, ())


def allgather_exchange_bytes(sg) -> int:
    """Modeled per-chip exchange bytes per superstep: every chip
    receives the other ``D-1`` chunks of the padded label vector
    (``4·Vc·(D-1)``, the ROADMAP scaling ceiling)."""
    return _I32 * int(sg.chunk_size) * max(int(sg.num_shards) - 1, 0)


# ---- superstep families ----------------------------------------------------


def superstep_cost(
    op: str,
    family: str,
    num_vertices: int,
    num_messages: int,
    num_edges: int,
    plan=None,
    weighted: bool | None = None,
    anchors: dict | None = None,
) -> CostEstimate:
    """Cost of ONE fused (single-device) superstep.

    With ``plan`` (a built BucketedModePlan) the padded
    slot counts are **exact** — read off the plan's own matrices; without
    one (the driver's plan-time ``impl_selected`` fires before the
    build, and the sort family never builds one) the r4-measured ~10%
    ladder padding estimates them. ``weighted`` adds the slot-aligned
    float32 weight gather to the byte/time model — weights double the
    gathered bytes, not the slots; the default ``None`` infers it from
    the plan's weight payload, while an explicit ``False`` models an op
    that ignores the payload (CC's min never reads weights even when the
    shared plan carries them).

    Model per family (docs/OBSERVABILITY.md "Compute-plane roofline"):

    - **sort**: one random gather of M label slots (the segment-mode
      sort rides inside the measured gather anchor), scatter V results.
    - **bucketed**: one random gather of the plan's padded slots
      (padding gathers the sentinel — same bandwidth), scatter V.
    """
    a = anchors if anchors is not None else rooflines()
    if plan is not None:
        family = _plan_family(plan)
        if weighted is None:
            weighted = _plan_weighted(plan)
    weighted = bool(weighted)
    m = max(int(num_messages), 1)
    v = int(num_vertices)
    gather = a["gather_slots_per_sec"]["v"]
    wf = 2 if weighted else 1
    if family == "sort":
        padded = m
        bytes_g = _I32 * m * wf
        bytes_s = _I32 * v
        compute = (m * wf) / gather
    elif family == "bucketed":
        padded = (
            _bucketed_padded_slots(plan) if plan is not None
            else int(m * _EST_PAD)
        )
        bytes_g = _I32 * padded * wf
        bytes_s = _I32 * v
        compute = (padded * wf) / gather
    else:
        raise ValueError(f"unknown superstep family {family!r}")
    return CostEstimate(
        op=op, family=family, devices=1,
        slots=m, padded_slots=padded,
        bytes_gathered=int(bytes_g), bytes_scattered=int(bytes_s),
        padding_overhead=padded / m,
        exchange_bytes=0,
        compute_seconds=compute, exchange_seconds=0.0,
        predicted_seconds=compute,
        predicted_per_chip=num_edges / compute if compute > 0 else 0.0,
        unit="edges/s/chip",
        roofline={"gather_slots_per_sec": a["gather_slots_per_sec"]},
    )


def sharded_superstep_cost(
    op: str,
    sg,
    num_edges: int,
    num_messages: int | None = None,
    weighted: bool | None = None,
    anchors: dict | None = None,
) -> CostEstimate:
    """Cost of ONE sharded superstep, derived from a built
    :class:`~graphmine_tpu.parallel.sharded.ShardedGraph` (shapes only —
    no device sync, no jax import; safe to call at operating-point build
    time on device-resident shards).

    Per-chip compute follows the shard's plan family — the stacked
    bucket plan (``bucket_send``) or the sort shard body over the padded
    ``[D, Mp]`` message arrays — and
    the exchange term models the per-superstep label collective: every
    chip receives the other ``D-1`` chunks of the padded label vector —
    the same bytes whether they arrive as one all_gather (``replicated``)
    or ``D`` ppermute hops (``ring``), so one model serves both
    schedules.
    """
    a = anchors if anchors is not None else rooflines()
    d = int(sg.num_shards)
    gather = a["gather_slots_per_sec"]["v"]
    exch_rate = a["exchange_bytes_per_sec"]["v"]
    if weighted is None:  # infer; explicit False models weight-blind ops (CC)
        weighted = sg.msg_weight is not None or bool(sg.bucket_weight)
    wf = 2 if weighted else 1
    # NOTE: shard_graph_arrays(lpa_only=True) trims the sort-body arrays
    # (msg_send may be None on a bucketed partition) — each
    # family reads its padded slot count off its OWN arrays.
    if sg.bucket_send:
        family = "bucketed"
        mp = None
        padded = sum(
            int(b.shape[1]) * int(b.shape[2]) for b in sg.bucket_send
        )
        bytes_g = _I32 * padded * wf
        bytes_s = _I32 * int(sg.chunk_size)
        compute = (padded * wf) / gather
    else:
        family = "sort"
        mp = int(sg.msg_send.shape[1])       # padded slots per shard
        padded = mp
        bytes_g = _I32 * mp * wf
        bytes_s = _I32 * int(sg.chunk_size)
        compute = (mp * wf) / gather
    m_total = (
        int(num_messages) if num_messages is not None
        else (mp if mp is not None else padded) * d
    )
    m_chip = max(m_total // max(d, 1), 1)    # real slots per chip (mean)
    exchange_bytes = allgather_exchange_bytes(sg)
    exchange = exchange_bytes / exch_rate
    predicted = compute + exchange
    return CostEstimate(
        op=op, family=family, devices=d,
        slots=m_chip, padded_slots=padded,
        bytes_gathered=int(bytes_g), bytes_scattered=int(bytes_s),
        padding_overhead=padded / m_chip,
        exchange_bytes=int(exchange_bytes),
        compute_seconds=compute, exchange_seconds=exchange,
        predicted_seconds=predicted,
        predicted_per_chip=(
            num_edges / (predicted * d) if predicted > 0 else 0.0
        ),
        unit="edges/s/chip",
        roofline={
            k: a[k]
            for k in ("gather_slots_per_sec", "exchange_bytes_per_sec")
        },
    )


# ---- LOF impls -------------------------------------------------------------


def lof_cost(
    impl: str,
    n: int,
    k: int,
    features: int = 8,
    devices: int = 1,
    anchors: dict | None = None,
) -> CostEstimate:
    """Cost of one LOF scoring pass over an ``[n, features]`` cloud.

    - **exact**: all-pairs distances — n² pairs at the measured
      pair rate (the top-k roofline is folded into that anchor); the
      ring-sharded scorer splits the rows, so pairs scale 1/D.
    - **ivf**: the end-to-end measured points/s at crossover scale —
      the candidate-reduction structure (inverted lists, probe fans)
      is data-dependent, so the model anchors on throughput rather
      than pretending to know the candidate count; ``slots`` reports
      the k-neighborhood gathers the LOF formula itself performs.
    """
    a = anchors if anchors is not None else rooflines()
    n = int(n)
    d = max(int(devices), 1)
    if impl not in ("exact", "ivf"):
        raise ValueError(f"unknown LOF impl family {impl!r}")
    if impl == "exact":
        pairs = n * n // d
        compute = pairs / a["lof_exact_pairs_per_sec"]["v"]
        slots = pairs
        bytes_g = _I32 * features * pairs
        keys = ("lof_exact_pairs_per_sec",)
    else:
        compute = n / (a["lof_ivf_points_per_sec"]["v"] * d)
        slots = n * max(k, 1) // d
        bytes_g = _I32 * features * slots
        keys = ("lof_ivf_points_per_sec",)
    return CostEstimate(
        op="lof_knn", family=impl, devices=d,
        slots=slots, padded_slots=slots,
        bytes_gathered=int(bytes_g), bytes_scattered=_I32 * n,
        padding_overhead=1.0,
        exchange_bytes=0,
        compute_seconds=compute, exchange_seconds=0.0,
        predicted_seconds=compute,
        predicted_per_chip=n / (compute * d) if compute > 0 else 0.0,
        unit="points/s/chip",
        roofline={key: a[key] for key in keys},
    )


# ---- achieved-vs-model emission -------------------------------------------


def emit_superstep_timing(
    sink,
    op: str,
    cost: CostEstimate | None,
    iteration: int,
    window: int,
    seconds: float,
    num_edges: int,
    variant: str | None = None,
    cold_compile: bool = False,
) -> dict | None:
    """Emit one ``superstep_timing`` record: achieved wall throughput for
    a window of ``window`` supersteps ending at ``iteration``, judged
    against ``cost``'s model. No-op without a sink or cost (a caller
    that could not build an estimate must not emit a record claiming
    one). The achieved fraction is predicted-time / achieved-time for
    the window — >1 means the model is conservative, far below 1 is the
    triage signal (docs/RUNBOOKS.md §12). It is ``None`` on a device kind
    the model has no anchors for (:func:`anchored`): the seeds describe
    one kind of chip, and a CPU or an unmeasured part judged against
    them yields a number that means nothing. Timing comes from the caller's
    EXISTING superstep sync (the driver already blocks per superstep for
    the labels-changed counter) — this adds zero device syncs.

    ``cold_compile=True`` marks a window whose wall time includes an XLA
    trace+compile (the ops fixpoint seams detect it from the process's
    compile counter — :func:`timed_fixpoint`): the record still ships
    the honest numbers, but obs_report's roofline section excludes such
    windows from the below-model flag — a compile-bearing window reading 0.05x model on
    healthy hardware is exactly the false positive the flag must not
    raise. (The driver-side windows need no marker: like its watchdog,
    the driver excludes each operating point's compile-bearing first
    superstep from the window instead.)
    """
    if sink is None or cost is None:
        return None
    window = max(int(window), 1)
    seconds = float(seconds)
    per_step = seconds / window
    achieved = (
        num_edges * window / seconds / max(cost.devices, 1)
        if seconds > 0 else 0.0
    )
    fraction = None
    if anchored(_running_device_kind()):
        # significant digits, not decimal places: a 1e-6 fraction must
        # not round to a report-breaking 0.0
        fraction = _sig(
            cost.predicted_seconds / per_step if per_step > 0 else 0.0
        )
    return sink.emit(
        "superstep_timing",
        op=op,
        family=cost.family,
        variant=variant if variant is not None else cost.family,
        iteration=int(iteration),
        window=window,
        seconds=round(seconds, 6),
        edges_per_sec_per_chip=round(achieved),
        predicted_edges_per_sec_per_chip=round(cost.predicted_per_chip),
        achieved_fraction=fraction,
        devices=cost.devices,
        cold_compile=bool(cold_compile),
        cost=cost.record(),
    )


def emit_shard_exchange(sink, op: str, sg, **kv) -> dict | None:
    """Emit one ``shard_exchange`` record: the modeled per-chip ICI bytes
    of one sharded superstep. Every shard family exchanges by one tiled
    ``all_gather`` of the label vector (``4·Vc·(D-1)`` a chip), so
    ``exchange_bytes``, ``frontier_bytes`` and ``ladder_bytes`` are that
    one number and ``frontier_frac`` is 1.0; the keys stay for the
    record's readers. The record's single emission point, once per
    sharded repair apply on the serve path. No-op without a sink."""
    if sink is None:
        return None
    d = int(sg.num_shards)
    ladder = allgather_exchange_bytes(sg)
    return sink.emit(
        "shard_exchange",
        op=op,
        family="bucketed" if sg.bucket_send else "sort",
        devices=d,
        peers=max(d - 1, 0),
        exchange_bytes=ladder,
        frontier_bytes=ladder,
        ladder_bytes=ladder,
        frontier_frac=1.0,
        **kv,
    )


class WindowTimer:
    """Tiny accumulator for the driver's per-window wall timing: add each
    superstep's already-measured duration, emit at the telemetry cadence,
    reset on operating-point changes. Host-only; no device interaction."""

    def __init__(self):
        self.seconds = 0.0
        self.steps = 0

    def add(self, seconds: float) -> None:
        self.seconds += float(seconds)
        self.steps += 1

    def reset(self) -> None:
        self.seconds = 0.0
        self.steps = 0

    def flush(
        self, sink, op, cost, iteration, num_edges, variant=None
    ) -> dict | None:
        """Emit the window accumulated so far (if any) and reset."""
        if not self.steps:
            return None
        rec = emit_superstep_timing(
            sink, op, cost, iteration, self.steps, self.seconds,
            num_edges, variant=variant,
        )
        self.reset()
        return rec


# Backend compiles this process has seen: incremented by the one
# jax.monitoring listener (pipeline/metrics.py installs it when a sink is
# made), read by timed_fixpoint. A plain integer here so that obs/ stays
# jax-free; without the listener it never moves and no window is marked.
_backend_compiles = 0


def note_backend_compile() -> None:
    global _backend_compiles
    _backend_compiles += 1


def backend_compiles() -> int:
    return _backend_compiles


def timed_fixpoint(fn):
    """``(result, seconds, cold_compile)`` with the result's device work
    completed — shared by the ops-layer fixpoint wrappers (cc/pagerank/
    LPA auto seams) so a jitted while_loop's wall time covers the actual
    compute, not the dispatch. ``fn`` returns a jax array or a tuple
    whose first element is one; duck-typed so this module stays
    jax-free.

    ``cold_compile`` is True when the process compiled (or loaded from
    the persistent cache) any program during the call: the window paid
    for more than its supersteps, and the caller stamps that on the
    timing record so the roofline flag skips it."""
    before = backend_compiles()
    t0 = time.perf_counter()
    out = fn()
    head = out[0] if isinstance(out, tuple) else out
    block = getattr(head, "block_until_ready", None)
    if block is not None:
        block()
    seconds = time.perf_counter() - t0
    return out, seconds, backend_compiles() > before
