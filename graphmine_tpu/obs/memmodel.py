"""Analytical memory-plane model: what a plan SHOULD cost in HBM.

The compute plane has achieved-vs-model attribution (``obs/costmodel.py``,
r13) and the product has a quality plane (r14) — but memory, the resource
every degradation ladder actually trips on, was modeled blind: the
planner picked schedules against hand-seeded byte constants and nothing
ever measured whether a run's real HBM peak matched the plan. This
module is the memory plane's single owner (ISSUE 14), the direct
analogue of the cost model, in the same GraphBLAST / propagation-blocking
tradition (PAPERS arXiv 1908.01407, 2011.08451) where explicit workspace
budgets ARE the scaling argument:

1. **Per-plan footprint inventory** — for every superstep family (sort /
   bucketed, fused and sharded) and LOF impl, derive a named
   byte inventory **directly off the already-built plan/graph objects**:
   CSR arrays, bucketed width-ladder mats, sharded twins plus the
   per-superstep all_gather exchange
   buffer, LOF exact distance/top-k workspace vs IVF cluster-batched
   workspace, weighted payload doubling
   (:func:`superstep_footprint`, :func:`sharded_superstep_footprint`,
   :func:`lof_footprint`). With a plan the counts are exact (the plan's
   own matrix shapes); without one the estimate is anchored to the seed
   constants below, so the pre-build view can never disagree with the
   planner's accept/reject arithmetic.

2. **One inventory, two consumers** — the byte seeds below
   (:data:`BYTES_PER_EDGE` …) are THE constants
   ``pipeline/planner.py``'s schedule model is derived from
   (``estimate_bytes_per_device`` delegates to
   :func:`schedule_bytes_per_device`); the same seeds decompose into the
   :func:`schedule_inventory` components the ``plan`` record ships. A
   recalibration (obs_report's memory section suggests one when measured
   peaks drift from model) therefore moves the planner and the records
   together, never one without the other.

3. **Measured watermarks** — :func:`emit_memory_watermark` is the single
   builder of schema-registered ``memory_watermark`` records (predicted
   vs achieved bytes + ``headroom_frac``), fed by the driver's
   ``memory_stats()`` samples at the existing phase/rung/telemetry
   cadence (``memory_stats`` is a host-side allocator query — zero extra
   device syncs) with host RSS as the backend-less fallback. The ``mem``
   sub-record (:meth:`MemEstimate.record`) mirrors the ``cost``
   sub-record: one builder, all-or-nothing validation
   (``obs.schema.MEM_KEYS``), ``tools/schema_lint.py`` flags inline
   ``mem={...}`` literals anywhere else.

The model is deliberately coarse — a per-phase budget, not an allocator
simulator. Its job is triage leverage: a rung whose predicted footprint
exceeds budget pre-degrades at plan time with the inventory in the
record (:func:`predegrade_superstep`), and a reactive OOM's degrade
record carries the last watermark + inventory so model-miss vs
fragmentation is triageable from the JSONL alone (docs/RUNBOOKS.md §14).

Import discipline: **stdlib only** — no jax, no numpy. Plan objects are
inspected by duck-typed attributes/shapes so this module loads on a
machine with no accelerator stack at all (the ``obs/`` contract).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from math import sqrt

from graphmine_tpu.obs.costmodel import (
    _bucketed_padded_slots,
    _plan_family,
    _plan_weighted,
)

_I32 = 4  # bytes per int32/float32 slot — the one word size

# ---- byte seeds (single owner) ---------------------------------------------
#
# The DESIGN.md-measured schedule model, decomposed: ``36 B/edge`` on the
# fused path (edge endpoints + message CSR + plan mats + gather
# transient), ``16 B/edge`` more when weighted (msg weights + slot-
# aligned weight mats), and the per-vertex label/exchange terms of each
# schedule. ``pipeline/planner.py`` derives its ``_BYTES_PER_*``
# constants FROM these — edit here, both consumers move.
#
# Checked, not retuned, on the graph that fills one chip (graph500-24,
# E = 260,376,136; TPU v5e, PERF.md §6, PR 33). Without the carried rows:
# the arrays are 33.8 B/edge (endpoints 8.0 + message CSR 16.3 + plan
# 9.5) and the allocator's peak 36.8 B/edge (9.57 GB: the arrays, labels,
# 0.65 GB of program code). The compiled scan's temporaries (22.4 B/edge,
# 5.83 GB) come on top: `peak_bytes_in_use` does not count them
# (`peak_bytes_reserved` does) though they are taken from the same memory:
# the seed's 6 B/edge of "gather transient" is a quarter of them. With the
# carried rows and their slot index the arrays are 51.4 B/edge (+ 9.3 rows
# + 8.3 index); since PR 36 the rows are held once, each program of the
# job updating them in place, and the largest program's temporaries are
# 6.7 B/edge (1.74 GB: `carried_rows_inventory` below), where the one
# compiled scan of PRs 32-35 took 37.6 (9.80 GB) and did not fit.
BYTES_PER_EDGE = 36.0
BYTES_PER_EDGE_WEIGHTED = 16.0
SINGLE_BYTES_PER_VERTEX = 8.0
REPLICATED_BYTES_PER_VERTEX = 16.0
RING_BYTES_PER_VERTEX = 24.0  # divided by D (labels are sharded)

# IVF cluster-batch balance pad (model seed): real Qmax/Lmax are
# data-dependent cluster sizes; the model assumes balanced clusters of
# n/C padded by this factor (k-means imbalance at the measured scales —
# ops/ann.py pads to the true max).
IVF_BALANCE_PAD = 2.0

# THE superstep family degrade order (one statement; the planner's
# ladder and SuperstepPlan.degrade_to and the plan-time pre-degrade below
# all read it): bucketed drops its padded plan matrices for the sort
# superstep; sort is the floor (None: nothing leaner exists). It lives
# here because this module stays jax-free.
FAMILY_DEGRADE = {"bucketed": "sort", "sort": None}


@dataclass(frozen=True)
class MemEstimate:
    """Predicted peak HBM footprint for one operating point, as a named
    per-device byte inventory. ``exact=True`` when the counts were read
    off a built plan's real matrix shapes; False for pre-build estimates
    (the ~10% ladder pad) and structural model seeds (IVF batches)."""

    op: str
    family: str          # superstep family / LOF impl / schedule name
    devices: int
    weighted: bool
    inventory: dict      # component -> bytes per device
    exact: bool
    unit: str = "bytes/device"

    @property
    def total_bytes(self) -> int:
        return int(sum(self.inventory.values()))

    def record(self) -> dict:
        """The ``mem`` sub-record (shape registered as
        ``obs.schema.MEM_KEYS`` — a half-stamped copy fails validation
        like a half-stamped cost record). This method is the SINGLE
        builder: ``tools/schema_lint.py`` flags inline ``mem={...}``
        literals anywhere else in the package."""
        return {
            "family": self.family,
            "devices": self.devices,
            "weighted": self.weighted,
            "total_bytes": self.total_bytes,
            "inventory": {
                k: int(v) for k, v in sorted(self.inventory.items())
            },
            "exact": self.exact,
            "unit": self.unit,
        }


# ---- schedule model (the planner's consumer) -------------------------------


def schedule_bytes_per_device(
    schedule: str,
    num_vertices: int,
    num_edges: int,
    num_devices: int,
    weighted: bool = False,
) -> int:
    """Modeled peak HBM per device for a whole-run ``schedule`` — the
    EXACT arithmetic ``pipeline/planner.py`` has always planned against
    (one ``int()`` over the float sum, so the planner's accept/reject
    decisions are bit-identical to the pre-ISSUE-14 constants)."""
    v = float(num_vertices)
    e = float(num_edges)
    d = float(max(num_devices, 1))
    edge = BYTES_PER_EDGE + (BYTES_PER_EDGE_WEIGHTED if weighted else 0.0)
    if schedule == "single":
        return int(edge * e + SINGLE_BYTES_PER_VERTEX * v)
    if schedule == "replicated":
        return int(edge * e / d + REPLICATED_BYTES_PER_VERTEX * v)
    if schedule == "ring":
        return int(edge * e / d + RING_BYTES_PER_VERTEX * v / d)
    raise ValueError(f"unknown schedule {schedule!r}")


def schedule_inventory(
    schedule: str,
    num_vertices: int,
    num_edges: int,
    num_devices: int = 1,
    weighted: bool = False,
) -> dict:
    """The seed constants decomposed into named components (per device).
    Component sums reproduce :func:`schedule_bytes_per_device` to within
    per-term rounding: 36 B/edge = endpoints 8 + CSR 16 + plan mats 6 +
    gather transient 6; weighted adds msg weights 8 + weight mats 8; the
    per-vertex terms are each schedule's label/exchange model."""
    v = float(num_vertices)
    e = float(num_edges)
    d = float(max(num_devices, 1))
    div = 1.0 if schedule == "single" else d
    inv = {
        "edge_endpoints": 8.0 * e / div,
        "message_csr": 16.0 * e / div,
        "plan_mats": 6.0 * e / div,
        "gather_transient": 6.0 * e / div,
    }
    if weighted:
        inv["msg_weights"] = 8.0 * e / div
        inv["weight_mats"] = 8.0 * e / div
    if schedule == "single":
        inv["labels"] = 8.0 * v
    elif schedule == "replicated":
        inv["labels_replicated"] = 8.0 * v
        inv["exchange_buffer"] = 8.0 * v
    elif schedule == "ring":
        inv["labels_sharded"] = 8.0 * v / d
        inv["ring_chunks"] = 16.0 * v / d
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    return {k: int(b) for k, b in inv.items()}


def schedule_footprint(
    schedule: str,
    num_vertices: int,
    num_edges: int,
    num_devices: int = 1,
    weighted: bool = False,
    op: str = "run_plan",
) -> MemEstimate:
    """The whole-run schedule model as a :class:`MemEstimate` — what the
    driver's ``plan`` record ships alongside the planner's verdict."""
    return MemEstimate(
        op=op, family=schedule, devices=max(int(num_devices), 1),
        weighted=bool(weighted),
        inventory=schedule_inventory(
            schedule, num_vertices, num_edges, num_devices, weighted
        ),
        exact=False,
    )


# ---- fused superstep families ----------------------------------------------


def superstep_footprint(
    op: str,
    family: str,
    num_vertices: int,
    num_messages: int,
    num_edges: int | None = None,
    plan=None,
    weighted: bool | None = None,
) -> MemEstimate:
    """Footprint of ONE fused (single-device) superstep operating point.

    With a built ``plan`` the counts are EXACT — the plan's own matrix
    shapes: edge endpoints + the message CSR + labels in/out + msg
    weights, plus the width-ladder mats + vertex ids + the hubs' row
    offsets (+ slot-aligned weight mats) + the gathered transient
    (bucketed). A plan with its slot index adds what the carried-rows
    job of ``ops/lpa.py`` holds on the device
    (:func:`carried_rows_inventory`: the rows, once, and the slot index by
    sender, exact; the hubs' histograms and the temporaries of the full
    gather, as the chip's compiler counts them; the rewrite's, which
    depend on the policy's top rung, are the admission's to add). The
    plan's own terms (``plan_mats``,
    ``plan_vertex_ids``, ``plan_hub_offsets``, ``weight_mats``,
    ``slot_index``) sum to the ``nbytes`` of the plan's arrays to the
    byte.

    WITHOUT a plan (the driver's plan-time pre-degrade fires before any
    build) the estimate is anchored to the SAME seed constants the
    planner accepted the run with — the fused bucketed path IS the
    measured ``BYTES_PER_EDGE`` model, so ``bucketed`` reproduces
    :func:`schedule_inventory`'s single-device decomposition exactly
    (the two consumers can never disagree about the path the planner
    just admitted, so an admitted run never spuriously pre-degrades)
    and ``sort`` drops the plan-mats term (the planner's documented
    degradation saving).
    """
    if plan is not None:
        family = _plan_family(plan)
        if weighted is None:
            weighted = _plan_weighted(plan)
    weighted = bool(weighted)
    v = int(num_vertices)
    m = max(int(num_messages), 1)
    e = int(num_edges) if num_edges is not None else m // 2
    if family not in FAMILY_DEGRADE:
        raise ValueError(f"unknown superstep family {family!r}")
    if plan is None:
        # Seed-anchored estimates (see docstring): the bucketed path is
        # the measured schedule model verbatim, so an admitted run can
        # never pre-degrade off the family the planner just accepted.
        inv = schedule_inventory("single", v, e, 1, weighted)
        if family == "sort":
            del inv["plan_mats"]
        return MemEstimate(
            op=op, family=family, devices=1, weighted=weighted,
            inventory=inv, exact=False,
        )
    inv = {
        "edge_endpoints": 2 * _I32 * e,
        "message_csr": _I32 * (2 * m + v + 1),
        "labels": 2 * _I32 * v,
    }
    if weighted:
        inv["msg_weights"] = _I32 * m
    if family == "sort":
        inv["gather_transient"] = _I32 * m * (2 if weighted else 1)
    else:
        padded = _bucketed_padded_slots(plan)
        ids = sum(int(x.shape[0]) for x in (plan.vertex_ids or ()))
        if plan.hist_vertex_ids is not None:
            ids += int(plan.hist_vertex_ids.shape[0])
        inv["plan_mats"] = _I32 * padded
        inv["plan_vertex_ids"] = _I32 * ids
        if plan.hist_row_offset is not None:
            inv["plan_hub_offsets"] = _I32 * int(plan.hist_row_offset.shape[0])
        if weighted:
            inv["weight_mats"] = _I32 * padded
        if getattr(plan, "out_slot", None) is not None:
            inv.update(carried_rows_inventory(plan))
        else:
            inv["gather_transient"] = _I32 * padded
    return MemEstimate(
        op=op, family=family, devices=1, weighted=weighted,
        inventory=inv, exact=True,
    )


def _slots(mat) -> int:
    return int(mat.shape[0]) * int(mat.shape[1])


# What a program of the carried-rows job holds beside its arguments while it
# runs, as the chip's compiler assigned it (`memory_analysis()` of each
# program compiled alone for a described v5e, from shapes: PERF.md §6, PR 36
# and PR 38: graph500-24's and graph500-22's plans, GAP Urand's at scale 24,
# two planted graphs). The rows themselves are in none of it: they are each
# updating program's donated argument, aliased to its result, held once.
#
# The classes take turns (the compiler schedules one class's work after
# another's, on a flat plan as on a skewed one), so a program's temporaries
# are its largest class's. What a class takes is set by how the chip lays an
# int32 `[n, w]` out, in tiles of 8 x 128: column-major (`w` up to 8, `n` up
# to 128: what the compiler picks for a narrow class, the plan's matrices
# among them) or row-major (`n` up to 8, `w` up to 128 LANES: 3.9 times the
# matrix at w = 33). The flat rows are row-major, so a narrow class passes
# through the lane-padded form on its way in (the gather) and out (the
# modes): that, not the class count, is why a plan of 28 like-sized narrow
# classes took 1.10 x its rows where graph500-24's took 0.46 x.
#   * the gather: the class's indices brought in range, as the plan holds
#     them, and the row-major form they are flattened from;
#   * the modes: the same two forms on the way out, then the reduce: the
#     pairwise count's matrix in and out; the row sort's key and the iota
#     the compiler adds to keep it stable, in and out (four; three where
#     the rows' slice is the matrix the sort reads: row-major, whole lanes);
#   * both: the labels, padded, in two memory spaces at once;
#   * not counted, because `lpa_modes_from_rows` bars it: a class that
#     starts at a multiple of its width `w` in rows whose length is one too
#     would be cut from a `[S / w, w]` view of ALL the rows, tiled to 128
#     lanes (92.6 GB for graph500-24's plan at w = 3; PERF.md §6, PR 42);
#   * the rewrite's compaction: a four-operand sort of V keys, in and out;
#   * the rewrite's expansion: five cap-long vectors (the scattered
#     differences and their running sums for source and value, the slot),
#     after the sort's operands are dead. A marked rewrite (PR 43: the
#     lowest rung's, which also says which rows it wrote to) holds as many
#     again for the rows' numbers and their two sorts; at a rung of M / 4096
#     that is a three-hundredth of what the top rung's five take, so it is
#     never the largest and has no term (graph500-24: 538,084,864 B marked
#     against the sort's 536,870,912 B; 554,551,808 B at M / 256);
#   * the dirty reduce (PR 43: `lpa_modes_from_dirty_rows`): ten V-long
#     vectors (the labels in two memory spaces, the loops' carry, the new
#     labels, and the count of K: the changed mask, the out-degree's two
#     slices and their difference, the select), which are all it holds at a
#     small V (3,644,928 B at 2^16 vertices); the classes' vertex ids laid
#     end to end; and one trip's rows: the pairwise count's three forms of
#     `[64, 32, 32]`, and the widest coarse width's `[8, W]` through the
#     sort and the run scan (eight forms). Its loops take turns and a trip
#     holds one group's rows, never the flat rows. At 2^24 vertices the
#     compiler holds fewer of the V-vectors at once (graph500-24:
#     677,249,536 B with the hubs' histograms' 536,870,912 B in it; GAP
#     Urand: none): an over-count, under the top rung's rewrite wherever a
#     vertex sends nine messages or more.
_TILE = (8, 128)
_PAIRWISE_MAX_W = 32  # ops/bucketed_mode.py's (this module imports no jax): wider classes are sorted
_REWRITE_SORT_WORDS = 8
_REWRITE_CAP_WORDS = 5
_MESH_CLASSES_AT_ONCE = 3  # carried_job_transients: the mesh `modes` program's classes overlap
_DIRTY_V_WORDS = 10
# the BFS level's V-vectors beside its largest class: the depths in two
# memory spaces and out, the hubs' write back, and the count of K (the
# changed mask, the out-degree's two slices and their difference, the select)
_ROW_MIN_V_WORDS = 8
# the bottom-up level (ISSUE 53: a loop over chunks of places, which reads the
# graph's message CSR). V-long: the spans' running end, first place and offset
# into `msg_send`, the depths out, and the count of K as the row min's. A trip's
# chunk-long vectors: the scattered differences and their running sums for
# source and vertex, the neighbour, its depth, and the spans cut out of the
# lists (a quarter of a chunk of each). graph500-24's shapes compiled for a described
# v5e at a chunk of 2^19: 202,003,968 B, 2.9 words a vertex beside six a
# place of the chunk (6,347,776 B at 2^16 vertices, where the lists are short).
_BOTTOM_UP_V_WORDS = 4
_BOTTOM_UP_CHUNK_WORDS = 8
_DIRTY_TRIP_ROWS = 8  # ops/bucketed_mode.py's _DIRTY_GROUP_ROWS: the rows a trip of the dirty reduce sorts
_DIRTY_TRIP_FORMS = 8
_DIRTY_PAIRWISE_SLOTS = 3 * 2048 * _PAIRWISE_MAX_W  # three forms of [2048 / 32, 32, 32]


def _tiled(n: int, w: int) -> tuple[int, int]:
    """Bytes of an int32 ``[n, w]`` on the chip, ``(as the compiler lays
    it out, row-major)``: the smaller of the two tiled layouts, and the
    one the flat rows reshape to. A single column is a vector."""
    up = lambda x, tile: -(-x // tile) * tile
    if w == 1:
        return (_I32 * up(n, 1024),) * 2
    col = _I32 * up(w, _TILE[0]) * up(n, _TILE[1])
    row = _I32 * up(n, _TILE[0]) * up(w, _TILE[1])
    return min(col, row), row


def carried_job_transients(
    plan, top_rung: int = 0, shards: int = 1, reduce: str = "mode",
    bottom_up_chunk: int = 0,
) -> dict:
    """Bytes of temporaries by program of the carried-rows job, from the
    plan's shapes (the note above): ``gather``, ``modes``, ``rewrite`` at
    ``top_rung`` messages (0 without a rung) and, where the one-chip job
    has one, ``dirty_modes``. ``reduce="min"`` is the BFS job
    (``ops/paths.py``), whose reduce is ``row_min`` and which has no dirty
    reduce: ``gather``, ``row_min``, ``rewrite`` and ``bottom_up``, the
    level that reads no row (ISSUE 53), whose loop takes
    ``bottom_up_chunk`` places a trip whatever the level's size: a trip's
    chunk-long vectors (:data:`_BOTTOM_UP_CHUNK_WORDS`) beside the level's
    V-vectors (:data:`_BOTTOM_UP_V_WORDS`); its compaction is the rewrite's
    sort less an operand and never the largest. A row's min reads the
    class's rows in the row-major form the flat rows reshape to and writes
    a vector; it is counted with the compiler's own form beside it, as the
    gather is, and with the V-vectors of the level
    (:data:`_ROW_MIN_V_WORDS`). The hubs' histograms are
    the inventory's own term. A weighted plan's reduce holds more (the
    weights ride through the sort) and no compile holds its count: at
    2^16 vertices the compiler kept one class's ``[n, w, w]`` pairwise
    products whole, in fast memory, which it cannot at a size that
    matters.

    ``shards`` > 1: ``plan`` is one shard's, by shapes, and the programs
    are the mesh job's (``parallel/sharded.py``). The gather and the
    rewrite are the one-chip programs on a shard's slice and hold what
    those hold (compiled for four described chips from graph500-25's
    partition: 628,905,984 B against 629,164,032 B for one chip over the
    very same shapes; PERF.md §6, PR 39). The classes of ``modes`` do not
    quite take turns there (a program with a collective is scheduled to
    hide its latency, not for the least memory): it held what its two
    largest classes take, at once, from graph500-25's partition (to
    0.1 %), and 2 % more than that from graph500-22's, where the one-chip
    program held one class. So the mesh ``modes`` is counted at its THREE
    largest classes."""
    v = int(plan.num_vertices)
    weighted = _plan_weighted(plan)
    gather, modes = [], []
    for idx in plan.send_idx or ():
        n, w = int(idx.shape[0]), int(idx.shape[1])
        kept, row = _tiled(n, w)
        gather.append(kept + row)
        sort_in_place = kept == row and w % _TILE[1] == 0
        if weighted:
            # labels, weights, scores and mask; the sort's key, weight and
            # iota, in and out, and the segmented sum's pair
            sorting = (4 if w <= _PAIRWISE_MAX_W else 8) * kept
        elif w <= _PAIRWISE_MAX_W:
            sorting = 2 * kept
        else:
            sorting = (3 if sort_in_place else 4) * kept
        modes.append(max(kept + row, sorting))
    at_once = _MESH_CLASSES_AT_ONCE if shards > 1 else 1
    labels = 2 * _I32 * (v + 1)
    rewrite = max(
        _REWRITE_SORT_WORDS * _I32 * v,
        _REWRITE_CAP_WORDS * _I32 * int(top_rung),
    ) if top_rung else 0
    if reduce == "min":
        level = _ROW_MIN_V_WORDS * _I32 * (v + 1)
        return {
            "gather": max(gather, default=0) + labels,
            "row_min": max(gather, default=0) + level,
            "rewrite": rewrite,
            "bottom_up": _I32 * (
                _BOTTOM_UP_CHUNK_WORDS * int(bottom_up_chunk)
                + _BOTTOM_UP_V_WORDS * (v + 1)
            ) if bottom_up_chunk else 0,
        }
    by_program = {
        "gather": max(gather, default=0) + labels,
        "modes": sum(sorted(modes, reverse=True)[:at_once]) + labels,
        "rewrite": rewrite,
    }
    if top_rung and shards == 1 and not weighted:
        # the one-chip job's second reduce, which runs after a marked
        # rewrite (no rung, no rewrite; the mesh job and a weighted plan's
        # keep the full reduce)
        shapes = [(int(i.shape[0]), int(i.shape[1])) for i in plan.send_idx or ()]
        widest = max((w for _, w in shapes), default=1)
        coarse = max(_PAIRWISE_MAX_W, 1 << (widest - 1).bit_length())
        by_program["dirty_modes"] = _I32 * (
            _DIRTY_V_WORDS * (v + 1) + sum(n for n, _ in shapes)
            + _DIRTY_PAIRWISE_SLOTS + _DIRTY_TRIP_FORMS * _DIRTY_TRIP_ROWS * coarse
        )
    return by_program


def row_sum_transients(plan) -> int:
    """Bytes of temporaries of one PageRank iteration over the fused
    ``plan`` (``ops/pagerank.py:_bucketed_iteration``, the program the
    message reading steps from the host), from the plan's shapes. Compiled
    alone the classes take turns, as the carried-rows job's do, so the
    program holds its largest class: the class's indices brought in range
    as the plan holds them and the gathered float32 rows in the row-major
    form the sum reads, beside the padded contributions in two memory
    spaces (:func:`carried_job_transients`'s count of the gather, which
    holds the same); and the V-vectors of the update (the contributions, the inflow, the rank
    out, the dangling select, the delta's difference). The same body
    inside a ``while_loop`` holds EVERY class's two forms at once
    (5,256,151,040 B at graph500-24's shapes, 11,005,842,432 B at GAP
    Urand's at scale 24, to 0.5 % the sum over the classes there; PERF.md
    §6, PR 41), which is why the job steps from the host. At or above the
    compiler's own count on a skewed and on a flat plan
    (``tests/test_chip_compile.py``)."""
    return carried_job_transients(plan)["gather"] + 5 * _I32 * int(plan.num_vertices)


def carried_rows_inventory(
    plan, top_rung: int = 0, shards: int = 1, reduce: str = "mode",
    bottom_up_chunk: int = 0,
) -> dict:
    """What the carried-rows job of ``ops/lpa.py`` holds on the device
    beyond a fused ``plan``, known from the plan's shapes before the index
    is built. Exact: ``carried_rows``, the classes' rows end to end, ONCE
    (every program that updates them does so in place; held by
    ``tests/test_chip_compile.py``); ``slot_index``, one slot per message
    and ``V + 1`` offsets by sender; ``labels`` in and out and the
    ``changed_mask``. Counted as the chip's compiler holds them, from the
    shapes and the chip's tiling and from no per-graph constant
    (:func:`carried_job_transients`; held to the compiler's own count on a
    skewed and on a flat plan by ``tests/test_chip_compile.py``):
    ``hub_histograms``, the hubs' ``[n, V]`` counts and the scatter's copy
    of them, and ``gather_transient``, the other temporaries of the job's
    largest program: the full gather, the row modes, or the rewrite at
    ``top_rung`` messages, the job's largest rung (its sort of V keys,
    then its cap-long vectors). On a plan of narrow classes the gather
    and the modes are the largest whatever the rung (GAP Urand at scale
    24: 2.49 and 2.38 GB against the top rung's 1.79). The sum holds the
    histograms beside the largest program's temporaries though the modes
    program holds them after its rows: an over-count on a graph with
    hubs. Program code is device memory too and is in no term (0.75 GB
    for graph500-24's programs, 0.2 GB for Urand's). The admission of
    ``ops/superstep_policy.admit_carried_rows`` holds the sum against the
    device's free memory. ``shards`` > 1: ``plan`` is one shard's, by
    shapes, of the mesh job: the terms are then ONE chip's (its rows, its
    index by global sender over the largest shard's messages, the padded
    label vector, replicated, twice), the programs the mesh job's
    (:func:`carried_job_transients`). ``reduce="min"`` is the BFS job's
    (``ops/paths.py``): the same rows and index, depths for labels, its own
    programs (the bottom-up level at ``bottom_up_chunk`` places a trip among them)
    and no histogram (its hubs take a ``segment_min`` over their senders'
    depths)."""
    v = int(plan.num_vertices)
    classes = [_slots(x) for x in plan.send_idx or ()]
    hubs = (
        0 if reduce == "min" or plan.hist_vertex_ids is None
        else int(plan.hist_vertex_ids.shape[0])
    )
    return {
        "carried_rows": _I32 * sum(classes),
        "slot_index": _I32 * (int(plan.num_messages) + v + 1),
        "labels": 2 * _I32 * v,
        "changed_mask": v,
        "hub_histograms": 2 * _I32 * hubs * v,
        "gather_transient": max(
            carried_job_transients(
                plan, top_rung, shards, reduce, bottom_up_chunk
            ).values()
        ),
    }


# ---- sharded supersteps ----------------------------------------------------


def _per_chip_bytes(arr) -> int:
    """Per-chip bytes of one stacked ``[D, ...]`` shard array."""
    n = 1
    for s in arr.shape[1:]:
        n *= int(s)
    return _I32 * n


def sharded_superstep_footprint(
    op: str,
    sg,
    weighted: bool | None = None,
    schedule: str = "replicated",
) -> MemEstimate:
    """Per-chip footprint of ONE sharded superstep, derived from a built
    ``ShardedGraph`` (shapes only — no device sync, no jax import; the
    ``sharded_superstep_cost`` contract).

    The shard arrays are counted at their REAL stacked shapes (the
    sharded twins of the fused inventory, padding included); the label
    terms follow ``schedule``: ``replicated`` holds the full label
    vector + updated copy plus the per-superstep all_gather exchange
    buffer, ``ring`` keeps labels sharded with two rotating ppermute
    chunks + staging (no replicated V-term at all — exactly why it is
    the planner's memory floor)."""
    d = int(sg.num_shards)
    vc = int(sg.chunk_size)
    v = int(sg.num_vertices)
    if weighted is None:
        weighted = sg.msg_weight is not None or bool(sg.bucket_weight)
    weighted = bool(weighted)
    # NOTE: shard_graph_arrays(lpa_only=True) trims the sort-body CSR
    # (msg_recv_local/msg_send/degrees may all be None on a bucketed
    # partition) — count only the arrays that exist, exactly like
    # sharded_superstep_cost.
    inv: dict = {}
    if sg.degrees is not None:
        inv["degrees"] = _per_chip_bytes(sg.degrees)
    msgs = 0
    if sg.msg_recv_local is not None:
        msgs += _per_chip_bytes(sg.msg_recv_local)
    if sg.msg_send is not None:
        msgs += _per_chip_bytes(sg.msg_send)
    if msgs:
        inv["shard_messages"] = msgs
    if sg.msg_weight is not None:
        inv["msg_weights"] = _per_chip_bytes(sg.msg_weight)
    if sg.bucket_send:
        family = "bucketed"
        mats = sum(_per_chip_bytes(b) for b in sg.bucket_send)
        inv["plan_mats"] = mats
        inv["plan_vertex_ids"] = sum(
            _per_chip_bytes(t) for t in sg.bucket_target
        )
        if sg.bucket_weight:
            inv["weight_mats"] = sum(
                _per_chip_bytes(w) for w in sg.bucket_weight
            )
        inv["gather_transient"] = mats
    else:
        family = "sort"
        inv["gather_transient"] = msgs // (2 if sg.msg_send is not None
                                           and sg.msg_recv_local is not None
                                           else 1)
    if schedule == "ring":
        inv["labels_sharded"] = 2 * _I32 * vc
        inv["ring_chunks"] = 2 * _I32 * vc
        inv["exchange_staging"] = 2 * _I32 * vc
    else:
        inv["labels_replicated"] = 2 * _I32 * v
        inv["exchange_buffer"] = 2 * _I32 * vc * d
    return MemEstimate(
        op=op, family=family, devices=d, weighted=weighted,
        inventory=inv, exact=True,
    )


# ---- LOF impls -------------------------------------------------------------


def ivf_model_clusters(n: int) -> int:
    """Mirror of ``ops/ann.default_n_clusters`` (~sqrt(N), rounded to a
    multiple of 8, min 8) — duplicated here as a model seed because the
    ops layer imports jax and this module must not."""
    return max(8, int(round(sqrt(max(int(n), 1)) / 8)) * 8)


def lof_footprint(
    impl: str,
    n: int,
    k: int,
    features: int = 8,
    devices: int = 1,
) -> MemEstimate:
    """Workspace footprint of one LOF scoring pass over ``[n, features]``.

    - **exact**: the ``[rows, n]`` all-pairs distance tile (the
      ring-sharded scorer splits the rows 1/D) + the top-k
      distance/index workspace.
    - **ivf**: centers + assignments + ONE cluster-batched search block
      (query block, distance block, per-batch top-k) under the balanced-
      cluster model (``n/C`` padded by :data:`IVF_BALANCE_PAD`); the
      real Qmax/Lmax are data-dependent, which is exactly why the
      measured watermark rides next to this estimate.
    """
    n = int(n)
    k = max(int(k), 1)
    f = int(features)
    d = max(int(devices), 1)
    if impl not in ("exact", "ivf"):
        raise ValueError(f"unknown LOF impl family {impl!r}")
    inv: dict = {"features": _I32 * n * f, "scores": _I32 * n}
    if impl == "exact":
        rows = -(-n // d)
        inv["distance_tile"] = _I32 * rows * n
        inv["topk_workspace"] = 2 * _I32 * rows * k
    else:
        c = ivf_model_clusters(n)
        b = int(IVF_BALANCE_PAD * n / c) + 1
        inv["centers"] = _I32 * c * f
        inv["assignments"] = 2 * _I32 * n
        inv["cluster_batch"] = _I32 * (b * f + b * b + 2 * b * k)
    return MemEstimate(
        op="lof_knn", family=impl, devices=d, weighted=False,
        inventory=inv, exact=False,
    )


# ---- plan-time pre-degrade -------------------------------------------------


def predegrade_superstep(
    family: str,
    num_vertices: int,
    num_messages: int,
    num_edges: int,
    weighted: bool,
    budget_bytes: int,
):
    """Walk the family ladder at PLAN time until the modeled footprint
    fits ``budget_bytes`` — the proactive twin of the driver's reactive
    OOM rungs: a rung the model already knows cannot fit is consumed
    before any device allocation, with the oversized inventory in the
    degrade record instead of an XLA OOM minutes later.

    Returns ``(family, fit_estimate, steps)`` where ``steps`` is the
    ``(from_family, to_family, oversized_estimate)`` descent trail
    (empty = the requested family fits). The sort floor is returned
    even when it does not fit: there is nothing leaner, and the
    planner's schedule model already accepted the run — the reactive
    ladder (and the watermark trail) owns whatever happens next."""
    budget = int(budget_bytes)
    steps = []
    while True:
        est = superstep_footprint(
            "lpa_superstep", family, num_vertices, num_messages,
            num_edges=num_edges, weighted=weighted,
        )
        nxt = FAMILY_DEGRADE[family]
        if est.total_bytes <= budget or nxt is None:
            return family, est, steps
        steps.append((family, nxt, est))
        family = nxt


# ---- measured watermarks ---------------------------------------------------


def rss_sample() -> dict | None:
    """Host-RSS fallback measurement for backends whose allocator does
    not report ``memory_stats()`` (the CPU backend) — the watermark
    then says so (``source: "rss"``) instead
    of silently comparing device model against nothing."""
    from graphmine_tpu.obs.heartbeat import rss_mb

    rss = rss_mb()
    if rss is None:
        return None
    b = int(rss * (1 << 20))
    return {"bytes_in_use": b, "peak_bytes_in_use": b, "source": "rss"}


def emit_memory_watermark(
    sink,
    op: str,
    est: MemEstimate | None,
    measured: dict | None,
    budget_bytes: int | None = None,
    **kv,
) -> dict | None:
    """Emit one ``memory_watermark`` record: the operating point's
    predicted footprint next to the measured bytes (device allocator or
    RSS fallback), plus ``headroom_frac`` against the planning budget.
    No-op without a sink, estimate or measurement (a record claiming a
    comparison neither side made would poison the waterfall). This is
    the record's single emission point — the schema-registered shape and
    the ``mem`` sub-record builder live together.

    ``achieved_bytes`` is the CURRENT ``bytes_in_use`` at the sampling
    boundary — the phase-attributable number the waterfall and the
    recalibration suggestion compare against this phase's model.
    ``peak_bytes_in_use`` is a process-LIFETIME allocator high-water
    mark (no portable reset exists), so it rides the record as context
    and drives ``headroom_frac`` (how close the PROCESS ever came to the
    budget — the conservative OOM-forecast number), but is never
    attributed to the phase that happened to sample it."""
    if sink is None or est is None or not measured:
        return None
    achieved = measured.get("bytes_in_use")
    if achieved is None:
        achieved = measured.get("peak_bytes_in_use")
    if achieved is None:
        return None
    achieved = int(achieved)
    headroom = None
    # headroom is only meaningful when the measurement and the budget
    # live in the same domain: a host-RSS fallback judged against a
    # per-device HBM budget would print a confident nonsense fraction
    # (and trip low-headroom rules on zero device pressure).
    if budget_bytes and measured.get("source", "device") == "device":
        worst = int(measured.get("peak_bytes_in_use") or achieved)
        headroom = round((int(budget_bytes) - worst) / int(budget_bytes), 4)
    rec = dict(
        op=op,
        predicted_bytes=est.total_bytes,
        achieved_bytes=achieved,
        headroom_frac=headroom,
        source=measured.get("source", "device"),
        mem=est.record(),
        **kv,
    )
    if budget_bytes:
        rec["budget_bytes"] = int(budget_bytes)
    for opt in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if measured.get(opt) is not None:
            rec[opt] = int(measured[opt])
    return sink.emit("memory_watermark", **rec)


# ---- serve-process accounting ---------------------------------------------


def serve_mem_budget_bytes() -> int | None:
    """The serve-process memory budget headroom is judged against:
    ``GRAPHMINE_SERVE_MEM_BUDGET_BYTES`` (malformed raises loudly — the
    AdmissionBounds discipline) falling back to host ``MemTotal``
    (/proc/meminfo), None where neither exists."""
    raw = os.environ.get("GRAPHMINE_SERVE_MEM_BUDGET_BYTES")
    if raw:
        try:
            return int(float(raw))
        except ValueError as e:
            raise ValueError(
                f"GRAPHMINE_SERVE_MEM_BUDGET_BYTES={raw!r} is not a number"
            ) from e
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


# The graphmine_memory_* gauge surface — ONE owner for the metric names
# and help strings (server /metrics, the fleet router, and the WAL's
# segment accounting all export from this table; registry.gauge is
# get-or-create with first-help-wins, so duplicated literals would
# silently diverge).
MEMORY_GAUGE_HELP = {
    "graphmine_memory_rss_bytes":
        "resident set size of this serve process",
    "graphmine_memory_snapshot_bytes":
        "array bytes of the snapshot currently serving queries",
    "graphmine_memory_index_bytes":
        "derived query-index bytes (adjacency, census, explain)",
    "graphmine_memory_wal_segment_bytes":
        "bytes held by retained write-ahead-log segments",
    "graphmine_memory_headroom_frac":
        "fraction of the process memory budget still free",
}

_GAUGE_OF_KEY = {
    "rss_bytes": "graphmine_memory_rss_bytes",
    "snapshot_bytes": "graphmine_memory_snapshot_bytes",
    "index_bytes": "graphmine_memory_index_bytes",
    "wal_segment_bytes": "graphmine_memory_wal_segment_bytes",
    "headroom_frac": "graphmine_memory_headroom_frac",
}


def export_memory_gauges(registry, payload: dict) -> None:
    """Mirror a memory payload's present keys into the
    ``graphmine_memory_*`` gauges (absent/None keys leave their gauge
    untouched — a router payload has no snapshot bytes to zero out)."""
    for key, name in _GAUGE_OF_KEY.items():
        val = payload.get(key)
        if val is not None:
            registry.gauge(name, MEMORY_GAUGE_HELP[name]).set(val)


def host_memory(budget_bytes: int | None = None) -> dict:
    """RSS + headroom for one serve process — the shared core of the
    replica's and the fleet router's ``/statusz`` memory sections."""
    from graphmine_tpu.obs.heartbeat import rss_mb

    rss = rss_mb()
    rss_bytes = int(rss * (1 << 20)) if rss is not None else None
    headroom = None
    if budget_bytes and rss_bytes is not None:
        headroom = round((budget_bytes - rss_bytes) / budget_bytes, 4)
    return {
        "rss_bytes": rss_bytes,
        "budget_bytes": budget_bytes,
        "headroom_frac": headroom,
    }
