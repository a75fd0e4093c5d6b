"""Sharded graph container + distributed LPA / CC supersteps.

The distributed design (SURVEY §2.3, §5): **1-D vertex-range sharding**.
Device ``d`` owns the contiguous vertex chunk ``[d*Vc, (d+1)*Vc)`` and every
message *received* by those vertices. Because the message CSR is sorted by
receiving vertex, each device's messages are a contiguous slice, padded to
the max shard size so shapes are static. One superstep is then:

    gather from the replicated label vector (local HBM, no comms)
      → shard-local segment-mode / segment-min over owned vertices
      → ``all_gather`` of the updated chunks over the mesh axis (ICI)

This is the TPU equivalent of a Pregel superstep's shuffle
(``Graphframes.py:81``): per-iteration cross-device traffic is exactly one
tiled all-gather of the V-length label vector — dense, contiguous and
ICI-friendly — instead of a JVM hash shuffle. Power-law skew (SURVEY §7
hard part 3) only affects padding, not correctness: chunks are padded to
the largest shard's message count.

Scale note: labels are replicated (int32 V-vector per device — ~400 MB at
100M vertices), which is the right trade on TPU where HBM is 16-32 GB and
the edge arrays dominate. The edge/message arrays — the actual O(E) term —
are fully sharded.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from graphmine_tpu.graph.container import Graph, build_graph
from graphmine_tpu.ops.bucketed_mode import (
    _SENTINEL,
    BucketedModePlan,
    _bucket_mode,
    _bucket_wmode,
    _extend_widths,
    _positions_by_key,
    gather_rows,
    lpa_modes_from_rows,
    rewrite_rows,
)
from graphmine_tpu.ops.segment import segment_mode
from graphmine_tpu.pipeline.resilience import DivergenceError


# ---- in-loop divergence tripwires -----------------------------------------
# Cheap on-device guards inside the superstep loops (ISSUE 2): NaN/Inf
# ranks, labels outside the padded vertex-id range, period-2 oscillation,
# CC monotonicity violations. The guards are pure device reductions over
# the replicated/sharded iterate; every K supersteps a host callback
# records the FIRST firing (kind, offending shard, superstep), and the
# non-jitted public wrappers raise a classified
# :class:`~graphmine_tpu.pipeline.resilience.DivergenceError` (retryable —
# the canonical cause is transient device corruption) instead of returning
# silently-garbage labels. Armed only when ``tripwire_every > 0``: the
# unarmed programs are byte-identical to the pre-tripwire ones.

_TRIP_KINDS = (
    "none", "label_out_of_range", "oscillation", "nonfinite_ranks",
    "cc_nonmonotone",
)
_TRIP: list = []
# One owner at a time for the trip buffer: the recorder callback's
# identity is baked into the compiled program at trace time (a per-call
# closure would defeat the jit cache and retrace every invocation), so
# the buffer is process-global — and concurrent ARMED calls from
# different threads could steal or erase each other's trips. Armed calls
# serialize on this lock; unarmed calls never touch it.
import threading as _threading

_TRIP_LOCK = _threading.Lock()


def _run_armed(thunk):
    """Run an armed (tripwire_every > 0) computation with exclusive
    ownership of the trip buffer, clearing stale state first and raising
    the recorded DivergenceError after the flush."""
    with _TRIP_LOCK:
        _TRIP.clear()
        return _raise_if_tripped(thunk())


def _record_trip(kind_code, shard, iteration):
    """Host side of the tripwire callback; keeps only the first event
    (later supersteps of an already-poisoned iterate add no forensics)."""
    if not _TRIP:
        _TRIP.append((int(kind_code), int(shard), int(iteration)))


def _fire_trip(fire, kind, shard, iteration):
    """Invoke the host recorder only when a guard actually fired — the
    clean path pays the reduction, never the callback."""
    lax.cond(
        fire,
        lambda args: jax.debug.callback(_record_trip, *args),
        lambda args: None,
        (kind, shard, iteration),
    )


def _raise_if_tripped(outputs):
    """Block on ``outputs``, flush pending callback effects, then surface
    the recorded trip as a DivergenceError. block_until_ready alone only
    waits for the OUTPUT buffers — under async dispatch a debug callback
    can still be queued on the callback thread when they land, and an
    unflushed exit-check firing would let corrupted labels escape."""
    jax.block_until_ready(outputs)
    barrier = getattr(jax, "effects_barrier", None)
    if barrier is not None:
        barrier()
    if _TRIP:
        code, shard, it = _TRIP[0]
        _TRIP.clear()
        raise DivergenceError(_TRIP_KINDS[code], shard, it)
    return outputs


def _label_tripwire(new, cur, prev, it, chunk_size, every):
    """LPA guards: label-out-of-range (a wrapped gather index / corrupted
    collective puts ids outside [0, v_pad)) and period-2 oscillation
    (state t+1 == state t-1 while != state t — synchronous LPA's known
    livelock; bounded max_iter hides it as a silently-wrong answer)."""
    v_pad = new.shape[0]
    bad = (new < 0) | (new >= v_pad)
    oob = jnp.any(bad)
    osc = jnp.all(new == prev) & jnp.any(new != cur)
    kind = jnp.where(oob, 1, jnp.where(osc, 2, 0))
    shard = (jnp.argmax(bad).astype(jnp.int32) // chunk_size)
    fire = (kind > 0) & (((it + 1) % every) == 0)
    _fire_trip(fire, kind, shard, it + 1)


def _cc_tripwire(new, cur, it, chunk_size, every):
    """CC guards: label range plus monotonicity — min-propagation labels
    can only decrease; any increase means corrupted state."""
    v_pad = new.shape[0]
    bad = (new < 0) | (new >= v_pad)
    mono = new > cur
    kind = jnp.where(jnp.any(bad), 1, jnp.where(jnp.any(mono), 4, 0))
    # Attribute the shard by the REPORTED kind: with simultaneous range
    # and monotonicity violations in different shards, blaming a
    # monotonicity-only shard for an out-of-range label would send
    # device forensics to the wrong chip.
    mask = jnp.where(jnp.any(bad), bad, mono)
    shard = (jnp.argmax(mask).astype(jnp.int32) // chunk_size)
    fire = (kind > 0) & (((it + 1) % every) == 0)
    _fire_trip(fire, kind, shard, it + 1)


def _lpa_range_tripwire(new, cur, it, chunk_size, every):
    """Range-only LPA guard for the fixpoint runner (r7 serving repair).
    The oscillation guard needs the previous iterate, which the fixpoint
    carry doesn't hold — a period-2 livelock simply never reaches
    frontier 0 and exhausts the repair budget, which the serving layer's
    full-recompute fallback already handles."""
    v_pad = new.shape[0]
    bad = (new < 0) | (new >= v_pad)
    kind = jnp.where(jnp.any(bad), 1, 0)
    shard = (jnp.argmax(bad).astype(jnp.int32) // chunk_size)
    fire = (kind > 0) & (((it + 1) % every) == 0)
    _fire_trip(fire, kind, shard, it + 1)


def _rank_tripwire(new, it, chunk_size, every):
    """PageRank guard: NaN/Inf anywhere in the rank vector. NaN is
    absorbing through the power iteration AND satisfies no convergence
    test (delta > tol is False for NaN), so an unguarded loop exits
    'converged' with garbage."""
    bad = ~jnp.isfinite(new)
    kind = jnp.where(jnp.any(bad), 3, 0)
    shard = (jnp.argmax(bad).astype(jnp.int32) // chunk_size)
    fire = (kind > 0) & (((it + 1) % every) == 0)
    _fire_trip(fire, kind, shard, it + 1)


# ---- on-device superstep telemetry ----------------------------------------
# Cheap counters ACCUMULATED IN THE LOOP CARRY (ISSUE 3): labels-changed /
# frontier size per superstep, per-shard active counts (the load-imbalance
# ratio GraphBLAST-style frontier telemetry makes sparse iteration
# debuggable with), and rank-residual norms for the power iteration. They
# ride the scan/while carry and come back WITH the final labels in the one
# existing device->host transfer — zero extra host syncs, zero extra
# collectives (the reductions run on the replicated/gathered iterate every
# device already holds). Off by default: the telemetry=False programs are
# byte-identical to the pre-telemetry ones.


@dataclass(frozen=True)
class SuperstepTelemetry:
    """Per-superstep counters from a sharded LPA/CC run.

    ``labels_changed[t]``: vertices whose label changed at superstep t
    (synchronous label propagation's frontier — exactly the vertices
    whose neighbors must re-reduce next step). ``shard_changed[t, d]``:
    the same count split by owning shard — the max/mean ratio is the
    load-imbalance signal (a power-law hub shard staying hot while the
    rest converge). ``iterations``: supersteps actually run (== rows for
    LPA's fixed count; the converged prefix for CC)."""

    labels_changed: np.ndarray      # [T] int32
    shard_changed: np.ndarray       # [T, D] int32
    iterations: int

    @property
    def frontier(self) -> np.ndarray:
        return self.labels_changed

    def imbalance_ratio(self) -> np.ndarray:
        """Per-superstep max-shard / mean-shard activity (1.0 = perfectly
        balanced; quiescent supersteps report 1.0, not NaN)."""
        mean = self.shard_changed.mean(axis=1)
        peak = self.shard_changed.max(axis=1, initial=0)
        return np.where(mean > 0, peak / np.maximum(mean, 1e-9), 1.0)


@dataclass(frozen=True)
class PowerIterTelemetry:
    """Per-iteration residuals from a sharded PageRank run:
    ``residuals[t]`` is the global L1 delta, ``shard_residuals[t, d]``
    its per-shard split (imbalance + where mass is still moving), over
    the ``iterations`` actually run before convergence/max_iter."""

    residuals: np.ndarray           # [T] float32
    shard_residuals: np.ndarray     # [T, D] float32
    iterations: int


def _telemetry_row(new, cur, chunk_size):
    """One superstep's counters, on device: (changed total, per-shard
    changed). Operates on the padded [D*Vc] iterate, so the reshape is
    exact; padding vertices never change (their label is their id)."""
    diff = new != cur
    d = new.shape[0] // chunk_size
    per_shard = jnp.sum(
        diff.reshape(d, chunk_size), axis=1, dtype=jnp.int32
    )
    return jnp.sum(per_shard), per_shard


def _residual_row(new, pr, chunk_size):
    """One power iteration's residuals: (L1 delta, per-shard L1)."""
    diff = jnp.abs(new - pr)
    d = new.shape[0] // chunk_size
    per_shard = diff.reshape(d, chunk_size).sum(axis=1)
    return jnp.sum(per_shard), per_shard


def _vertex_axes(mesh):
    """The mesh axes the vertex dimension is sharded over.

    A 1-D mesh uses the plain vertex axis; a multi-slice 2-D
    ``("dcn", "ici")`` mesh shards vertices over both axes (slice-major),
    so collectives decompose hierarchically — ICI inside a slice, DCN
    across slices."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ShardedGraph:
    """Vertex-range-sharded message CSR with static shapes.

    Fields (D = mesh size, Vc = padded vertices per shard, Mp = padded
    messages per shard):

    msg_recv_local : int32 [D, Mp]  receiver minus chunk start; padding = Vc
                     (out-of-range ⇒ dropped by segment reductions)
    msg_send       : int32 [D, Mp]  global sender vertex id; padding = 0
    degrees        : int32 [D, Vc]  per-owned-vertex message count (0 ⇒ keep)
    num_vertices   : int            true V (static)
    chunk_size     : int            Vc (static)
    num_shards     : int            D (static)
    """

    msg_recv_local: jax.Array
    msg_send: jax.Array
    degrees: jax.Array
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    chunk_size: int = dataclasses.field(metadata=dict(static=True))
    num_shards: int = dataclasses.field(metadata=dict(static=True))
    # Stacked degree-bucket plan for the fast LPA shard body (see
    # ops/bucketed_mode.py for the single-device analysis): per width
    # class c, bucket_send[c] is int32 [D, n_c, w_c] of global sender ids
    # (padding rows/slots = padded_vertices, the label sentinel slot) and
    # bucket_target[c] is int32 [D, n_c] of LOCAL owned-vertex indices
    # (padding rows = chunk_size, dropped by the scatter). Shapes are
    # uniform across shards — SPMD requires one program. Empty tuples =
    # no plan; the sort-based segment_mode body is used instead.
    bucket_send: tuple = ()
    bucket_target: tuple = ()
    # Optional float32 [D, Mp] per-message weights (weighted LPA via the
    # sort shard body; padding slots carry weight 0 and are dropped by the
    # recv sentinel anyway).
    msg_weight: jax.Array | None = None
    # Weighted bucket plan (r2): per class, float32 [D, n_c, w_c] weights
    # aligned slot-for-slot with bucket_send (padding slots 0). Empty on
    # unweighted graphs.
    bucket_weight: tuple = ()
    # Slot index by sender, a shard each (added by with_shard_slot_index
    # for the carried-rows mesh job below, never by partition_graph): with
    # a shard's class rows laid end to end as one flat int32[S] buffer,
    # GLOBAL sender s's label sits in that shard's flat slots
    # out_slot[d][out_ptr[d][s]:out_ptr[d][s + 1]]. Both are FLAT, shard
    # after shard, placed P(axes): out_ptr int32 [D * (padded_vertices +
    # 1)], out_slot int32 [D * the largest shard's messages], a shorter
    # shard's tail padded with S (names no slot). Flat because a chip's
    # [1, n] block and the [n] vector the row functions read are tiled
    # differently on a TPU: the squeeze between them is a copy of the
    # whole block (1.06 GB of out_slot at graph500-25, PERF.md PR 39).
    out_ptr: jax.Array | None = None
    out_slot: jax.Array | None = None

    @property
    def padded_vertices(self) -> int:
        return self.chunk_size * self.num_shards


def partition_graph(
    graph_or_src,
    dst=None,
    num_vertices: int | None = None,
    num_shards: int | None = None,
    mesh=None,
    pad_multiple: int = 8,
    build_bucket_plan: bool = False,
    lpa_only: bool = False,
    timings: dict | None = None,
) -> ShardedGraph:
    """Partition a graph's message CSR into vertex-range shards (host-side).

    Accepts either a :class:`Graph` or raw ``(src, dst)`` arrays. The shard
    count comes from ``num_shards`` or ``mesh``. ``build_bucket_plan``
    precomputes the stacked degree-bucket plan the fast LPA shard body
    uses (host work + its own HBM, amortized once per graph like the CSR
    itself) — opt in when the partition feeds LPA; CC/PageRank/ring
    consumers never read it. ``lpa_only`` (needs the bucket plan)
    never materializes the sort-body arrays ``shard_graph_arrays(...,
    lpa_only=True)`` would drop anyway: at 10^9 messages they are 8 GB of
    host copies nothing reads. ``timings``, when given, receives
    ``plan_seconds``: the part of this call spent in the plan builders
    (the ``plan_build`` record's seconds; the rest is slicing).

    The per-shard work (slice copies and the plan builder's per-shard
    pass) runs in threads, one per shard or per (shard, class): NumPy
    releases the interpreter lock in its copies, sorts and gathers, so
    set-up is not D x serial.
    """
    if lpa_only and not build_bucket_plan:
        raise ValueError(
            "lpa_only drops the sort-body arrays; pass build_bucket_plan "
            "with it"
        )
    if mesh is not None and num_shards is None:
        num_shards = mesh.size
    if num_shards is None:
        raise ValueError("pass num_shards or mesh")
    if not isinstance(graph_or_src, Graph):
        # One source of truth for message-CSR construction semantics.
        # Host-side (r3): this graph exists only to be sliced into shards
        # below — materializing it on one device first would OOM exactly
        # the configs the multi-device schedules are for.
        graph_or_src = build_graph(
            graph_or_src, dst, num_vertices=num_vertices, to_device=False
        )
    g = graph_or_src
    recv = np.asarray(g.msg_recv)
    send = np.asarray(g.msg_send)
    w_msg = None if g.msg_weight is None else np.asarray(g.msg_weight, np.float32)
    num_vertices = g.num_vertices

    d = num_shards
    vc = -(-num_vertices // d)  # ceil
    vc = -(-vc // pad_multiple) * pad_multiple
    ptr = np.asarray(g.msg_ptr, dtype=np.int64)
    offsets = _shard_message_offsets(ptr, d, vc)
    counts = np.diff(offsets)
    mp = max(int(counts.max(initial=0)), 1)
    mp = -(-mp // pad_multiple) * pad_multiple
    # Hard int32 guard on the EXACT padded per-shard message count (the
    # planner's plan-time model uses an estimate; receiver-range sharding
    # is data-skew-dependent, so the real bound is checked here): the
    # shard bodies gather with int32 indices into the [mp]-row message
    # arrays, and a count past 2^31-1 would wrap silently (VERDICT r4
    # weak 2). Loud failure with the remedy instead.
    int32_max = (1 << 31) - 1
    if mp > int32_max:
        worst = int(np.argmax(counts))
        raise ValueError(
            f"per-shard message count {mp:,} (shard {worst} holds "
            f"{int(counts[worst]):,} of {len(recv):,} messages) exceeds the "
            f"int32 gather-index bound {int32_max:,}; add devices so every "
            f"receiver-range shard's messages fit int32"
        )

    # Per-shard slice copies write straight into the padded rows (no temp
    # per shard, no full-array pre-fill — only the padded tails are filled).
    recv_local = None if lpa_only else np.empty((d, mp), dtype=np.int32)
    send_pad = np.empty((d, mp), dtype=np.int32)
    w_pad = None if w_msg is None else np.zeros((d, mp), dtype=np.float32)

    def slice_shard(s):
        lo, hi = offsets[s], offsets[s + 1]
        n = hi - lo
        if recv_local is not None:
            np.subtract(
                recv[lo:hi], s * vc, out=recv_local[s, :n], casting="unsafe"
            )
            recv_local[s, n:] = vc  # Vc = drop sentinel
        send_pad[s, :n] = send[lo:hi]
        send_pad[s, n:] = 0
        if w_pad is not None:
            w_pad[s, :n] = w_msg[lo:hi]

    _in_threads(slice_shard, range(d))

    # Degrees come free from the CSR pointer (O(V) diff, not an O(M)
    # bincount over the messages); padded vertices get degree 0.
    deg = np.zeros(d * vc, dtype=np.int32)
    deg[:num_vertices] = np.diff(ptr).astype(np.int32)
    deg = deg.reshape(d, vc)

    bucket_send, bucket_target, bucket_weight = (), (), ()
    t_plan = time.perf_counter()
    if build_bucket_plan:
        bucket_send, bucket_target, bucket_weight = _build_shard_bucket_plan(
            deg, send_pad, counts, vc, d, w_pad
        )
    if timings is not None:
        timings["plan_seconds"] = time.perf_counter() - t_plan

    # Fields stay host-side (NumPy): shard_graph_arrays does the one
    # device placement, directly to the mesh sharding — no staging copy
    # on the default device.
    return ShardedGraph(
        msg_recv_local=recv_local,
        msg_send=None if lpa_only else send_pad,
        degrees=None if lpa_only else deg,
        num_vertices=num_vertices,
        chunk_size=vc,
        num_shards=d,
        bucket_send=bucket_send,
        bucket_target=bucket_target,
        msg_weight=None if lpa_only else w_pad,
        bucket_weight=bucket_weight,
    )


def _in_threads(fn, tasks) -> list:
    """``[fn(t) for t in tasks]`` in threads (NumPy releases the
    interpreter lock in its copies, sorts and gathers)."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    tasks = list(tasks)
    workers = min(len(tasks), os.cpu_count() or 1, 16)
    if workers <= 1:
        return [fn(t) for t in tasks]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(fn, tasks))


def _shard_message_offsets(ptr: np.ndarray, d: int, vc: int) -> np.ndarray:
    """int64 ``[d + 1]``: where each vertex-range shard's messages start
    in the receiver-sorted CSR, read off the int64 row pointers (O(D), no
    pass over the messages). A host CSR may hold more than 2^31 messages;
    only a single shard may not, which the caller checks on the
    differences."""
    ptr = np.asarray(ptr, dtype=np.int64)
    starts = np.minimum(np.arange(d + 1, dtype=np.int64) * vc, len(ptr) - 1)
    return ptr[starts]


def _build_shard_bucket_plan(deg, send_pad, counts, chunk_size, d, w_pad=None):
    """Stacked per-shard degree-bucket plan with uniform shapes.

    Every shard's owned vertices are bucketed on the shared 1.10x width
    ladder (``ops/bucketed_mode._extend_widths``); per class the row count
    is padded to the max across shards so one SPMD program serves all
    devices. No histogram path here — a per-shard [n, V] count matrix
    would replicate per device; mega-hubs ride wide sort rows instead, on
    the same ladder at the same step however long the row (a tenth of
    padding at most: at graph500-25 over four chips the rows past 2048
    are nearly half a shard's slots).

    Two passes in threads: a stable argsort groups a shard's vertices by
    class and counts them (the row counts have to be known across shards
    before any matrix is allocated), then every (shard, class) gathers its
    rows straight into its slice of the stacked ``[D, n_c, w_c]`` arrays.
    All offsets are int64: a shard's message run may be 2^31-1 long.
    Semantics are pinned against the direct ``_class_rows`` reference by
    ``tests/test_sharded.py::test_bucket_plan_matches_class_rows_reference``.
    """
    sentinel_send = chunk_size * d          # the label sentinel slot
    widths = _extend_widths(int(deg.max(initial=1)))
    n_classes = len(widths)

    def group(s):
        # ineligible (deg == 0) vertices sort to a trailing pseudo-class;
        # stability keeps rows in ascending vertex order within a class,
        # matching _class_rows' nonzero() order
        cls = np.searchsorted(widths, np.maximum(deg[s], 1))
        key = np.where(deg[s] > 0, cls, n_classes)
        # local CSR start of each owned vertex inside the shard's run
        ptr = np.zeros(chunk_size, dtype=np.int64)
        np.cumsum(deg[s, :-1], dtype=np.int64, out=ptr[1:])
        return (
            np.argsort(key, kind="stable"),
            np.bincount(key, minlength=n_classes + 1),
            ptr,
        )

    grouped = _in_threads(group, range(d))
    cnt = np.stack([g[1] for g in grouped])                   # [d, classes+1]
    start = np.zeros_like(cnt)
    np.cumsum(cnt[:, :-1], axis=1, out=start[:, 1:])
    used = [c for c in range(n_classes) if cnt[:, c].any()]
    rows_max = [int(cnt[:, c].max()) for c in used]
    bucket_send = [
        np.empty((d, n_c, int(widths[c])), dtype=np.int32)
        for c, n_c in zip(used, rows_max)
    ]
    bucket_target = [np.empty((d, n_c), dtype=np.int32) for n_c in rows_max]
    bucket_weight = [] if w_pad is None else [
        np.zeros(b.shape, dtype=np.float32) for b in bucket_send
    ]

    def fill(task):
        s, k = task
        c, (order, _, ptr) = used[k], grouped[s]
        # _class_rows clamps gather indices to the shard's true message count
        max_idx = max(int(counts[s]) - 1, 0)
        n = int(cnt[s, c])
        rows = order[start[s, c]: start[s, c] + n]
        offs = np.arange(int(widths[c]), dtype=np.int64)[None, :]
        idx = np.minimum(ptr[rows][:, None] + offs, max_idx)
        valid = offs < deg[s, rows][:, None]
        send_c = bucket_send[k][s]
        send_c[:n] = np.where(valid, send_pad[s][idx], sentinel_send)
        send_c[n:] = sentinel_send
        # Padding rows get DISTINCT targets chunk_size + j: the shard
        # body scatters them into in-range scratch slots past the real
        # chunk (sliced away), keeping unique_indices honest with no
        # OOB index.
        bucket_target[k][s, :n] = rows
        bucket_target[k][s, n:] = chunk_size + np.arange(n, rows_max[k])
        if w_pad is not None:
            bucket_weight[k][s, :n] = np.where(valid, w_pad[s][idx], 0.0)

    # one task per (shard, class), the largest first: more tasks than
    # shards, so a host with more cores than shards uses them
    tasks = sorted(
        ((s, k) for s in range(d) for k in range(len(used))),
        key=lambda t: -int(cnt[t[0], used[t[1]]]) * int(widths[used[t[1]]]),
    )
    _in_threads(fill, tasks)
    return tuple(bucket_send), tuple(bucket_target), tuple(bucket_weight)


def shard_graph_arrays(sg: ShardedGraph, mesh, lpa_only: bool = False) -> ShardedGraph:
    """Place the per-shard arrays on the mesh (leading dim over the vertex axis).

    ``lpa_only`` (valid only with a bucket plan): drop the sort-body CSR
    arrays — the bucketed LPA shard body never reads them, and at
    100M-edge scale they are ~GBs of idle HBM (they cannot merely stay on
    host: the jitted entry points stage every pytree leaf to device).
    Pass such a graph only to ``sharded_label_propagation``; CC/PageRank/
    ring consumers fail loudly on the ``None`` fields.
    """
    axes = _vertex_axes(mesh)
    spec = NamedSharding(mesh, P(axes, None))
    spec3 = NamedSharding(mesh, P(axes, None, None))
    flat = NamedSharding(mesh, P(axes))
    if lpa_only and not sg.bucket_send:
        raise ValueError(
            "lpa_only requires partition_graph(build_bucket_plan=True)"
        )
    place = (lambda a, s: None) if lpa_only else jax.device_put
    return ShardedGraph(
        msg_recv_local=place(sg.msg_recv_local, spec),
        msg_send=place(sg.msg_send, spec),
        degrees=place(sg.degrees, spec),
        num_vertices=sg.num_vertices,
        chunk_size=sg.chunk_size,
        num_shards=sg.num_shards,
        bucket_send=tuple(jax.device_put(b, spec3) for b in sg.bucket_send),
        bucket_target=tuple(jax.device_put(t, spec) for t in sg.bucket_target),
        # msg_weight is a sort-body array too (the bucketed body reads
        # bucket_weight) — drop it under lpa_only like the rest.
        msg_weight=None if sg.msg_weight is None else place(sg.msg_weight, spec),
        bucket_weight=tuple(jax.device_put(b, spec3) for b in sg.bucket_weight),
        out_ptr=None if sg.out_ptr is None else jax.device_put(sg.out_ptr, flat),
        out_slot=None if sg.out_slot is None else jax.device_put(sg.out_slot, flat),
    )


def _shard_specs(mesh):
    data_spec = P(_vertex_axes(mesh), None)
    rep = P()
    in_specs = (rep, data_spec, data_spec, data_spec)
    return in_specs, rep


def _check_mesh(sg: ShardedGraph, mesh) -> None:
    mesh_size = mesh.size
    if mesh_size != sg.num_shards:
        raise ValueError(
            f"graph was partitioned into {sg.num_shards} shards but the mesh "
            f"has {mesh_size} devices; re-run partition_graph(mesh=mesh)"
        )


def _lpa_shard_body(labels_full, recv_local, send, deg, weight, *, chunk_size, axes):
    """Per-device LPA superstep body (runs under shard_map). ``weight``:
    optional [1, Mp] per-message weights (weighted mode), else None."""
    recv_local = recv_local[0]
    send = send[0]
    deg = deg[0]
    with jax.named_scope("lpa_sharded"):
        with jax.named_scope("msg_gather"):
            msg = labels_full[send]
        mode, _ = segment_mode(
            recv_local, msg, num_segments=chunk_size,
            weights=None if weight is None else weight[0],
        )
        with jax.named_scope("write_back"):
            start = lax.axis_index(axes).astype(jnp.int32) * chunk_size
            own = lax.dynamic_slice(labels_full, (start,), (chunk_size,))
            new_own = jnp.where(deg > 0, mode, own).astype(jnp.int32)
        with jax.named_scope("exchange"):
            return lax.all_gather(new_own, axes, tiled=True)


def _lpa_shard_body_bucketed(
    labels_full, bucket_send, bucket_target, bucket_weight=None, *,
    chunk_size, axes
):
    """Fast LPA shard body: degree-bucketed dense mode per shard.

    Same comms as :func:`_lpa_shard_body` (one tiled all_gather); the
    shard-local reduction swaps the global segment-mode sort for the
    bucketed plan (see ops/bucketed_mode.py — gather-bound analysis).
    Padding rows gather the sentinel label and scatter to DISTINCT
    in-range targets ``chunk_size + j`` of an extended scratch region
    that is sliced away at the end; vertices with no messages are in no
    bucket and keep their label. ``bucket_weight`` (r2): slot-aligned
    weights switch the row modes to weighted argmax.
    """
    with jax.named_scope("lpa_sharded"):
        lbl_pad = jnp.concatenate(
            [labels_full, jnp.full((1,), _SENTINEL, jnp.int32)]
        )
        start = lax.axis_index(axes).astype(jnp.int32) * chunk_size
        own = lax.dynamic_slice(labels_full, (start,), (chunk_size,))
        own = _shard_row_modes(
            lbl_pad, own, bucket_send, bucket_target, bucket_weight
        )
        with jax.named_scope("exchange"):
            return lax.all_gather(
                own[:chunk_size].astype(jnp.int32), axes, tiled=True
            )


def _shard_row_modes(table, own, row_idx, row_target, row_weight):
    """The reduce of every degree class of one shard: gather the class's
    dense rows from ``table``, take the row-wise mode, write it to the
    class's LOCAL targets in ``own`` (the stacked-plan twin of
    ``ops/bucketed_mode._row_modes``: indices arrive as ``[1, n, w]``
    shard slices). Returns ``own`` extended by a scratch region the caller
    slices away.

    Padding rows carry DISTINCT targets chunk_size + j (j < n_c): one
    scratch extension by the max class width keeps every scatter index
    in range and unique. Do NOT "optimize" this back to out-of-bounds
    indices with mode="drop" — under shard_map the XLA:CPU lowering of
    a unique_indices OOB scatter was observed corrupting the last
    in-range slot with a shifted read (caught by
    tools/consistency_sweep.py; see docs/DESIGN.md)."""
    n_max = max((t.shape[-1] for t in row_target), default=0)
    own = jnp.concatenate([own, jnp.zeros((n_max,), own.dtype)])
    wmats = row_weight or (None,) * len(row_idx)
    for ridx, tgt, wmat in zip(row_idx, row_target, wmats):
        width = f"w{ridx.shape[-1]}"
        with jax.named_scope("row_gather"), jax.named_scope(width):
            mat = table[ridx[0]]
        with jax.named_scope("row_mode"), jax.named_scope(width):
            vals = (
                _bucket_mode(mat) if wmat is None
                else _bucket_wmode(mat, wmat[0])
            )
        with jax.named_scope("write_back"):
            own = own.at[tgt[0]].set(vals, unique_indices=True)
    return own


def _cc_shard_body(labels_full, recv_local, send, deg, *, chunk_size, axes):
    recv_local = recv_local[0]
    send = send[0]
    deg = deg[0]
    msg = labels_full[send]
    neigh_min = jax.ops.segment_min(msg, recv_local, num_segments=chunk_size)
    start = lax.axis_index(axes).astype(jnp.int32) * chunk_size
    own = lax.dynamic_slice(labels_full, (start,), (chunk_size,))
    new_own = jnp.where(deg > 0, jnp.minimum(own, neigh_min), own).astype(jnp.int32)
    full = lax.all_gather(new_own, axes, tiled=True)
    # Pointer jumping on the (replicated) full vector — no extra comms.
    return jnp.minimum(full, full[full])


def _padded_init_labels(sg: ShardedGraph) -> jax.Array:
    v_pad = sg.padded_vertices
    return jnp.arange(v_pad, dtype=jnp.int32)


def _scan_supersteps(
    step_fn, labels: jax.Array, max_iter: int,
    tripwire_every: int = 0, chunk_size: int = 0, collect: bool = False,
):
    """Fixed-count superstep driver (LPA semantics: exactly max_iter).
    ``tripwire_every > 0`` arms the label tripwires every K supersteps
    (the carry then also holds the previous iterate for the oscillation
    guard); ``collect`` stacks :func:`_telemetry_row` as scan outputs and
    returns ``(labels, (changed[T], shard_changed[T, D]))`` — the
    counters travel with the result, no extra syncs. With both off the
    program is the original lean one."""
    if not tripwire_every and not collect:

        def step(labels, _):
            return step_fn(labels), None

        labels, _ = lax.scan(step, labels, None, length=max_iter)
        return labels

    if not tripwire_every:
        # collect-only: no oscillation guard, so don't thread a second
        # [D*Vc] prev-labels buffer through the carry just to ignore it
        # — telemetry targets exactly the large-graph runs where that
        # extra HBM would hurt.
        def step_c(cur, _):
            new = step_fn(cur)
            return new, _telemetry_row(new, cur, chunk_size)

        labels, ys = lax.scan(step_c, labels, None, length=max_iter)
        return labels, ys

    def step(carry, it):
        cur, prev = carry
        new = step_fn(cur)
        if tripwire_every:
            _label_tripwire(new, cur, prev, it, chunk_size, tripwire_every)
        ys = _telemetry_row(new, cur, chunk_size) if collect else None
        return (new, cur), ys

    (labels, prev), ys = lax.scan(
        step, (labels, labels), jnp.arange(max_iter, dtype=jnp.int32)
    )
    if tripwire_every:
        # Unconditional exit check (every=1): when max_iter is not a
        # multiple of K the last supersteps run unchecked, and garbage
        # must never leave the loop silently.
        _label_tripwire(
            labels, prev, prev, jnp.int32(max_iter - 1), chunk_size, 1
        )
    return (labels, ys) if collect else labels


# Telemetry ring-buffer bound for unbounded (max_iter=0) fixpoint runs:
# pointer jumping converges in O(log V) supersteps, so 4096 rows is far
# past any real trajectory; a pathological overrun overwrites the last
# row rather than growing an O(V)-row buffer alongside the labels.
_FIXPOINT_TELEMETRY_CAP = 4096


def _fixpoint_supersteps(
    step_fn, sg: ShardedGraph, max_iter: int, tripwire_every: int = 0,
    init_labels=None, collect: bool = False, guard=_cc_tripwire,
):
    """Run supersteps until no label changes (CC semantics), bounded by
    ``max_iter`` when nonzero. Shared by the replicated-label and ring
    schedules so the convergence logic has one home. ``tripwire_every``
    arms the ``guard`` tripwire every K supersteps — the CC guards
    (range + monotonicity) by default; the LPA fixpoint runner passes
    its range-only guard (min-monotonicity doesn't hold for mode
    propagation). ``init_labels`` resumes a checkpointed run
    mid-fixpoint or seeds a warm-start repair. ``collect`` accumulates
    :func:`_telemetry_row` into a fixed-size buffer carried through the
    while_loop and returns
    ``(labels, (changed[cap], shard_changed[cap, D], it_end))``."""
    limit = max_iter if max_iter > 0 else sg.num_vertices + 2
    cap = min(limit, _FIXPOINT_TELEMETRY_CAP)

    def cond(state):
        changed, it = state[1], state[2]
        return (changed > 0) & (it < limit)

    def loop_body(state):
        labels = state[0]
        it = state[2]
        new = step_fn(labels)
        if tripwire_every:
            guard(new, labels, it, sg.chunk_size, tripwire_every)
        if collect:
            total, per_shard = _telemetry_row(new, labels, sg.chunk_size)
            row = jnp.minimum(it, cap - 1)
            buf_c = state[3].at[row].set(total)
            buf_s = state[4].at[row].set(per_shard)
            return new, total, it + 1, buf_c, buf_s
        changed = jnp.sum(new != labels, dtype=jnp.int32)
        return new, changed, it + 1

    labels0 = (
        _padded_init_labels(sg) if init_labels is None
        else _pad_labels(init_labels, sg)
    )
    state0 = (labels0, jnp.int32(1), jnp.int32(0))
    if collect:
        state0 = state0 + (
            jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap, sg.num_shards), jnp.int32),
        )
    out = lax.while_loop(cond, loop_body, state0)
    labels, it_end = out[0], out[2]
    if tripwire_every:
        # Exit check (every=1): a poisoned-but-stable state ends the
        # fixpoint loop between two K-aligned checks; garbage must never
        # leave the loop silently. Monotonicity needs history, so only
        # the range guard applies here (cur=new disables it).
        guard(labels, labels, it_end - 1, sg.chunk_size, 1)
    if collect:
        return labels[: sg.num_vertices], (out[3], out[4], it_end)
    return labels[: sg.num_vertices]


def sharded_label_propagation(
    sg: ShardedGraph, mesh, max_iter: int = 5,
    init_labels: jax.Array | None = None, tripwire_every: int = 0,
    telemetry: bool = False,
):
    """Distributed synchronous LPA; semantics identical to
    :func:`graphmine_tpu.ops.lpa.label_propagation` (asserted by the
    virtual-device parity tests). Returns int32 labels ``[V]``.

    ``tripwire_every``: arm the in-loop divergence tripwires
    (label-out-of-range, period-2 oscillation) every K supersteps — a
    firing raises :class:`~graphmine_tpu.pipeline.resilience.DivergenceError`
    (retryable, with the offending shard index) instead of returning
    garbage labels. 0 (default) = off, the exact pre-tripwire program.

    ``telemetry``: also return a :class:`SuperstepTelemetry` —
    ``(labels, telemetry)`` — whose per-superstep counters accumulate in
    the scan carry and come back with the labels in the same transfer
    (no per-iteration host syncs; labels are bit-identical either way).
    """
    if not tripwire_every:
        out = _sharded_lpa_jit(sg, mesh, max_iter, init_labels, 0, telemetry)
    else:
        out = _run_armed(
            lambda: _sharded_lpa_jit(
                sg, mesh, max_iter, init_labels, tripwire_every, telemetry
            )
        )
    if not telemetry:
        return out
    labels, (changed, per_shard) = out
    return labels, SuperstepTelemetry(
        np.asarray(changed), np.asarray(per_shard), int(max_iter)
    )


def _build_lpa_step(sg: ShardedGraph, mesh):
    """The per-superstep LPA callable for one (graph, mesh) — shared by
    the fixed-count driver (:func:`_sharded_lpa_jit`) and the fixpoint
    repair entry (:func:`_sharded_lpa_fixpoint_jit`). Traced under jit."""
    axes = _vertex_axes(mesh)
    rep = P()
    if sg.bucket_send:
        # Fast path: stacked degree-bucket plan (built by partition_graph);
        # weighted graphs carry slot-aligned bucket_weight matrices (r2).
        n = len(sg.bucket_send)
        nw = len(sg.bucket_weight)
        body = shard_map(
            partial(_lpa_shard_body_bucketed, chunk_size=sg.chunk_size, axes=axes),
            mesh=mesh,
            in_specs=(
                rep,
                (P(axes, None, None),) * n,
                (P(axes, None),) * n,
                (P(axes, None, None),) * nw,
            ),
            out_specs=rep,
            # The output is a tiled all_gather — replicated by construction,
            # which the vma checker cannot infer statically.
            check_vma=False,
        )
        return lambda l: body(l, sg.bucket_send, sg.bucket_target, sg.bucket_weight)
    in_specs, _ = _shard_specs(mesh)
    data_spec = P(axes, None)
    body = shard_map(
        partial(_lpa_shard_body, chunk_size=sg.chunk_size, axes=axes),
        mesh=mesh,
        in_specs=in_specs + (data_spec,),  # None weights: empty subtree
        out_specs=rep,
        check_vma=False,
    )
    return lambda l: body(
        l, sg.msg_recv_local, sg.msg_send, sg.degrees, sg.msg_weight
    )


@partial(jax.jit, static_argnames=("max_iter", "mesh", "tripwire_every", "telemetry"))
def _sharded_lpa_jit(
    sg: ShardedGraph, mesh, max_iter: int, init_labels, tripwire_every: int,
    telemetry: bool = False,
):
    _check_mesh(sg, mesh)
    step = _build_lpa_step(sg, mesh)
    labels = _padded_init_labels(sg) if init_labels is None else _pad_labels(init_labels, sg)
    out = _scan_supersteps(
        step, labels, max_iter,
        tripwire_every=tripwire_every, chunk_size=sg.chunk_size,
        collect=telemetry,
    )
    if telemetry:
        labels, ys = out
        return labels[: sg.num_vertices], ys
    return out[: sg.num_vertices]


# ---- carried rows on a mesh: a shard's gathered rows as state ---------------
#
# The mesh form of ``ops/lpa.py:_carried_rows_job`` (ISSUE 39). Each chip
# keeps its shard's gathered rows across supersteps in one donated buffer
# (``int32 [D * S]`` placed ``P(axes)``: flat, so that a chip's block IS the
# ``[S]`` vector the row functions update and no program reshapes it; as
# ``[D, S]`` every rewrite copied a chip's rows in and out, PERF.md PR 39),
# and a superstep that follows
# few changed labels rewrites only the slots behind their senders, through a
# slot index a shard (``ShardedGraph.out_ptr`` / ``out_slot``). Every chip
# knows which labels changed: a superstep ends in the tiled ``all_gather``
# that replicates the new labels. The row update has one implementation,
# ``ops/bucketed_mode.py``'s ``gather_rows`` / ``rewrite_rows`` /
# ``lpa_modes_from_rows``: the shard bodies hand them the shard's slice of
# the stacked plan as a ``BucketedModePlan`` (:func:`_shard_plan`).


def shard_row_slots(sg: ShardedGraph) -> int:
    """S: the padded slots of one shard's dense rows (uniform across
    shards: the stacked plan's shapes are)."""
    return sum(int(b.shape[1]) * int(b.shape[2]) for b in sg.bucket_send)


def shard_plan_shapes(sg: ShardedGraph, messages_per_shard: int):
    """One shard's plan by SHAPES (no data): what the admission and the
    cost of the carried-rows mesh job read. ``num_vertices`` is the padded
    vertex space (the replicated label vector every chip sorts and
    indexes), ``num_messages`` the largest shard's."""
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)
    f32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)
    return BucketedModePlan(
        vertex_ids=tuple(i32(*t.shape[1:]) for t in sg.bucket_target),
        msg_idx=None, num_vertices=sg.padded_vertices,
        num_messages=int(messages_per_shard),
        send_idx=tuple(i32(*b.shape[1:]) for b in sg.bucket_send),
        weight_mat=tuple(f32(*w.shape[1:]) for w in sg.bucket_weight) or None,
    )


def with_shard_slot_index(sg: ShardedGraph, counts) -> ShardedGraph:
    """The HOST partition ``sg`` (NumPy fields, before
    :func:`shard_graph_arrays`) with its slot index a shard: per shard the
    transpose of its ``bucket_send``, one stable counting sort of the
    senders behind the shard's padded slots
    (``ops/bucketed_mode._positions_by_key``, what ``with_slot_index``
    sorts by), the shards in threads. Padding slots name the sentinel
    ``padded_vertices`` and fall out of the sort; the mesh plan has no
    histogram hubs, so every message has a slot (held against ``counts``,
    the shards' true message counts). Comes back as it is when a shard has
    more slots than an int32 counts, or none."""
    s, v_pad = shard_row_slots(sg), sg.padded_vertices
    if s == 0 or s >= np.iinfo(np.int32).max:
        return sg
    m_max = max(int(np.max(counts, initial=0)), 1)
    out_ptr = np.empty((sg.num_shards, v_pad + 1), np.int32)
    out_slot = np.empty((sg.num_shards, m_max), np.int32)

    def index(d):
        keys = np.concatenate([b[d].reshape(-1) for b in sg.bucket_send])
        ptr, slot = _positions_by_key(keys, v_pad)
        assert len(slot) == int(counts[d]), (d, len(slot), int(counts[d]))
        out_ptr[d] = ptr
        out_slot[d, :len(slot)] = slot
        out_slot[d, len(slot):] = s  # a shorter shard's tail names no slot

    _in_threads(index, range(sg.num_shards))
    return dataclasses.replace(
        sg, out_ptr=out_ptr.reshape(-1), out_slot=out_slot.reshape(-1)
    )


def _shard_plan(sg: ShardedGraph, bucket_send=(), bucket_target=(),
                bucket_weight=(), out_ptr=None, out_slot=None):
    """One shard's slice of the stacked plan (``[1, ...]`` leaves, as they
    arrive under ``shard_map``) as the ``BucketedModePlan`` the one-chip
    row functions read: ``send_idx`` the shard's sender matrices over the
    padded label vector (padding slots name ``padded_vertices``, the
    sentinel ``_with_sentinel`` appends), ``vertex_ids`` its LOCAL targets,
    the index its own. A program hands over the fields it reads; without
    ``bucket_send`` the classes are there by shape alone (the row modes
    read the rows they are handed, and of the matrices only the shapes)."""
    send_idx = tuple(b[0] for b in bucket_send) or tuple(
        jax.ShapeDtypeStruct(b.shape[1:], jnp.int32) for b in sg.bucket_send
    )
    return BucketedModePlan(
        vertex_ids=tuple(t[0] for t in bucket_target), msg_idx=None,
        num_vertices=sg.padded_vertices,
        num_messages=0 if out_slot is None else out_slot.shape[0],
        send_idx=send_idx,
        weight_mat=tuple(w[0] for w in bucket_weight) or None,
        out_ptr=out_ptr, out_slot=out_slot,
    )


def shard_messages(sg: ShardedGraph) -> int:
    """The largest shard's messages: a shard's length of ``out_slot``."""
    return sg.out_slot.shape[0] // sg.num_shards


@partial(jax.jit, static_argnames=("mesh", "num_vertices", "chunk_size", "slots"))
def _mesh_job_start(init_labels, mesh, num_vertices, chunk_size, slots):
    """``(labels, rows)`` before the first superstep: the padded label
    vector on every chip, and each chip's blank rows."""
    d = mesh.size
    labels = jnp.arange(d * chunk_size, dtype=jnp.int32)
    if init_labels is not None:
        labels = labels.at[:num_vertices].set(init_labels.astype(jnp.int32))
    return (
        lax.with_sharding_constraint(labels, NamedSharding(mesh, P())),
        lax.with_sharding_constraint(
            jnp.zeros((d * slots,), jnp.int32),
            NamedSharding(mesh, P(_vertex_axes(mesh))),
        ),
    )


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=0)
def _mesh_gather_program(rows, labels, sg: ShardedGraph, mesh):
    """Every class of every shard gathered anew from the replicated
    ``labels`` into the shard's flat rows, in place."""
    axes = _vertex_axes(mesh)
    n = len(sg.bucket_send)

    def body(rows, labels, bucket_send):
        return gather_rows(rows, labels, _shard_plan(sg, bucket_send))

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(), (P(axes, None, None),) * n),
        out_specs=P(axes), check_vma=False,
    )(rows, labels, sg.bucket_send)


@partial(jax.jit, static_argnames=("mesh", "cap"), donate_argnums=0)
def _mesh_rewrite_program(rows, labels, changed, sg: ShardedGraph, mesh, cap: int):
    """Each shard's slots behind the ``changed`` senders rewritten with
    their ``labels``, in place; ``cap`` bounds the messages they send to
    ANY one shard (the caller's promise: the largest shard's K)."""
    spec = P(_vertex_axes(mesh))

    def body(rows, labels, changed, out_ptr, out_slot):
        plan = _shard_plan(sg, out_ptr=out_ptr, out_slot=out_slot)
        return rewrite_rows(rows, labels, changed, plan, cap)

    return shard_map(
        body, mesh=mesh, in_specs=(spec, P(), P(), spec, spec),
        out_specs=spec, check_vma=False,
    )(rows, labels, changed, sg.out_ptr, sg.out_slot)


@partial(jax.jit, static_argnames=("mesh",))
def _mesh_modes_program(rows, labels, sg: ShardedGraph, mesh):
    """``(new labels, changed, K, count)`` of one superstep over the
    shards' ``rows``: each shard's row modes written to its owned range,
    the tiled ``all_gather``, then on the replicated vector ``changed``,
    the count of changed vertices, and K: the messages the changed
    vertices send to the shard they send most to (one ``pmax``), which
    picks the next superstep's update for every shard."""
    axes = _vertex_axes(mesh)
    n, nw = len(sg.bucket_send), len(sg.bucket_weight)
    scratch = max((t.shape[-1] for t in sg.bucket_target), default=0)

    def body(rows, labels, bucket_target, bucket_weight, out_ptr):
        plan = _shard_plan(
            sg, bucket_target=bucket_target, bucket_weight=bucket_weight,
            out_ptr=out_ptr,
        )
        start = lax.axis_index(axes).astype(jnp.int32) * sg.chunk_size
        own = lax.dynamic_slice(labels, (start,), (sg.chunk_size,))
        # padding rows write to distinct in-range scratch places past the
        # owned range (see _shard_row_modes): sliced away
        own = jnp.concatenate([own, jnp.zeros((scratch,), jnp.int32)])
        own = lpa_modes_from_rows(rows, own, plan)[: sg.chunk_size]
        with jax.named_scope("lpa_sharded"), jax.named_scope("exchange"):
            new = lax.all_gather(own, axes, tiled=True)
        with jax.named_scope("superstep"), jax.named_scope("changed_count"):
            changed = new != labels
            out_deg = plan.out_ptr[1:] - plan.out_ptr[:-1]
            k = lax.pmax(
                jnp.sum(jnp.where(changed, out_deg, 0), dtype=jnp.int32), axes
            )
            count = jnp.sum(changed, dtype=jnp.int32)
        return new, changed, k, count

    flat, spec, spec3 = P(axes), P(axes, None), P(axes, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(flat, P(), (spec,) * n, (spec3,) * nw, flat),
        out_specs=(P(), P(), P(), P()), check_vma=False,
    )(rows, labels, sg.bucket_target, sg.bucket_weight, sg.out_ptr)


def carried_label_propagation(
    sg: ShardedGraph, mesh, max_iter: int = 5,
    init_labels: jax.Array | None = None, clock=None, programs=None,
):
    """``(labels [V], per_step)`` of ``max_iter`` LPA supersteps over a
    placed partition with its slot index (:func:`with_shard_slot_index`),
    stepped from the host: ``ops/lpa.py:_carried_rows_job`` on a mesh, the
    same labels as :func:`sharded_label_propagation` bit for bit.

    Each superstep first brings every shard's rows up to the labels it
    starts from, by the update its predecessor's count picks: K, the
    messages the changed vertices send to the shard that receives most of
    them. ``cap`` is a static argument and SPMD needs one program for all
    shards, so the rung is picked by the LARGEST shard's K (a sum over
    shards would not bound one shard's count) against
    ``delta_rungs(the largest shard's messages)``. K above every rung
    gathers every class anew (:func:`_mesh_gather_program`; the first
    superstep always); K <= a rung rewrites that many slots a shard
    (:func:`_mesh_rewrite_program`, compiled when a job first takes the
    rung). Then :func:`_mesh_modes_program`. The loop is the one-chip
    job's (``ops/superstep_policy.step_carried_rows``): the host waits once
    a superstep, for K, which it reads from its own replica; ``max_iter``
    is the length of that loop and no program's argument. ``per_step`` is
    ``_carried_rows_job``'s: ``changed_vertices``, ``changed_messages``
    (the largest shard's K), ``branch``, and with a ``clock`` ``seconds``.
    ``programs`` (``ops/superstep_policy.ProgramLog``, the caller's where a
    sink wants the ``program_memory`` records) notes each program run.

    One process only: the host steps the chips it addresses."""
    from graphmine_tpu.ops.superstep_policy import (
        delta_rungs,
        noting,
        step_carried_rows,
    )

    _check_mesh(sg, mesh)
    if sg.out_slot is None:
        raise ValueError("the partition has no slot index (with_shard_slot_index)")
    start = noting(programs, "start", _mesh_job_start)
    gather = noting(programs, "gather", _mesh_gather_program)
    rewrite = noting(programs, "rewrite", _mesh_rewrite_program)
    modes = noting(programs, "modes", _mesh_modes_program)
    labels, rows = start(
        init_labels, mesh, sg.num_vertices, sg.chunk_size, shard_row_slots(sg)
    )
    labels, per_step = step_carried_rows(
        max_iter, delta_rungs(shard_messages(sg)), shard_messages(sg) + 1,
        rows, labels,
        gather=lambda rows, labels: gather(rows, labels, sg, mesh),
        rewrite=lambda rows, labels, changed, cap: rewrite(
            rows, labels, changed, sg, mesh, cap=cap
        ),
        modes=lambda rows, labels: modes(rows, labels, sg, mesh),
        clock=clock,
    )
    if sg.num_vertices != sg.padded_vertices:
        labels = labels[: sg.num_vertices]
    return labels, per_step


def sharded_lpa_fixpoint(
    sg: ShardedGraph, mesh, max_iter: int = 0,
    init_labels: jax.Array | None = None, tripwire_every: int = 0,
):
    """Warm-start LPA run to FIXPOINT — the serving delta-repair entry
    (r7, docs/SERVING.md): ``init_labels`` seeds the previous snapshot's
    labels and supersteps run until no label changes, bounded by
    ``max_iter`` (0 = unbounded). Returns
    ``(labels[:V], iterations, converged)`` — ``converged=False`` means
    the budget exhausted first (the serving layer then falls back to a
    cold full recompute rather than publish a non-fixpoint).

    Same shard bodies, comms and mesh semantics as
    :func:`sharded_label_propagation`; only the loop driver differs
    (while-until-quiescent instead of a fixed scan).
    ``tripwire_every`` arms the range-only LPA guard every K supersteps.
    """
    if not tripwire_every:
        out = _sharded_lpa_fixpoint_jit(sg, mesh, max_iter, init_labels, 0)
    else:
        out = _run_armed(
            lambda: _sharded_lpa_fixpoint_jit(
                sg, mesh, max_iter, init_labels, tripwire_every
            )
        )
    labels, (changed, _per_shard, it_end) = out
    it = int(it_end)
    row = min(it, changed.shape[0]) - 1
    converged = it == 0 or int(changed[row]) == 0
    return labels, it, converged


@partial(jax.jit, static_argnames=("max_iter", "mesh", "tripwire_every"))
def _sharded_lpa_fixpoint_jit(
    sg: ShardedGraph, mesh, max_iter: int, init_labels, tripwire_every: int,
):
    _check_mesh(sg, mesh)
    step = _build_lpa_step(sg, mesh)
    return _fixpoint_supersteps(
        step, sg, max_iter, tripwire_every=tripwire_every,
        init_labels=init_labels, collect=True, guard=_lpa_range_tripwire,
    )


def sharded_connected_components(
    sg: ShardedGraph, mesh, max_iter: int = 0, tripwire_every: int = 0,
    init_labels: jax.Array | None = None, telemetry: bool = False,
):
    """Distributed weakly-connected components (min-propagation + pointer
    jumping); parity with :func:`graphmine_tpu.ops.cc.connected_components`.
    ``tripwire_every``: arm the CC divergence tripwires (label range +
    min-monotonicity) every K supersteps; see
    :func:`sharded_label_propagation`. ``init_labels``: resume a
    checkpointed fixpoint mid-run (min-propagation is monotone, so a
    resumed trajectory converges to the identical fixpoint).
    ``telemetry``: return ``(labels, SuperstepTelemetry)`` — counters
    ride the while-loop carry (rows past the converged prefix are
    trimmed host-side; no extra device syncs)."""
    if not tripwire_every:
        out = _sharded_cc_jit(sg, mesh, max_iter, 0, init_labels, telemetry)
    else:
        out = _run_armed(
            lambda: _sharded_cc_jit(
                sg, mesh, max_iter, tripwire_every, init_labels, telemetry
            )
        )
    if not telemetry:
        return out
    labels, (changed, per_shard, it_end) = out
    n = min(int(it_end), changed.shape[0])
    return labels, SuperstepTelemetry(
        np.asarray(changed)[:n], np.asarray(per_shard)[:n], int(it_end)
    )


@partial(jax.jit, static_argnames=("max_iter", "mesh", "tripwire_every", "telemetry"))
def _sharded_cc_jit(
    sg: ShardedGraph, mesh, max_iter: int, tripwire_every: int,
    init_labels=None, telemetry: bool = False,
):
    _check_mesh(sg, mesh)
    in_specs, rep = _shard_specs(mesh)
    axes = _vertex_axes(mesh)
    body = shard_map(
        partial(_cc_shard_body, chunk_size=sg.chunk_size, axes=axes),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=rep,
        check_vma=False,
    )
    step = lambda l: body(l, sg.msg_recv_local, sg.msg_send, sg.degrees)
    return _fixpoint_supersteps(
        step, sg,
        max_iter, tripwire_every=tripwire_every, init_labels=init_labels,
        collect=telemetry,
    )


def _pad_labels(labels: jax.Array, sg: ShardedGraph) -> jax.Array:
    v_pad = sg.padded_vertices
    pad = jnp.arange(sg.num_vertices, v_pad, dtype=jnp.int32)
    return jnp.concatenate([labels.astype(jnp.int32), pad])


def _check_pagerank_weighted(sg, out_degrees, weighted):
    """Resolve/validate the weighted flag for the distributed PageRank
    schedules (one owner; used by the replicated and ring paths).

    ``None`` -> weighted iff the graph carries ``msg_weight``. A weighted
    run requires FLOAT out-edge weight sums (``ops.degrees.out_weights``):
    integer out-degrees would mix w-weighted messages with 1/deg outflow
    and silently stop conserving rank mass.
    """
    if weighted is None:
        weighted = sg.msg_weight is not None
    if weighted:
        if sg.msg_weight is None:
            raise ValueError("weighted=True but the graph has no msg_weight")
        if not jnp.issubdtype(jnp.result_type(out_degrees), jnp.floating):
            raise ValueError(
                "weighted PageRank needs float out-edge weight sums "
                "(ops.degrees.out_weights), not integer out-degrees; pass "
                "weighted=False for unweighted ranks on this graph"
            )
    return weighted


def _pagerank_terms(out_degrees, v: int, v_pad: int):
    """Padded degree-derived PageRank terms shared by the replicated and
    ring schedules (one owner for the dangling/teleport semantics).
    ``out_degrees`` may be int out-degrees (unweighted) or float out-edge
    weight sums (weighted; each vertex splits rank in proportion to edge
    weight — NetworkX semantics, matching ``ops.pagerank(weights=...)``).
    Returns ``(inv_out, reset, dangling)``, each ``[v_pad]``."""
    out = jnp.zeros((v_pad,), jnp.float32).at[:v].set(
        jnp.asarray(out_degrees).astype(jnp.float32)
    )
    live = jnp.arange(v_pad) < v
    inv_out = jnp.where(out > 0, 1.0 / jnp.maximum(out, 1e-30), 0.0)
    dangling = (out <= 0) & live
    reset = jnp.where(live, 1.0 / v, 0.0).astype(jnp.float32)
    return inv_out, reset, dangling


def _pagerank_shard_body(state, recv_local, send, deg, weight=None, *,
                         chunk_size, axes, alpha):
    """Per-device PageRank power-iteration step.

    ``state``: (pr_full, inv_out_full, dangling_mass_reset_full) — the
    replicated rank vector and precomputed degree terms. Messages ride the
    same vertex-range-sharded CSR as LPA; per-iteration comms is one tiled
    all_gather of the rank chunk. ``weight``: optional [1, Mp] per-message
    weights — with float out-strengths in ``inv_out`` this is weighted
    PageRank (contribution = rank x w/out_w).
    """
    pr_full, inv_out_full, reset_full, dangling_full = state
    recv_local = recv_local[0]
    send = send[0]
    contrib_full = pr_full * inv_out_full
    msg = contrib_full[send] * (recv_local < chunk_size)
    if weight is not None:
        msg = msg * weight[0]
    inflow = jax.ops.segment_sum(msg, recv_local, num_segments=chunk_size)
    dangling_mass = jnp.sum(jnp.where(dangling_full, pr_full, 0.0))
    start = lax.axis_index(axes).astype(jnp.int32) * chunk_size
    reset_own = lax.dynamic_slice(reset_full, (start,), (chunk_size,))
    new_own = alpha * (inflow + dangling_mass * reset_own) + (1.0 - alpha) * reset_own
    return lax.all_gather(new_own, axes, tiled=True)


def sharded_pagerank(
    sg: ShardedGraph,
    mesh,
    out_degrees: jax.Array,
    alpha: float = 0.85,
    max_iter: int = 100,
    tol: float = 1e-6,
    weighted: bool | None = None,
    tripwire_every: int = 0,
    init_ranks: jax.Array | None = None,
    telemetry: bool = False,
):
    """Distributed PageRank over the vertex-range-sharded message CSR.

    ``sg`` must be partitioned from a **directed** graph
    (``build_graph(..., symmetric=False)``); ``out_degrees`` is the
    directed out-degree vector ``[V]`` (see
    :func:`graphmine_tpu.ops.degrees.out_degrees`) — or, for a weighted
    run, the float out-edge weight sums
    (:func:`graphmine_tpu.ops.degrees.out_weights`): rank then splits
    across out-edges in proportion to weight, matching
    ``ops.pagerank(weights=...)``. ``weighted=None`` follows
    ``sg.msg_weight`` presence; int out_degrees on a weighted run are
    rejected (the w/out mixture would silently conserve no rank mass) —
    pass ``weighted=False`` for unweighted ranks on a weighted graph.
    Parity with :func:`graphmine_tpu.ops.pagerank.pagerank` is asserted
    by the virtual-device tests. Returns float32 ranks ``[V]`` summing
    to 1. ``tripwire_every``: arm the NaN/Inf rank tripwire every K
    power iterations (a NaN rank satisfies no convergence test —
    ``delta > tol`` is False for NaN — so an unguarded loop exits
    'converged' with garbage); see :func:`sharded_label_propagation`.
    ``init_ranks``: resume a checkpointed power iteration mid-run (the
    iteration is a fixed-point map, so a resumed trajectory matches the
    uninterrupted one). ``telemetry``: return
    ``(ranks, PowerIterTelemetry)`` — per-iteration L1 residuals (global
    + per-shard) accumulated in the loop carry, fetched with the ranks
    (no extra syncs; a NaN-poisoned run's residual trail shows WHERE the
    iteration went wrong, not just that it did).
    """
    if not tripwire_every:
        out = _sharded_pagerank_jit(
            sg, mesh, out_degrees, alpha, max_iter, tol, weighted, 0,
            init_ranks, telemetry,
        )
    else:
        out = _run_armed(lambda: _sharded_pagerank_jit(
            sg, mesh, out_degrees, alpha, max_iter, tol, weighted,
            tripwire_every, init_ranks, telemetry,
        ))
    if not telemetry:
        return out
    ranks, (res, shard_res, it_end) = out
    n = min(int(it_end), res.shape[0])
    return ranks, PowerIterTelemetry(
        np.asarray(res)[:n], np.asarray(shard_res)[:n], int(it_end)
    )


@partial(jax.jit, static_argnames=("max_iter", "mesh", "weighted", "tripwire_every", "telemetry"))
def _sharded_pagerank_jit(
    sg: ShardedGraph, mesh, out_degrees, alpha, max_iter: int, tol,
    weighted: bool | None, tripwire_every: int, init_ranks=None,
    telemetry: bool = False,
):
    _check_mesh(sg, mesh)
    weighted = _check_pagerank_weighted(sg, out_degrees, weighted)
    inv_out, reset, dangling = _pagerank_terms(
        out_degrees, sg.num_vertices, sg.padded_vertices
    )

    in_specs, rep = _shard_specs(mesh)
    data_spec = P(_vertex_axes(mesh), None)
    body = shard_map(
        partial(
            _pagerank_shard_body,
            chunk_size=sg.chunk_size,
            axes=_vertex_axes(mesh),
            alpha=alpha,
        ),
        mesh=mesh,
        in_specs=((rep, rep, rep, rep),) + in_specs[1:]
        + ((data_spec,) if weighted else ()),
        out_specs=rep,
        check_vma=False,
    )

    cap = max(max_iter, 1)

    def cond(state):
        delta, it = state[1], state[2]
        return (delta > tol) & (it < max_iter)

    def step(state):
        pr, it = state[0], state[2]
        args = (sg.msg_weight,) if weighted else ()
        new = body(
            (pr, inv_out, reset, dangling), sg.msg_recv_local, sg.msg_send,
            sg.degrees, *args,
        )
        if tripwire_every:
            _rank_tripwire(new, it, sg.chunk_size, tripwire_every)
        if telemetry:
            delta, per_shard = _residual_row(new, pr, sg.chunk_size)
            row = jnp.minimum(it, cap - 1)
            return (new, delta, it + 1,
                    state[3].at[row].set(delta),
                    state[4].at[row].set(per_shard))
        delta = jnp.abs(new - pr).sum()
        return new, delta, it + 1

    if init_ranks is None:
        pr0 = reset
    else:
        # zero-pad: padded vertices carry exactly 0 rank in every
        # uninterrupted iteration (reset/inv_out/dangling are all 0
        # there), so a zero-padded resume matches it bit-for-bit
        pr0 = jnp.zeros((sg.padded_vertices,), jnp.float32).at[
            : sg.num_vertices
        ].set(init_ranks.astype(jnp.float32))
    state0 = (pr0, jnp.float32(1.0), jnp.int32(0))
    if telemetry:
        state0 = state0 + (
            jnp.zeros((cap,), jnp.float32),
            jnp.zeros((cap, sg.num_shards), jnp.float32),
        )
    out = lax.while_loop(cond, step, state0)
    pr, it_end = out[0], out[2]
    if tripwire_every:
        # Exit check (every=1): a NaN delta FAILS `delta > tol` and ends
        # the loop immediately — often before the K-th iteration check —
        # so the final ranks are always re-guarded before they escape.
        _rank_tripwire(pr, it_end - 1, sg.chunk_size, 1)
    if telemetry:
        return pr[: sg.num_vertices], (out[3], out[4], it_end)
    return pr[: sg.num_vertices]
