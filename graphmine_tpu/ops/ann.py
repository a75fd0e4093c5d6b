"""Approximate kNN: TPU-native IVF-flat (k-means + cluster-probe search).

Why this exists (r5, measured): the exact all-pairs scorer runs at XLA's
row-sort rate — `lax.top_k` on a [1024, 262144] f32 tile reads 1.6G
elem/s against 1.85G for a plain row sort — so no exact implementation
on XLA's sort gets meaningfully faster (docs/DESIGN.md "Exact kNN is at
the sort rate"). The remaining lever is FEWER CANDIDATE PAIRS. IVF-flat
measured 0.95–0.98 recall@32 touching 6–13% of N on Gaussian data — the
WORST case for it (real LOF feature clouds are clustered, which is
exactly what inverted lists exploit).

TPU-first shape discipline — everything the device sees is static:

- **k-means** (:func:`kmeans`): Lloyd iterations where the assignment
  step is the row-tiled `cross_knn` matmul (MXU) and the update is one
  `segment_sum`; empty clusters keep their previous center.
- **Inverted lists** (:func:`_inverted_lists`): the points sorted into
  cluster order, clusters cut into sublists of at most ``l_cap``
  members, every sublist's member row padded to one static ``Lmax``.
  Built on the device, from the probe table where it lies: one program
  sorts and counts, the host fetches two ``[C]`` vectors and two
  scalars, decides the shapes and runs the guards, a second program cuts
  the tables (a NumPy builder held the chip idle 1.7 s of an 11.4 s
  job, PERF.md §6, PR 34; it is the reference in ``tests/test_ann.py``).
- **Cluster-batched search**: each query probes its ``n_probe`` nearest
  centers; (query, sublist) pairs are grouped BY SUBLIST and chopped
  into chunks of one static ``B = 4096`` query slots, so the device runs
  a single ``lax.map`` over chunks of ``[B, F] x [F, Lmax]`` distance
  blocks + a selection of the ``k`` nearest — no irregular
  [N, n_probe * Lmax] gather (XLA's gather runs at ~0.2 G elem/s on this
  chip whatever the table; PERF.md §7.4). A member belongs to exactly
  one cluster, so per-query candidates are duplicate-free by
  construction and the final merge is one selection over
  ``n_probe * k``.
- **Selection** (:func:`_select_k`, PR 28): one stable sort of
  (distance, id) pairs, first ``k`` columns kept. The ids ride the sort,
  so nothing is looked up by position afterwards: ``lax.top_k`` followed
  by ``m_gid[positions]`` spent 3.64 ms a ``[4096, 1024]`` chunk, 2.8 of
  them in the lookup, against 1.26 ms for the sort that carries the ids
  (v5e, PERF.md §6, PR 28), with the same neighbours bit for bit.

The result contract matches :func:`graphmine_tpu.ops.knn.knn`:
``(d2, idx)`` ascending, self excluded — so
:func:`graphmine_tpu.ops.lof.lof_from_knn` consumes it unchanged
(``lof_scores(impl="ivf")``). Shapes (C, n_sub, Lmax, R, p_max) are
data-dependent, so one XLA compile per dataset shape — the same trade
the bucketed LPA plan makes, amortized over every LOF call on that
cloud.

The reference has no kNN at all; this extends the north-star scorer
(BASELINE.json "kNN-graph + LOF") past the all-pairs wall.
"""

from __future__ import annotations

from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.obs.spans import stage_span
from graphmine_tpu.ops.knn import cross_knn


_ASSIGN_TILE = 1 << 15  # [32768, C] distance tiles: 64 MB at C=512
_CHUNK_B = 4096          # query slots of one search chunk
_MERGE_T = 16384         # queries of one merge tile


def default_n_clusters(n: int) -> int:
    """The IVF index's default cluster count for an ``n``-point set:
    ``~sqrt(N)``, rounded to a multiple of 8, min 8. Single owner —
    :func:`ivf_knn`'s default, the streaming re-fit's full-window sizing
    (and its exact-warmup gate ``n < 4 * C``) must size the SAME
    index, or a retune here would silently desync what they
    build/gate."""
    return max(8, int(round(np.sqrt(n) / 8)) * 8)


@jax.jit
def _assign_tiled(points: jax.Array, centers: jax.Array) -> jax.Array:
    """Nearest-center id per point via row-tiled full [T, C] distances
    (one matmul + argmin per tile — no top_k machinery; C is small)."""
    n = points.shape[0]
    n_pad = -(-n // _ASSIGN_TILE) * _ASSIGN_TILE
    with jax.named_scope("ivf"), jax.named_scope("assign"):
        tiles = jnp.pad(points, ((0, n_pad - n), (0, 0))).reshape(
            n_pad // _ASSIGN_TILE, _ASSIGN_TILE, -1
        )
        c_sq = jnp.sum(centers * centers, axis=1)

        def tile(p):
            cross = lax.dot_general(
                p, centers, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=lax.Precision.HIGHEST,
            )
            # |p|^2 is constant per row — argmin doesn't need it
            return jnp.argmin(c_sq[None, :] - 2.0 * cross, axis=1)

        return lax.map(tile, tiles).reshape(n_pad)[:n].astype(jnp.int32)


@jax.jit
def _lloyd_step(points: jax.Array, centers: jax.Array) -> jax.Array:
    a = _assign_tiled(points, centers)
    c = centers.shape[0]
    with jax.named_scope("ivf"), jax.named_scope("lloyd_update"):
        sums = jax.ops.segment_sum(points, a, num_segments=c)
        counts = jax.ops.segment_sum(
            jnp.ones((points.shape[0],), jnp.float32), a, num_segments=c
        )
        return jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None],
            centers,
        )


def kmeans(points, n_clusters: int, iters: int = 5, seed: int = 0):
    """Lloyd k-means, MXU-assigned. Returns float32 centers
    ``[n_clusters, F]``. Deterministic in ``seed`` (init = a seeded
    sample of the points). Iterations are host-unrolled calls of one
    jitted step — a ``lax.scan`` around the tiled assignment hit a
    multi-minute XLA:TPU compile (the r4 scan-nesting pathology class);
    the unrolled form compiles the step once and reuses it."""
    pts = np.asarray(points, np.float32)
    n = pts.shape[0]
    if n_clusters > n:
        raise ValueError(f"n_clusters={n_clusters} > num points {n}")
    rng = np.random.default_rng(seed)
    init = pts[rng.choice(n, n_clusters, replace=False)]
    centers = jnp.asarray(init)
    pts_dev = jnp.asarray(pts)
    for _ in range(iters):
        centers = _lloyd_step(pts_dev, centers)
    return centers


def _select_k(d2, ids, k: int):
    """The ``k`` smallest of each row of ``d2 [R, W]`` with the ids that
    sit beside them in ``ids [R, W]``: ``([R, k] d2 ascending, [R, k]
    ids)``. One stable sort of (distance, id) pairs on the distance
    alone, first ``k`` columns kept: the sort carries the ids, so no id
    is looked up by position afterwards (module docstring, "Selection").

    The order is ``lax.top_k(-d2, k)``'s, ties included, on ANY input:
    among equal distances the lower position comes first, which is what
    ``is_stable=True`` says, so this leans on no invariant of its
    callers. The two-key form (``num_keys=2``, unstable, one operand
    fewer: 0.85 against 1.26 ms a chunk) was measured and dropped: it
    needs position order and id order to agree on every tie, which holds
    inside one search chunk (``_probe_census`` sorts the members of a
    cluster stably, so ``_index_tables`` lays them out in id order) and
    NOT in the merge, where equal distances from two probed
    clusters sit in probe order; it changed 1,494 of 262,144 neighbour
    lists on the pipeline cell's cloud (PERF.md §6, PR 28).

    ``inf`` slots (padding, the self slot, the merge's junk row) sort
    after every finite distance, in position order. A NaN distance sorts
    after ``inf`` (``lax.sort``'s total order), so it is never taken
    ahead of a real candidate; where ``lax.top_k`` put one was the
    backend's choice, and nothing here checks features for NaN."""
    d2_sorted, ids_sorted = lax.sort(
        (d2, ids), dimension=1, num_keys=1, is_stable=True
    )
    return d2_sorted[:, :k], ids_sorted[:, :k]


@partial(jax.jit, static_argnames=("k",))
def _search_clusters(q_vec, q_gid, m_vec, m_gid, m_valid, k: int):
    """One cluster's block: exact distances from its padded query batch
    to its padded member list, the k nearest kept. Shapes: q_vec [Qmax, F],
    m_vec [Lmax, F]; returns ([Qmax, k] d2 asc, [Qmax, k] global ids)."""
    with jax.named_scope("ivf"):
        with jax.named_scope("search_distance"):
            cross = lax.dot_general(
                q_vec, m_vec, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=lax.Precision.HIGHEST,  # the r4 MXU bf16 lesson
            )
            d2 = (
                jnp.sum(q_vec * q_vec, axis=1)[:, None]
                - 2.0 * cross
                + jnp.sum(m_vec * m_vec, axis=1)[None, :]
            )
            d2 = jnp.maximum(d2, 0.0)
            d2 = jnp.where(~m_valid[None, :], jnp.inf, d2)
            d2 = jnp.where(q_gid[:, None] == m_gid[None, :], jnp.inf, d2)  # self
        with jax.named_scope("search_topk"):
            return _select_k(d2, jnp.broadcast_to(m_gid, d2.shape), k)


def _search_chunks(pts, m_gid, m_valid, q_gid, row_sub, k: int):
    """Default (single-device) executor for the cluster-batched search:
    one ``lax.map`` over the fixed-size query chunks. Inputs are the
    host float32 points and the tables :func:`_inverted_lists` built
    (int32 member/query ids and bool member validity, device arrays;
    ``row_sub`` a host vector); returns ``([R, B, k] d2, [R, B, k]
    gid)``. Padded duplicate query slots produce junk rows; they are
    never read back (the take table only maps REAL pairs)."""
    pts_dev = jnp.asarray(pts)
    m_gid_dev = jnp.asarray(m_gid)
    m_valid_dev = jnp.asarray(m_valid)

    def one_chunk(args):
        qg, s = args
        with jax.named_scope("ivf"), jax.named_scope("search_gather"):
            mg = m_gid_dev[s]
            q_vec, m_vec, valid = pts_dev[qg], pts_dev[mg], m_valid_dev[s]
        return _search_clusters(q_vec, qg, m_vec, mg, valid, k)

    return lax.map(one_chunk, (jnp.asarray(q_gid), jnp.asarray(row_sub)))


def _exact_fallback(pts, k, guard: str, detail: str, sink):
    """The honest exit when an IVF pathology guard trips: run the exact
    path — but LOUDLY (ADVICE r5). The silent version cost a round of
    triage: 'ivf' timings that were secretly exact-path timings.
    ``guard`` names which guard fired; the warning + ``ivf_fallback``
    metrics record carry it."""
    import warnings

    from graphmine_tpu.ops.knn import knn as exact_knn

    warnings.warn(
        f"ivf_knn guard {guard!r} tripped ({detail}); falling back to the "
        "exact kNN path",
        stacklevel=3,
    )
    if sink is not None:
        sink.emit("ivf_fallback", guard=guard, detail=detail)
    with stage_span(sink, "knn_exact", n=len(pts), k=k) as stage:
        return stage.sync(exact_knn(pts, k, impl="auto"))


class _GuardTripped(Exception):
    """An IVF pathology guard fired while the inverted lists were being
    built: carries what :func:`_exact_fallback` reports."""

    def __init__(self, guard: str, detail: str):
        super().__init__(guard, detail)
        self.guard, self.detail = guard, detail


def ivf_knn(
    points,
    k: int,
    n_clusters: int | None = None,
    n_probe: int = 16,
    seed: int = 0,
    kmeans_iters: int = 5,
    sink=None,
    centers=None,
    search_exec=None,
):
    """Approximate k nearest neighbors (IVF-flat). ``(d2, idx)`` like
    :func:`~graphmine_tpu.ops.knn.knn`: ``[N, k]`` ascending squared
    distances, self excluded, float32/int32.

    ``n_clusters`` defaults to ``~sqrt(N)`` (rounded to a multiple of 8,
    min 8); ``n_probe`` nearest clusters are searched per query —
    recall rises with ``n_probe / n_clusters`` (measured 0.95–0.98 at
    6–13% candidate fraction on Gaussian clouds; r-series, no chip
    record). Falls back to the exact
    path when the cloud is too small for the machinery to pay
    (``N < 4 * n_clusters`` or ``k >= Lmax`` after clustering); pathology
    guards (capacity / probe skew / chunk-index bound) also fall back,
    each with a ``warnings.warn`` and — when ``sink`` (a
    :class:`~graphmine_tpu.pipeline.metrics.MetricsSink`) is given — an
    ``ivf_fallback`` record naming the guard (ADVICE r5).

    ``centers`` (r6): pre-trained float32 ``[C, F]`` k-means centers —
    skips the Lloyd iterations entirely (the expensive part of index
    construction) and only re-assigns points against them. The streaming
    LOF scorer reuses one trained index across sliding windows this way
    (centroids are stable between chunks; see
    :class:`~graphmine_tpu.ops.streaming_lof.StreamingLOF`).

    ``search_exec`` (r6): overrides the device executor for the
    cluster-batched search stage — ``(pts, m_gid, m_valid, q_gid,
    row_sub, k) -> (d2_all, gid_all)`` of shape ``[R', B, k]`` with
    ``R' >= R`` chunk rows (extra padded rows appended at the END are
    sliced off; their results are never read). ``pts`` and ``row_sub``
    are host arrays; ``m_gid``, ``m_valid`` and ``q_gid`` are arrays on
    the default device (``np.asarray`` fetches one, as the mesh executor
    does to pad its rows). The mesh-sharded LOF path
    distributes exactly this stage — the dominant distance work — over
    devices (:func:`graphmine_tpu.parallel.knn.sharded_lof`).
    """
    pts = np.asarray(points, np.float32)
    n, f = pts.shape
    if not 0 < k < n:
        raise ValueError(f"k={k} must be in (0, {n})")
    if centers is not None:
        centers = jnp.asarray(np.asarray(centers, np.float32))
        if centers.ndim != 2 or centers.shape[1] != f:
            raise ValueError(
                f"centers must be [C, {f}], got {tuple(centers.shape)}"
            )
        n_clusters = int(centers.shape[0])
    elif n_clusters is None:
        n_clusters = default_n_clusters(n)
    n_probe = min(n_probe, n_clusters)
    from graphmine_tpu.ops.knn import knn as exact_knn

    if n < 4 * n_clusters:
        # documented sizing fallback, not a pathology guard: tiny clouds
        # route to the exact path by design, no warning
        with stage_span(sink, "knn_exact", n=n, k=k) as stage:
            return stage.sync(exact_knn(pts, k, impl="auto"))

    if centers is None:
        with stage_span(
            sink, "ivf_train", n=n, k=k, n_clusters=n_clusters
        ) as stage:
            centers = stage.sync(
                kmeans(pts, n_clusters, iters=kmeans_iters, seed=seed)
            )
    # probe assignment: each query's n_probe nearest centers; column 0
    # is the owning cluster (a point is always a member of its own
    # nearest cluster's list).
    with stage_span(sink, "ivf_probe", n=n, n_clusters=n_clusters) as stage:
        probe = stage.sync(cross_knn(jnp.asarray(pts), centers, n_probe)[1])
    try:
        with stage_span(sink, "ivf_lists") as stage:
            lists = _inverted_lists(probe, k, n_clusters)
            stage.note(**lists.counts, host_bytes=lists.host_bytes)
            stage.sync((lists.m_gid, lists.m_valid, lists.q_gid, *lists.slots))
    except _GuardTripped as tripped:
        return _exact_fallback(pts, k, tripped.guard, tripped.detail, sink)
    del probe

    r_rows, chunk_b, p_max = lists.r_rows, lists.chunk_b, lists.p_max
    exec_fn = search_exec if search_exec is not None else _search_chunks
    with stage_span(
        sink, "ivf_search", n_pairs=lists.n_pairs, chunk_rows=r_rows, k=k
    ) as stage:
        d2_all, gid_all = stage.sync(exec_fn(
            pts, lists.m_gid, lists.m_valid, lists.q_gid, lists.row_sub, k
        ))
    # the merge's half of the index: ivf_lists again, the take table built
    # on the device (nothing fetched, nothing handed on as a host array).
    with stage_span(sink, "ivf_lists", p_max=p_max, host_bytes=0) as stage:
        if d2_all.shape[0] < r_rows or d2_all.shape != (
            d2_all.shape[0], chunk_b, k
        ) or gid_all.shape != d2_all.shape:
            # a short/misshapen executor result would otherwise clamp real
            # pair indices onto the junk row in the merge gather — degraded
            # results with no error. Fail loudly instead.
            raise ValueError(
                f"search_exec returned shapes {tuple(d2_all.shape)}/"
                f"{tuple(gid_all.shape)}; expected [R'>= {r_rows}, "
                f"{chunk_b}, {k}] with extra rows appended at the end"
            )
        junk = r_rows * chunk_b
        take = stage.sync(_take_table(
            *lists.slots, p_max=p_max, junk=junk, merge_t=_MERGE_T
        ))
        # the concatenates below are where the job's device memory peaks
        # (the search results, their flat copies and a slice on its way
        # into one, 2.7 GB each at 262K x 128; PERF.md §6, PR 34): of the
        # index only the take table is still alive there
        del lists
        # [R', B, k] -> per-pair rows -> tiled [T, p_max * k] merges (one
        # monolithic [N, p_max * k] gather + top_k would hold ~4 GB of
        # merge operands at 262K x 16 x 128). Queries with fewer than
        # p_max pairs pad with the appended all-inf junk row: never
        # selected. The slice to r_rows * chunk_b drops any
        # executor-padded chunk rows (a mesh executor pads R to a
        # device-count multiple) AND pins the junk-row sentinel id below
        # at the same flat index either way.
        d2_flat = jnp.concatenate(
            [d2_all.reshape(-1, k)[:junk],
             jnp.full((1, k), jnp.inf, d2_all.dtype)]
        )
        gid_flat = jnp.concatenate(
            [gid_all.reshape(-1, k)[:junk], jnp.full((1, k), -1, jnp.int32)]
        )
        stage.sync((d2_flat, gid_flat))

    # NB: the flat result arrays are jit ARGUMENTS, not closure captures
    # — a closed-over concrete array is baked into the HLO as a constant,
    # and serializing the ~GB-scale [R * B, k] buffers hung XLA:TPU
    # compilation for minutes (found the hard way, r5).
    n_pad = take.shape[0] * take.shape[1]
    with stage_span(sink, "ivf_merge", n=n, k=k, p_max=p_max) as stage:
        d2_out, gid_out = _merge_tiles(d2_flat, gid_flat, take, k)
        return stage.sync((
            d2_out.reshape(n_pad, k)[:n],
            gid_out.reshape(n_pad, k)[:n],
        ))


def _exclusive_cumsum(counts):
    return np.cumsum(counts) - counts


def _inverted_lists(probe, k: int, n_clusters: int):
    """The index, from the probe table where it lies on the device: the
    member and query tables the search executor takes and, per probed
    (query, cluster) cell, where the pair's result rows will lie (what
    :func:`_take_table` turns into the merge's take table). Two jitted
    programs build every table of ``n`` or ``n_pairs`` rows; between
    them the host fetches two ``[C]`` vectors and two scalars, runs the
    four pathology guards (raising :class:`_GuardTripped`) and does the
    O(C + R) bookkeeping that decides the shapes."""
    n, n_probe = probe.shape
    chunk_b = _CHUNK_B
    if n * n_probe >= (1 << 31):
        # every pair takes a result row, so the row ids below pass the
        # int32 bound as well; and the pairs' own ids would not fit
        raise _GuardTripped(
            "index_bound",
            f"merge-gather row ids reach {n * n_probe:,} >= 2^31 "
            "(int32 device gather would wrap)",
        )
    # ---- SIZE-CAPPED inverted sublists ---------------------------------
    # k-means on clustered data skews hard (one blob -> one giant
    # cluster); an uncapped member matrix sets Lmax = that cluster's
    # size, and every chunk probing it pays [B, Lmax] distance + top_k
    # work — measured WORSE than exact at 262K on 64-blob data. Big
    # clusters are split into sublists of at most l_cap members; a query
    # probing the cluster searches all of its sublists (pairs expand
    # accordingly; the per-query merge pads to the max pair count).
    l_cap = max(2 * (-(-n // n_clusters)), k + 1)
    order, pair_cell, probe_subs, *small = _probe_census(
        probe, n_clusters=n_clusters, l_cap=l_cap
    )
    small = jax.device_get(small)
    sizes, c_queries = (v.astype(np.int64) for v in small[:2])
    probed_min, p_max = (int(v) for v in small[2:])
    starts = _exclusive_cumsum(sizes)
    n_subs_per_c = np.maximum(-(-sizes // l_cap), 1)
    n_sub = int(n_subs_per_c.sum())
    sub_cluster = np.repeat(np.arange(n_clusters), n_subs_per_c)
    sub_first = _exclusive_cumsum(n_subs_per_c)
    sub_rank = np.arange(n_sub) - sub_first[sub_cluster]
    sub_start = starts[sub_cluster] + sub_rank * l_cap
    sub_len = np.clip(sizes[sub_cluster] - sub_rank * l_cap, 0, l_cap)
    l_max = int(sub_len.max())
    if k >= sizes.max():
        # no cluster can fill its own top-k; recall craters — the honest
        # move is the exact path.
        raise _GuardTripped(
            "k_unfillable",
            f"k={k} >= largest cluster size {int(sizes.max())}",
        )
    # Two pathology guards (code-review r5), both -> honest exact path:
    #
    # 1. CAPACITY: a query whose probed clusters hold < k+1 members
    #    total cannot fill its top-k; the inf-padded slots would reach
    #    lof_from_knn, whose duplicate-floor eps reads dists.sum() —
    #    one inf row silently zeroes EVERY LOF score.
    # 2. SKEW: one dominant cluster (k-means found no real structure)
    #    expands into ~size/l_cap sublists per probe; the pair tables
    #    and [n_pairs, k] result buffers then scale with that skew —
    #    the same blowup class the sublist cap fixed on the member
    #    side. IVF has nothing to exploit on such a cloud anyway.
    if probed_min < k + 1:
        raise _GuardTripped(
            "capacity",
            f"a query's probed clusters hold {probed_min} "
            f"members < k+1={k + 1} (its top-k cannot fill)",
        )
    if p_max > 4 * n_probe:
        raise _GuardTripped(
            "skew",
            f"probe expansion {p_max} sublists/query > 4*n_probe="
            f"{4 * n_probe} (one dominant cluster; IVF has no structure "
            "to exploit)",
        )
    # (query, sublist) pairs grouped by sublist, then chopped into
    # FIXED-size chunks of B query slots: one hot sublist probed by half
    # the queries would otherwise set a padded [Qmax] batch shape and an
    # O(n_sub x Qmax x k) result — the first 262K run OOMed exactly
    # there. Chunk rows bound the device working set independent of
    # probe skew. The sublists of one cluster are probed by the same
    # queries, so the device sorted the pairs by CLUSTER (n * n_probe
    # keys, not n_pairs) and a sublist's chunks are runs of its
    # cluster's segment of that order.
    q_counts = c_queries[sub_cluster]
    n_pairs = int(q_counts.sum())
    chunks_per_s = -(-q_counts // chunk_b)       # ceil; 0 for unprobed
    r_rows = int(chunks_per_s.sum())
    # Loud int32 bound (ADVICE r5): the merge-gather take table indexes
    # the flat [r_rows * chunk_b + 1] result rows in int32 on the device
    # — a row id past 2^31-1 would wrap to a junk gather instead of
    # failing. The junk-row sentinel id r_rows * chunk_b is the largest
    # value stored.
    if r_rows * chunk_b >= (1 << 31):
        raise _GuardTripped(
            "index_bound",
            f"merge-gather row ids reach {r_rows * chunk_b:,} >= 2^31 "
            "(int32 device gather would wrap)",
        )
    row_sub = np.repeat(np.arange(n_sub), chunks_per_s)
    chunk_first = _exclusive_cumsum(chunks_per_s)
    chunk_rank = np.arange(r_rows) - np.repeat(chunk_first, chunks_per_s)
    c_start = _exclusive_cumsum(c_queries)
    row_start = c_start[sub_cluster[row_sub]] + chunk_rank * chunk_b
    row_len = np.minimum(q_counts[row_sub] - chunk_rank * chunk_b, chunk_b)
    # Valid (row, slot) cells in row-major order visit the pairs in
    # sublist order (chunks ascend within each ascending sublist), so a
    # pair's flat [R * B] result row is the first cell of its sublist's
    # first chunk plus the query's rank among the cluster's probers; a
    # cluster's sublists have the same chunk count, `stride` cells apart.
    c_base = chunk_first[sub_first] * chunk_b - c_start
    c_stride = chunks_per_s[sub_first] * chunk_b
    row_sub = row_sub.astype(np.int32)
    offsets = [a.astype(np.int32) for a in (
        sub_start, sub_len, row_start, row_len, c_base, c_stride
    )]
    m_gid, m_valid, q_gid, slot_base, slot_stride = _index_tables(
        order, pair_cell, probe, *offsets, l_max=l_max, chunk_b=chunk_b
    )
    return SimpleNamespace(
        m_gid=m_gid, m_valid=m_valid, q_gid=q_gid, row_sub=row_sub,
        r_rows=r_rows, chunk_b=chunk_b, p_max=p_max, n_pairs=n_pairs,
        slots=(probe_subs, slot_base, slot_stride),
        counts=dict(n_sub=n_sub, l_max=l_max, n_pairs=n_pairs, p_max=p_max,
                    chunk_rows=r_rows),
        host_bytes=sum(a.nbytes for a in [*small, *offsets, row_sub]),
    )


@partial(jax.jit, static_argnames=("n_clusters", "l_cap"))
def _probe_census(probe, n_clusters: int, l_cap: int):
    """What the host needs of the probe table ``[n, n_probe]`` to decide
    the index's shapes, and the two orders its tables are cut from:

    - ``order [n]``: the points in cluster order, ascending id inside a
      cluster (a stable sort of column 0, the owning cluster);
    - ``pair_cell [n * n_probe]``: the flat (query, probe column) cells
      in cluster order, query-ascending inside a cluster (a stable sort
      on the cluster id alone);
    - ``probe_subs [n, n_probe]``: sublists behind each probed cluster;
    - ``sizes [C]``, ``c_queries [C]``: members of, and queries probing,
      each cluster; the fewest members any query's probes hold; the most
      sublists any query probes (``p_max``).
    """
    n, n_probe = probe.shape
    with jax.named_scope("ivf"), jax.named_scope("lists_census"):
        edges = jnp.arange(n_clusters + 1, dtype=jnp.int32)

        def by_cluster(keys):
            keys, cells = lax.sort(
                (keys, jnp.arange(keys.shape[0], dtype=jnp.int32)),
                num_keys=1, is_stable=True,
            )
            return cells, jnp.diff(jnp.searchsorted(keys, edges))

        order, sizes = by_cluster(probe[:, 0])
        pair_cell, c_queries = by_cluster(probe.reshape(-1))
        probe_sizes = sizes[probe]
        probe_subs = jnp.maximum(-(-probe_sizes // l_cap), 1)
        return (
            order, pair_cell, probe_subs, sizes, c_queries,
            probe_sizes.sum(axis=1).min(), probe_subs.sum(axis=1).max(),
        )


def _runs(flat, start, length, width: int):
    """``[R, width]`` rows of ``flat``: row ``r`` is the run
    ``flat[start[r] : start[r] + length[r]]``, its tail repeating the
    run's last element (an empty run repeats ``flat[start[r]]``, clamped
    into ``flat``); with the mask of real cells."""
    last = flat[jnp.minimum(
        start + jnp.maximum(length - 1, 0), flat.shape[0] - 1
    )]
    padded = jnp.pad(flat, (0, width))
    rows = jax.vmap(lambda s: lax.dynamic_slice(padded, (s,), (width,)))(start)
    real = jnp.arange(width)[None, :] < length[:, None]
    return jnp.where(real, rows, last[:, None]), real


@partial(jax.jit, static_argnames=("l_max", "chunk_b"))
def _index_tables(
    order, pair_cell, probe, sub_start, sub_len, row_start, row_len,
    c_base, c_stride, l_max: int, chunk_b: int,
):
    """The tables of ``n`` and ``n_pairs`` rows, cut from
    :func:`_probe_census`'s two orders at the offsets the host worked
    out: member ids ``[n_sub, l_max]`` with their validity, the chunked
    query ids ``[R, chunk_b]`` (padded slots repeat the chunk's last
    real query), and per (query, probe column) cell the flat result row
    of its pair in the cluster's first sublist and the distance to the
    same query's row in the next."""
    n, n_probe = probe.shape
    with jax.named_scope("ivf"), jax.named_scope("lists_tables"):
        m_gid, m_valid = _runs(order, sub_start, sub_len, l_max)
        q_gid, _ = _runs(pair_cell // n_probe, row_start, row_len, chunk_b)
        # a cell's rank in cluster order: the inverse permutation, by a
        # sort (the keys are distinct) where a scatter is 3x slower
        _, rank = lax.sort(
            (pair_cell, jnp.arange(n * n_probe, dtype=jnp.int32)), num_keys=1
        )
        slot_base = rank.reshape(n, n_probe) + c_base[probe]
        return m_gid, m_valid, q_gid, slot_base, c_stride[probe]


@partial(jax.jit, static_argnames=("p_max", "junk", "merge_t"))
def _take_table(probe_subs, slot_base, slot_stride, p_max: int, junk: int,
                merge_t: int):
    """The merge's take table ``[T, merge_t, p_max]``: each query's flat
    result rows in probe-column order, a split cluster's sublists
    ascending inside its column; columns past the query's pairs, and the
    rows that pad ``n`` to whole tiles, hold the ``junk`` row."""
    n = probe_subs.shape[0]
    with jax.named_scope("ivf"), jax.named_scope("lists_take"):
        col_start = jnp.cumsum(probe_subs, axis=1) - probe_subs
        # [n, p_max, n_probe]: rank of column j inside each probed
        # cluster's span of columns; exactly one cluster holds a real j
        t = jnp.arange(p_max)[None, :, None] - col_start[:, None, :]
        hit = (t >= 0) & (t < probe_subs[:, None, :])
        rows = slot_base[:, None, :] + t * slot_stride[:, None, :]
        take = jnp.where(
            hit.any(axis=2), jnp.where(hit, rows, 0).sum(axis=2), junk
        )
        n_pad = -(-n // merge_t) * merge_t
        take = jnp.pad(take, ((0, n_pad - n), (0, 0)), constant_values=junk)
        return take.reshape(n_pad // merge_t, merge_t, p_max)


@partial(jax.jit, static_argnames=("k",))
def _merge_tiles(d2_flat, gid_flat, take_tiles, k: int):
    """Per-query merge: gather each tile's pair rows, one selection of
    the k nearest among the ``p_max * k`` candidates (duplicate-free:
    every member belongs to exactly one sublist)."""
    merge_t, p_max = take_tiles.shape[1], take_tiles.shape[2]

    def tile(tk):
        with jax.named_scope("ivf"):
            with jax.named_scope("merge_gather"):
                d2_t = d2_flat[tk].reshape(merge_t, p_max * k)
                gid_t = gid_flat[tk].reshape(merge_t, p_max * k)
            with jax.named_scope("merge_topk"):
                return _select_k(d2_t, gid_t, k)

    return lax.map(tile, take_tiles)
