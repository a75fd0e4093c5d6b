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
- **Inverted lists**: points are permuted host-side into cluster order,
  every cluster's member row padded to one static ``Lmax``.
- **Cluster-batched search**: each query probes its ``n_probe`` nearest
  centers; (query, cluster) pairs are grouped BY CLUSTER host-side and
  padded to one static ``Qmax``, so the device runs a single
  ``lax.map`` over clusters of ``[Qmax, F] x [F, Lmax]`` distance
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
(``lof_scores(impl="ivf")``). Shapes (C, Qmax, Lmax) are data-dependent,
so one XLA compile per dataset shape — the same trade the bucketed LPA
plan makes, amortized over every LOF call on that cloud.

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


def default_n_clusters(n: int) -> int:
    """The IVF index's default cluster count for an ``n``-point set:
    ``~sqrt(N)``, rounded to a multiple of 8, min 8. Single owner —
    :func:`ivf_knn`'s default, the streaming re-fit's full-window sizing
    (and its exact-warmup gate ``n < 4 * C``), and the stream bench's
    reuse micro-bench must all size the SAME index, or a retune here
    would silently desync what they build/gate/measure."""
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
    inside one search chunk (``_inverted_lists`` lays members out in id
    order) and NOT in the merge, where equal distances from two probed
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
    one ``lax.map`` over the fixed-size query chunks. Inputs are the host
    tables :func:`ivf_knn` built (float32 points, int32 member/query ids,
    bool member validity); returns ``([R, B, k] d2, [R, B, k] gid)``.
    Padded duplicate query slots produce junk rows; they are never read
    back (``slot_of_pair`` only maps REAL pairs)."""
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
    bench triage: 'ivf' timings that were secretly exact-path timings.
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
    6–13% candidate fraction on Gaussian clouds; the bench lof tier
    records recall on its real feature cloud). Falls back to the exact
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
    sliced off; their results are never read). The mesh-sharded LOF path
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
    with stage_span(sink, "ivf_probe", n=n, n_clusters=n_clusters):
        _, probe = cross_knn(jnp.asarray(pts), centers, n_probe)
        probe = np.asarray(probe)
    try:
        with stage_span(sink, "ivf_lists") as stage:
            lists = _inverted_lists(pts, k, probe, n_clusters, n_probe)
            stage.note(**lists.counts)
    except _GuardTripped as tripped:
        return _exact_fallback(pts, k, tripped.guard, tripped.detail, sink)

    r_rows, chunk_b, p_max = lists.r_rows, lists.chunk_b, lists.p_max
    exec_fn = search_exec if search_exec is not None else _search_chunks
    with stage_span(
        sink, "ivf_search", n_pairs=lists.n_pairs, chunk_rows=r_rows, k=k
    ) as stage:
        d2_all, gid_all = stage.sync(exec_fn(
            pts, lists.m_gid, lists.m_valid, lists.q_gid, lists.row_sub, k
        ))
    # the host half of the merge: ivf_lists again. The two concatenates
    # it dispatches run on the device while the host builds the take
    # table, so this span does not wait for them (ivf_merge does).
    with stage_span(sink, "ivf_lists", p_max=p_max):
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
        # [R', B, k] -> per-pair rows -> tiled [T, p_max * k] merges (one
        # monolithic [N, p_max * k] gather + top_k would hold ~4 GB of
        # merge operands at 262K x 16 x 128). Queries with fewer than
        # p_max pairs pad with the appended all-inf junk row: never
        # selected. The slice to r_rows * chunk_b drops any
        # executor-padded chunk rows (a mesh executor pads R to a
        # device-count multiple) AND pins the junk-row sentinel id below
        # at the same flat index either way.
        d2_flat = jnp.concatenate(
            [d2_all.reshape(-1, k)[: r_rows * chunk_b],
             jnp.full((1, k), jnp.inf, d2_all.dtype)]
        )
        gid_flat = jnp.concatenate(
            [gid_all.reshape(-1, k)[: r_rows * chunk_b],
             jnp.full((1, k), -1, jnp.int32)]
        )
        junk = r_rows * chunk_b
        merge_t = 16384
        n_pad = -(-n // merge_t) * merge_t
        take = np.full((n_pad, p_max), junk, np.int64)
        pairs_per_q = lists.pairs_per_q
        pair_col = (
            np.arange(lists.n_pairs)
            - np.repeat(np.cumsum(pairs_per_q) - pairs_per_q, pairs_per_q)
        )
        take[lists.pair_q, pair_col] = lists.slot_of_pair
        # Explicit int32, not an implicit jnp downcast: the bound above
        # guarantees every row id (junk sentinel included) fits, and the
        # cast states the invariant instead of relying on x64-mode
        # defaults.
        take = take.astype(np.int32).reshape(n_pad // merge_t, merge_t, p_max)

    # NB: the flat result arrays are jit ARGUMENTS, not closure captures
    # — a closed-over concrete array is baked into the HLO as a constant,
    # and serializing the ~GB-scale [R * B, k] buffers hung XLA:TPU
    # compilation for minutes (found the hard way, r5).
    with stage_span(sink, "ivf_merge", n=n, k=k, p_max=p_max) as stage:
        d2_out, gid_out = _merge_tiles(d2_flat, gid_flat, jnp.asarray(take), k)
        return stage.sync((
            d2_out.reshape(n_pad, k)[:n],
            gid_out.reshape(n_pad, k)[:n],
        ))


def _inverted_lists(pts, k: int, probe, n_clusters: int, n_probe: int):
    """Host side of the index, all NumPy: from the fetched probe table
    to the member and query tables the search executor takes and the
    pair bookkeeping the merge needs. Raises :class:`_GuardTripped` when
    a pathology guard fires."""
    n = len(pts)
    assign = probe[:, 0]
    # ---- host: SIZE-CAPPED inverted sublists ---------------------------
    # k-means on clustered data skews hard (one blob -> one giant
    # cluster); an uncapped member matrix sets Lmax = that cluster's
    # size, and every chunk probing it pays [B, Lmax] distance + top_k
    # work — measured WORSE than exact at 262K on 64-blob data. Big
    # clusters are split into sublists of at most l_cap members; a query
    # probing the cluster searches all of its sublists (pairs expand
    # accordingly; the per-query merge pads to the max pair count).
    order = np.argsort(assign, kind="stable")     # members in cluster order
    sizes = np.bincount(assign, minlength=n_clusters)
    starts = np.zeros(n_clusters, np.int64)
    np.cumsum(sizes[:-1], out=starts[1:])
    l_cap = max(2 * (-(-n // n_clusters)), k + 1)
    n_subs_per_c = np.maximum(-(-sizes // l_cap), 1)
    n_sub = int(n_subs_per_c.sum())
    sub_cluster = np.repeat(np.arange(n_clusters), n_subs_per_c)
    sub_first = np.zeros(n_clusters, np.int64)
    np.cumsum(n_subs_per_c[:-1], out=sub_first[1:])
    sub_rank = np.arange(n_sub) - sub_first[sub_cluster]
    sub_start = starts[sub_cluster] + sub_rank * l_cap
    sub_len = np.minimum(sizes[sub_cluster] - sub_rank * l_cap, l_cap)
    sub_len = np.maximum(sub_len, 0)
    l_max = int(sub_len.max())
    if k >= sizes.max():
        # no cluster can fill its own top-k; recall craters — the honest
        # move is the exact path.
        raise _GuardTripped(
            "k_unfillable",
            f"k={k} >= largest cluster size {int(sizes.max())}",
        )
    # member id matrix [n_sub, Lmax] (clamps keep empty sublists
    # in-bounds; their rows are fully masked)
    j = np.arange(l_max)
    m_rows = sub_start[:, None] + np.minimum(
        j[None, :], np.maximum(sub_len[:, None] - 1, 0)
    )
    m_gid = order[np.minimum(m_rows, n - 1)].astype(np.int32)
    m_valid = j[None, :] < sub_len[:, None]

    # (query, sublist) pairs grouped by sublist, then chopped into
    # FIXED-size chunks of B query slots: one hot sublist probed by half
    # the queries would otherwise set a padded [Qmax] batch shape and an
    # O(n_sub x Qmax x k) result — the first 262K run OOMed exactly
    # there. Chunk rows bound the device working set independent of
    # probe skew.
    chunk_b = 4096
    probe_subs = n_subs_per_c[probe]              # [N, p] sublists/probe
    pairs_per_q = probe_subs.sum(axis=1)          # [N]
    p_max = int(pairs_per_q.max())

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
    probed_sizes = sizes[probe].sum(axis=1)       # members across probes
    if int(probed_sizes.min()) < k + 1:
        raise _GuardTripped(
            "capacity",
            f"a query's probed clusters hold {int(probed_sizes.min())} "
            f"members < k+1={k + 1} (its top-k cannot fill)",
        )
    if p_max > 4 * n_probe:
        raise _GuardTripped(
            "skew",
            f"probe expansion {p_max} sublists/query > 4*n_probe="
            f"{4 * n_probe} (one dominant cluster; IVF has no structure "
            "to exploit)",
        )
    pair_q = np.repeat(
        np.arange(n, dtype=np.int64), pairs_per_q
    )
    # expand each probed cluster c into sub_first[c] .. +n_subs_per_c[c]
    flat_c = probe.reshape(-1).astype(np.int64)
    flat_q_subs = probe_subs.reshape(-1)
    pair_c = (
        np.repeat(sub_first[flat_c], flat_q_subs)
        + (
            np.arange(int(flat_q_subs.sum()))
            - np.repeat(
                np.cumsum(flat_q_subs) - flat_q_subs, flat_q_subs
            )
        )
    )
    n_pairs = len(pair_q)
    pair_order = np.argsort(pair_c, kind="stable")
    q_counts = np.bincount(pair_c, minlength=n_sub)
    q_starts = np.zeros(n_sub, np.int64)
    np.cumsum(q_counts[:-1], out=q_starts[1:])
    chunks_per_s = -(-q_counts // chunk_b)       # ceil; 0 for unprobed
    r_rows = int(chunks_per_s.sum())
    # Loud int32 bound (ADVICE r5): the merge-gather take table indexes
    # the flat [r_rows * chunk_b + 1] result rows, and jnp.asarray would
    # SILENTLY downcast an int64 host table to int32 on device — a row id
    # past 2^31-1 would wrap to a junk gather instead of failing. The
    # junk-row sentinel id r_rows * chunk_b is the largest value stored.
    if r_rows * chunk_b >= (1 << 31):
        raise _GuardTripped(
            "index_bound",
            f"merge-gather row ids reach {r_rows * chunk_b:,} >= 2^31 "
            "(int32 device gather would wrap)",
        )
    row_sub = np.repeat(np.arange(n_sub), chunks_per_s)
    chunk_rank = (
        np.arange(r_rows) - np.repeat(
            np.cumsum(chunks_per_s) - chunks_per_s, chunks_per_s
        )
    )
    row_start = q_starts[row_sub] + chunk_rank * chunk_b
    row_len = np.minimum(
        q_counts[row_sub] - chunk_rank * chunk_b, chunk_b
    )
    jb = np.arange(chunk_b)
    q_rows = row_start[:, None] + np.minimum(
        jb[None, :], np.maximum(row_len[:, None] - 1, 0)
    )
    q_valid = jb[None, :] < row_len[:, None]
    q_gid = pair_q[pair_order[q_rows]].astype(np.int32)  # [R, B]

    # inverse mapping: valid (row, slot) cells in row-major order visit
    # sorted pair positions 0..P-1 in order (chunks ascend within each
    # ascending sublist), so each REAL pair's flat [R * B] result row is
    # its valid-cell flat index.
    slot_of_pair = np.empty(n_pairs, np.int64)
    slot_of_pair[pair_order] = np.arange(
        r_rows * chunk_b
    ).reshape(r_rows, chunk_b)[q_valid]
    return SimpleNamespace(
        m_gid=m_gid, m_valid=m_valid, q_gid=q_gid,
        row_sub=row_sub.astype(np.int32), r_rows=r_rows, chunk_b=chunk_b,
        p_max=p_max, n_pairs=n_pairs, pair_q=pair_q, pairs_per_q=pairs_per_q,
        slot_of_pair=slot_of_pair,
        counts=dict(n_sub=n_sub, l_max=l_max, n_pairs=n_pairs, p_max=p_max,
                    chunk_rows=r_rows),
    )


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
