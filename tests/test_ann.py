"""IVF-flat approximate kNN (r5): contract, recall, determinism, and the
size-capped sublist machinery.

Exactness is NOT the contract — recall is. The bounds here are 3x-slack
versions of measured values (gaussian 0.977, blobs 0.9999 at the default
knobs) so a structural regression (broken inversion, leaked junk rows,
wrong merge mapping) fails loudly while backend float jitter does not.
"""

import numpy as np
import pytest

from graphmine_tpu.ops.ann import ivf_knn, kmeans
from graphmine_tpu.ops.knn import knn

pytestmark = pytest.mark.ann  # the --ann-only tier-1 lane


@pytest.fixture(scope="module")
def clouds():
    rng = np.random.default_rng(1)
    n, f = 20000, 8
    gauss = rng.normal(size=(n, f)).astype(np.float32)
    blob_c = rng.normal(size=(8, f)).astype(np.float32) * 3
    blobs = (
        blob_c[rng.integers(0, 8, n)]
        + rng.normal(size=(n, f)).astype(np.float32)
    )
    return {"gauss": gauss, "blobs": blobs}


def _recall(exact_idx, got_idx, k):
    return np.mean([
        len(set(exact_idx[i]) & set(got_idx[i])) / k
        for i in range(len(exact_idx))
    ])


@pytest.mark.parametrize("cloud", ["gauss", "blobs"])
def test_ivf_contract_and_recall(clouds, cloud):
    pts = clouds[cloud]
    n, k = pts.shape[0], 32
    exact_i = np.asarray(knn(pts, k=k, impl="xla")[1])
    d2, gid = ivf_knn(pts, k=k, n_probe=16)
    d2, gid = np.asarray(d2), np.asarray(gid)
    # contract: ascending distances, self excluded, real ids only (a
    # leaked merge-padding junk row would surface as -1)
    assert (np.diff(d2, axis=1) >= -1e-6).all()
    assert (gid != np.arange(n)[:, None]).all()
    assert ((gid >= 0) & (gid < n)).all()
    # returned distances are EXACT for the returned candidates
    for i in range(0, n, 997):
        dd = ((pts[i] - pts[gid[i]]) ** 2).sum(-1)
        np.testing.assert_allclose(dd, d2[i], rtol=1e-4, atol=1e-4)
    # recall: measured 0.977 (gauss — the worst case for IVF) and 0.9999
    # (blobs); assert with slack
    rec = _recall(exact_i, gid, k)
    assert rec > (0.9 if cloud == "gauss" else 0.99), rec
    # determinism: same seed, same index
    _, gid2 = ivf_knn(pts, k=k, n_probe=16)
    np.testing.assert_array_equal(gid, np.asarray(gid2))


def test_ivf_sublist_capping_on_skewed_clusters():
    """Moderate skew (one cluster a few multiples of l_cap): the capped
    sublists (the fix for the 262K first-run blowup) stay on the FAST
    path and must return correct, junk-free results with high recall."""
    rng = np.random.default_rng(3)
    n, f, k = 12000, 8, 16
    # ~40% of mass in one tight blob: its k-means cluster splits into a
    # handful of sublists (> 1, below the 4x-probe skew fallback)
    tight = rng.normal(size=(int(n * 0.4), f)).astype(np.float32) * 0.1
    rest = rng.normal(size=(n - tight.shape[0], f)).astype(np.float32) * 5
    pts = np.concatenate([tight, rest]).astype(np.float32)
    exact_i = np.asarray(knn(pts, k=k, impl="xla")[1])
    d2, gid = ivf_knn(pts, k=k, n_clusters=16, n_probe=8)
    d2, gid = np.asarray(d2), np.asarray(gid)
    assert ((gid >= 0) & (gid < n)).all() and (gid != np.arange(n)[:, None]).all()
    assert (np.diff(d2, axis=1) >= -1e-6).all()
    assert _recall(exact_i, gid, k) > 0.9


def test_ivf_pathological_skew_falls_back_to_exact():
    """A cloud k-means cannot structure must take the exact path — the
    approximate machinery would otherwise blow up its pair tables
    (code-review r5) or leak inf rows into LOF, which zeroes EVERY score
    through the duplicate-floor eps. The natural trigger is DUPLICATE
    rows (discrete graph features are full of them): every duplicate
    ties its center assignment to the same argmin winner, so one cluster
    absorbs them all and its sublist expansion blows past the 4x-probe
    skew bound. (A merely *dense* blob does NOT trigger this — sampled
    k-means init drops ~90% of centers inside it and splits it fine,
    which the moderate-skew test above exercises.)"""
    rng = np.random.default_rng(4)
    n, f, k = 8000, 8, 16
    dup = np.tile(rng.normal(size=(1, f)).astype(np.float32), (int(n * 0.9), 1))
    rest = rng.normal(size=(n - dup.shape[0], f)).astype(np.float32) * 8
    pts = np.concatenate([dup, rest]).astype(np.float32)
    want_d, want_i = knn(pts, k=k, impl="xla")
    d2, gid = ivf_knn(pts, k=k, n_clusters=64, n_probe=8)
    # exact fallback -> identical result, and in particular no inf/-1
    np.testing.assert_array_equal(np.asarray(gid), np.asarray(want_i))
    np.testing.assert_allclose(
        np.asarray(d2), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )


def test_ivf_small_cloud_falls_back_to_exact():
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(200, 8)).astype(np.float32)
    want = np.asarray(knn(pts, k=8, impl="xla")[1])
    got = np.asarray(ivf_knn(pts, k=8)[1])
    np.testing.assert_array_equal(got, want)


def test_ivf_rejects_bad_k():
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(100, 4)).astype(np.float32)
    with pytest.raises(ValueError):
        ivf_knn(pts, k=0)
    with pytest.raises(ValueError):
        ivf_knn(pts, k=100)


def test_kmeans_deterministic_and_shaped():
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(5000, 8)).astype(np.float32)
    c1 = np.asarray(kmeans(pts, 32, iters=3, seed=5))
    c2 = np.asarray(kmeans(pts, 32, iters=3, seed=5))
    np.testing.assert_array_equal(c1, c2)
    assert c1.shape == (32, 8)
    assert not np.array_equal(c1, np.asarray(kmeans(pts, 32, iters=3, seed=6)))
    with pytest.raises(ValueError):
        kmeans(pts[:10], 32)


def test_lof_ivf_tracks_exact(clouds):
    """lof_scores(impl='ivf') stays close to the exact scorer — the
    on-silicon harness measured AUROC 0.9895 vs 0.9905; here the scores
    themselves must correlate tightly on both cloud shapes."""
    from graphmine_tpu.ops.lof import lof_scores

    for cloud in ("gauss", "blobs"):
        pts = clouds[cloud][:8000]
        exact = np.asarray(lof_scores(pts, k=32, impl="xla"))
        approx = np.asarray(lof_scores(pts, k=32, impl="ivf"))
        frac_close = np.mean(np.abs(exact - approx) < 0.05 * np.abs(exact) + 0.01)
        assert frac_close > 0.95, (cloud, frac_close)


def test_ivf_guard_fallback_warns_and_records():
    """ADVICE r5: a pathology guard routing ivf_knn to the exact path
    must warn and (with a sink) emit an ivf_fallback record naming the
    guard — a silent bypass once mislabeled bench timings as 'ivf'."""
    from graphmine_tpu.ops.ann import ivf_knn
    from graphmine_tpu.ops.knn import knn
    from graphmine_tpu.pipeline.metrics import MetricsSink

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(64, 4)).astype(np.float32)
    m = MetricsSink()
    with pytest.warns(UserWarning, match="ivf_knn guard"):
        d2, idx = ivf_knn(pts, k=40, n_clusters=8, sink=m)
    rec = m.of_phase("ivf_fallback")
    assert rec and rec[0]["guard"] == "k_unfillable"
    assert "k=40" in rec[0]["detail"]
    # the fallback result IS the exact result
    d2x, _ = knn(pts, 40, impl="auto")
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2x), atol=1e-5)
    # lof_scores threads the sink through to the same record
    from graphmine_tpu.ops.lof import lof_scores

    m2 = MetricsSink()
    with pytest.warns(UserWarning, match="ivf_knn guard"):
        lof_scores(pts, k=40, impl="ivf", sink=m2)
    assert m2.of_phase("ivf_fallback")


# -- the selection: (distance, id) pairs sorted, first k kept (PR 28) ---------
#
# Every block below is built on an integer lattice (coordinates 0..3), so
# each squared distance is exact in float32 whatever the order of the
# arithmetic: the NumPy oracle and the device agree bit for bit, and equal
# distances are everywhere.


def _oracle_select(d2, ids, k):
    """``lax.top_k``'s order: ascending distance, the lower POSITION on a
    tie (``np.lexsort((position, d2))[:k]`` row by row)."""
    pos = np.arange(d2.shape[1])
    order = np.stack([np.lexsort((pos, row))[:k] for row in d2])
    return (np.take_along_axis(d2, order, axis=1),
            np.take_along_axis(ids, order, axis=1))


def _search_block(valid_len, k, seed):
    """One cluster's block as ``ann._index_tables`` lays it out: member ids
    ascending, padded slots repeating the last id behind ``m_valid``
    False; the first half of the queries are members (a self slot each),
    the second half come from outside the sublist."""
    from graphmine_tpu.ops.ann import _search_clusters

    rng = np.random.default_rng(seed)
    l_max, q_max, n = 64, 32, 500
    pts = rng.integers(0, 4, size=(n, 8)).astype(np.float32)
    members = np.sort(rng.choice(n, valid_len, replace=False)).astype(np.int32)
    m_gid = np.concatenate(
        [members, np.full(l_max - valid_len, members[-1], np.int32)]
    )
    m_valid = np.arange(l_max) < valid_len
    outside = np.setdiff1d(np.arange(n), members)
    q_gid = np.concatenate([
        rng.choice(members, q_max // 2), rng.choice(outside, q_max // 2)
    ]).astype(np.int32)
    got = _search_clusters(
        pts[q_gid], q_gid, pts[m_gid], m_gid, m_valid, k
    )
    d2 = ((pts[q_gid][:, None, :] - pts[m_gid][None, :, :]) ** 2).sum(-1)
    d2 = np.where(~m_valid[None, :], np.inf, d2).astype(np.float32)
    d2 = np.where(q_gid[:, None] == m_gid[None, :], np.float32(np.inf), d2)
    ids = np.broadcast_to(m_gid, d2.shape)
    return got, _oracle_select(d2, ids, k), d2


def _merge_block(finite_per_row, k, seed):
    """Search results as the merge sees them: ``[rows, k]`` ascending
    distances from a handful of values (ties across a query's rows), ids
    distinct and DESCENDING across rows so that position order and id
    order disagree on every such tie, a junk row of ``inf`` / ``-1`` last,
    and a take table whose short queries pad with the junk row."""
    from graphmine_tpu.ops.ann import _merge_tiles

    rng = np.random.default_rng(seed)
    rows, merge_t, p_max, tiles = 30, 8, 3, 2
    d2_flat = np.sort(
        rng.integers(0, 6, size=(rows, k)).astype(np.float32), axis=1
    )
    d2_flat[:, finite_per_row:] = np.inf
    gid_flat = np.arange(rows * k, dtype=np.int32)[::-1].reshape(rows, k).copy()
    d2_flat = np.concatenate([d2_flat, np.full((1, k), np.inf, np.float32)])
    gid_flat = np.concatenate([gid_flat, np.full((1, k), -1, np.int32)])
    take = np.stack([
        rng.choice(rows, p_max, replace=False)
        for _ in range(tiles * merge_t)
    ]).astype(np.int32)
    take[::3, -1] = rows  # a query with fewer than p_max pairs
    got = _merge_tiles(
        d2_flat, gid_flat, take.reshape(tiles, merge_t, p_max), k
    )
    got = tuple(np.asarray(g).reshape(tiles * merge_t, k) for g in got)
    d2 = d2_flat[take].reshape(tiles * merge_t, p_max * k)
    ids = gid_flat[take].reshape(tiles * merge_t, p_max * k)
    return got, _oracle_select(d2, ids, k), d2


@pytest.mark.parametrize("site, finite", [
    pytest.param("search", 50, id="search-ties-padding-self"),
    pytest.param("search", 10, id="search-fewer-than-k-finite"),
    pytest.param("merge", 16, id="merge-ties-across-rows"),
    pytest.param("merge", 4, id="merge-fewer-than-k-finite"),
    pytest.param("ivf_knn", None, id="ivf_knn-equals-parent"),
])
def test_selection_keeps_top_k_order(site, finite):
    """The sort of (distance, id) pairs returns what ``lax.top_k`` +
    gather-by-position returned, bit for bit, ties included: planted
    ties, ``inf`` padding, a self slot, and rows with fewer than ``k``
    finite candidates (the ``inf`` slots then come out in position
    order too). ``finite`` is the valid members of a search block's 64
    slots, or the finite columns of each of a merge query's <= 3 rows.
    NaN is not covered: validated structural features are finite, and
    ``_select_k``'s docstring says where one would land."""
    if site == "ivf_knn":
        # digests of the PARENT's output (commit 7b30f92: lax.top_k +
        # m_gid[j] / take_along_axis) on this cloud; every row has ties
        import hashlib

        rng = np.random.default_rng(28)
        pts = rng.integers(0, 4, size=(6000, 8)).astype(np.float32)
        d2, idx = ivf_knn(pts, k=24, n_clusters=32, n_probe=6)
        d2, idx = np.asarray(d2), np.asarray(idx)
        assert (np.diff(d2, axis=1) == 0).any(axis=1).all()
        assert hashlib.sha256(d2.tobytes()).hexdigest() == (
            "75e1fa03687c9def2f3e94bc2ab3aa38c39a073727b2405707597d28837aaba2"
        )
        assert hashlib.sha256(idx.tobytes()).hexdigest() == (
            "3c031a70621a519634f35462e59abb2ef1a7d026f2384cc6003d79a3088a4359"
        )
        return
    k = 16
    block = _search_block if site == "search" else _merge_block
    (got_d2, got_ids), (want_d2, want_ids), d2 = block(finite, k, seed=3)
    # the block holds what its name says
    assert (np.isfinite(d2).sum(axis=1) < k).all() == (finite < k)
    assert (want_d2[:, 1:] == want_d2[:, :-1]).any(axis=1).all()
    np.testing.assert_array_equal(
        np.asarray(got_d2).view(np.uint32), want_d2.view(np.uint32)
    )
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)


# -- the index tables, built on the device (PR 34) ----------------------------
#
# ``_oracle_lists`` is the NumPy builder that lived in ``ops/ann.py`` until
# PR 34 (``_inverted_lists`` and the take table of ``ivf_knn``'s second
# ``ivf_lists`` span), kept as the plain reference: stable argsorts,
# ``repeat`` / ``cumsum`` expansions and fancy indexing over the (query,
# sublist) pairs, nothing of the device builder's sorts by cluster and runs.
# Why each step is what it is stays written in ``ops/ann.py``.


def _oracle_lists(n, k, probe, n_clusters, chunk_b=4096, merge_t=16384):
    from types import SimpleNamespace

    from graphmine_tpu.ops.ann import _GuardTripped

    n_probe = probe.shape[1]
    assign = probe[:, 0]
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
        raise _GuardTripped(
            "k_unfillable",
            f"k={k} >= largest cluster size {int(sizes.max())}",
        )
    j = np.arange(l_max)
    m_rows = sub_start[:, None] + np.minimum(
        j[None, :], np.maximum(sub_len[:, None] - 1, 0)
    )
    m_gid = order[np.minimum(m_rows, n - 1)].astype(np.int32)
    m_valid = j[None, :] < sub_len[:, None]

    probe_subs = n_subs_per_c[probe]              # [N, p] sublists/probe
    pairs_per_q = probe_subs.sum(axis=1)          # [N]
    p_max = int(pairs_per_q.max())
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
    pair_q = np.repeat(np.arange(n, dtype=np.int64), pairs_per_q)
    # expand each probed cluster c into sub_first[c] .. +n_subs_per_c[c]
    flat_c = probe.reshape(-1).astype(np.int64)
    flat_q_subs = probe_subs.reshape(-1)
    pair_c = (
        np.repeat(sub_first[flat_c], flat_q_subs)
        + (
            np.arange(int(flat_q_subs.sum()))
            - np.repeat(np.cumsum(flat_q_subs) - flat_q_subs, flat_q_subs)
        )
    )
    n_pairs = len(pair_q)
    pair_order = np.argsort(pair_c, kind="stable")
    q_counts = np.bincount(pair_c, minlength=n_sub)
    q_starts = np.zeros(n_sub, np.int64)
    np.cumsum(q_counts[:-1], out=q_starts[1:])
    chunks_per_s = -(-q_counts // chunk_b)       # ceil; 0 for unprobed
    r_rows = int(chunks_per_s.sum())
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
    row_len = np.minimum(q_counts[row_sub] - chunk_rank * chunk_b, chunk_b)
    jb = np.arange(chunk_b)
    q_rows = row_start[:, None] + np.minimum(
        jb[None, :], np.maximum(row_len[:, None] - 1, 0)
    )
    q_valid = jb[None, :] < row_len[:, None]
    q_gid = pair_q[pair_order[q_rows]].astype(np.int32)  # [R, B]
    # valid (row, slot) cells in row-major order visit sorted pair
    # positions 0..P-1 in order, so each REAL pair's flat [R * B] result
    # row is its valid-cell flat index
    slot_of_pair = np.empty(n_pairs, np.int64)
    slot_of_pair[pair_order] = np.arange(
        r_rows * chunk_b
    ).reshape(r_rows, chunk_b)[q_valid]

    # the merge's take table: a query's pairs in pair order, short
    # queries and the rows past n padded with the junk row
    n_pad = -(-n // merge_t) * merge_t
    take = np.full((n_pad, p_max), r_rows * chunk_b, np.int64)
    pair_col = (
        np.arange(n_pairs)
        - np.repeat(np.cumsum(pairs_per_q) - pairs_per_q, pairs_per_q)
    )
    take[pair_q, pair_col] = slot_of_pair
    take = take.astype(np.int32).reshape(n_pad // merge_t, merge_t, p_max)
    return SimpleNamespace(
        m_gid=m_gid, m_valid=m_valid, q_gid=q_gid,
        row_sub=row_sub.astype(np.int32), r_rows=r_rows, p_max=p_max,
        n_pairs=n_pairs, n_sub=n_sub, q_counts=q_counts, take=take,
    )


def _list_cloud(case):
    """``(points, centers, n_probe, k)`` of one table case."""
    rng = np.random.default_rng(34)
    f = 8
    if case == "skewed":
        # 40% of the mass in one tight blob: its cluster splits
        tight = rng.normal(size=(4800, f)) * 0.1
        pts = np.concatenate([tight, rng.normal(size=(7200, f)) * 5])
        pts = pts.astype(np.float32)
        return pts, kmeans(pts, 16, seed=0), 8, 16
    pts = rng.normal(size=(9000, f)).astype(np.float32)
    if case == "balanced":
        return pts, kmeans(pts, 96, seed=0), 16, 24
    if case == "spill":
        # 12 clusters probed by 9000 * 6 / 12 queries each, more than one
        # chunk of 4096; a 13th centre far from every point, which owns
        # nothing and which no query probes
        centers = np.asarray(kmeans(pts, 12, seed=0))
        far = np.full((1, f), 1e3, np.float32)
        return pts, np.concatenate([centers, far]), 6, 16
    # untrained centres handed in, as ``centers=`` takes them: a sample of
    # the points, neither a multiple of 8 nor balanced (some split)
    return pts, pts[rng.choice(len(pts), 37, replace=False)], 5, 16


@pytest.mark.parametrize("case", ["balanced", "skewed", "spill", "centers"])
def test_device_built_index_tables_equal_the_numpy_reference(case):
    """Every table of the index, array for array: members of a sublist in
    ascending id, pairs grouped by sublist and query-ascending inside it,
    chunks of 4096 in sublist order with padded slots repeating the last
    real query, a query's result rows in probe-column order in ``take``
    (a split cluster's sublists ascending inside its column), the junk
    row everywhere else."""
    import jax.numpy as jnp

    from graphmine_tpu.ops import ann
    from graphmine_tpu.ops.knn import cross_knn

    pts, centers, n_probe, k = _list_cloud(case)
    n, n_clusters = len(pts), len(centers)
    _, probe = cross_knn(jnp.asarray(pts), jnp.asarray(centers), n_probe)
    want = _oracle_lists(n, k, np.asarray(probe), n_clusters)
    # the case holds what its name says
    assert (want.p_max > n_probe) == (case in ("skewed", "centers"))
    assert ((want.q_counts > ann._CHUNK_B).any()
            and (want.q_counts == 0).any()) == (case == "spill")

    got = ann._inverted_lists(probe, k, n_clusters)
    for name in ("m_gid", "m_valid", "q_gid", "row_sub"):
        table = np.asarray(getattr(got, name))
        assert table.dtype == getattr(want, name).dtype, name
        np.testing.assert_array_equal(table, getattr(want, name), name)
    assert (got.r_rows, got.p_max, got.n_pairs, got.counts["n_sub"]) == (
        want.r_rows, want.p_max, want.n_pairs, want.n_sub
    )
    take = ann._take_table(
        *got.slots, p_max=got.p_max, junk=got.r_rows * ann._CHUNK_B,
        merge_t=ann._MERGE_T,
    )
    assert take.dtype == want.take.dtype
    np.testing.assert_array_equal(np.asarray(take), want.take)
    # nothing of n or n_pairs rows crossed to the host to get there
    assert got.host_bytes < 16 * (n_clusters + want.n_sub + want.r_rows) + 64


def _guard_cloud(guard):
    """``(points, k, ivf_knn keywords)`` on which ``guard`` fires."""
    rng = np.random.default_rng(0)
    if guard == "k_unfillable":
        # k above any cluster's size
        return rng.normal(size=(64, 4)).astype(np.float32), 40, dict(n_clusters=8)
    if guard == "capacity":
        # one probe a query, and a far cluster of 10 points for k = 20
        pts = np.concatenate([
            rng.normal(size=(190, 4)), rng.normal(size=(10, 4)) + 100.0
        ]).astype(np.float32)
        centers = np.concatenate([pts[:3], pts[-1:]])
        return pts, 20, dict(centers=centers, n_probe=1)
    # duplicate rows pile into one cluster (see the skew test above)
    dup = np.tile(rng.normal(size=(1, 8)), (7200, 1))
    pts = np.concatenate([dup, rng.normal(size=(800, 8)) * 8])
    return pts.astype(np.float32), 16, dict(n_clusters=64, n_probe=8)


@pytest.mark.parametrize(
    "guard", ["k_unfillable", "capacity", "skew", "index_bound"]
)
def test_each_guard_trips_as_the_reference_does_and_lands_exact(
    guard, monkeypatch
):
    """The four guards fire from the device's reductions on the inputs,
    under the names and with the details the NumPy builder gave, and end
    in ``_exact_fallback``: a warning, an ``ivf_fallback`` record, the
    exact neighbours."""
    import jax.numpy as jnp

    from graphmine_tpu.ops import ann
    from graphmine_tpu.ops.knn import cross_knn
    from graphmine_tpu.pipeline.metrics import MetricsSink

    chunk_b = ann._CHUNK_B
    if guard == "index_bound":
        # no cloud a test can hold has 2^31 result rows: make the rows
        # 2^26 slots long instead, on the balanced cloud of the tables test
        chunk_b = 1 << 26
        monkeypatch.setattr(ann, "_CHUNK_B", chunk_b)
        pts, centers, n_probe, k = _list_cloud("balanced")
        kw = dict(centers=centers, n_probe=n_probe)
    else:
        pts, k, kw = _guard_cloud(guard)
    sink = MetricsSink()
    with pytest.warns(UserWarning, match=f"ivf_knn guard '{guard}'"):
        d2, idx = ivf_knn(pts, k=k, sink=sink, **kw)
    (rec,) = sink.of_phase("ivf_fallback")
    assert rec["guard"] == guard
    want_d2, want_idx = knn(pts, k, impl="auto")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(want_d2))

    # the reference trips the same guard with the same words
    centers = kw.get("centers")
    if centers is None:
        centers = kmeans(pts, kw["n_clusters"], iters=5, seed=0)
    n_probe = min(kw.get("n_probe", 16), len(centers))
    _, probe = cross_knn(jnp.asarray(pts), jnp.asarray(centers), n_probe)
    with pytest.raises(ann._GuardTripped) as tripped:
        _oracle_lists(
            len(pts), k, np.asarray(probe), len(centers), chunk_b=chunk_b
        )
    assert (tripped.value.guard, tripped.value.detail) == (guard, rec["detail"])


def test_ivf_knn_equals_a_run_through_the_reference_tables(clouds):
    """``ivf_knn``'s ``(d2, idx)`` against the same search and merge fed
    the NumPy reference's tables: bit for bit."""
    import jax.numpy as jnp

    from graphmine_tpu.ops import ann
    from graphmine_tpu.ops.knn import cross_knn

    pts, k, n_probe = clouds["blobs"], 8, 8
    n, n_clusters = len(pts), ann.default_n_clusters(len(pts))
    d2, idx = ivf_knn(pts, k=k, n_probe=n_probe)

    centers = kmeans(pts, n_clusters, iters=5, seed=0)
    _, probe = cross_knn(jnp.asarray(pts), centers, n_probe)
    ref = _oracle_lists(n, k, np.asarray(probe), n_clusters)
    d2_all, gid_all = ann._search_chunks(
        pts, ref.m_gid, ref.m_valid, ref.q_gid, ref.row_sub, k
    )
    d2_flat = np.concatenate(
        [np.asarray(d2_all).reshape(-1, k), np.full((1, k), np.inf, np.float32)]
    )
    gid_flat = np.concatenate(
        [np.asarray(gid_all).reshape(-1, k), np.full((1, k), -1, np.int32)]
    )
    want_d2, want_idx = ann._merge_tiles(d2_flat, gid_flat, ref.take, k)
    np.testing.assert_array_equal(
        np.asarray(d2).view(np.uint32),
        np.asarray(want_d2).reshape(-1, k)[:n].view(np.uint32),
    )
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(want_idx).reshape(-1, k)[:n]
    )
