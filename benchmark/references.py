"""Plain references the benchmark decides ``correct`` by. NumPy and SciPy
on the host, float64 where there are floats; nothing of the program is
imported and nothing the program made is taken, only its answers.

``numpy_lpa``, ``scipy_cc`` and ``rank_auroc`` are copies of
``chip_smoke.py``'s at PR 22 (proven on the chip there).
"""

from __future__ import annotations

import numpy as np


def mode_smallest(recv, labels_in, num_vertices: int):
    """Per receiver: the most frequent incoming label, the smallest on a
    tie. Returns (receivers that got a message, their new label)."""
    pair, count = np.unique(
        recv.astype(np.int64) * num_vertices + labels_in, return_counts=True
    )
    r, lab = pair // num_vertices, pair % num_vertices
    order = np.lexsort((lab, -count, r))
    r, lab = r[order], lab[order]
    first = np.ones(len(r), bool)
    first[1:] = r[1:] != r[:-1]
    return r[first], lab[first]


def numpy_lpa(src, dst, num_vertices: int, max_iter: int) -> np.ndarray:
    """Synchronous label propagation: messages flow both ways along every
    row, duplicates counted, initial label = vertex id, the most frequent
    incoming label wins and the smallest wins a tie; a vertex that
    receives nothing keeps its label."""
    recv = np.concatenate([dst, src]).astype(np.int64)
    send = np.concatenate([src, dst]).astype(np.int64)
    labels = np.arange(num_vertices, dtype=np.int64)
    for _ in range(max_iter):
        r, lab = mode_smallest(recv, labels[send], num_vertices)
        labels = labels.copy()
        labels[r] = lab
    return labels


def threaded_lpa(u, v, num_vertices: int, max_iter: int, one_way: bool = False,
                 slices: int = 32, workers: int = 8) -> np.ndarray:
    """``numpy_lpa`` on a simple graph too large for it (graph500-22 has
    128 M messages a superstep): the same rule, with the messages sorted by
    receiver once and every superstep's sort-and-count run per receiver
    range in threads (NumPy releases the interpreter lock in ``sort`` and in
    fancy indexing). ``(u, v)`` are distinct edges; ``one_way`` sends along
    u -> v only (the control: the graph taken as directed)."""
    from concurrent.futures import ThreadPoolExecutor

    bits = max(1, int(num_vertices - 1).bit_length())
    u64, v64 = u.astype(np.int64), v.astype(np.int64)
    keys = (v64 << bits) | u64                      # receiver v, sender u
    if not one_way:
        keys = np.concatenate([keys, (u64 << bits) | v64])
    del u64, v64
    slices = min(slices, num_vertices)
    per = -(-num_vertices // slices)                # receivers per slice
    which = ((keys >> bits) // per).astype(np.uint8)
    mask = (1 << bits) - 1

    def prepare(i):
        k = np.sort(keys[which == i])
        return (k >> bits).astype(np.int64), (k & mask).astype(np.int64)

    def superstep(part, labels):
        recv, send = part
        if len(recv) == 0:
            return recv, recv
        pair = np.sort((recv << bits) | labels[send])
        start = np.ones(len(pair), bool)
        start[1:] = pair[1:] != pair[:-1]
        at = np.flatnonzero(start)
        count = np.diff(np.append(at, len(pair)))
        r, lab = pair[at] >> bits, pair[at] & mask
        seg = np.ones(len(r), bool)
        seg[1:] = r[1:] != r[:-1]
        seg_at = np.flatnonzero(seg)
        best = np.maximum.reduceat(count, seg_at)
        seg_id = np.cumsum(seg) - 1
        winners = np.flatnonzero(count == best[seg_id])  # label-ascending per receiver
        first = np.ones(len(winners), bool)
        first[1:] = seg_id[winners][1:] != seg_id[winners][:-1]
        return r[winners[first]], lab[winners[first]]

    labels = np.arange(num_vertices, dtype=np.int64)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        parts = list(pool.map(prepare, range(slices)))
        del keys, which
        for _ in range(max_iter):
            new = labels.copy()
            for r, lab in pool.map(lambda p: superstep(p, labels), parts):
                new[r] = lab
            labels = new
    return labels


def scipy_cc(src, dst, num_vertices: int) -> np.ndarray:
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    pair = np.unique(np.asarray(src, np.int64) * num_vertices + dst)
    adj = coo_matrix(
        (np.ones(len(pair), bool), (pair // num_vertices, pair % num_vertices)),
        shape=(num_vertices, num_vertices),
    )
    return connected_components(adj, directed=False)[1]


def canonical_partition(labels) -> np.ndarray:
    """Every vertex named by the first vertex of its class: two label
    vectors are the same partition exactly when these are equal."""
    _, first, inverse = np.unique(
        np.asarray(labels), return_index=True, return_inverse=True
    )
    return first[inverse]


def partition_mismatches(a, b) -> int:
    return int((canonical_partition(a) != canonical_partition(b)).sum())


def rank_auroc(scores, positive) -> float:
    from scipy.stats import rankdata

    ranks = rankdata(np.asarray(scores, np.float64))
    n_pos = int(positive.sum())
    n_neg = len(positive) - n_pos
    return float(
        (ranks[positive].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
    )


# -- the outlier chapter: structural features, kNN, LOF, all float64 ----------


def structural_features(src, dst, communities, num_vertices: int) -> np.ndarray:
    """The eight per-vertex features of the LOF chapter, standardised:
    log1p of out-degree, in-degree, message degree (rows in either
    direction, duplicates counted), own community's size, mean message
    degree of the senders, distinct communities among the senders; the
    share of messages from the own community; the local clustering
    coefficient on the simple undirected graph."""
    from scipy.sparse import coo_matrix

    n = num_vertices
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    comm = np.asarray(communities, np.int64)
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    in_deg = np.bincount(dst, minlength=n).astype(np.float64)
    msg_deg = out_deg + in_deg
    recv = np.concatenate([dst, src])
    send = np.concatenate([src, dst])
    comm_size = np.bincount(comm, minlength=n).astype(np.float64)[comm]
    mean_sender_deg = (np.bincount(recv, weights=msg_deg[send], minlength=n)
                       / np.maximum(msg_deg, 1.0))
    same = comm[send] == comm[recv]
    same_frac = np.bincount(recv[same], minlength=n) / np.maximum(msg_deg, 1.0)
    pairs = np.unique(recv * n + comm[send])
    distinct = np.bincount(pairs // n, minlength=n).astype(np.float64)

    keep = src != dst
    und = np.unique(np.minimum(src, dst)[keep] * n + np.maximum(src, dst)[keep])
    a, b = und // n, und % n
    adj = coo_matrix((np.ones(2 * len(a)), (np.concatenate([a, b]),
                                            np.concatenate([b, a]))),
                     shape=(n, n)).tocsr()
    triangles = np.asarray(adj.multiply(adj @ adj).sum(axis=1)).ravel() / 2.0
    simple_deg = np.asarray(adj.sum(axis=1)).ravel()
    wedges = simple_deg * (simple_deg - 1.0) / 2.0
    clustering = np.where(wedges > 0, triangles / np.maximum(wedges, 1.0), 0.0)

    feats = np.column_stack([
        np.log1p(out_deg), np.log1p(in_deg), np.log1p(msg_deg),
        np.log1p(comm_size), np.log1p(mean_sender_deg), np.log1p(distinct),
        same_frac, clustering,
    ])
    mu, sd = feats.mean(axis=0), feats.std(axis=0)
    return (feats - mu) / np.maximum(sd, 1e-6)


def exact_knn(points, k: int):
    """Distances to and indices of the k nearest other points, ascending,
    exact in float64 (a KD-tree: the cloud has eight dimensions)."""
    from scipy.spatial import cKDTree

    dist, idx = cKDTree(points).query(points, k=k + 1, workers=-1)
    # self is among the k+1 at distance 0; with duplicate rows it need not
    # come first, so drop it wherever it is (else drop the farthest)
    rows = np.arange(len(points))[:, None]
    is_self = idx == rows
    no_self = ~is_self.any(axis=1)
    is_self[no_self, -1] = True
    keep = ~is_self
    return (dist[keep].reshape(len(points), k),
            idx[keep].reshape(len(points), k))


def lof_from_knn(dist, idx) -> np.ndarray:
    """Local outlier factor (Breunig et al.) from a kNN table, with the
    reach distances floored at 1e-3 of the mean positive kNN distance, the
    program's stated guard against duplicate rows."""
    k = dist.shape[1]
    positive = dist > 0
    eps = 1e-3 * dist[positive].sum() / max(int(positive.sum()), 1)
    kdist = dist[:, -1]
    reach = np.maximum(np.maximum(kdist[idx], dist), eps)
    lrd = k / np.maximum(reach.sum(axis=1), 1e-12)
    return lrd[idx].mean(axis=1) / np.maximum(lrd, 1e-12)
