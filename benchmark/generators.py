"""Seeded input generators of the benchmark (NumPy only, nothing of the
program imported). A configuration's file names one of them under
``generator`` and gives its arguments; ``make(name, args, seed)`` is the
one way in, so a later configuration picks a generator by name.

``rmat_undirected`` is written here; ``planted_anomaly_graph`` and
``inject_structural_anomalies`` are copies of ``graphmine_tpu/datasets.py``
at PR 22 and ``write_parquet`` of ``chip_smoke.py``: the yardstick keeps
its own so that a later change to the program cannot move the inputs.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Draws are made in this many independently seeded chunks, whatever the
# machine: the thread count changes the speed and never the edges.
_RMAT_CHUNKS = 32


def _rmat_chunk_keys(child_seed, n: int, scale: int, a: float, b: float,
                     c: float, perm) -> np.ndarray:
    """One chunk of draws as int64 keys ``lo * V + hi`` of its undirected
    edges, self-loops dropped, ids permuted."""
    rng = np.random.default_rng(child_seed)
    src = np.zeros(n, np.int32)
    dst = np.zeros(n, np.int32)
    for _ in range(scale):
        r = rng.random(n, dtype=np.float32)
        # quadrant: [0,a) (0,0); [a,a+b) (0,1); [a+b,a+b+c) (1,0); rest (1,1)
        src_bit = r >= a + b
        dst_bit = ((r >= a) & ~src_bit) | (r >= a + b + c)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    src, dst = perm[src], perm[dst]
    keep = src != dst
    lo = np.minimum(src, dst)[keep].astype(np.int64)
    return (lo << scale) | np.maximum(src, dst)[keep]


def _sorted_distinct(keys: np.ndarray) -> np.ndarray:
    keys.sort()
    first = np.ones(len(keys), bool)
    first[1:] = keys[1:] != keys[:-1]
    return keys[first]


def rmat_undirected(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int = 0):
    """Graph500's Kronecker (R-MAT) draw, made the simple undirected graph
    that LDBC Graphalytics loads: ``edge_factor * 2**scale`` draws, vertex
    ids permuted, self-loops dropped, (u, v) and (v, u) one edge, duplicates
    dropped. Returns ``(u, v)`` int32 with ``u < v``, sorted by (u, v).
    Draws and the de-duplicating sort run in threads over fixed chunks and
    fixed key ranges (NumPy releases the interpreter lock in both)."""
    v = 1 << scale
    n = int(edge_factor) * v
    *chunk_seeds, perm_seed = np.random.SeedSequence(int(seed)).spawn(_RMAT_CHUNKS + 1)
    perm = np.random.default_rng(perm_seed).permutation(v).astype(np.int32)
    sizes = [n // _RMAT_CHUNKS + (i < n % _RMAT_CHUNKS) for i in range(_RMAT_CHUNKS)]
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        keys = np.concatenate(list(pool.map(
            lambda job: _rmat_chunk_keys(job[0], job[1], scale, a, b, c, perm),
            zip(chunk_seeds, sizes),
        )))
        # ids are permuted, so equal slices of the key range hold equal shares
        slices = min(16, v)
        which = (keys >> (2 * scale - (slices.bit_length() - 1))).astype(np.uint8)
        parts = list(pool.map(
            lambda i: _sorted_distinct(keys[which == i]), range(slices)))
        del keys, which
        lo = np.concatenate(list(pool.map(lambda k: (k >> scale).astype(np.int32), parts)))
        hi = np.concatenate(list(pool.map(lambda k: (k & (v - 1)).astype(np.int32), parts)))
    return lo, hi


def planted_anomaly_graph(
    num_vertices: int,
    num_edges: int,
    n_communities: int | None = None,
    size_skew: float = 0.7,
    n_friends: int = 4,
    hub_skew: float = 1.3,
    hub_scale: float = 20.0,
    p_noise: float = 0.03,
    num_anomalies: int | None = None,
    edges_per_anomaly: int = 60,
    seed: int = 0,
):
    """Planted communities over a sparse hub skeleton plus injected
    anomalies. Vertices land in Zipf-sized blocks; each draws a pool of
    ``n_friends`` partners inside its block, pareto-skewed toward the
    block's first rows; every edge row anchors a uniform vertex and picks
    from the anchor's pool, so the row budget lands as duplicate
    multiplicity over a sparse skeleton (the upstream job keeps
    duplicates); a ``p_noise`` share of partners is re-drawn across the
    graph; ``num_anomalies`` vertices (default ``max(32, V/2000)``) are
    wired to uniform endpoints. Returns ``(src, dst, is_anomaly,
    communities)``: int32 directed rows, the bool anomaly mask, the planted
    block per vertex."""
    rng = np.random.default_rng(seed)
    v, e = num_vertices, num_edges
    if n_communities is None:
        n_communities = max(8, v >> 9)
    w = (1.0 + np.arange(n_communities)) ** -size_skew
    w /= w.sum()
    comm = rng.choice(n_communities, size=v, p=w).astype(np.int32)
    order = np.argsort(comm, kind="stable")
    sizes = np.bincount(comm, minlength=n_communities).astype(np.int64)
    starts = np.zeros(n_communities, np.int64)
    np.cumsum(sizes[:-1], out=starts[1:])

    sz = sizes[comm]
    raw = rng.pareto(hub_skew, size=(v, n_friends))
    loc = np.minimum(
        (raw * sz[:, None] / hub_scale).astype(np.int64), (sz - 1)[:, None]
    )
    friends = order[starts[comm][:, None] + loc]

    anchors = rng.integers(0, v, e)
    partners = friends[anchors, rng.integers(0, n_friends, e)]
    noise = rng.random(e) < p_noise
    partners[noise] = rng.integers(0, v, int(noise.sum()))

    if num_anomalies is None:
        num_anomalies = max(32, v // 2000)
    src, dst, is_anomaly = inject_structural_anomalies(
        anchors.astype(np.int32), partners.astype(np.int32), v,
        num_anomalies=num_anomalies, edges_per_anomaly=edges_per_anomaly,
        seed=seed + 1,
    )
    return src, dst, is_anomaly, comm


def inject_structural_anomalies(src, dst, num_vertices: int,
                                num_anomalies: int,
                                edges_per_anomaly: int = 20, seed: int = 0):
    """Wire ``num_anomalies`` random vertices to uniform endpoints: the
    held-out outliers of the LOF AUROC metric (BASELINE.json). Returns
    ``(src, dst, is_anomaly)`` with the new rows appended."""
    rng = np.random.default_rng(seed)
    anomalies = rng.choice(num_vertices, size=num_anomalies, replace=False)
    a_src = np.repeat(anomalies, edges_per_anomaly)
    a_dst = rng.integers(0, num_vertices, num_anomalies * edges_per_anomaly)
    mask = np.zeros(num_vertices, dtype=bool)
    mask[anomalies] = True
    return (np.concatenate([src, a_src]).astype(np.int32),
            np.concatenate([dst, a_dst]).astype(np.int32), mask)


def domain_name(i: int) -> str:
    return f"d{i:07d}.example"


def domain_id(name: str) -> int:
    return int(name[1:8])


def write_parquet(src, dst, num_vertices: int, path: str) -> None:
    """The upstream job's ingestion format: domain-string columns
    ``_c1``/``_c2``, one row per outlink, duplicates kept."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    names = pa.array([domain_name(i) for i in range(num_vertices)])

    def col(ids):
        return pa.DictionaryArray.from_arrays(
            pa.array(ids, pa.int32()), names
        ).cast(pa.string())

    pq.write_table(pa.table({"_c1": col(src), "_c2": col(dst)}), path)


_GENERATORS = {
    "rmat_undirected": rmat_undirected,
    "planted_anomaly_graph": planted_anomaly_graph,
}


def make(name: str, args: dict, seed: int):
    if name not in _GENERATORS:
        raise KeyError(f"unknown generator {name!r}; have {sorted(_GENERATORS)}")
    return _GENERATORS[name](**args, seed=seed)
