"""Algorithm ``lcc``: LDBC Graphalytics' local clustering coefficient (spec
v1.0, algorithm LCC) on an undirected graph, through
``gm.clustering_coefficient``: one exact pass, every wedge of every vertex
counted and none sampled.

    LCC(v) = |{(u, w) : u, w in N(v), (u, w) in E}| / (d(v) * (d(v) - 1))

over ordered pairs, which on an undirected graph is ``2 T(v) / (d(v) (d(v)
- 1))`` with ``T(v)`` the triangles through ``v``, and 0 where ``d(v) < 2``.
``N(v)`` is the neighbourhood in the simple undirected graph: an edge
counts for both endpoints, a duplicate or reversed edge once, a self-loop
not at all.

The reference is that definition in NumPy with integer counts and one
float64 division, over the whole vertex space, and imports nothing of the
program. What it may not do is form the pairs of ``N(v)`` for every ``v``:
graph500-22's hub has 163,352 neighbours and the graph 2.5e11 such pairs.
So it forms every triangle once, from the corner of least (degree, id):
each vertex keeps the neighbours that rank above it (1,029 at most on that
graph, 7.2e9 pairs in all), every pair ``(v, w)`` of those is looked up in
the sorted array of edge keys, and a pair that is an edge is a triangle,
credited to its three corners. A triangle's lowest corner sees its other
two among its higher neighbours, so none is missed and none found twice.
The pairs are formed in blocks of rows of one length, the needles of a
block sorted so that ``searchsorted`` walks the keys in order (16 M
needles in 2.7 s and not 36), the blocks spread over threads. Pairs of two
hubs (the 32,768 highest ranks: six pairs in seven on that graph) are read
from a plain boolean table of the edges among them instead, which the
sorted keys would answer alike at three times the seconds.

The control breaks the undirected guarantee: neighbourhoods read from the
edges as drawn, out-neighbours only (``N(v) = {w : (v, w) drawn}``), so
``T`` keeps the triangles in which ``v`` has the smallest id and ``d`` is
the out-degree.

The answer is floats and Graphalytics validates it by an epsilon match: a
relative 1e-4 per vertex against the double-precision run, and exactly 0
where the reference is 0. Integer counts and one float32 division sit at
6e-8; counts kept in bfloat16 or in float32 running sums, a sampled
estimate or a dropped class of wedges each fail it
(``tests/test_lcc_graphalytics.py``).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TOLERANCE = 1e-4  # relative, per vertex: Graphalytics' epsilon match for LCC

# pairs formed and looked up at once by one thread (8 B a needle, a few
# arrays of them)
_BLOCK_PAIRS = 1 << 22
# the highest ranks whose pairs are looked up in a plain table of bool
_HUBS = 1 << 15


def check_program() -> None:
    """Before any input is made: a program whose exact clustering
    coefficient lists every oriented wedge on the host (28 B a wedge, 405 GB
    on graph500-22) cannot run this cell, and says so in seconds instead of
    being killed after the draw. The kernel that enumerates wedges on the
    device registers its stage spans; the older one has none of them."""
    import graphmine_tpu as gm
    from graphmine_tpu.obs import schema

    if not hasattr(gm, "clustering_coefficient"):
        raise SystemExit("algorithms/lcc: this program has no "
                         "clustering_coefficient; it cannot run this cell")
    missing = [s for s in ("lcc_core", "lcc_tail")
               if s not in getattr(schema, "STAGE_SPANS", ())]
    if missing:
        raise SystemExit(f"algorithms/lcc: this program registers no stage span "
                         f"{missing}: its clustering_coefficient expands every "
                         "oriented wedge on the host (405 GB at graph500-22); it "
                         "cannot run this cell")


def run(graph, sink, traffic):
    import graphmine_tpu as gm

    return gm.clustering_coefficient(graph, sink=sink), 1  # one pass a job


def facts(records: list) -> dict:
    """What the warm-up job's ``plan_build`` record says of the LCC plan:
    the wedges the dense core holds and all of them, the core's size, the
    bytes the plan keeps on the device. A program that writes no such
    record states no such fact."""
    plan = next((r for r in records if r.get("phase") == "plan_build"
                 and r.get("op") == "lcc"), None)
    if plan is None:
        return {}
    said = {k: plan[k] for k in ("core_vertices", "core_edges", "classes",
                                 "wedges_core", "wedges_tail", "resident_bytes")
            if k in plan}
    if "wedges_core" in said and "wedges_tail" in said:
        said["wedges_total"] = said["wedges_core"] + said["wedges_tail"]
    return said


# -- the plain reference ------------------------------------------------------


def _simple_edges(u, v, n: int):
    """Distinct undirected edges ``a < b`` without self-loops."""
    u, v = np.asarray(u, np.int64), np.asarray(v, np.int64)
    keep = u != v
    keys = np.unique(np.minimum(u, v)[keep] * n + np.maximum(u, v)[keep])
    return keys // n, keys % n


def _triangles(u, v, n: int, lowest_id_only: bool = False) -> np.ndarray:
    """Triangles through every vertex (int64 ``[n]``), each formed once from
    its corner of least (degree, id) as the module's note says. Vertices
    are renamed by that rank, so a vertex's higher neighbours are a sorted
    row and an edge's key is ``low rank * n + high rank``. With
    ``lowest_id_only`` a triangle is credited to its corner of smallest id
    alone (the control's out-neighbour reading)."""
    a, b = _simple_edges(u, v, n)
    degree = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    by_rank = np.lexsort((np.arange(n), degree))  # rank -> id
    rank = np.empty(n, np.int64)
    rank[by_rank] = np.arange(n)
    keys = np.sort(np.minimum(rank[a], rank[b]) * n + np.maximum(rank[a], rank[b]))
    low, high = keys // n, keys % n
    above = np.bincount(low, minlength=n)  # neighbours that rank higher
    start = np.zeros(n + 1, np.int64)
    np.cumsum(above, out=start[1:])
    # six pairs in seven join two of the highest ranks: those are looked up
    # in a plain table (1 GB of bool at most), the rest in the sorted keys
    first_hub = n - min(_HUBS, n // 8)
    among_hubs = np.zeros((n - first_hub, n - first_hub), bool)
    among_hubs[low[low >= first_hub] - first_hub, high[low >= first_hub] - first_hub] = True

    blocks = []  # (centres, row length): rows of one length, a block's worth
    centres_by_length = np.argsort(above, kind="stable")
    lengths = above[centres_by_length]
    first = np.searchsorted(lengths, 2)
    cuts = np.flatnonzero(np.diff(lengths[first:])) + 1 + first
    for lo, hi in zip(np.r_[first, cuts], np.r_[cuts, n]) if first < n else ():
        d = int(lengths[lo])
        rows = max(1, _BLOCK_PAIRS // (d * (d - 1) // 2))
        blocks += [(centres_by_length[i:min(i + rows, hi)], d)
                   for i in range(lo, hi, rows)]

    local = threading.local()
    totals = []

    def count(block):
        centres, d = block
        if not hasattr(local, "triangles"):
            local.triangles = np.zeros(n, np.int64)
            totals.append(local.triangles)
        rows = high[start[centres][:, None] + np.arange(d)]  # [m, d], ascending
        i, j = np.triu_indices(d, 1)
        v1, v2 = rows[:, i].ravel(), rows[:, j].ravel()  # v1 < v2
        closed = np.zeros(len(v1), bool)  # pairs that are an edge
        hubs = np.flatnonzero(v1 >= first_hub)
        closed[hubs] = among_hubs[v1[hubs] - first_hub, v2[hubs] - first_hub]
        others = np.flatnonzero(v1 < first_hub)
        needles = v1[others] * n + v2[others]
        by_key = np.argsort(needles)  # sorted needles walk the keys in order
        needles = needles[by_key]
        at = np.minimum(np.searchsorted(keys, needles), len(keys) - 1)
        closed[others[by_key[keys[at] == needles]]] = True
        closed = np.flatnonzero(closed)
        corners = by_rank[np.stack([np.repeat(centres, len(i))[closed],
                                    v1[closed], v2[closed]])]
        if lowest_id_only:
            corners = corners.min(axis=0)
        local.triangles += np.bincount(corners.ravel(), minlength=n)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(count, blocks))
    return sum(totals, np.zeros(n, np.int64))


def _coefficient(triangles: np.ndarray, degree: np.ndarray) -> np.ndarray:
    degree = degree.astype(np.float64)
    pairs = degree * (degree - 1.0)
    return np.where(pairs > 0, 2.0 * triangles / np.maximum(pairs, 1.0), 0.0)


def reference(u, v, num_vertices: int, traffic):
    a, b = _simple_edges(u, v, num_vertices)
    degree = (np.bincount(a, minlength=num_vertices)
              + np.bincount(b, minlength=num_vertices))
    return _coefficient(_triangles(u, v, num_vertices), degree)


def control(u, v, num_vertices: int, traffic):
    """The undirected guarantee broken: ``N(v)`` is the out-neighbours of
    ``v`` in the edges as drawn (``u -> v``, duplicates and self-loops
    dropped)."""
    u, v = np.asarray(u, np.int64), np.asarray(v, np.int64)
    drawn = np.unique(u[u != v] * num_vertices + v[u != v])
    out_degree = np.bincount(drawn // num_vertices, minlength=num_vertices)
    return _coefficient(
        _triangles(u, v, num_vertices, lowest_id_only=True), out_degree)


def compare(got, want) -> list:
    """Every coefficient against the reference's, over the whole vertex
    space: the widest relative gap beside Graphalytics' epsilon, and the
    vertices that are not exactly 0 where the reference is."""
    got = np.asarray(got, np.float64)
    some = want > 0
    gaps = np.zeros(len(want))
    gaps[some] = np.abs(got[some] - want[some]) / want[some]
    at = int(np.argmax(gaps))
    gap = float(gaps[at])
    not_zero = int(np.count_nonzero(got[~some]))
    return [
        {"check": "lcc_widest_relative_gap", "value": gap, "limit": TOLERANCE,
         "ok": bool(gap <= TOLERANCE), "compared": len(want), "at_vertex": at,
         "nonzero": int(some.sum()), "mean": float(got.mean())},
        {"check": "lcc_nonzero_where_reference_is_zero", "value": not_zero,
         "limit": 0, "ok": not_zero == 0},
    ]
