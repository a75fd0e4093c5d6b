"""Algorithm ``bfs``: LDBC Graphalytics' breadth-first search (spec v1.0,
algorithm BFS) on an undirected graph, through ``gm.bfs_distances``: from
one source vertex, the depth of every vertex, the search run until a level
reaches nothing.

    depth(source) = 0
    depth(v)      = the least number of edges on a path from the source to v
    depth(v)      = 9223372036854775807 where no path reaches v

An edge is walked either way. The source is stated by rule, not by id
(``traffic["source"]``): LDBC's properties file names one per dataset and
is not here, so it is ``lowest_id_with_an_edge``, the lowest vertex id
that has an edge in the id space the edges come in, resolved from the
draw by ``run`` (from the graph's degrees) and by the reference (from the
edges) alike. A list of ids stands for itself.

The reference builds its own CSR of the drawn edges (SciPy, a counting
sort) and walks it with ``scipy.sparse.csgraph.breadth_first_order``,
undirected; a depth is its predecessor's plus one, resolved a level a pass
over the visit order. It imports nothing of the program and knows no plan,
no rows and no frontier rung. It answers ``[2, V]`` int64: the depths as
Graphalytics writes them, and under them 1 where the vertex has an edge,
which ``compare`` needs for the share of such vertices the search reached.

The control breaks the undirected guarantee: the same search with every
edge walked one way only, ``u -> v`` as it was drawn.

The answer is integers and stated exact: the limit is 0 mismatches over
the whole vertex space. A second record, ``bfs_reached_share``, fails a
draw whose source sits in a component of two: no run times a trivial job.
"""

from __future__ import annotations

import inspect

import numpy as np

UNREACHED = np.iinfo(np.int64).max  # as Graphalytics writes an unreached vertex
_PROGRAM_UNREACHED = np.iinfo(np.int32).max  # as the program's int32 depths do
REACHED_SHARE_MIN = 0.5

# what run() asks of the program
_NEEDS = ("direction", "plan", "sink", "return_levels")


def check_program() -> None:
    """Before any input is made: a program whose ``bfs_distances`` takes no
    plan relaxes all 521 M messages in every pass of one ``while_loop``
    beside a graph that fills the chip; it cannot run this cell, and says
    so in seconds, not after a draw of 260 M edges."""
    import graphmine_tpu as gm

    if not hasattr(gm, "bfs_distances"):
        raise SystemExit("algorithms/bfs: this program has no bfs_distances; "
                         "it cannot run this cell")
    have = inspect.signature(gm.bfs_distances).parameters
    missing = [p for p in _NEEDS if p not in have]
    if missing:
        raise SystemExit(f"algorithms/bfs: this program's bfs_distances takes no "
                         f"{missing}: every pass of its search is full width over "
                         "the message arrays; it cannot run this cell")


def run(graph, sink, traffic):
    import jax.numpy as jnp

    import graphmine_tpu as gm

    rule = traffic["source"]
    if isinstance(rule, str):  # the reference's _sources() turns away another name
        sources = jnp.argmax(graph.degrees() > 0)[None]  # stays on the device
    else:
        sources = np.asarray(rule, np.int32)
    return gm.bfs_distances(graph, sources, direction="both", plan="auto",
                            sink=sink, return_levels=True)


def _sources(u, v, traffic) -> np.ndarray:
    rule = traffic["source"]
    if not isinstance(rule, str):
        return np.asarray(rule, np.int64)
    if rule != "lowest_id_with_an_edge":
        raise ValueError(f"algorithms/bfs: no source rule {rule!r}")
    return np.asarray([min(int(np.min(u)), int(np.min(v)))])


def _search(u, v, n: int, traffic, one_way: bool) -> np.ndarray:
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import breadth_first_order

    u, v = np.asarray(u), np.asarray(v)
    touched = np.zeros(n, np.int64)
    touched[u] = 1
    touched[v] = 1
    adjacency = csr_matrix((np.ones(len(u), bool), (u, v)), shape=(n, n))
    depth = np.full(n, UNREACHED, np.int64)
    for source in _sources(u, v, traffic):
        order, before = breadth_first_order(
            adjacency, int(source), directed=one_way, return_predecessors=True)
        found = np.full(n, UNREACHED, np.int64)
        found[source] = 0
        todo = order[1:]
        while todo.size:  # a level a pass: the visit order never steps back
            above = found[before[todo]]
            known = above != UNREACHED
            found[todo[known]] = above[known] + 1
            todo = todo[~known]
        np.minimum(depth, found, out=depth)
    return np.stack([depth, touched])


def reference(u, v, num_vertices: int, traffic):
    return _search(u, v, num_vertices, traffic, one_way=False)


def control(u, v, num_vertices: int, traffic):
    """The undirected guarantee broken: an edge is walked from ``u`` to
    ``v`` only."""
    return _search(u, v, num_vertices, traffic, one_way=True)


def compare(got, want) -> list:
    """Every depth against the reference's, over the whole vertex space,
    the program's int32 "unreached" read as Graphalytics' int64 one; and the
    share of the vertices with an edge that the answer reached."""
    got = np.asarray(got)
    if got.ndim == 2:  # the control's answer, shaped as the reference's
        got = got[0]
    if got.dtype != np.int64:  # the program's
        got = got.astype(np.int64)
        got[got == _PROGRAM_UNREACHED] = UNREACHED
    depths, touched = want
    bad = int((got != depths).sum())
    reached = got != UNREACHED
    share = float(reached.sum() / max(int(touched.sum()), 1))
    return [
        {"check": "bfs_depth_mismatches", "value": bad, "limit": 0,
         "ok": bad == 0, "compared": len(depths),
         "deepest": int(got[reached].max(initial=0))},
        {"check": "bfs_reached_share", "value": share,
         "limit": REACHED_SHARE_MIN, "ok": share >= REACHED_SHARE_MIN,
         "reached": int(reached.sum()), "with_an_edge": int(touched.sum())},
    ]
