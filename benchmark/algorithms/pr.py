"""Algorithm ``pr``: LDBC Graphalytics' PageRank (spec v1.0, algorithm
PR) on an undirected graph, through ``gm.pagerank``: a stated count of
iterations from ``1/|V|``, no tolerance ends it early.

    PR_0(v) = 1/|V|
    PR_i(v) = (1 - d)/|V| + d * ( sum over u in N_in(v) of PR_{i-1}(u)/|N_out(u)|
                                  + (1/|V|) * sum over dangling w of PR_{i-1}(w) )

On an undirected graph ``N_in = N_out =`` the neighbours, so every edge
carries rank both ways and ``|N_out|`` is the degree; a dangling vertex is
one that sends nothing (here: an isolated vertex of the vertex space). ``d``
is the traffic file's ``damping``, the count its ``iterations``.

The reference is that formula word for word in float64 NumPy over the whole
vertex space: no plan, no rows, one ``bincount`` a direction. The control
is the DIRECTED reading, the same formula with rank flowing one way along
each edge as it was drawn, which is what ``gm.pagerank`` computed before it
could read an undirected graph's messages. The answer is floats and
Graphalytics validates it by an epsilon match: a relative 1e-4 per vertex
against the double-precision run. Contributions rounded to bfloat16 (8 bits
of mantissa, 4e-3) fail it by a factor of ten and more (held by
``tests/test_pagerank_graphalytics.py``); float32 sums of 10^6 terms done
in chunks stay two orders inside it.
"""

from __future__ import annotations

import inspect

import numpy as np

TOLERANCE = 1e-4  # relative, per vertex: Graphalytics' epsilon match for PR

# what run() asks of the program
_NEEDS = ("alpha", "max_iter", "tol", "directed", "plan", "sink")


def check_program() -> None:
    """Before any input is made: a program whose ``pagerank`` cannot read an
    undirected graph's messages or run a stated count cannot run this cell,
    and says so in seconds, not after a draw of 260 M edges."""
    import graphmine_tpu as gm

    have = inspect.signature(gm.pagerank).parameters
    missing = [p for p in _NEEDS if p not in have]
    if missing:
        raise SystemExit(f"algorithms/pr: this program's pagerank takes no "
                         f"{missing}; it ranks the edges as drawn and stops on a "
                         "tolerance; it cannot run this cell")


def run(graph, sink, traffic):
    import graphmine_tpu as gm

    ranks = gm.pagerank(graph, alpha=traffic["damping"],
                        max_iter=traffic["iterations"], tol=None,
                        directed=False, plan="auto", sink=sink)
    return ranks, traffic["iterations"]


def _power(u, v, n: int, iterations: int, damping: float, one_way: bool):
    u, v = np.asarray(u, np.intp), np.asarray(v, np.intp)  # bincount's own type, once
    ways = [(u, v)] if one_way else [(u, v), (v, u)]
    out = sum(np.bincount(send, minlength=n) for send, _ in ways).astype(np.float64)
    share = np.where(out > 0, 1.0 / np.maximum(out, 1.0), 0.0)
    dangling = out == 0
    rank = np.full(n, 1.0 / n)
    for _ in range(iterations):
        sent = rank * share
        inflow = sum(np.bincount(recv, weights=sent[send], minlength=n)
                     for send, recv in ways)
        rank = (1.0 - damping) / n + damping * (inflow + rank[dangling].sum() / n)
    return rank


def reference(u, v, num_vertices: int, traffic):
    return _power(u, v, num_vertices, traffic["iterations"], traffic["damping"],
                  one_way=False)


def control(u, v, num_vertices: int, traffic):
    """The undirected guarantee broken: rank flows from ``u`` to ``v`` only."""
    return _power(u, v, num_vertices, traffic["iterations"], traffic["damping"],
                  one_way=True)


def compare(got, want) -> list:
    """Every rank against the reference's, over the whole vertex space: the
    widest relative gap beside Graphalytics' epsilon. Every reference rank
    is at least ``(1 - d)/|V|``, so the division is safe."""
    got = np.asarray(got, np.float64)
    gaps = np.abs(got - want) / want
    at = int(np.argmax(gaps))
    gap = float(gaps[at])
    return [{"check": "rank_widest_relative_gap", "value": gap, "limit": TOLERANCE,
             "ok": bool(gap <= TOLERANCE), "compared": len(want), "at_vertex": at,
             "rank_sum": float(got.sum())}]
