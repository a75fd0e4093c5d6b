"""The plain reference of weakly connected components by min-label
propagation, and the control made from it. NumPy on the host; nothing of
the program is imported.

``references.scipy_cc`` (a union-find over the same edges) is the second,
independent answer: ``canonical_partition`` of either names every vertex by
the smallest id of its component, which is what the program states.
"""

from __future__ import annotations

import numpy as np


def numpy_min_label(u, v, num_vertices: int, max_supersteps: int | None = None):
    """Synchronous min-label propagation from label = vertex id: in every
    superstep each edge carries its endpoints' labels of the superstep
    before both ways, and a vertex takes the smallest of its own and what
    arrived. No pointer jumping, no plan. Runs until a superstep moves
    nothing (that confirming superstep is counted, as the program counts
    its own) or for ``max_supersteps``; stopped early it is the control: an
    engine that gives up before the fixpoint.

    Returns ``(labels, supersteps)``. Self-loops and duplicate edges change
    nothing; a vertex without an edge keeps its own id."""
    u, v = np.asarray(u), np.asarray(v)
    labels = np.arange(num_vertices, dtype=np.int64)
    supersteps = 0
    while max_supersteps is None or supersteps < max_supersteps:
        new = labels.copy()
        np.minimum.at(new, v, labels[u])
        np.minimum.at(new, u, labels[v])
        supersteps += 1
        moved = bool((new != labels).any())
        labels = new
        if not moved:
            break
    return labels, supersteps
