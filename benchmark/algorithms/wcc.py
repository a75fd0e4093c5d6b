"""Algorithm ``wcc``: LDBC Graphalytics' weakly connected components through
``gm.connected_components``, to the fixpoint: the supersteps are the
program's answer. The reference is SciPy's union-find named by the smallest
member id; the control breaks the fixpoint guarantee (the plain min-label
engine stopped after two supersteps). The answer is integers and stated
exact: the limit is 0."""

from __future__ import annotations

import numpy as np

import references
import references_wcc


def run(graph, sink, traffic):
    import graphmine_tpu as gm

    return gm.connected_components(
        graph, plan="auto", return_iterations=True, sink=sink)


def reference(u, v, num_vertices: int, traffic):
    return references.canonical_partition(references.scipy_cc(u, v, num_vertices))


def control(u, v, num_vertices: int, traffic):
    """The fixpoint guarantee broken: an engine that stops after two
    supersteps."""
    return references_wcc.numpy_min_label(u, v, num_vertices, max_supersteps=2)[0]


def compare(got, want) -> list:
    """Every label against the reference's, over the whole vertex space."""
    bad = int((got != want).sum())
    return [{"check": "wcc_label_mismatches", "value": bad, "limit": 0,
             "ok": bad == 0, "compared": len(want),
             "components": int(len(np.unique(want)))}]
