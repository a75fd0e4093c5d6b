"""Community census + induced subgraphs — vectorized.

Replaces the reference's driver-side outlier-prep loops
(``Graphframes.py:92-120``): collecting every vertex per community
(O(C·V)) and scanning the full edge table per vertex (O(C·V·E)) become a
handful of segment-sums and boolean masks, all on device, no host loop
over communities (SURVEY §7 hard part 4: masks, never per-community host
loops).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from graphmine_tpu.graph.container import Graph


@jax.jit
def community_sizes(labels: jax.Array) -> jax.Array:
    """Vertex count per label value, shape ``[V]`` (0 for unused labels).

    ``sizes[labels]`` gives each vertex its community's size. This is the
    per-community census the reference printed at ``Graphframes.py:120``.
    """
    v = labels.shape[0]
    with jax.named_scope("census"), jax.named_scope("sizes"):
        ones = jnp.ones_like(labels)
        return jax.ops.segment_sum(ones, labels, num_segments=v)


def intra_community_edge_mask(labels: jax.Array, graph: Graph) -> jax.Array:
    """Boolean ``[E]``: edge endpoints share a community.

    The vectorized form of the reference's per-vertex edge scan
    (``Graphframes.py:109-113``): the induced subgraph of every community,
    all at once.
    """
    return labels[graph.src] == labels[graph.dst]


@jax.jit
def community_edge_counts(labels: jax.Array, graph: Graph) -> jax.Array:
    """Intra-community edge count per label value, shape ``[V]``."""
    v = labels.shape[0]
    with jax.named_scope("census"), jax.named_scope("edge_counts"):
        mask = intra_community_edge_mask(labels, graph)
        return jax.ops.segment_sum(
            mask.astype(jnp.int32), labels[graph.src], num_segments=v
        )


def census_table(labels: jax.Array, graph: Graph):
    """Host-friendly summary: (label values, vertex counts, intra-edge counts),
    dense arrays over present labels only — the structured replacement for the
    reference's print-per-community loop (``Graphframes.py:100-120``).

    Host graphs (``build_graph(to_device=False)``, r3) compute with NumPy
    bincounts — no O(E) device transfer for graphs the memory planner kept
    off-device; identical results (tested)."""
    import numpy as np

    labels_np = np.asarray(labels)
    if isinstance(graph.src, np.ndarray):
        v = labels_np.shape[0]
        sizes = np.bincount(labels_np, minlength=v)
        src = graph.src
        mask = labels_np[src] == labels_np[graph.dst]
        edges = np.bincount(labels_np[src[mask]], minlength=v)
    else:
        sizes = np.asarray(community_sizes(labels))
        edges = np.asarray(community_edge_counts(labels, graph))
    present = np.flatnonzero(sizes > 0)
    return present, sizes[present], edges[present]
