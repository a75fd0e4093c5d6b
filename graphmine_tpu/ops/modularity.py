"""Newman modularity of a community partition.

The reference evaluates community quality only by eyeballing counts
(``Graphframes.py:85,120``); SURVEY §7.7 names Louvain-modularity
comparison as the scale-up capability. This metric is the shared yardstick
for LPA vs Louvain partitions.

Conventions (matching networkx / python-louvain on weighted multigraphs):
the graph is a symmetric weighted message list (both directions of every
edge present) plus per-vertex self-loop weights; a self-loop of weight w
contributes 2w to its vertex's degree and 2w to its community's internal
weight.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from graphmine_tpu.graph.container import Graph


@partial(jax.jit, static_argnames=("num_vertices",))
def modularity_weighted(
    labels: jax.Array,
    recv: jax.Array,
    send: jax.Array,
    weight: jax.Array,
    self_weight: jax.Array,
    num_vertices: int,
    gamma: float = 1.0,
) -> jax.Array:
    """Q = sum_c [ Sigma_in_c / 2m  -  gamma * (Sigma_tot_c / 2m)^2 ].

    ``recv``/``send``/``weight`` are the symmetric message list (self-loops
    excluded, carried in ``self_weight``). Out-of-range ids (padding
    sentinels) are dropped by the segment ops.
    """
    with jax.named_scope("modularity"), jax.named_scope("q"):
        w = weight.astype(jnp.float32)
        k = (
            jax.ops.segment_sum(w, recv, num_segments=num_vertices)
            + 2.0 * self_weight
        )
        two_m = jnp.maximum(k.sum(), 1e-12)
        valid = recv < num_vertices
        intra_msgs = jnp.where(
            valid
            & (labels[jnp.minimum(recv, num_vertices - 1)] == labels[send]),
            w, 0.0,
        ).sum()
        sigma_in = intra_msgs + 2.0 * self_weight.sum()
        sigma_tot = jax.ops.segment_sum(k, labels, num_segments=num_vertices)
        return sigma_in / two_m - gamma * jnp.sum((sigma_tot / two_m) ** 2)


def message_weights(graph: Graph) -> tuple[jax.Array, jax.Array]:
    """Split a symmetric graph's messages into ``(w [M], self_w [V])``.

    The single home of the self-loop convention shared by modularity and
    Louvain's level construction: self-loop messages carry weight 0 in
    ``w`` and accumulate half their weight per appearance into ``self_w``
    (each self-loop edge appears twice in the symmetric list, so a
    self-loop of weight x adds 2x to its vertex's degree). Per-edge
    weights come from ``graph.msg_weight`` when present, else 1.
    """
    _require_symmetric(graph)
    v = graph.num_vertices
    is_self = graph.msg_recv == graph.msg_send
    base = 1.0 if graph.msg_weight is None else graph.msg_weight.astype(jnp.float32)
    w = jnp.where(is_self, 0.0, base)
    self_w = jax.ops.segment_sum(
        jnp.where(is_self, 0.5 * base, 0.0), graph.msg_recv, num_segments=v,
        indices_are_sorted=True,
    )
    return w, self_w


def modularity(labels: jax.Array, graph: Graph, gamma: float = 1.0) -> jax.Array:
    """Modularity of ``labels`` on a :class:`Graph` — per-edge weights when
    the graph carries them (``build_graph(edge_weights=...)``), else unit
    weights; duplicate edges counted with multiplicity, self-loops handled.

    Host graphs (``build_graph(to_device=False)``, r3) dispatch to a NumPy
    twin with identical conventions — no O(E) device transfer for graphs
    the memory planner kept off-device."""
    import numpy as np

    if isinstance(graph.msg_recv, np.ndarray):
        return _modularity_host(labels, graph, gamma)
    w, self_w = message_weights(graph)
    return modularity_weighted(
        labels, graph.msg_recv, graph.msg_send, w, self_w,
        graph.num_vertices, gamma,
    )


def _require_symmetric(graph: Graph) -> None:
    """Shared guard: both modularity paths read the symmetric message
    list."""
    if not graph.symmetric:
        raise ValueError(
            "the message-weight decomposition needs the symmetric message "
            "list (both edge directions); rebuild with symmetric=True"
        )


def _modularity_host(labels, graph: Graph, gamma: float):
    """NumPy twin of ``modularity_weighted`` + ``message_weights`` (same
    self-loop and weight conventions; float64 accumulation)."""
    import numpy as np

    _require_symmetric(graph)
    v = graph.num_vertices
    recv = graph.msg_recv
    send = graph.msg_send
    labels = np.asarray(labels)
    base = (
        np.ones(len(recv), np.float64) if graph.msg_weight is None
        else np.asarray(graph.msg_weight, np.float64)
    )
    is_self = recv == send
    w = np.where(is_self, 0.0, base)
    self_w = np.bincount(
        recv, weights=np.where(is_self, 0.5 * base, 0.0), minlength=v
    )
    k = np.bincount(recv, weights=w, minlength=v) + 2.0 * self_w
    two_m = max(float(k.sum()), 1e-12)
    intra = float(w[labels[recv] == labels[send]].sum())
    sigma_in = intra + 2.0 * float(self_w.sum())
    sigma_tot = np.bincount(labels, weights=k, minlength=v)
    return sigma_in / two_m - gamma * float(np.sum((sigma_tot / two_m) ** 2))
