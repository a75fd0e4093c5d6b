"""Ring-sharded kNN + LOF over the device mesh.

The north-star outlier path (BASELINE.json: "kNN-graph + LOF ... batched
all-pairs-distance + top-k") runs single-device in :mod:`ops/knn` — every
row's distances need every point, so a naive GSPMD partition of the
all-pairs matmul replicates the full ``[N, F]`` point set per device.
This module is the memory-scalable design, the same schedule as
:mod:`parallel/ring`'s LPA: points stay row-sharded, chunks rotate around
the mesh ring via ``ppermute``, and each device folds the visiting chunk
into a running top-k for its own rows. Per-device memory is
O(N/D x (F + k)) plus one visiting chunk — no replicated [N, F] term,
and each rotation step's distance tile is still one MXU matmul.

Semantics match :func:`graphmine_tpu.ops.knn.knn` (self excluded by
global id, duplicates kept, squared Euclidean, ascending) — pinned by
the virtual-mesh parity tests — with one scoped difference: among
*exactly tied* distances (duplicate points), neighbor order follows the
ring visit order rather than ascending global index, so tied neighbor
id lists can differ while the distance lists agree.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from graphmine_tpu.ops.ann import _select_k
from graphmine_tpu.ops.knn import _tiled_knn
# the one jitted wrapper of the shared LOF formula (ops/lof.py owns it)
from graphmine_tpu.ops.lof import _lof_from_knn_jit as _lof_from_knn
from graphmine_tpu.parallel.mesh import VERTEX_AXIS, cached_jit_shard_map


def _knn_ring_body(pts, *, n: int, k: int, chunk: int, num_shards: int,
                   row_tile: int):
    """Per-device ring kNN (runs under shard_map; ``pts`` is this device's
    ``[chunk, F]`` row slice). Each hop folds the visiting chunk into the
    running top-k via the shared :func:`ops.knn._tiled_knn` core
    (id-equality self-exclusion, padding slots masked) and one selection
    over ``[chunk, 2k]`` (:func:`ops.ann._select_k`: the sort carries the
    ids); D-1 ppermute hops total."""
    my = lax.axis_index(VERTEX_AXIS).astype(jnp.int32)
    local_gid = my * chunk + jnp.arange(chunk, dtype=jnp.int32)
    best_d = jnp.full((chunk, k), jnp.inf, jnp.float32)
    best_g = jnp.zeros((chunk, k), jnp.int32)
    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    visit = pts
    for r in range(num_shards):
        owner = jnp.mod(my - r, num_shards)
        visit_gid = owner * chunk + jnp.arange(chunk, dtype=jnp.int32)
        d2, idx = _tiled_knn(
            pts, visit, k, row_tile,
            ref_mask=visit_gid < n,
            query_ids=local_gid, ref_ids=visit_gid,
        )
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_g = jnp.concatenate([best_g, visit_gid[idx]], axis=1)
        best_d, best_g = _select_k(cat_d, cat_g, k)
        if r != num_shards - 1:
            visit = lax.ppermute(visit, VERTEX_AXIS, perm)
    return best_d, best_g


def _compiled_body(mesh, n: int, k: int, chunk: int, row_tile: int):
    """One compiled ring program per (mesh, n, k, chunk, row_tile) — a
    fresh wrapper per call would re-trace the D-unrolled ring every
    invocation."""
    return cached_jit_shard_map(
        ("knn_ring", mesh, n, k, chunk, row_tile),
        lambda: shard_map(
            partial(_knn_ring_body, n=n, k=k, chunk=chunk,
                    num_shards=mesh.size, row_tile=row_tile),
            mesh=mesh,
            in_specs=P(VERTEX_AXIS, None),
            out_specs=(P(VERTEX_AXIS, None), P(VERTEX_AXIS, None)),
        ),
    )


def can_shard(n: int, num_devices: int, k: int) -> bool:
    """Whether an ``[n, F]`` point set can ride the ring with this ``k``:
    every per-device chunk (``ceil(n/D)``) must hold at least ``k``
    candidates for the per-hop top-k. The single owner of the constraint
    :func:`sharded_knn` enforces — dispatchers use this instead of
    re-deriving it."""
    return 0 < k < n and k <= -(-n // num_devices)


def sharded_knn(points, mesh, k: int, row_tile: int = 1024):
    """k nearest neighbors with the point set sharded over a 1-D mesh.

    ``points``: host ``[N, F]`` array. Returns ``(d2, idx)`` jax arrays
    of shape ``[N, k]``, vertex-range sharded over the mesh — same
    contract as :func:`graphmine_tpu.ops.knn.knn` (ascending squared
    distances, self excluded, duplicates kept).
    """
    points = np.asarray(points, np.float32)
    n, f = points.shape
    d = mesh.size
    chunk = -(-n // d)
    if not can_shard(n, d, k):
        if not 0 < k < n:
            raise ValueError(f"k={k} must be < number of points {n}")
        raise ValueError(
            f"k={k} exceeds the per-device chunk {chunk} (= ceil(N/D)); "
            "use fewer devices or the single-device ops.knn path"
        )
    padded = np.zeros((d * chunk, f), np.float32)
    padded[:n] = points
    pts = jax.device_put(padded, NamedSharding(mesh, P(VERTEX_AXIS, None)))
    d2, gid = _compiled_body(mesh, n, k, chunk, row_tile)(pts)
    return d2[:n], gid[:n]


def _ivf_search_body(q_gid, row_sub, pts, m_gid, m_valid, *, k: int):
    """Per-device slice of the IVF cluster-batched search (runs under
    shard_map): this device's chunk rows, one ``lax.map`` of the shared
    :func:`ops.ann._search_clusters` block over them. Points and the
    member tables are replicated — they are O(N x F) / O(n_sub x Lmax)
    small next to the O(candidate-pairs) distance work being split."""
    from graphmine_tpu.ops.ann import _search_clusters

    def one_chunk(args):
        qg, s = args
        mg = m_gid[s]
        return _search_clusters(pts[qg], qg, pts[mg], mg, m_valid[s], k)

    return lax.map(one_chunk, (q_gid, row_sub))


def mesh_ivf_search_exec(mesh):
    """A ``search_exec`` for :func:`graphmine_tpu.ops.ann.ivf_knn` that
    splits the cluster-batched search — the dominant distance work — over
    ``mesh``. Chunk rows are padded to a device-count multiple (appended
    at the end: ``ivf_knn`` slices real rows back off) and row-sharded;
    each device searches its share. One compiled program per (mesh, table
    shapes, k) — the same compile-per-dataset trade the single-device IVF
    path already makes."""

    def exec_fn(pts, m_gid, m_valid, q_gid, row_sub, k):
        d = mesh.size
        r, b = q_gid.shape
        r_pad = -(-r // d) * d
        qg = np.zeros((r_pad, b), np.int32)
        qg[:r] = q_gid
        # padded rows point at sublist 0 with query id 0: searched like
        # any chunk, sliced off by the caller, never read back
        rs = np.zeros((r_pad,), np.int32)
        rs[:r] = row_sub
        body = cached_jit_shard_map(
            ("ivf_search", mesh, pts.shape, m_gid.shape, r_pad, b, k),
            lambda: shard_map(
                partial(_ivf_search_body, k=k),
                mesh=mesh,
                in_specs=(
                    P(VERTEX_AXIS, None), P(VERTEX_AXIS),
                    P(None, None), P(None, None), P(None, None),
                ),
                out_specs=(
                    P(VERTEX_AXIS, None, None), P(VERTEX_AXIS, None, None)
                ),
            ),
        )
        return body(
            jnp.asarray(qg), jnp.asarray(rs), jnp.asarray(pts),
            jnp.asarray(m_gid), jnp.asarray(m_valid),
        )

    return exec_fn


def sharded_lof(points, mesh, k: int = 128, row_tile: int = 1024,
                impl: str = "auto", sink=None):
    """Distributed LOF scores over the device mesh.

    ``impl`` (r6, same policy surface as :func:`ops.lof.lof_scores`):

    - ``"exact"`` — ring-sharded all-pairs kNN (the r2 path): points stay
      row-sharded, chunks rotate via ``ppermute``.
    - ``"ivf"`` — the IVF-flat candidate reduction with its search stage
      sharded over the mesh (:func:`mesh_ivf_search_exec`), so the mesh
      path does LESS work per output slot instead of ring all-pairs. The
      index build (k-means, inverted lists) and final merge stay
      host/default-device — they are a small fraction of the exact
      path's distance work. A pathology-guard fallback inside ``ivf_knn``
      lands on the single-device exact path, LOUDLY (warning +
      ``ivf_fallback`` record through ``sink``).
    - ``"auto"`` — :func:`ops.lof.select_lof_impl`'s measured crossover
      decides (IVF from ~131K points); the choice is emitted as an
      ``impl_selected`` record when ``sink`` is given.

    The post-kNN gathers (``kdist[idx]``, ``lrd[idx]``) touch only ``[N]``
    vectors, so GSPMD's inserted collectives are small. Returns float32
    ``[N]``.
    """
    from graphmine_tpu.ops.lof import select_lof_impl

    if impl not in ("auto", "ivf", "exact"):
        raise ValueError(
            f"unknown sharded LOF impl {impl!r}; use 'auto', 'ivf' or "
            "'exact'"
        )
    n = int(np.asarray(points).shape[0])
    family, reason = select_lof_impl(n, k, impl=impl)
    if sink is not None:
        from graphmine_tpu.obs.costmodel import lof_cost
        from graphmine_tpu.ops.lof import resolved_ivf_min_points

        sink.emit(
            "impl_selected", op="lof_knn", impl=family, requested=impl,
            n=n, k=k, devices=int(mesh.size), reason=reason,
            thresholds={"lof_ivf_min_points": resolved_ivf_min_points()},
            cost=lof_cost(
                family, n, k, features=int(np.asarray(points).shape[-1]),
                devices=int(mesh.size),
            ).record(),
        )
    if family == "ivf":
        from graphmine_tpu.ops.ann import ivf_knn

        d2, gid = ivf_knn(
            points, k=k, sink=sink, search_exec=mesh_ivf_search_exec(mesh)
        )
        return _lof_from_knn(d2, gid, k)
    d2, gid = sharded_knn(points, mesh, k, row_tile)
    return _lof_from_knn(d2, gid, k)
