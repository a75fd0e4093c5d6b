"""Streaming LOF — outlier scores for point streams too large for all-pairs.

The BASELINE.json config ladder ends at "Twitter-2010 (41M/1.4B, streaming
LOF on v5p-64)": at that scale the O(N^2) all-pairs pass of
:mod:`graphmine_tpu.ops.lof` is off the table. The streaming design scores
each arriving chunk against a fixed-capacity reference *window*:

- **fit**: kNN of window against itself → per-reference k-distance and
  local reachability density (lrd), exactly batch LOF's model state;
- **score**: chunk-vs-window cross kNN (one MXU matmul per row tile),
  reachability against the window's k-distances, LOF(q) = mean lrd of
  q's reference neighbors / lrd(q) — the classic reference-model LOF
  (sklearn's ``novelty=True`` scoring), validated against that oracle;
- **slide**: scored chunks enter the window ring-buffer style, evicting
  the oldest points; re-fit happens on the padded window.

TPU-first details: the window lives in a fixed ``[capacity, F]`` buffer
with a validity mask, so every fit/score step compiles once and reruns for
the whole stream — no shape churn while the window fills (SURVEY §7 hard
part 4: static shapes over dynamic ones).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from graphmine_tpu.ops.knn import cross_knn


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class LOFModel:
    """Fitted reference-window state: points + mask + k-distance + lrd."""

    refs: jax.Array       # [M, F] padded reference points
    mask: jax.Array       # bool [M] — valid slots
    kdist: jax.Array      # [M] distance to k-th neighbor (within window)
    lrd: jax.Array        # [M] local reachability density
    k: int = dataclasses.field(metadata=dict(static=True), default=20)


@partial(jax.jit, static_argnames=("k", "row_tile"))
def fit_lof(refs: jax.Array, mask: jax.Array | None = None, k: int = 20,
            row_tile: int = 1024) -> LOFModel:
    """Fit the LOF reference model on a (possibly padded) point set.

    ``mask`` marks valid rows; invalid rows get zeroed model state and never
    act as neighbors. Needs at least ``k + 1`` valid points.
    """
    m = refs.shape[0]
    if mask is None:
        mask = jnp.ones((m,), bool)
    # self-exclusion: ask for k+1 within the window and drop column 0
    # (the point itself at distance 0; under duplicates any zero-distance
    # column is an equally valid self representative).
    d2, idx = cross_knn(refs, refs, k=k + 1, ref_mask=mask, row_tile=row_tile)
    d2, idx = d2[:, 1:], idx[:, 1:]
    dists = jnp.sqrt(jnp.maximum(d2, 0.0))
    # duplicate guard, same rule as batch lof_scores: floor reach distances
    # at a fraction of the mean positive kNN distance
    pos = (dists > 0) & mask[:, None]
    eps = 1e-3 * jnp.where(pos, dists, 0.0).sum() / jnp.maximum(pos.sum(), 1)
    kdist = dists[:, -1]
    reach = jnp.maximum(jnp.maximum(kdist[idx], dists), eps)
    lrd = k / jnp.maximum(reach.sum(axis=1), 1e-12)
    zero = jnp.zeros_like(kdist)
    return LOFModel(
        refs=refs, mask=mask,
        kdist=jnp.where(mask, kdist, zero),
        lrd=jnp.where(mask, lrd, zero),
        k=k,
    )


@partial(jax.jit, static_argnames=("k",))
def _model_state_from_knn(d2: jax.Array, idx: jax.Array, k: int):
    """k-distance + lrd from a self-excluding kNN result (``[M, k]``) —
    the :func:`fit_lof` formula factored out so the IVF re-fit path
    (``StreamingLOF(impl="ivf")``) shares the duplicate-floor eps and
    reach semantics with the exact fit bit-for-bit."""
    dists = jnp.sqrt(jnp.maximum(d2, 0.0))
    pos = (dists > 0) & jnp.isfinite(dists)
    eps = 1e-3 * jnp.where(pos, dists, 0.0).sum() / jnp.maximum(pos.sum(), 1)
    kdist = dists[:, -1]
    reach = jnp.maximum(jnp.maximum(kdist[idx], dists), eps)
    lrd = k / jnp.maximum(reach.sum(axis=1), 1e-12)
    return kdist, lrd


@partial(jax.jit, static_argnames=("row_tile",))
def score_lof(model: LOFModel, queries: jax.Array, row_tile: int = 1024) -> jax.Array:
    """LOF score per query against the fitted window (higher = outlier)."""
    d2, idx = cross_knn(
        queries, model.refs, k=model.k, ref_mask=model.mask, row_tile=row_tile
    )
    dists = jnp.sqrt(jnp.maximum(d2, 0.0))
    pos = dists > 0
    eps = 1e-3 * jnp.where(pos, dists, 0.0).sum() / jnp.maximum(pos.sum(), 1)
    reach = jnp.maximum(jnp.maximum(model.kdist[idx], dists), eps)
    lrd_q = model.k / jnp.maximum(reach.sum(axis=1), 1e-12)
    return jnp.mean(model.lrd[idx], axis=1) / jnp.maximum(lrd_q, 1e-12)


class StreamingLOF:
    """Sliding-window streaming LOF scorer.

    >>> s = StreamingLOF(k=20, capacity=4096)
    >>> for chunk in stream:            # chunks of [n_i, F] points
    ...     scores = s.update(chunk)    # scores, then admits the chunk

    Each chunk is scored against the current window, then written into the
    fixed-capacity ring buffer (evicting the oldest points) and the model is
    re-fit. All device steps have static shapes once the feature dim and
    chunk size are seen, so the stream runs from a handful of compilations.

    ``impl="ivf"`` (r6): the window re-fit — the dominant cost term, a
    ``[capacity, capacity]`` self-kNN every admitted chunk — routes
    through the IVF-flat index (:func:`graphmine_tpu.ops.ann.ivf_knn`)
    with **one reused set of k-means centers**: the window slides by one
    chunk per re-fit, so its cluster structure is stable between fits,
    and re-fits skip the Lloyd iterations entirely (points are only
    re-assigned against the trained centers — one small matmul).
    Chunk-vs-window *scoring* stays exact cross-kNN (it is
    ``[chunk, capacity]``, far off the all-pairs wall). Centers train on
    the first FULL window (earlier re-fits stay exact — centers fit to
    a small early sample would index every later window badly);
    ``ivf_retrain_every=N`` re-trains every N IVF re-fits to track
    drift (0 = train once, the default — the ring buffer's content
    drifts one chunk at a time; the reuse win has no chip record).
    """

    def __init__(self, k: int = 20, capacity: int = 4096,
                 admit_threshold: float | None = None, impl: str = "exact",
                 ivf_retrain_every: int = 0, sink=None, centers=None):
        """``admit_threshold``: if set, points scoring above it are flagged
        but NOT admitted to the window. Without it, persistent outlier
        clusters eventually enter the window and start looking normal —
        sometimes wanted (regime change), sometimes not (contamination).

        ``centers`` (r7): pre-trained float32 ``[C, F]`` k-means centers
        to seed the IVF re-fit path with — a serving-layer scorer
        resuming from a snapshot skips Lloyd entirely (the same
        ``ivf_knn(centers=...)`` reuse the first full window would
        otherwise train; ``ivf_retrain_every`` still refreshes them on
        its cadence). Ignored under ``impl="exact"``."""
        if capacity <= k + 1:
            raise ValueError(f"capacity {capacity} must exceed k+1 = {k + 1}")
        if impl not in ("exact", "ivf"):
            raise ValueError(f"unknown impl {impl!r}; use 'exact' or 'ivf'")
        if ivf_retrain_every < 0:
            raise ValueError("ivf_retrain_every must be >= 0 (0 = once)")
        self.k = k
        self.capacity = capacity
        self.admit_threshold = admit_threshold
        self.impl = impl
        self.ivf_retrain_every = ivf_retrain_every
        self.ivf_retrains = 0  # kmeans trainings performed (reuse metric)
        self._sink = sink
        self._ivf_fits = 0     # re-fits that actually rode the index
        # trained [C, F] centers (impl="ivf"); seeded from `centers` when
        # given so a resumed scorer never re-trains what a prior
        # process/snapshot already paid for
        self._centers = (
            None if centers is None else np.asarray(centers, np.float32)
        )
        self._refs: np.ndarray | None = None  # [capacity, F]
        self._valid = 0        # number of valid slots (grows to capacity)
        self._write = 0        # ring-buffer write head
        self._model: LOFModel | None = None

    @property
    def fitted(self) -> bool:
        return self._model is not None

    def sync(self) -> None:
        """Block until the most recent re-fit has completed on device.

        ``update`` blocks on the chunk's *scores* (host fetch) but
        dispatches the window re-fit asynchronously — its cost is normally
        absorbed by the next chunk's scoring. Call this after the last
        chunk when measuring throughput, so the final fit's device time is
        inside the timed window."""
        if self._model is not None:
            jax.block_until_ready(self._model)

    def update(self, chunk) -> np.ndarray:
        """Score ``chunk`` against the window, then admit it and re-fit.

        Returns ``[n]`` LOF scores. The first chunk bootstraps the window
        (needs at least ``k + 1`` points) and is scored *in-window* with the
        self-excluding batch formula; every later chunk is scored against
        the window as fitted *before* the chunk entered it.
        """
        chunk = np.asarray(chunk, dtype=np.float32)
        if chunk.ndim != 2:
            raise ValueError("chunk must be [n, features]")
        bootstrap = self._model is None
        if bootstrap:
            if len(chunk) < self.k + 1:
                raise ValueError(
                    f"first chunk needs >= k+1 = {self.k + 1} points, got {len(chunk)}"
                )
            from graphmine_tpu.ops.lof import lof_scores

            scores = np.asarray(lof_scores(jnp.asarray(chunk), k=self.k))
        else:
            scores = np.asarray(score_lof(self._model, jnp.asarray(chunk)))
        admit = chunk
        if self.admit_threshold is not None:
            admit = chunk[scores <= self.admit_threshold]
        if bootstrap and len(admit) < self.k + 1:
            # raise before touching window state, so the caller can retry
            # with a bigger/cleaner chunk and bootstrap again
            raise ValueError(
                f"admit_threshold leaves {len(admit)} bootstrap points; "
                f"need >= k+1 = {self.k + 1}"
            )
        if len(admit):
            if self._refs is None:
                self._refs = np.zeros((self.capacity, chunk.shape[1]), np.float32)
            self._admit(admit)
            self._fit()
        return scores

    def _fit(self) -> None:
        if self.impl == "ivf":
            self._fit_ivf()
        else:
            self._model = fit_lof(
                jnp.asarray(self._refs), jnp.asarray(self._mask()), k=self.k
            )

    def _fit_ivf(self) -> None:
        """Window re-fit through the IVF index with reused centers.

        The index is sized for the FULL window (``~sqrt(capacity)``
        clusters) and its centers train on the first FULL window — not
        merely the first one past the index's minimum viable size:
        centers fit to a small early sample (one regime of the stream)
        would index every later full-capacity window badly, degraded
        recall with no announcement. Until the fill, re-fits take the
        exact path — the stream warms up exact, then switches to the
        index once, permanently. The self-kNN result feeds the same
        k-distance/lrd model state as :func:`fit_lof` (ivf_knn excludes
        self by id, exactly like the batch scorer's kNN contract).
        """
        from graphmine_tpu.ops.ann import default_n_clusters, ivf_knn, kmeans

        n_clusters = default_n_clusters(self.capacity)
        valid = self._valid
        pts = self._refs[:valid]
        if valid < self.capacity:
            self._model = fit_lof(
                jnp.asarray(self._refs), jnp.asarray(self._mask()), k=self.k
            )
            return
        retrain = self._centers is None or (
            self.ivf_retrain_every
            and self._ivf_fits % self.ivf_retrain_every == 0
        )
        if retrain:
            self._centers = kmeans(pts, n_clusters, seed=0)
            self.ivf_retrains += 1
        self._ivf_fits += 1
        d2, idx = ivf_knn(
            pts, k=self.k, centers=self._centers, sink=self._sink
        )
        kdist, lrd = _model_state_from_knn(d2, idx, self.k)
        pad = self.capacity - valid
        self._model = LOFModel(
            refs=jnp.asarray(self._refs),
            mask=jnp.asarray(self._mask()),
            kdist=jnp.pad(kdist, (0, pad)),
            lrd=jnp.pad(lrd, (0, pad)),
            k=self.k,
        )

    def _mask(self) -> np.ndarray:
        mask = np.zeros(self.capacity, bool)
        mask[: self._valid] = True
        return mask

    def _admit(self, chunk: np.ndarray) -> None:
        take = chunk[-self.capacity:]  # only the newest fit in the window
        n = len(take)
        end = min(self._write + n, self.capacity)
        first = end - self._write
        self._refs[self._write:end] = take[:first]
        if first < n:
            self._refs[: n - first] = take[first:]
        self._write = (self._write + n) % self.capacity
        self._valid = min(self._valid + n, self.capacity)
