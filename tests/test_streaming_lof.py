"""Streaming LOF tests: sklearn novelty-mode oracle + sliding-window behavior."""

import functools

import numpy as np
import pytest

from graphmine_tpu.ops.knn import cross_knn
from graphmine_tpu.ops.streaming_lof import StreamingLOF, fit_lof, score_lof


def test_cross_knn_matches_brute(rng):
    q = rng.normal(size=(37, 4)).astype(np.float32)
    r = rng.normal(size=(53, 4)).astype(np.float32)
    d2, idx = cross_knn(q, r, k=5, row_tile=16)
    full = ((q[:, None, :] - r[None, :, :]) ** 2).sum(-1)
    want_idx = np.argsort(full, axis=1, kind="stable")[:, :5]
    np.testing.assert_allclose(
        np.sort(np.asarray(d2), axis=1),
        np.sort(np.take_along_axis(full, want_idx, 1), axis=1),
        rtol=2e-4, atol=2e-4,
    )


def test_cross_knn_mask_excludes_slots(rng):
    q = rng.normal(size=(8, 3)).astype(np.float32)
    r = np.concatenate([q, rng.normal(size=(20, 3)).astype(np.float32)])
    mask = np.ones(28, bool)
    mask[:8] = False  # the exact copies are masked out
    _, idx = cross_knn(q, r, k=4, ref_mask=mask)
    assert (np.asarray(idx) >= 8).all()


def test_score_matches_sklearn_novelty(rng):
    from sklearn.neighbors import LocalOutlierFactor

    refs = rng.normal(size=(300, 5)).astype(np.float32)
    queries = np.concatenate(
        [rng.normal(size=(40, 5)), rng.normal(loc=6.0, size=(10, 5))]
    ).astype(np.float32)
    k = 15
    model = fit_lof(refs, k=k)
    got = np.asarray(score_lof(model, queries))
    oracle = LocalOutlierFactor(n_neighbors=k, novelty=True).fit(refs)
    want = -oracle.score_samples(queries)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_fit_with_padding_matches_unpadded(rng):
    pts = rng.normal(size=(100, 4)).astype(np.float32)
    padded = np.zeros((160, 4), np.float32)
    padded[:100] = pts
    mask = np.zeros(160, bool)
    mask[:100] = True
    m1 = fit_lof(pts, k=10)
    m2 = fit_lof(padded, mask, k=10)
    np.testing.assert_allclose(np.asarray(m2.kdist[:100]), np.asarray(m1.kdist), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m2.lrd[:100]), np.asarray(m1.lrd), rtol=1e-4)
    q = rng.normal(size=(20, 4)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(score_lof(m2, q)), np.asarray(score_lof(m1, q)), rtol=1e-4
    )


def test_streaming_flags_outliers(rng):
    # admit_threshold keeps flagged outliers out of the window, so a
    # persistent outlier cluster cannot launder itself into "normal"
    s = StreamingLOF(k=10, capacity=512, admit_threshold=2.0)
    aurocs = []
    for step in range(6):
        inliers = rng.normal(size=(120, 3)).astype(np.float32)
        outliers = rng.normal(loc=7.0, size=(8, 3)).astype(np.float32)
        chunk = np.concatenate([inliers, outliers])
        scores = s.update(chunk)
        assert scores.shape == (128,)
        if step == 0:
            continue  # bootstrap chunk scored in-window
        from graphmine_tpu.ops.lof import auroc

        y = np.zeros(128, bool)
        y[120:] = True
        aurocs.append(auroc(scores, y))
    assert min(aurocs) > 0.95


@functools.cache
def _injected_shell_auroc(seed: int) -> float:
    """Detection AUROC of outliers that ride a stream of blobs. Inlier
    radii about each centre follow a chi(8) law (mean ~2.83, 99.9th
    percentile ~4.4); the injected 0.5 % sit on a uniform [4, 6] radial
    shell JUST outside that envelope, so the value has room to move both
    ways (a +/-12 uniform box saturates it at exactly 1.0)."""
    from graphmine_tpu.ops.lof import auroc

    n, f, chunk, cap = 1 << 14, 8, 1 << 11, 1 << 11
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(32, f)).astype(np.float32) * 4
    assign = rng.integers(0, 32, n)
    pts = centers[assign] + rng.normal(size=(n, f)).astype(np.float32)
    is_out = rng.random(n) < 0.005
    n_out = int(is_out.sum())
    direction = rng.normal(size=(n_out, f)).astype(np.float32)
    direction /= np.linalg.norm(direction, axis=1, keepdims=True)
    radius = rng.uniform(4.0, 6.0, (n_out, 1)).astype(np.float32)
    pts[is_out] = centers[assign[is_out]] + direction * radius

    s = StreamingLOF(k=32, capacity=cap)
    scores = np.empty(n, np.float32)
    for lo in range(0, n, chunk):
        scores[lo:lo + chunk] = s.update(pts[lo:lo + chunk])
    warm = slice(cap, None)  # the first window-fill is a still-warming model
    return float(auroc(scores[warm], is_out[warm]))


_SHELL_SEEDS = (11, 12, 13)


@pytest.mark.parametrize("seed", _SHELL_SEEDS)
def test_injected_shell_auroc_band(seed):
    import jax

    value = _injected_shell_auroc(seed)
    assert value < 0.999, value  # not saturated, on every backend
    if jax.default_backend() == "cpu":
        # measured 0.9857-0.9901 on the CPU; an accelerator's kNN ties and
        # rounding may shift it, so the floor holds where it was measured
        assert value > 0.9, value


def test_injected_shell_auroc_spread():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("the spread was measured on the CPU backend")
    values = [_injected_shell_auroc(seed) for seed in _SHELL_SEEDS]
    assert max(values) - min(values) < 0.03, values


def test_persistent_cluster_absorbed_without_threshold(rng):
    # documents the flip side: with no admit threshold, a recurring outlier
    # cluster eventually joins the window and scores as normal
    s = StreamingLOF(k=10, capacity=512)
    for _ in range(4):
        chunk = np.concatenate(
            [rng.normal(size=(120, 3)), rng.normal(loc=7.0, size=(8, 3))]
        ).astype(np.float32)
        scores = s.update(chunk)
    assert scores[120:].mean() < 1.5  # absorbed


def test_window_eviction_adapts(rng):
    # distribution shift: after the window slides, the new regime is inlier
    s = StreamingLOF(k=8, capacity=256)
    a = rng.normal(loc=0.0, size=(256, 2)).astype(np.float32)
    s.update(a)
    b = rng.normal(loc=10.0, size=(256, 2)).astype(np.float32)
    high = s.update(b).mean()  # shifted chunk looks outlying vs regime A
    c = rng.normal(loc=10.0, size=(256, 2)).astype(np.float32)
    low = s.update(c).mean()  # window is now full of regime B
    assert high > 5 * low


def test_ivf_refit_reuses_one_trained_index():
    """r6 index reuse: impl="ivf" trains k-means ONCE (the first window
    big enough for the index), re-fits every later window against the
    reused centers, and scores must track the exact-impl stream tightly;
    ivf_retrain_every=N re-trains on the drift cadence."""
    rng = np.random.default_rng(11)
    n, f, chunk, cap, k = 1 << 14, 8, 1 << 10, 1 << 11, 16
    centers = rng.normal(size=(8, f)).astype(np.float32) * 4
    pts = (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, f)).astype(np.float32)
    )

    def run(**kw):
        s = StreamingLOF(k=k, capacity=cap, **kw)
        out = np.empty(n, np.float32)
        for lo in range(0, n, chunk):
            out[lo:lo + chunk] = s.update(pts[lo:lo + chunk])
        s.sync()
        return s, out

    s_exact, sc_exact = run()
    s_ivf, sc_ivf = run(impl="ivf")
    assert s_ivf.ivf_retrains == 1  # trained once, reused ever after
    assert s_ivf._ivf_fits >= 10
    warm = slice(cap, None)
    frac_close = np.mean(
        np.abs(sc_ivf[warm] - sc_exact[warm])
        < 0.05 * np.abs(sc_exact[warm]) + 0.01
    )
    assert frac_close > 0.95, frac_close

    s_rt, _ = run(impl="ivf", ivf_retrain_every=4)
    assert s_rt.ivf_retrains > 1

    with pytest.raises(ValueError, match="impl"):
        StreamingLOF(k=4, capacity=64, impl="annoy")
    with pytest.raises(ValueError, match="ivf_retrain_every"):
        StreamingLOF(k=4, capacity=64, impl="ivf", ivf_retrain_every=-1)


def test_ivf_small_windows_warm_up_exact(rng):
    """Windows that have not FILLED yet take the exact fit — the stream
    warms up exact (bit-for-bit the same fit as impl='exact') and the
    index trains only on a full window, never on a small early sample
    that would index every later window badly."""
    pts = rng.normal(size=(90, 4)).astype(np.float32)
    s_e = StreamingLOF(k=8, capacity=512)
    s_i = StreamingLOF(k=8, capacity=512, impl="ivf")
    np.testing.assert_array_equal(s_e.update(pts), s_i.update(pts))
    assert s_i.ivf_retrains == 0  # window not full: no training yet
    q = rng.normal(size=(4, 4)).astype(np.float32)
    np.testing.assert_array_equal(s_e.update(q), s_i.update(q))
    assert s_i.ivf_retrains == 0  # 94/512 valid: still warming up exact


def test_first_chunk_too_small():
    s = StreamingLOF(k=10, capacity=128)
    with pytest.raises(ValueError):
        s.update(np.zeros((5, 2), np.float32))
    with pytest.raises(ValueError):
        StreamingLOF(k=10, capacity=10)


def test_failed_bootstrap_is_retryable(rng):
    # a rejected bootstrap (threshold filters too much) must not corrupt
    # state: the next update re-bootstraps cleanly
    s = StreamingLOF(k=5, capacity=64, admit_threshold=1e-6)
    bad = rng.normal(size=(10, 2)).astype(np.float32)
    with pytest.raises(ValueError):
        s.update(bad)
    assert not s.fitted
    s.admit_threshold = 10.0
    scores = s.update(rng.normal(size=(20, 2)).astype(np.float32))
    assert s.fitted and scores.shape == (20,)


def test_update_with_empty_chunk():
    import numpy as np
    from graphmine_tpu.ops.streaming_lof import StreamingLOF

    rng = np.random.default_rng(0)
    s = StreamingLOF(k=3, capacity=32)
    s.update(rng.normal(size=(16, 4)).astype(np.float32))
    out = s.update(np.zeros((0, 4), np.float32))
    assert out.shape == (0,)
