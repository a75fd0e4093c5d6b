"""Extended cross-path consistency sweep (manual; heavier than CI's fuzz).

Runs the one-answer invariant — every LPA/CC/PageRank/PPR/kNN execution
path agrees — over many random graph shapes and seeds, unweighted AND
weighted, on the virtual 8-device mesh. CI's ``test_consistency_fuzz``
covers 7 pinned cases; this sweeps hundreds. Run before releases or
after touching any superstep/plan/partition code:

    JAX_PLATFORMS=cpu \\
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=. python tools/consistency_sweep.py [num_seeds] [first_seed] [--big]

Chunking into fresh processes is AUTOMATIC since r4 (XLA:CPU's LLVM JIT
arena exhausts after a bounded number of unique-shape compilations per
process — and the 1.10x width ladder's extra bucket classes dropped the
per-process ceiling from ~50 to ~20 small-tier seeds): a parent re-execs
the sweep in ``GRAPHMINE_SWEEP_CHUNK``-seed children (default 12 small /
4 big). ``first_seed`` still works for manual ranges.
``--big`` switches to the big-graph tier: fewer, larger cases (2K-40K
vertices) with injected mega-hubs (degree 2500-6000) so the histogram /
wide bucket classes and large ring rotations are exercised.

Exits nonzero on the first disagreement with a full repro line.
This sweep caught a real shard_map scatter miscompile in round 2
(docs/DESIGN.md) and the PPR convergence-coupling gap fixed by the
pmax-coupled stopping rule.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np


def _cases(num_seeds: int, first_seed: int):
    """Small-graph tier: many shapes, isolates, self-loops, duplicates."""
    for seed in range(first_seed, first_seed + num_seeds):
        rng = np.random.default_rng(seed)
        v = int(rng.integers(8, 700))
        e = int(rng.integers(1, 12 * v))
        shape = rng.choice(["uniform", "powerlaw", "star", "chain"])
        if shape == "uniform":
            src = rng.integers(0, v, e).astype(np.int32)
            dst = rng.integers(0, v, e).astype(np.int32)
        elif shape == "powerlaw":
            raw = rng.pareto(1.1, size=2 * e)
            ids = np.minimum((raw * v / 15).astype(np.int64), v - 1).astype(np.int32)
            src, dst = ids[:e], ids[e:]
        elif shape == "star":
            hub = int(rng.integers(0, v))
            src = np.full(e, hub, np.int32)
            dst = rng.integers(0, v, e).astype(np.int32)
        else:  # chain + noise
            base = np.arange(min(e, v - 1), dtype=np.int32)
            extra = rng.integers(0, v, max(e - len(base), 0)).astype(np.int32)
            src = np.concatenate([base, extra[: max(e - len(base), 0)]])
            dst = np.concatenate(
                [base + 1,
                 rng.integers(0, v, len(src) - len(base)).astype(np.int32)]
            )
        it = int(rng.integers(1, 6))
        weights = None
        if rng.random() < 0.5:
            # ZERO weights included (r3): weights >= 0 are legal, and the
            # all-zero-hub argmax bug (ADVICE r2) lived exactly in the
            # region the old 1/4..15/4 draw never reached.
            weights = (rng.integers(0, 16, len(src)) / 4.0).astype(np.float32)
        tag = (f"seed={seed} v={v} e={len(src)} shape={shape} iters={it} "
               f"weighted={weights is not None}")
        yield tag, src, dst, v, it, weights, rng


def _big_cases(num_seeds: int, first_seed: int):
    """Mega-hub big-graph tier: histogram/wide bucket classes, big rings."""
    for seed in range(first_seed, first_seed + num_seeds):
        rng = np.random.default_rng(7000 + seed)
        v = int(rng.integers(2000, 40000))
        e = int(rng.integers(v, 8 * v))
        hub = rng.integers(0, v, 3).astype(np.int32)
        hub_e = int(rng.integers(2500, 6000))
        src = np.concatenate(
            [rng.integers(0, v, e), np.repeat(hub, hub_e)]
        ).astype(np.int32)
        dst = np.concatenate(
            [rng.integers(0, v, e), rng.integers(0, v, 3 * hub_e)]
        ).astype(np.int32)
        weights = None
        if seed % 2:
            # zero weights included — mega-hubs with all-zero incoming
            # weight exercise the masked histogram argmax (ADVICE r2)
            weights = (rng.integers(0, 16, len(src)) / 4.0).astype(np.float32)
        tag = f"big seed={seed} v={v} e={len(src)} weighted={weights is not None}"
        yield tag, src, dst, v, 3, weights, rng


def sweep(num_seeds: int = 30, first_seed: int = 0, big: bool = False) -> int:
    import jax
    import jax.numpy as jnp

    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.ops.bucketed_mode import (
        build_graph_and_plan,
        lpa_superstep_bucketed,
    )
    from graphmine_tpu.ops.cc import connected_components
    from graphmine_tpu.ops.census import census_table
    from graphmine_tpu.ops.degrees import out_degrees, out_weights
    from graphmine_tpu.ops.features import (
        vertex_features,
        vertex_features_host,
    )
    from graphmine_tpu.ops.modularity import modularity
    from graphmine_tpu.ops.knn import knn
    from graphmine_tpu.ops.lof import lof_scores
    from graphmine_tpu.ops.lpa import label_propagation
    from graphmine_tpu.ops.pagerank import pagerank, parallel_personalized_pagerank
    from graphmine_tpu.parallel.knn import can_shard, sharded_knn, sharded_lof
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.ppr import sharded_personalized_pagerank
    from graphmine_tpu.parallel.ring import (
        ring_connected_components,
        ring_label_propagation,
        ring_pagerank,
    )
    from graphmine_tpu.parallel.sharded import (
        partition_graph,
        shard_graph_arrays,
        sharded_connected_components,
        sharded_label_propagation,
        sharded_pagerank,
    )

    d = min(8, len(jax.devices()))
    mesh = make_mesh(d)
    step = jax.jit(lpa_superstep_bucketed)
    gen = _big_cases(num_seeds, first_seed) if big else _cases(num_seeds, first_seed)
    checked = 0
    for tag, src, dst, v, it, weights, rng in gen:
        g = build_graph(src, dst, num_vertices=v, edge_weights=weights)
        want = np.asarray(label_propagation(g, max_iter=it, plan=None))

        g2, plan = build_graph_and_plan(src, dst, num_vertices=v, edge_weights=weights)
        lbl = jnp.arange(v, dtype=jnp.int32)
        for _ in range(it):
            lbl = step(lbl, g2, plan)
        assert np.array_equal(want, np.asarray(lbl)), f"fused != sort: {tag}"

        sgf = shard_graph_arrays(
            partition_graph(g, mesh=mesh, build_bucket_plan=True), mesh
        )
        assert np.array_equal(
            want, np.asarray(sharded_label_propagation(sgf, mesh, max_iter=it))
        ), f"sharded bucketed != sort: {tag}"
        sg = shard_graph_arrays(partition_graph(g, mesh=mesh), mesh)
        assert np.array_equal(
            want, np.asarray(sharded_label_propagation(sg, mesh, max_iter=it))
        ), f"sharded sort != sort: {tag}"
        assert np.array_equal(
            want, np.asarray(ring_label_propagation(sg, mesh, max_iter=it))
        ), f"ring != sort: {tag}"

        cc = np.asarray(connected_components(g))
        assert np.array_equal(
            cc, np.asarray(sharded_connected_components(sg, mesh))
        ), f"sharded cc: {tag}"
        assert np.array_equal(
            cc, np.asarray(ring_connected_components(sg, mesh))
        ), f"ring cc: {tag}"

        # r3 host twins (scale-out mode's paths): census / modularity /
        # features on a host-resident graph must match the device ops.
        gh = build_graph(src, dst, num_vertices=v, edge_weights=weights,
                         to_device=False)
        for a, b in zip(census_table(want, g), census_table(want, gh)):
            assert np.array_equal(a, b), f"host census: {tag}"
        q0 = float(modularity(jnp.asarray(want), g))
        q1 = float(modularity(want, gh))
        assert abs(q0 - q1) < 2e-4, f"host modularity {q0} vs {q1}: {tag}"
        if not big:
            f0 = np.asarray(vertex_features(g, jnp.asarray(want)))
            f1 = vertex_features_host(gh, want, include_clustering=True)
            assert np.allclose(f0, f1, rtol=2e-4, atol=2e-5), (
                f"host features: {tag}"
            )

        gd = build_graph(src, dst, num_vertices=v, symmetric=False,
                         edge_weights=weights)
        sgd = shard_graph_arrays(partition_graph(gd, mesh=mesh), mesh)
        if weights is None:
            pr_want = np.asarray(pagerank(gd, max_iter=40))
            ow = out_degrees(gd)
        else:
            pr_want = np.asarray(pagerank(gd, max_iter=40, weights=jnp.asarray(weights)))
            ow = out_weights(gd)
        pr_s = np.asarray(sharded_pagerank(sgd, mesh, ow, max_iter=40))
        pr_r = np.asarray(ring_pagerank(sgd, mesh, ow, max_iter=40))
        assert np.allclose(pr_s, pr_want, rtol=3e-4, atol=1e-7), f"sharded pr: {tag}"
        assert np.allclose(pr_r, pr_want, rtol=3e-4, atol=1e-7), f"ring pr: {tag}"

        if not big:
            # source-sharded PPR vs the single-device batched op (the pmax
            # coupling makes both iterate in lockstep — tight tolerance)
            n_src = int(rng.integers(1, 12))
            srcs = rng.integers(0, v, n_src).astype(np.int32)
            ppr_want = np.asarray(parallel_personalized_pagerank(gd, srcs, max_iter=25))
            ppr_got = np.asarray(
                sharded_personalized_pagerank(gd, srcs, mesh, max_iter=25)
            )
            assert np.allclose(
                ppr_got, ppr_want, rtol=3e-4, atol=1e-7
            ), f"sharded ppr: {tag}"

            # ring-sharded kNN/LOF vs single-device (random point clouds)
            n_pts = int(rng.integers(d * 3, 400))
            f_dim = int(rng.integers(2, 12))
            k = int(rng.integers(2, min(16, -(-n_pts // d)) + 1))
            if can_shard(n_pts, d, k):
                pts = rng.normal(size=(n_pts, f_dim)).astype(np.float32)
                # one all-pairs pass at k+1: the first k columns are the
                # k-NN answer (top-k prefixes are stable), the extra
                # column feeds the boundary-tie mask below
                kx = min(k + 1, n_pts - 1)
                kd1, ki1 = knn(pts, k=kx, impl="xla")
                kd1, ki1 = np.asarray(kd1), np.asarray(ki1)
                sd = np.asarray(sharded_knn(pts, mesh, k=k, row_tile=32)[0])
                assert np.allclose(
                    sd, kd1[:, :k], rtol=1e-5, atol=1e-5
                ), f"sharded knn d2: {tag}"
                lw = np.asarray(lof_scores(pts, k=k, impl="xla"))
                lg = np.asarray(sharded_lof(pts, mesh, k=k, row_tile=32))
                # LOF is only defined up to kNN tie-breaking: when a row's
                # k-th and (k+1)-th neighbor distances coincide within the
                # paths' ACTUAL distance discrepancy (usually 0 or a few
                # float32 ulps — seed 5018 found an exact boundary tie in
                # a random cloud), the two paths may legitimately keep
                # different neighbor SETS, and the difference propagates
                # two hops (k-distance -> neighbors' lrd -> LOF). Tiered
                # assert: every row must agree tightly UNLESS it sits in
                # the two-hop neighborhood of a boundary tie — a
                # disagreement anywhere else always fails, so the check
                # cannot go vacuous even though one tie at k=14 blankets
                # 2/3 of a 330-point cloud two hops out (seed 6009).
                close = np.isclose(lg, lw, rtol=5e-3, atol=2e-3)
                if not close.all() and kd1.shape[1] > k:
                    ki = ki1[:, :k]
                    gap = kd1[:, k] - kd1[:, k - 1]
                    obs_row = np.abs(sd - kd1[:, :k]).max(axis=1)
                    # the excuse stays honest only while the tie window is
                    # ulp-scale: if the paths' distances ever drift to the
                    # magnitude the allclose above merely tolerates, a
                    # window built on that drift could blanket every row
                    # and excuse a real bug — fail LOUDLY on drift instead.
                    # Per-ROW scale (ADVICE r4): judging every row against
                    # the cloud's LARGEST k-distance would let one
                    # big-scale row excuse genuine drift on a small one.
                    eps32 = np.finfo(np.float32).eps
                    row_scale = np.maximum(kd1[:, k - 1], 1.0)
                    drift = obs_row > 32 * eps32 * row_scale
                    assert not drift.any(), (
                        f"sharded knn d2 drift {obs_row[drift].max():.3g} "
                        f"on {int(drift.sum())} row(s): {tag}"
                    )
                    # 2*obs_row: a row's k-th and (k+1)-th candidates are
                    # each independently perturbed (and the (k+1)-th
                    # column is not in sd to measure)
                    eps_row = 2 * obs_row + 8 * eps32 * (
                        np.maximum(kd1[:, k - 1], 1e-30)
                    )
                    tie = gap <= eps_row
                    amb = tie | tie[ki].any(1)
                    amb |= amb[ki].any(1)
                    close |= amb
                assert close.all(), f"sharded lof: {tag}"

        checked += 1
        if checked % 10 == 0 or big:
            print(f"{checked}/{num_seeds} ok (last: {tag})", flush=True)
    print(f"consistency sweep: all {checked} cases agree across every path")
    return 0


def _chunk_size(big: bool) -> int:
    """Seeds per child process (env-tunable, clamped >= 1 — a zero or
    negative override must not spawn empty children forever)."""
    return max(
        int(os.environ.get("GRAPHMINE_SWEEP_CHUNK", "4" if big else "12")), 1
    )


def _chunked_main(n: int, first: int, big: bool) -> int:
    """Self-chunking driver: re-exec the sweep in fresh child processes
    every ``chunk`` seeds. XLA:CPU's LLVM JIT arena exhausts after a
    bounded number of unique-shape compilations per process ("Cannot
    allocate memory" from execution_engine.cc) — with the r4 1.10x width
    ladder (~3.5x the populated bucket classes per graph) the ceiling
    dropped from ~50 to ~20 small-tier seeds, so chunking is now
    automatic instead of operator folklore."""
    chunk = _chunk_size(big)
    done = 0
    while done < n:
        take = min(chunk, n - done)
        argv = [sys.executable, os.path.abspath(__file__),
                str(take), str(first + done)] + (["--big"] if big else [])
        rc = subprocess.run(argv).returncode
        if rc != 0:
            return rc
        done += take
    print(f"consistency sweep: all {n} cases agree across every path "
          f"(chunked x{chunk})")
    return 0


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--big"]
    big = "--big" in sys.argv[1:]
    n = int(args[0]) if args else 30
    first = int(args[1]) if len(args) > 1 else 0
    if os.environ.get("_GRAPHMINE_SWEEP_CHILD") == "1" or n <= _chunk_size(big):
        sys.exit(sweep(n, first, big))
    os.environ["_GRAPHMINE_SWEEP_CHILD"] = "1"  # children run directly
    sys.exit(_chunked_main(n, first, big))
