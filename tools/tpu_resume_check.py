"""Hardware-proven checkpoint/resume (VERDICT r4 item 8).

Four rounds tested recovery on CPU meshes only; this tool proves it on
the real accelerator by interrupting an actual pipeline run the way a
preempted TPU job dies — SIGKILL from outside, no atexit, no cleanup —
then resuming from the surviving npz checkpoint:

  1. FRESH    full pipeline run (CLI, checkpointed) — the oracle labels
              and the fresh wall-clock.
  2. KILLED   same run; the parent polls for the first checkpoint file
              and SIGKILLs the process mid-LPA (cadence=1 saves every
              superstep, so the kill lands between supersteps k and 20).
  3. RESUMED  same run with ``--resume``: picks up at iteration k from
              the npz (fingerprint-checked against this exact graph),
              finishes, and must produce labels BYTE-IDENTICAL to the
              fresh run — LPA is deterministic, so resume-then-finish
              and run-straight-through are the same trajectory.

The dataset is a 25M-edge string-domain parquet over 262,144 domains
with a Pareto-tail degree skew: big enough that supersteps are real
device work, small enough to generate in-tool. The reference has no
recovery story at all (``persist()`` at ``Graphframes.py:82`` is
in-memory caching);
SURVEY §5 names checkpoint/resume as the failure-recovery subsystem.

Prints ONE JSON line; exit 0 iff labels match bit-exactly. Run on the
machine with the chip (a CPU run proves only the CPU path again); the
children take the chip one after another, the parent never does:

    python tools/tpu_resume_check.py
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

MAX_ITER = 20  # wider kill window than the parity default of 5


def _powerlaw_edges(v: int, e: int, seed: int):
    """Pareto-tail endpoints: degree skew comparable to web graphs (the
    bundled data's hub pattern, BASELINE.md)."""
    rng = np.random.default_rng(seed)
    raw = rng.pareto(1.2, size=2 * e)
    ids = np.minimum((raw * v / 50).astype(np.int64), v - 1).astype(np.int32)
    perm = rng.permutation(v).astype(np.int32)  # decorrelate id order
    ids = perm[ids]
    return ids[:e], ids[e:]


def _make_dataset(tmp: str) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    v, e = 1 << 18, 25_000_000
    src, dst = _powerlaw_edges(v, e, seed=9)
    names = pa.array([f"d{i:07d}.example" for i in range(v)])
    col = lambda ids: pa.DictionaryArray.from_arrays(
        pa.array(ids, pa.int32()), names
    ).cast(pa.string())
    path = os.path.join(tmp, "edges.parquet")
    pq.write_table(pa.table({"_c1": col(src), "_c2": col(dst)}), path)
    return path


# The chip belongs to one process at a time, so this parent never touches
# jax: each child names its device on its first stdout line, then runs the
# pipeline CLI's own main() on its arguments.
_REPORT_DEVICE_THEN_RUN = (
    "import sys, jax; print(jax.devices()[0], flush=True); "
    "from graphmine_tpu.pipeline.driver import main; main(sys.argv[1:])"
)


def _cli(data: str, ckpt_dir: str, resume: bool = False) -> list[str]:
    argv = [
        sys.executable, "-c", _REPORT_DEVICE_THEN_RUN,
        "--data-path", data,
        "--batch-rows", "4000000",
        "--max-iter", str(MAX_ITER),
        "--outlier-method", "none",
        "--checkpoint-dir", ckpt_dir,
        "--checkpoint-every", "1",
    ]
    if resume:
        argv.append("--resume")
    return argv


def _ckpt_artifacts(ckpt_dir: str) -> list[str]:
    """Paths whose existence marks a landed checkpoint: the npz
    (single-device runs) or the sharded manifest (multi-device runs write
    the manifest format since ISSUE 2)."""
    return [
        os.path.join(ckpt_dir, "lpa_labels.npz"),
        os.path.join(ckpt_dir, "lpa_sharded", "manifest.json"),
    ]


def _load_ckpt(ckpt_dir: str):
    """Newest state across both checkpoint formats — the same
    checkpoint.load_newest the driver's --resume uses, so this tool can
    never accept a checkpoint the driver would reject."""
    from graphmine_tpu.pipeline import checkpoint as ckpt

    out = ckpt.load_newest(ckpt_dir)
    if out is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir!r}")
    labels, it = out
    return np.asarray(labels), it


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="graphmine_resume_")
    try:
        data = _make_dataset(tmp)
        dirs = {k: os.path.join(tmp, k) for k in ("fresh", "killed")}

        # 1. fresh straight-through run
        t0 = time.perf_counter()
        fresh = subprocess.run(
            _cli(data, dirs["fresh"]), check=True, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        device = fresh.stdout.splitlines()[0]
        fresh_s = time.perf_counter() - t0
        want, it = _load_ckpt(dirs["fresh"])
        assert it == MAX_ITER, it

        # 2. killed run: SIGKILL as soon as the first checkpoint lands
        # (plus one beat so the kill interrupts a LIVE superstep)
        marks = _ckpt_artifacts(dirs["killed"])
        p = subprocess.Popen(
            _cli(data, dirs["killed"]), cwd=_REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        deadline = time.time() + 1200
        while not any(os.path.exists(mk) for mk in marks) and time.time() < deadline:
            if p.poll() is not None:
                raise RuntimeError(
                    f"run finished (rc={p.returncode}) before the kill — "
                    "checkpoint never appeared"
                )
            time.sleep(0.02)
        time.sleep(0.5)
        p.send_signal(signal.SIGKILL)
        p.wait()
        _, killed_at = _load_ckpt(dirs["killed"])
        if killed_at >= MAX_ITER:
            raise RuntimeError(
                f"kill landed after the final superstep (iteration "
                f"{killed_at}) — nothing left to resume; rerun"
            )

        # 3. resume the killed run to completion
        t0 = time.perf_counter()
        subprocess.run(
            _cli(data, dirs["killed"], resume=True), check=True, cwd=_REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        resumed_s = time.perf_counter() - t0
        got, it = _load_ckpt(dirs["killed"])
        assert it == MAX_ITER, it

        identical = bool(np.array_equal(got, want))
        print(json.dumps({
            "metric": "checkpoint_resume_labels_identical",
            "value": 1.0 if identical else 0.0,
            "unit": "bool",
            "vs_baseline": 1.0 if identical else 0.0,
            "detail": {
                "num_edges": 25_000_000,
                "max_iter": MAX_ITER,
                "interrupted_after_iteration": killed_at,
                "fresh_wall_seconds": round(fresh_s, 2),
                "resumed_wall_seconds": round(resumed_s, 2),
                "communities": int(len(np.unique(want))),
                "device": device,
            },
        }), flush=True)
        return 0 if identical else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
