"""chip_smoke.py's contract off the chip: with default arguments and no
TPU it fails at once and prints no result; ``--rehearse`` drives every
phase on the CPU at a tiny size and still never prints an ``ok`` line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as --chips 1 expects
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, env=env, timeout=timeout,
    )


def test_no_accelerator_fails_without_a_result():
    out = _smoke()
    assert out.returncode == 2
    assert out.stdout == ""
    assert "not a TPU" in out.stderr


def test_rehearsal_runs_every_phase_and_never_says_ok():
    out = _smoke(
        "--rehearse", "--vertices", "2048", "--edges", "100000",
        "--batch-rows", "30000", "--lof-k", "64",
        "--exact-vertices", "512", "--exact-edges", "8000",
    )
    assert out.returncode == 4, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert [r["phase"] for r in lines if "phase" in r] == [
        "build", "exact", "pipeline", "serve"
    ]
    checks = {r["check"]: r["ok"] for r in lines if "check" in r}
    assert len(checks) >= 18 and all(checks.values()), checks
    assert not any("ok" in r and "check" not in r for r in lines)
    assert lines[-1]["rehearsal"] == "passed"
