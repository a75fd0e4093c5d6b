"""The repo's inventory of options and by-hand tools (ROADMAP D3's ratchet).

``OPTION_NAMES`` is every ``GRAPHMINE_*`` name the program, its tools and
its examples read (a name ending in ``_`` is a family's prefix: the code
builds the rest). A new option fails here until someone lists it, and a
listed one fails once its last reader is gone: the count moves only by
an edit someone sees.

The three tools below are run by hand on the machine with the chip and
imported by no other test: each must load, and every module it imports,
at the top or inside a function, must be there to find.
"""

import ast
import functools
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCANNED = ("graphmine_tpu", "tools", "chip_smoke.py", "examples", "__graft_entry__.py")
_OPTION = re.compile(r"GRAPHMINE_[A-Z0-9_]+")

OPTION_NAMES = frozenset("""
GRAPHMINE_ADMIT_ GRAPHMINE_ADMIT_DEADLINE_S GRAPHMINE_ADMIT_DEFER_FRAC
GRAPHMINE_ADMIT_MAX_LAG_S GRAPHMINE_ADMIT_MAX_PENDING_ROWS
GRAPHMINE_ADMIT_MAX_QUEUE_DEPTH GRAPHMINE_ADMIT_RETRY_AFTER_S
GRAPHMINE_ALERT_ GRAPHMINE_ALERT_ANOMALY_RATE GRAPHMINE_ALERT_CANARY_RECALL
GRAPHMINE_ALERT_INGEST_LAG_FOR_S GRAPHMINE_ALERT_INGEST_LAG_S
GRAPHMINE_ALERT_LOF_PSI GRAPHMINE_ALERT_MEM_HEADROOM GRAPHMINE_ALERT_SIZE_PSI
GRAPHMINE_CANARY_SEED GRAPHMINE_DIVERGENCE GRAPHMINE_DRYRUN_CHILD
GRAPHMINE_DRYRUN_TIMEOUT GRAPHMINE_DRYRUN_TPU GRAPHMINE_FAIR_QUANTUM_ROWS
GRAPHMINE_FLEET_ GRAPHMINE_FLEET_BREAKER_BACKOFF_BASE_S
GRAPHMINE_FLEET_BREAKER_BACKOFF_MAX_S GRAPHMINE_FLEET_BREAKER_OPEN_FAILURES
GRAPHMINE_FLEET_BREAKER_OPEN_RATE GRAPHMINE_FLEET_BREAKER_WINDOW
GRAPHMINE_FLEET_DEFAULT_DEADLINE_MS GRAPHMINE_FLEET_DOWN_AFTER_PROBES
GRAPHMINE_FLEET_DRAIN_GRACE_S GRAPHMINE_FLEET_MIN_HEALTHY
GRAPHMINE_FLEET_PROBE_INTERVAL_S GRAPHMINE_FLEET_PROBE_TIMEOUT_S
GRAPHMINE_FLEET_PROMOTE_TIMEOUT_S GRAPHMINE_FLEET_QUORUM
GRAPHMINE_FLEET_READ_TIMEOUT_S GRAPHMINE_FLEET_REJOIN_TIMEOUT_S
GRAPHMINE_FLEET_RELOAD_CADENCE_S GRAPHMINE_FLEET_RELOAD_TIMEOUT_S
GRAPHMINE_FLEET_RETRY_AFTER_S GRAPHMINE_FLEET_WRITE_TIMEOUT_S
GRAPHMINE_HBM_BYTES GRAPHMINE_LOF_IVF_MIN_N GRAPHMINE_NATIVE_LIB
GRAPHMINE_PROFILEZ_DIR GRAPHMINE_QUALITY GRAPHMINE_QUALITY_LOF_THRESHOLD
GRAPHMINE_READY_MAX_AGE_S GRAPHMINE_ROOFLINE_ GRAPHMINE_ROOFLINE_FILE
GRAPHMINE_SERVE_MEM_BUDGET_BYTES GRAPHMINE_SWEEP_CHILD GRAPHMINE_SWEEP_CHUNK
GRAPHMINE_TENANT_BOUNDS GRAPHMINE_TEST_TPU GRAPHMINE_WAL_RETAIN_SEGMENTS
GRAPHMINE_WAL_SEGMENT_BYTES GRAPHMINE_WEDGE_BUDGET GRAPHMINE_WRITER_SHARDS
""".split())


@functools.cache
def _names_in_the_tree() -> frozenset[str]:
    found = set()
    for entry in _SCANNED:
        top = os.path.join(REPO, entry)
        paths = [top] if os.path.isfile(top) else [
            os.path.join(base, name)
            for base, dirs, files in os.walk(top)
            if "__pycache__" not in base
            for name in files
        ]
        for path in paths:
            try:
                with open(path, encoding="utf-8") as f:
                    found.update(_OPTION.findall(f.read()))
            except UnicodeDecodeError:  # a built library, not source
                continue
    return frozenset(found)


def test_option_names_are_listed():
    unlisted = sorted(_names_in_the_tree() - OPTION_NAMES)
    assert not unlisted, f"new option names, list them in OPTION_NAMES: {unlisted}"


def test_listed_option_names_exist():
    gone = sorted(OPTION_NAMES - _names_in_the_tree())
    assert not gone, f"listed option names nothing reads any more: {gone}"


def _imported_modules(source: str) -> set[str]:
    names = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names.add(node.module)
    return names


@pytest.mark.parametrize(
    "tool", ["ingest_stress.py", "tpu_backend_audit.py", "tpu_resume_check.py"]
)
def test_tool_module_loads(tool):
    path = os.path.join(REPO, "tools", tool)
    spec = importlib.util.spec_from_file_location(f"_tool_{tool[:-3]}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)  # __name__ is not "__main__": main() does not run
    assert callable(module.main)
    with open(path) as f:
        wanted = _imported_modules(f.read())
    missing = sorted(m for m in wanted if importlib.util.find_spec(m) is None)
    assert not missing, f"tools/{tool} imports modules that are not there: {missing}"
