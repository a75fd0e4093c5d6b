#!/usr/bin/env python
"""Schema-rot lint: every phase literal emitted anywhere in
``graphmine_tpu/`` must be registered in ``obs/schema.py``.

Runtime validation (``validate_records`` over e2e streams) only covers
phases that HAPPEN to fire in a test run — an emit call on a cold path
(a rare failover branch, a fault-only record) can carry a typo'd or
unregistered phase for months before an incident finally exercises it,
and then the triage tooling drops exactly the record the operator
needs. This lint closes that gap statically: it scans the package
source for first-argument string literals of the record-emitting calls
(``.emit("...")``, ``.timed("...")``, ``._emit("...")``) and fails on
any phase missing from the schema registry.

Limitations, by design: phases passed as variables are invisible here —
they remain covered by the runtime validation path (``MetricsSink``
consumers assert ``validate_records == []`` over e2e streams), so the
two checks together cover both shapes.

Usage::

    python tools/schema_lint.py          # exit 1 on violations
    python tools/schema_lint.py --list   # also print every found phase

Wired into tier-1 via
``tests/test_trace.py::test_schema_lint_package_is_clean``.
Stdlib-only.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

_REPO = __file__.rsplit("/", 2)[0]
if _REPO not in sys.path:  # allow `python tools/schema_lint.py` anywhere
    sys.path.insert(0, _REPO)

from graphmine_tpu.obs.schema import SCHEMAS  # noqa: E402

# First-arg string literal of a record-emitting call. `\s*` crosses
# newlines, so multi-line call formatting is caught; `emit=False`-style
# kwargs don't match (no `(` after the word); `emit_admission(...)`
# doesn't match (the word boundary is inside the identifier).
_EMIT_RE = re.compile(
    r"\b(?:emit|timed|_emit)\(\s*[\"']([A-Za-z_][A-Za-z0-9_]*)[\"']"
)

# Inline cost sub-record construction (ISSUE 12): the `cost` payload has
# ONE builder — obs/costmodel.CostEstimate.record(), whose shape the
# runtime validator pins against schema.COST_KEYS. A hand-rolled
# `cost={...}` / `cost=dict(...)` at an emit site would drift from that
# shape silently on cold paths, exactly the rot this lint exists for.
_INLINE_COST_RE = re.compile(r"\bcost\s*=\s*(?:\{|dict\()")
_COST_OWNER = os.path.join("graphmine_tpu", "obs", "costmodel.py")

# Inline mem sub-record construction (ISSUE 14): the `mem` payload has
# ONE builder — obs/memmodel.MemEstimate.record(), whose shape the
# runtime validator pins against schema.MEM_KEYS. A hand-rolled
# `mem={...}` at an emit site would drift from the memory-plane
# tooling's expectations silently on cold paths — the cost-lint rot
# class, applied to the memory plane.
_INLINE_MEM_RE = re.compile(r"\bmem\s*=\s*(?:\{|dict\()")
_MEM_OWNER = os.path.join("graphmine_tpu", "obs", "memmodel.py")

# Inline sketch sub-record construction (ISSUE 13): `*_sketch` payloads
# have ONE builder — obs/sketch.QuantileSketch.to_state(), whose shape
# the runtime validator pins against schema.SKETCH_KEYS. A hand-rolled
# `lof_sketch={...}` at an emit site would drift from the merge/report
# tooling's expectations silently on cold paths — same rot class as the
# cost lint above.
_INLINE_SKETCH_RE = re.compile(r"\b\w+_sketch\s*=\s*(?:\{|dict\()")
_SKETCH_OWNERS = (
    os.path.join("graphmine_tpu", "obs", "sketch.py"),
    os.path.join("graphmine_tpu", "obs", "quality.py"),
)

# Inline shard-plane record emission (ISSUE 17): the shard_publish /
# epoch_commit / shard_degraded family has ONE builder —
# serve/shardplane.emit_shard_record(), which validates the phase name
# before anything reaches the sink. A raw sink.emit("shard_publish",...)
# elsewhere would bypass that gate and drift from the registered shapes.
_INLINE_SHARD_RE = re.compile(
    r"emit\(\s*[\"'](?:shard_publish|epoch_commit|shard_degraded)[\"']"
)
_SHARD_OWNER = os.path.join("graphmine_tpu", "serve", "shardplane.py")

# Named scopes (ISSUE 25): the names on the device timeline are string
# literals registered in schema.DEVICE_SCOPES, so a report that groups
# device seconds by scope can say which names exist. The one computed
# name is the degree class: a variable called `width`, bound to
# f"w{...}" and nothing else.
_SCOPE_RE = re.compile(r"\bjax\.named_scope\(\s*([^)]*?)\s*\)")
_SCOPE_LITERAL_RE = re.compile(r"[\"']([A-Za-z_][A-Za-z0-9_]*)[\"']$")
_WIDTH_BINDING_RE = re.compile(r"\bwidth\s*=\s*(.*)")

# Stage spans (ISSUE 35): the first argument after the sink of every
# `stage_span(...)` call is a string literal registered in
# schema.STAGE_SPANS: the benchmark's readers and a device trace's idle
# gaps go by these names. `\s*` crosses newlines.
_STAGE_RE = re.compile(r"\bstage_span\(\s*[^,()]+,\s*([^,)]*?)\s*[,)]")
_STAGE_OWNER = os.path.join("graphmine_tpu", "obs", "spans.py")

PACKAGE_DIR = os.path.join(_REPO, "graphmine_tpu")


def _py_files(root: str):
    """``(path relative to the repo, text)`` of every ``.py`` under
    ``root``, in a fixed order."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    yield os.path.relpath(path, _REPO), f.read()


def scan(root: str = PACKAGE_DIR) -> list:
    """All (phase, file, line) triples of string-literal phase emits."""
    found = []
    for rel, text in _py_files(root):
        for m in _EMIT_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            found.append((m.group(1), rel, line))
    return found


def _scan_inline(root, pattern, owners) -> list:
    """``(file, line)`` pairs of an inline sub-record kwarg literal
    outside its owning builder module(s)."""
    found = []
    for rel, text in _py_files(root):
        if rel in owners:
            continue
        for i, raw in enumerate(text.splitlines(), 1):
            # crude comment strip: good enough for a kwarg lint (a
            # '#' inside a string arg would hide a same-line match,
            # which no real emit call shape does)
            code = raw.split("#", 1)[0]
            if pattern.search(code):
                found.append((rel, i))
    return found


def scan_inline_costs(root: str = PACKAGE_DIR) -> list:
    """``(file, line)`` pairs of inline ``cost={...}``/``cost=dict(...)``
    literals outside the single builder (obs/costmodel.py)."""
    return _scan_inline(root, _INLINE_COST_RE, (_COST_OWNER,))


def scan_inline_mems(root: str = PACKAGE_DIR) -> list:
    """``(file, line)`` pairs of inline ``mem={...}``/``mem=dict(...)``
    literals outside the single builder (obs/memmodel.py)."""
    return _scan_inline(root, _INLINE_MEM_RE, (_MEM_OWNER,))


def scan_inline_sketches(root: str = PACKAGE_DIR) -> list:
    """``(file, line)`` pairs of inline ``*_sketch={...}`` literals
    outside the sketch builders (obs/sketch.py, obs/quality.py)."""
    return _scan_inline(root, _INLINE_SKETCH_RE, _SKETCH_OWNERS)


def scan_inline_shard_records(root: str = PACKAGE_DIR) -> list:
    """``(file, line)`` pairs of direct shard-plane record emits outside
    the single builder (serve/shardplane.emit_shard_record)."""
    return _scan_inline(root, _INLINE_SHARD_RE, (_SHARD_OWNER,))


def _scan_scope_calls(root: str) -> list:
    """``(argument text, file, line)`` of every ``jax.named_scope(...)``
    call, plus ``("width=<rhs>", file, line)`` of every binding of the
    one variable a scope may be computed through."""
    found = []
    for rel, text in _py_files(root):
        for m in _SCOPE_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            found.append((m.group(1), rel, line))
        if "named_scope(width)" in text:
            for m in _WIDTH_BINDING_RE.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                found.append(("width=" + m.group(1).strip(), rel, line))
    return found


def scan_scopes(root: str = PACKAGE_DIR) -> list:
    """All (scope, file, line) triples of string-literal named scopes."""
    out = []
    for arg, path, line in _scan_scope_calls(root):
        m = _SCOPE_LITERAL_RE.match(arg)
        if m:
            out.append((m.group(1), path, line))
    return out


def scope_violations(root: str = PACKAGE_DIR, check_unused: bool = True) -> list:
    """Named scopes that are not registered in schema.DEVICE_SCOPES or
    are computed (anything but a literal, or `width` bound to f"w{...}"),
    and registered scopes that no code uses."""
    from graphmine_tpu.obs.schema import DEVICE_SCOPES

    out, used = [], set()
    for arg, path, line in _scan_scope_calls(root):
        m = _SCOPE_LITERAL_RE.match(arg)
        if m:
            used.add(m.group(1))
            if m.group(1) not in DEVICE_SCOPES:
                out.append(
                    f"{path}:{line}: named scope {m.group(1)!r} is not "
                    "registered in graphmine_tpu/obs/schema.py (DEVICE_SCOPES)"
                )
        elif arg.startswith("width="):
            if not arg.startswith('width=f"w{'):
                out.append(
                    f"{path}:{line}: `width` names a scope and must be "
                    f'bound to f"w{{...}}", not {arg[6:]!r}'
                )
        elif arg != "width":
            out.append(
                f"{path}:{line}: computed scope name {arg!r} — scope names "
                'are string literals (the degree class f"w{...}" through '
                "`width` is the one exception)"
            )
    if check_unused:
        out.extend(
            f"graphmine_tpu/obs/schema.py: DEVICE_SCOPES lists {name!r}, "
            "which no jax.named_scope uses"
            for name in sorted(DEVICE_SCOPES - used)
        )
    return out


def scan_stages(root: str = PACKAGE_DIR) -> list:
    """``(name argument text, file, line)`` of every ``stage_span(sink,
    <name>, ...)`` call outside the module that defines it."""
    found = []
    for rel, text in _py_files(root):
        if rel == _STAGE_OWNER:
            continue
        for m in _STAGE_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            found.append((m.group(1), rel, line))
    return found


def stage_violations(root: str = PACKAGE_DIR, check_unused: bool = True) -> list:
    """Stage spans that are not registered in schema.STAGE_SPANS or whose
    name is computed, and registered stages that no code opens."""
    from graphmine_tpu.obs.schema import STAGE_SPANS

    out, used = [], set()
    for arg, path, line in scan_stages(root):
        m = _SCOPE_LITERAL_RE.match(arg)
        if m is None:
            out.append(
                f"{path}:{line}: computed stage span name {arg!r} — stage "
                "names are string literals"
            )
            continue
        used.add(m.group(1))
        if m.group(1) not in STAGE_SPANS:
            out.append(
                f"{path}:{line}: stage span {m.group(1)!r} is not registered "
                "in graphmine_tpu/obs/schema.py (STAGE_SPANS)"
            )
    if check_unused:
        out.extend(
            f"graphmine_tpu/obs/schema.py: STAGE_SPANS lists {name!r}, "
            "which no stage_span opens"
            for name in sorted(STAGE_SPANS - used)
        )
    return out


def violations(root: str = PACKAGE_DIR) -> list:
    """Emitted-but-unregistered phases plus inline cost sub-records:
    list of human-readable strings (empty = clean). The tier-1 test
    asserts on this."""
    out = [
        f"{path}:{line}: phase {phase!r} is emitted but not registered "
        "in graphmine_tpu/obs/schema.py"
        for phase, path, line in scan(root)
        if phase not in SCHEMAS
    ]
    out.extend(
        f"{path}:{line}: inline cost=... literal — build cost sub-records "
        "with graphmine_tpu/obs/costmodel.py (CostEstimate.record()), the "
        "single shape owner"
        for path, line in scan_inline_costs(root)
    )
    out.extend(
        f"{path}:{line}: inline mem=... literal — build mem sub-records "
        "with graphmine_tpu/obs/memmodel.py (MemEstimate.record()), the "
        "single shape owner"
        for path, line in scan_inline_mems(root)
    )
    out.extend(
        f"{path}:{line}: inline *_sketch=... literal — build sketch "
        "sub-records with graphmine_tpu/obs/sketch.py "
        "(QuantileSketch.to_state()), the single shape owner"
        for path, line in scan_inline_sketches(root)
    )
    out.extend(
        f"{path}:{line}: direct shard-plane record emit — route "
        "shard_publish/epoch_commit/shard_degraded through "
        "graphmine_tpu/serve/shardplane.py (emit_shard_record), the "
        "single builder"
        for path, line in scan_inline_shard_records(root)
    )
    # unused registrations are a fact about the package, not about
    # whatever tree a caller points the lint at
    out.extend(scope_violations(root, check_unused=root == PACKAGE_DIR))
    out.extend(stage_violations(root, check_unused=root == PACKAGE_DIR))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--list", action="store_true",
                    help="print every literal phase emit found")
    args = ap.parse_args(argv)
    found = scan()
    if args.list:
        for phase, path, line in found:
            mark = " " if phase in SCHEMAS else "!"
            print(f"{mark} {phase:<24} {path}:{line}")
    bad = violations()
    if bad:
        print(f"schema_lint: {len(bad)} unregistered phase emit(s):",
              file=sys.stderr)
        for b in bad:
            print(f"  {b}", file=sys.stderr)
        return 1
    print(
        f"schema_lint: {len(found)} literal phase emit(s), all registered "
        f"({len(SCHEMAS)} phases in the registry)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
