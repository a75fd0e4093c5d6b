#!/usr/bin/env python
"""Bench trajectory tooling: diff, regression gate, silicon manifest.

The repo's perf record is a pile of ``BENCH_*.json`` driver artifacts
read by humans (ISSUE 12): nothing compares two captures, renders the
multi-round trajectory, or tracks which tiers still lack a silicon
capture (ROADMAP carried that backlog as prose). This tool closes all
three gaps, **stdlib-only** (no jax, no numpy — runnable on any box
holding the artifacts):

- **Trajectory table**: every tier's headline metric across every given
  capture, CPU-fallback and error records marked as such — the perf
  record as one table instead of N files.
- **Regression gate**: the two newest captures (by the artifact's ``n``
  round number) compared metric-by-metric with per-tier noise
  tolerances (:data:`TIER_TOLERANCE`; direction-aware — seconds regress
  UP, throughput regresses DOWN). Exit 1 names every metric past
  tolerance, so CI can gate on a fresh ``bench.py`` run vs the newest
  committed file.
- **Silicon-capture manifest** (``--manifest``; also behind
  ``bench.py --list-missing``): which tiers/sub-records exist ONLY as
  ``*_cpu_fallback`` records (or not at all) across the whole
  trajectory — the machine-readable replacement for ROADMAP's
  hand-maintained "Silicon capture backlog" list.

Inputs: ``BENCH_*.json`` driver artifacts (``{n, cmd, rc, tail,
parsed}`` — ``tail`` holds the stdout tail's JSON record lines,
``parsed`` the final suite-summary record) or a fresh ``bench.py``
stdout capture (plain JSON-lines). With no file arguments, every
``BENCH_*.json`` next to the repo's ``bench.py`` is loaded; a single
file argument is gated against the newest committed artifact.

Usage::

    python tools/bench_diff.py BENCH_r04.json BENCH_r05.json
    python tools/bench_diff.py                    # full committed trajectory
    python tools/bench_diff.py fresh_run.jsonl    # fresh vs newest committed
    python tools/bench_diff.py --manifest         # + pending-capture manifest

Exit codes: 0 clean, 1 regression past tolerance (or non-empty manifest
under ``--strict``), 2 usage/load error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The tier universe — mirrors bench.py's _TIER_ORDER (pinned equal by
# tests/test_costmodel.py so the two can never drift; bench.py imports
# numpy at module load, which this stdlib-only tool must not).
ALL_TIERS = (
    "chip", "roofline", "northstar", "sharded",
    "cc", "e2e", "lof", "snap", "quality", "weighted", "stream", "serve",
)

# Detail sub-records the manifest tracks per tier: each ships inside its
# tier's record `detail` and counts as silicon-captured only when seen in
# a NON-fallback record (the ROADMAP backlog named exactly these).
SUB_RECORDS = {
    "stream": ("ivf_reuse",),
    "serve": ("write_load", "replicated_read", "writer_failover",
              "latency_quantiles", "quality_pass", "multi_tenant",
              "sharded_write", "memory"),
    # the per-tier memory sub-record (ISSUE 14: model + measured child
    # peak RSS) is tracked on the headline tier; every tier carries it,
    # but one manifest row is the signal "this round recorded memory"
    "chip": ("memory",),
}

# metric-name prefix -> tier, for records read from a tail where no
# suite summary maps them (a fresh bench stdout mid-run, old artifacts).
_METRIC_TIER_PREFIXES = (
    ("lpa_100m", "northstar"),
    ("lpa_", "chip"),
    ("roofline_", "roofline"),
    ("sharded_lpa", "sharded"),
    ("cc_", "cc"),
    ("e2e_", "e2e"),
    ("lof_", "lof"),
    ("snap_", "snap"),
    ("community_quality", "quality"),
    ("weighted_lpa", "weighted"),
    ("streaming_lof", "stream"),
    ("serve_", "serve"),
    ("bench_", None),  # bench_<tier>_capture_failed error records
)

# Per-tier noise tolerance (fraction of the older value). Defaults to
# DEFAULT_TOLERANCE; overrides document WHY they are looser:
DEFAULT_TOLERANCE = 0.10
TIER_TOLERANCE = {
    # best-ARI over few seeds is seed-noisy at toy scale: the committed
    # r04→r05 silicon pair itself swings 1.0 → 0.827 (-17%).
    "quality": 0.30,
    # whole-pipeline wall time: host phases (wedge probe, parquet IO)
    # add machine-load jitter beyond the kernel noise band.
    "e2e": 0.15,
    # window-chunked streaming scorer: chunk boundaries beat against the
    # window size.
    "stream": 0.15,
    # qps through a live HTTP stack: scheduler noise.
    "serve": 0.25,
}

# Units where DOWN is an improvement (everything else: up is better).
LOWER_BETTER_UNITS = frozenset(("s", "seconds", "ms", "us"))

# Per-tier memory sub-record gate (ISSUE 14): peak bytes regress UP.
# Child RSS is noisier than kernel rates (allocator arenas, import
# order), hence the looser default; override with --tolerance memory=F.
MEMORY_TOLERANCE = 0.25


class BenchLoadError(Exception):
    pass


def _tier_of_metric(metric: str):
    if not isinstance(metric, str):
        return None
    for prefix, tier in _METRIC_TIER_PREFIXES:
        if metric.startswith(prefix):
            if tier is None:  # bench_<tier>_capture_failed
                m = re.match(r"bench_(\w+)_capture_failed", metric)
                return m.group(1) if m and m.group(1) in ALL_TIERS else None
            return tier
    return None


def _records_from_lines(text: str) -> list:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    return out


def _is_fallback(rec: dict) -> bool:
    metric = rec.get("metric", "")
    if isinstance(metric, str) and metric.endswith("_cpu_fallback"):
        return True
    cap = (rec.get("detail") or {}).get("capture") or {}
    return bool(cap.get("cpu_fallback"))


def load_bench(path: str) -> dict:
    """One capture, normalized: ``{label, n, tiers, records}`` where
    ``tiers[tier] = {"metric", "value", "unit", "vs", "err"?,
    "cpu_fallback"}``. Accepts a driver artifact (``{n, tail, parsed}``)
    or a raw bench.py stdout / JSONL capture."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise BenchLoadError(f"cannot read {path}: {e}") from e
    label = os.path.basename(path)
    m = re.search(r"BENCH_r?0*(\d+)", label)
    n = None
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict) and "tail" in obj:
        records = _records_from_lines(obj.get("tail") or "")
        parsed = obj.get("parsed")
        n = obj.get("n", int(m.group(1)) if m else None)
    else:
        # raw stdout / JSONL: every line is its own record; the suite
        # summary (if the run finished) is the last record with "suite"
        records = _records_from_lines(text)
        parsed = next(
            (r for r in reversed(records) if "suite" in r), None
        )
        n = int(m.group(1)) if m else None
    if not records and not (
        isinstance(parsed, dict) and isinstance(parsed.get("suite"), dict)
    ):
        raise BenchLoadError(
            f"{path}: no bench records found (not a BENCH_*.json artifact "
            "or a bench.py stdout capture, or the capture failed before "
            "any tier record — see the artifact's rc/tail)"
        )

    tiers: dict = {}
    # 1) the suite summary knows every tier, including ones whose full
    # records scrolled out of the artifact's bounded stdout tail
    if isinstance(parsed, dict):
        for tier, entry in (
            (parsed.get("suite") or {}).get("tiers") or {}
        ).items():
            if "err" in entry:
                tiers[tier] = {"err": entry["err"]}
                continue
            metric = entry.get("m")
            tiers[tier] = {
                "metric": metric,
                "value": entry.get("v"),
                "unit": entry.get("u"),
                "vs": entry.get("vs"),
                "cpu_fallback": bool(
                    isinstance(metric, str)
                    and metric.endswith("_cpu_fallback")
                ),
            }
    # 2) overlay full tail records (carry detail; fallback flag is
    # authoritative there via detail.capture)
    for rec in records:
        if "suite" in rec:
            continue
        metric = rec.get("metric", "")
        tier = _tier_of_metric(metric)
        if tier is None:
            continue
        if "error" in rec:
            tiers.setdefault(tier, {"err": str(rec["error"])[:120]})
            continue
        tiers[tier] = {
            "metric": metric,
            "value": rec.get("value"),
            "unit": rec.get("unit"),
            "vs": rec.get("vs_baseline"),
            "cpu_fallback": _is_fallback(rec),
            "detail": rec.get("detail") or {},
        }
    return {"label": label, "path": path, "n": n, "tiers": tiers,
            "records": records}


# ---- trajectory table ------------------------------------------------------


def _fmt_value(entry) -> str:
    if entry is None:
        return "-"
    if "err" in entry:
        return "ERR"
    v, unit = entry.get("value"), entry.get("unit") or ""
    if v is None:
        return "?"
    if isinstance(v, (int, float)) and abs(v) >= 1e6:
        s = f"{v / 1e6:.1f}M"
    elif isinstance(v, (int, float)) and abs(v) >= 1e4:
        s = f"{v / 1e3:.0f}K"
    elif isinstance(v, float):
        s = f"{v:.3g}"
    else:
        s = str(v)
    if unit and unit not in ("error",):
        s += {"edges/s/chip": "", "slots/s": "", "points/s/chip": ""}.get(
            unit, unit if unit == "s" else f" {unit}"
        )
    if entry.get("cpu_fallback"):
        s += "*"
    return s


def trajectory_table(captures: list) -> list:
    """The full multi-capture table, one row per tier (``*`` marks a
    CPU-fallback value, ``ERR`` a failed capture, ``-`` a tier that did
    not exist that round)."""
    cols = [c["label"].replace("BENCH_", "").replace(".json", "")
            for c in captures]
    seen = [t for t in ALL_TIERS
            if any(t in c["tiers"] for c in captures)]
    width = max([len(t) for t in seen] + [6])
    cw = [max(len(col), 10) for col in cols]
    lines = [
        "  " + " " * width + "  "
        + "  ".join(col.rjust(w) for col, w in zip(cols, cw))
    ]
    for tier in seen:
        cells = [
            _fmt_value(c["tiers"].get(tier)).rjust(w)
            for c, w in zip(captures, cw)
        ]
        lines.append(f"  {tier:<{width}}  " + "  ".join(cells))
    lines.append("  (* = CPU-fallback record, not a silicon number)")
    return lines


# ---- regression gate -------------------------------------------------------


def diff_captures(old: dict, new: dict, tolerances: dict | None = None):
    """Metric-by-metric comparison -> (rows, regressions). Each row is a
    human line; ``regressions`` lists the offending metric names (the
    exit-1 payload). Capture-status changes (silicon → fallback/error)
    are reported but gate only under --strict-capture (callers append
    them from the returned ``capture_changes``)."""
    tol_map = dict(TIER_TOLERANCE)
    tol_map.update(tolerances or {})
    rows, regressions, capture_changes = [], [], []
    for tier in ALL_TIERS:
        o, nw = old["tiers"].get(tier), new["tiers"].get(tier)
        if o is None and nw is None:
            continue
        if o is None:
            rows.append(f"  {tier:<10} NEW       {_fmt_value(nw)}")
            continue
        if nw is None:
            capture_changes.append(
                f"{tier}: present in {old['label']} but missing in "
                f"{new['label']}"
            )
            rows.append(f"  {tier:<10} GONE      (was {_fmt_value(o)})")
            continue
        o_err, n_err = "err" in o, "err" in nw
        if o_err and n_err:
            rows.append(f"  {tier:<10} ERR->ERR")
            continue
        if n_err:
            capture_changes.append(
                f"{tier}: captured in {old['label']} but ERR in "
                f"{new['label']} ({nw['err']})"
            )
            rows.append(f"  {tier:<10} CAPTURE   ok -> ERR")
            continue
        if o_err:
            rows.append(f"  {tier:<10} FIXED     ERR -> {_fmt_value(nw)}")
            continue
        if bool(o.get("cpu_fallback")) != bool(nw.get("cpu_fallback")):
            direction = (
                "cpu_fallback -> silicon" if o.get("cpu_fallback")
                else "silicon -> cpu_fallback"
            )
            if not o.get("cpu_fallback"):
                capture_changes.append(
                    f"{tier}: {direction} — values not comparable"
                )
            rows.append(
                f"  {tier:<10} CAPTURE   {direction} (values not compared)"
            )
            continue
        ov, nv = o.get("value"), nw.get("value")
        if not isinstance(ov, (int, float)) or not isinstance(
            nv, (int, float)
        ) or ov == 0:
            rows.append(f"  {tier:<10} ?         {ov} -> {nv}")
            # the memory gate is independent of headline-value validity:
            # a tier with a broken headline can still regress its bytes
            _memory_gate(tier, o, nw, tol_map, rows, regressions)
            continue
        unit = nw.get("unit") or o.get("unit") or ""
        lower_better = unit in LOWER_BETTER_UNITS
        delta = (nv - ov) / abs(ov)
        tol = tol_map.get(tier, DEFAULT_TOLERANCE)
        worse = delta > tol if lower_better else delta < -tol
        verdict = "REGRESSED" if worse else (
            "improved" if (delta < 0) == lower_better and delta != 0
            else "ok"
        )
        rows.append(
            f"  {tier:<10} {verdict:<9} {_fmt_value(o)} -> {_fmt_value(nw)}"
            f"  ({delta:+.1%}, tol ±{tol:.0%}{', lower=better' if lower_better else ''})"
        )
        if worse:
            regressions.append(
                f"{nw.get('metric', tier)}: {ov} -> {nv} ({delta:+.1%} "
                f"past the ±{tol:.0%} {tier} tolerance)"
            )
        _memory_gate(tier, o, nw, tol_map, rows, regressions)
    return rows, regressions, capture_changes


def _memory_gate(tier, o, nw, tol_map, rows, regressions) -> None:
    """Memory sub-record gate (ISSUE 14): per-tier measured peak bytes
    regress UP. Upper-bound samples (the child did not raise the
    cumulative rusage max — another child's peak, not this tier's) are
    not comparable and never gate. Runs for every tier whose BOTH
    captures carry a comparable sample, independently of the headline
    value's validity (callers skip it only where values are cross-
    platform incomparable: err records, fallback-status mismatches)."""
    om = (o.get("detail") or {}).get("memory") or {}
    nm = (nw.get("detail") or {}).get("memory") or {}
    opk, npk = om.get("peak_rss_bytes"), nm.get("peak_rss_bytes")
    if not (
        isinstance(opk, (int, float)) and isinstance(npk, (int, float))
        and opk > 0
        and not om.get("upper_bound") and not nm.get("upper_bound")
    ):
        return
    mdelta = (npk - opk) / opk
    mtol = tol_map.get("memory", MEMORY_TOLERANCE)
    mworse = mdelta > mtol
    verdict = "MEM-REGRESS" if mworse else "mem-ok"
    rows.append(
        f"  {tier:<10} {verdict:<9} peak "
        f"{opk / (1 << 20):,.0f}MiB -> {npk / (1 << 20):,.0f}MiB"
        f"  ({mdelta:+.1%}, tol ±{mtol:.0%}, lower=better)"
    )
    if mworse:
        regressions.append(
            f"{tier}.memory.peak_rss_bytes: {opk} -> {npk} "
            f"({mdelta:+.1%} past the ±{mtol:.0%} memory "
            "tolerance — bytes regress UP)"
        )


# ---- silicon-capture manifest ---------------------------------------------


def silicon_manifest(captures: list) -> dict:
    """Machine-readable capture status across the whole trajectory — the
    ROADMAP "Silicon capture backlog" replacement. A tier (or tracked
    sub-record) is ``silicon`` once ANY capture holds a real record for
    it; ``cpu_fallback`` when only fallback records exist; ``missing``
    when it predates every given capture. ``pending`` lists everything
    not yet silicon — the work list for the next healthy-TPU window."""
    status = {t: "missing" for t in ALL_TIERS}
    subs = {
        f"{t}.{s}": "missing" for t, names in SUB_RECORDS.items()
        for s in names
    }
    for cap in captures:
        for tier, entry in cap["tiers"].items():
            if tier not in status or "err" in entry:
                continue
            if entry.get("cpu_fallback"):
                if status[tier] == "missing":
                    status[tier] = "cpu_fallback"
            else:
                status[tier] = "silicon"
            detail = entry.get("detail") or {}
            for s in SUB_RECORDS.get(tier, ()):
                if s in detail:
                    key = f"{tier}.{s}"
                    if entry.get("cpu_fallback"):
                        if subs[key] == "missing":
                            subs[key] = "cpu_fallback"
                    else:
                        subs[key] = "silicon"
    pending = sorted(
        [t for t, st in status.items() if st != "silicon"]
        + [k for k, st in subs.items() if st != "silicon"]
    )
    return {
        "captures": [c["label"] for c in captures],
        "tiers": status,
        "sub_records": subs,
        "pending": pending,
        "hint": (
            "one healthy-TPU window: `python bench.py` (tier all) refreshes "
            "BENCH_*.json + bench_logs/; see ROADMAP.md 'Silicon capture "
            "backlog'"
        ),
    }


# ---- CLI -------------------------------------------------------------------


def committed_bench_files(repo_dir: str = _REPO) -> list:
    return sorted(glob.glob(os.path.join(repo_dir, "BENCH_*.json")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", help="BENCH_*.json artifacts or "
                    "fresh bench.py stdout captures (default: every "
                    "committed BENCH_*.json; one file gates against the "
                    "newest committed)")
    ap.add_argument("--manifest", action="store_true",
                    help="also print the silicon-capture manifest (JSON)")
    ap.add_argument("--strict", action="store_true",
                    help="with --manifest: exit 1 when pending is non-empty")
    ap.add_argument("--no-gate", action="store_true",
                    help="trajectory table only; skip the regression gate")
    ap.add_argument("--strict-capture", action="store_true",
                    help="capture downgrades (silicon -> cpu_fallback/ERR/"
                    "gone) gate like value regressions")
    ap.add_argument("--tolerance", action="append", default=[],
                    metavar="TIER=FRAC",
                    help="override a tier's noise tolerance, e.g. chip=0.05")
    args = ap.parse_args(argv)

    tolerances = {}
    for spec in args.tolerance:
        tier, _, frac = spec.partition("=")
        try:
            tolerances[tier] = float(frac)
        except ValueError:
            print(f"bench_diff: bad --tolerance {spec!r}", file=sys.stderr)
            return 2

    paths = list(args.files)
    gate_path = None  # single-file mode: this file MUST be the gate's new side
    if not paths:
        paths = committed_bench_files()
    elif len(paths) == 1:
        gate_path = os.path.abspath(paths[0])
        committed = [
            p for p in committed_bench_files()
            if os.path.abspath(p) != gate_path
        ]
        paths = committed + paths  # the lone file is the newest capture
    if not paths:
        print("bench_diff: no BENCH_*.json files found", file=sys.stderr)
        return 2

    captures = []
    for p in paths:
        try:
            captures.append(load_bench(p))
        except BenchLoadError as e:
            # A capture round that produced NO records (an unreachable
            # device, rc=1) is part of the trajectory's history, not a
            # tooling error — keep an empty column for it, in round
            # order (the filename still knows its n).
            print(f"bench_diff: note: {e}", file=sys.stderr)
            label = os.path.basename(p)
            m = re.search(r"BENCH_r?0*(\d+)", label)
            captures.append({
                "label": label, "path": p,
                "n": int(m.group(1)) if m else None,
                "tiers": {}, "records": [],
            })
    if not captures:
        return 2
    # stable order: round number when known; a fresh capture without one
    # sorts last (= the newest side of the gate). In single-file mode
    # the named file is PINNED last regardless of its parsed round
    # number — the user asked to gate THAT capture, and a re-run of an
    # old round (BENCH_r03 re-captured) must not silently fall out of
    # the comparison.
    captures.sort(
        key=lambda c: (1 << 30) if c["n"] is None else int(c["n"])
    )
    if gate_path is not None:
        pinned = [
            c for c in captures if os.path.abspath(c["path"]) == gate_path
        ]
        captures = [
            c for c in captures if os.path.abspath(c["path"]) != gate_path
        ] + pinned

    print("== bench trajectory ==")
    for line in trajectory_table(captures):
        print(line)

    rc = 0
    gated = [c for c in captures if c["tiers"]]
    if not args.no_gate and len(gated) >= 2:
        old, new = gated[-2], gated[-1]
        print(f"\n== regression gate: {old['label']} -> {new['label']} ==")
        rows, regressions, capture_changes = diff_captures(
            old, new, tolerances
        )
        for r in rows:
            print(r)
        if capture_changes:
            print("  capture changes:")
            for c in capture_changes:
                print(f"    {c}")
        if regressions or (args.strict_capture and capture_changes):
            print(
                f"\nbench_diff: {len(regressions) + (len(capture_changes) if args.strict_capture else 0)} "
                "regression(s) past tolerance:", file=sys.stderr,
            )
            for r in regressions:
                print(f"  REGRESSION {r}", file=sys.stderr)
            if args.strict_capture:
                for c in capture_changes:
                    print(f"  CAPTURE    {c}", file=sys.stderr)
            rc = 1
        else:
            print("  gate: clean (no regression past tolerance)")

    if args.manifest:
        manifest = silicon_manifest(captures)
        print("\n== silicon-capture manifest ==")
        print(json.dumps(manifest, indent=2))
        if args.strict and manifest["pending"]:
            print(
                f"bench_diff: --strict: {len(manifest['pending'])} "
                "tier(s)/sub-record(s) still pending silicon capture",
                file=sys.stderr,
            )
            rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
