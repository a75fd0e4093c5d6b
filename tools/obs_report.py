#!/usr/bin/env python
"""Offline triage: join a metrics JSONL into a human report.

The metrics stream (``--metrics-out``) is an append-mode JSONL whose
records carry run/trace/span identity (docs/OBSERVABILITY.md). This tool
reconstructs, **from the JSONL alone** (no repo state, no checkpoint
dir):

- the run header: run_id, start time, wall clock, and the liveness
  verdict — ``ok`` / ``error`` from the ``run_end`` record, or, when the
  stream just *ends*, ``HUNG`` (heartbeats outlived the last phase
  record) vs ``DEAD`` (everything stopped together);
- the **phase waterfall** from ``span`` records (offset + duration bars);
- the **per-superstep throughput table** (``lpa_iter``: labels changed,
  seconds, edges/sec/chip with a trend bar);
- **superstep telemetry**: frontier size and per-shard load-imbalance
  ratios at the tripwire/checkpoint cadence;
- the **roofline** section (ISSUE 12): achieved-vs-cost-model throughput
  per ``superstep_timing`` window, with an achieved-fraction column and
  loud flags on windows below ``--roofline-min-frac`` of model — the
  triage step RUNBOOKS §12 offers before "blame the device";
- the **memory** section (ISSUE 14): the per-phase predicted-vs-peak
  waterfall from ``memory_watermark`` records, flagged under-estimates,
  a recalibration suggestion for the ``obs/memmodel.py`` byte seeds,
  and every memory-attributed degrade (plan-time pre-degrades, reactive
  OOMs with their last watermark) — RUNBOOKS §14's "read the waterfall
  before shrinking the graph" view;
- the **recovery timeline**: every retry / degrade / mesh_degrade /
  tripwire / watchdog_timeout / checkpoint rollback / resume, in causal
  order, each with its span path — *which* incident hit *which* phase on
  *which* mesh rung;
- the **serving SLO** section: per-endpoint latency quantiles
  (nearest-rank over raw ``access_log`` seconds — the exact offline
  twin of the server's live bucket estimates), error/slow-request
  rates, the repair-debt timeline each ``delta_apply``'s ledger
  snapshot traces out, and (r9) the **admission timeline** beside it —
  every accept/queue/coalesce/shed verdict with the debt state that
  decided it, coalesce merges, and shed events (RUNBOOKS §8 keys its
  triage off this view);
- the **fleet** section (r10): replica health-state transitions,
  the circuit-breaker timeline, fleet-degraded (read-only) flips, and
  the route-verdict mix — which replica states and breaker episodes
  explain the 503s a reader saw (RUNBOOKS §9 keys its triage off this
  view);
- the **writer failover** section (r11): the WAL append/replay
  aggregate, ship-lag episodes, every ``writer_promote`` step and every
  ``publish_fenced`` refusal, in causal order — the promotion timeline
  RUNBOOKS §10 says to read before forcing writes on a read-only
  fleet;
- the **fleet traces** section (ISSUE 11): the ``trace_stitch``
  cross-process join rendered inline — complete per-delta timelines
  (admission → WAL fsync → apply → publish → each replica visible, each
  line attributed to the emitting process) and the failover epoch-fence
  sequence;
- the **quality & alerts** section (ISSUE 13): the result-quality
  timeline — one row per published version joining
  ``quality_snapshot`` / ``quality_drift`` / ``canary_score`` (anomaly
  rate, churn, PSI sketch drift, canary recall, pass seconds), sketch
  quantiles of the latest snapshot, and every alert firing/resolved
  transition (RUNBOOKS §13 keys its triage off this view).

Usage::

    python tools/obs_report.py METRICS.jsonl [--run-id ID] [--out PATH]
    python tools/obs_report.py OBS_DIR           # a fleet --obs-dir

A directory argument is treated as a fleet ``--obs-dir``: every
``*.jsonl`` shard inside is merged into one report view (the fleet is
one logical run, so ``--run-id`` selection is skipped).

A reused metrics file holds several ``run_start``-delimited segments; the
default is the most recent run (``--run-id`` selects another). Exit code
0 on success, 2 when the file is missing/empty or the run id is unknown,
**3 when the reported run carries schema violations or half-stamped
trace records** (the all-or-nothing identity rule in ``obs/schema.py``),
**4 when the stream ends with a firing page-severity alert** (the
canary scorer-regression rule is the built-in page — the result-quality
CI gate, distinct from 3 so CI can tell "telemetry rotted" from "the
scorer regressed") — so CI can run this as a post-e2e gate;
``--lenient`` downgrades both to a report note. Stdlib-only (usable on
a machine with no jax at all).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = __file__.rsplit("/", 2)[0]
if _REPO not in sys.path:  # allow `python tools/obs_report.py` from anywhere
    sys.path.insert(0, _REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:  # sibling import when loaded as a module
    sys.path.insert(0, _TOOLS)

from graphmine_tpu.obs.schema import (  # noqa: E402
    RECOVERY_PHASES,
    validate_record,
    validate_records,
)

import trace_stitch  # noqa: E402  — the cross-process join (ISSUE 11)

BAR = "█"
BAR_WIDTH = 30


def load_records(path: str):
    """Parse a JSONL file tolerantly: unparseable/unknown-shape lines are
    counted, not fatal — a torn final line (the process died mid-write)
    is exactly the stream this tool exists to read."""
    records, bad = [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad += 1
                continue
            if not isinstance(rec, dict) or "phase" not in rec:
                bad += 1
                continue
            records.append(rec)
    return records, bad


def split_runs(records):
    """Group records into runs. Preferred key: ``run_id`` (order of first
    appearance). Records with no run_id (pre-tracing streams) fall into
    segments delimited by ``run_start`` records, keyed ``segment-N``."""
    runs: dict = {}
    order: list = []
    seg_key = None
    seg = 0
    for rec in records:
        rid = rec.get("run_id")
        if rid is None:
            if rec.get("phase") == "run_start" or seg_key is None:
                seg += 1
                seg_key = f"segment-{seg}"
            rid = seg_key
        if rid not in runs:
            runs[rid] = []
            order.append(rid)
        runs[rid].append(rec)
    return runs, order


def _fmt_offset(rec, t0):
    return f"+{rec.get('t', t0) - t0:8.2f}s"


def _short(path: str) -> str:
    return path[4:] if path.startswith("run/") else path  # strip "run/"


def _short_path(rec):
    return _short(rec.get("span_path", ""))


def _bar(frac: float, width: int = BAR_WIDTH) -> str:
    n = max(0, min(width, round(frac * width)))
    return BAR * n


def _phase_waterfall(records, t0):
    spans = [r for r in records if r.get("phase") == "span"]
    rows = []
    if spans:
        for r in spans:
            secs = float(r.get("seconds", 0.0))
            start = float(r.get("t", t0)) - secs - t0
            # stage spans sit under their chapter: indent by depth
            depth = max(str(r.get("span_path", "")).count("/") - 1, 0)
            rows.append((start, "  " * depth + r.get("name", "?"), secs,
                         r.get("status", "ok"), _short_path(r)))
    else:  # pre-span streams: fall back to timed phase records
        for r in records:
            if "seconds" in r and r.get("phase") not in (
                "lpa_iter", "span", "superstep_telemetry"
            ):
                secs = float(r["seconds"])
                rows.append((float(r.get("t", t0)) - secs - t0,
                             r["phase"], secs, "ok", ""))
    if not rows:
        return ["  (no phase records)"]
    rows.sort()
    total = max((s + d for s, _, d, _, _ in rows), default=1.0) or 1.0
    width = max(len(n) for _, n, _, _, _ in rows)
    out = []
    for start, name, secs, status, _ in rows:
        flag = "" if status == "ok" else f"  [{status.upper()}]"
        out.append(
            f"  {name:<{width}}  {start:8.2f}s  {secs:8.2f}s  "
            f"{_bar(secs / total)}{flag}"
        )
    # implementation selections (r6): which kNN family the LOF phase
    # actually deployed (the auto-policy's measured-crossover decision)
    # belongs next to the waterfall bar it explains — WITH the deciding
    # crossover constants and the model's numbers (ISSUE 12 small fix:
    # a policy flip must be explainable from the JSONL alone).
    for r in records:
        if r.get("phase") == "impl_selected":
            out.append(
                f"  [impl_selected] {r.get('op', '?')}: {r.get('impl', '?')}"
                f" (n={r.get('n', '?')}, k={r.get('k', '?')}) — "
                f"{r.get('reason', '')}"
            )
            extra = _decision_evidence(r)
            if extra:
                out.append(f"      {extra}")
    # plan builds (r7): the host cost of materializing a superstep plan
    # (width classes + padded slots/edge) — visible here instead of
    # hiding inside first-call latency.
    for r in records:
        if r.get("phase") == "plan_build":
            cached = " (cached)" if r.get("cached") else ""
            out.append(
                f"  [plan_build] {r.get('op', '?')}: {r.get('family', '?')}"
                f" in {float(r.get('seconds', 0.0)):.3f}s{cached} — "
                f"classes={r.get('width_classes', '?')}, "
                f"slots/edge={r.get('padded_slots_per_edge', '?')}"
            )
            extra = _decision_evidence(r, thresholds=False)
            if extra:
                out.append(f"      {extra}")
    return out


def _decision_evidence(r, thresholds: bool = True) -> str:
    """The crossover constants + cost-model numbers an auto decision
    shipped (ISSUE 12): rendered under its waterfall line so "why did
    the policy flip" never requires repo state — older streams without
    the keys render nothing."""
    def num(v, spec=","):
        return format(v, spec) if isinstance(v, (int, float)) else str(v)

    bits = []
    thr = r.get("thresholds")
    if thresholds and isinstance(thr, dict):
        bits.append(
            "thresholds: "
            + ", ".join(f"{k}={num(v)}" for k, v in sorted(thr.items()))
        )
    cost = r.get("cost")
    if isinstance(cost, dict):
        bits.append(
            f"model: {num(cost.get('predicted_per_chip', 0), ',.0f')} "
            f"{cost.get('unit', '?')} "
            f"(padded x{cost.get('padding_overhead', '?')}, "
            f"{num(cost.get('bytes_gathered', 0))} B gathered"
            + (
                f", {num(cost.get('exchange_bytes', 0))} B ICI"
                if cost.get("exchange_bytes") else ""
            )
            + ")"
        )
    return "  ".join(bits)


def _superstep_table(records):
    iters = [r for r in records if r.get("phase") == "lpa_iter"]
    if not iters:
        return ["  (no lpa_iter records)"]
    peak = max(r.get("edges_per_sec_per_chip", 0) for r in iters) or 1
    out = ["  it  changed   seconds   edges/sec/chip"]
    for r in iters:
        eps = r.get("edges_per_sec_per_chip", 0)
        out.append(
            f"  {r.get('iteration', '?'):>2}  {r.get('labels_changed', 0):>7}"
            f"  {r.get('seconds', 0):>8.4f}  {eps:>14,}  {_bar(eps / peak, 20)}"
        )
    return out


def _roofline_section(records, min_frac: float):
    """Achieved-vs-model roofline attribution (ISSUE 12): one row per
    ``superstep_timing`` window — achieved edges/s/chip next to the
    analytical cost model's prediction, with the achieved fraction and a
    loud flag on windows below ``min_frac`` of model (the RUNBOOKS §12
    "read this before blaming the device" signal). The exchange-vs-
    compute split comes from the window's cost sub-record. Empty list =
    no superstep_timing records (pre-ISSUE-12 stream)."""
    timings = [r for r in records if r.get("phase") == "superstep_timing"]
    if not timings:
        return []
    out = [
        "  op               it  win  family/variant     "
        "achieved/chip      model/chip   frac  exch%"
    ]
    flagged = 0
    exchange_windows = 0
    for r in timings:
        # None: the run's device kind has no roofline anchors (costmodel
        # .anchored) — nothing to judge the window against, never flagged
        unanchored = r.get("achieved_fraction") is None
        frac = float(r.get("achieved_fraction") or 0.0)
        # a window that paid an XLA trace+compile (the ops seams mark
        # it) reads far below model on healthy hardware — report the
        # honest number, but never raise the triage flag on it
        cold = bool(r.get("cold_compile"))
        below = frac < min_frac and not cold and not unanchored
        flagged += below
        fam = f"{r.get('family', '?')}/{r.get('variant', '?')}"
        if int(r.get("devices", 1) or 1) > 1:
            fam += f"@{r['devices']}dev"
        note = ""
        if below:
            note = f"  << below {min_frac:g}x model"
        elif unanchored:
            note = "  (no roofline anchors for this device kind)"
        elif cold and frac < min_frac:
            note = "  (window includes XLA compile — not flagged)"
        # exchange column (ISSUE 15): the model's exchange share of the
        # window — the "is this superstep exchange-bound" number the §15
        # runbook reads before blaming the ICI
        cost = r.get("cost")
        exch_col, split = "    -", None
        if isinstance(cost, dict) and cost.get("exchange_bytes"):
            cs = float(cost.get("compute_seconds", 0.0) or 0.0)
            es = float(cost.get("exchange_seconds", 0.0) or 0.0)
            tot = (cs + es) or 1.0
            exch_col = f"{100 * es / tot:>4.0f}%"
            split = (
                f"      model split: compute {100 * cs / tot:.0f}% / "
                f"exchange {100 * es / tot:.0f}% "
                f"({cost['exchange_bytes']:,} B ICI per superstep)"
            )
            exchange_windows += 1
        out.append(
            f"  {str(r.get('op', '?')):<15} {r.get('iteration', '?'):>3}"
            f"  {r.get('window', '?'):>3}  {fam:<17}"
            f"  {int(r.get('edges_per_sec_per_chip', 0) or 0):>13,}"
            f"  {int(r.get('predicted_edges_per_sec_per_chip', 0) or 0):>14,}"
            f"  {'  n/a' if unanchored else format(frac, '>5.2f')}"
            f"  {exch_col}{note}"
        )
        if split:
            out.append(split)
    if flagged:
        out.append(
            f"  {flagged} window(s) below {min_frac:g}x of model — read "
            "the telemetry/imbalance tables above before blaming the "
            "device (docs/RUNBOOKS.md §12)"
        )
    roof = next(
        (
            r["cost"]["roofline"] for r in reversed(timings)
            if isinstance(r.get("cost"), dict)
            and isinstance(r["cost"].get("roofline"), dict)
        ),
        None,
    )
    if roof:
        anchors = ", ".join(
            f"{k}={v:,.3g}" for k, v in sorted(roof.items())
            if isinstance(v, (int, float))
        )
        out.append(f"  model anchors: {anchors}")
        if roof.get("provenance"):
            out.append(f"  anchor provenance: {roof['provenance']}")
        # Exchange-anchor provenance flag (ISSUE 15 small fix): the
        # exchange split above divides by `exchange_bytes_per_sec`,
        # which has never been measured on silicon — a window reading
        # below model because of an optimistic exchange seed is a model
        # problem, not a device problem, and the verdict must say so
        # instead of letting a below-model flag rest silently on an
        # unmeasured anchor.
        prov = str(roof.get("provenance") or "")
        if exchange_windows and "exchange_bytes_per_sec: model seed" in prov:
            out.append(
                f"  !! {exchange_windows} window(s) carry an exchange "
                "split anchored to the UNMEASURED exchange_bytes_per_sec "
                "model seed — no chip record measures it "
                "(re-seed via GRAPHMINE_ROOFLINE_FILE) before "
                "trusting a below-model exchange verdict "
                "(docs/RUNBOOKS.md §15)"
            )
    return out


def _device_section(records):
    """One ``profile_dir`` capture, reduced by the run itself
    (``device_scope`` / ``device_idle`` records, obs/devtrace.py), and
    the run's compiles by program. Empty without either."""
    scopes = [r for r in records if r.get("phase") == "device_scope"]
    idle = [r for r in records if r.get("phase") == "device_idle"]
    compiles = [r for r in records if r.get("phase") == "compile"]
    out = []
    if scopes:
        busy = sum(float(r["device_seconds"]) for r in scopes) or 1.0
        out.append(
            "  device seconds by named scope (a fusion carries the scope "
            "of its root instruction):"
        )
        out.append(f"  {'scope':<34}{'program':<30}{'seconds':>10}"
                   f"{'share':>8}{'ops':>9}  launched under")
        for r in scopes[:40]:
            secs = float(r["device_seconds"])
            out.append(
                f"  {r['scope']:<34}{str(r['module'])[:28]:<30}{secs:>10.4f}"
                f"{100 * secs / busy:>7.1f}%{r['events']:>9}  "
                f"{_short(r.get('stage_path', ''))}"
            )
        if len(scopes) > 40:
            rest = sum(float(r["device_seconds"]) for r in scopes[40:])
            out.append(f"  ... {len(scopes) - 40} more rows, {rest:.4f}s")
    for r in idle:
        busy_s, idle_s = float(r["busy_seconds"]), float(r["idle_seconds"])
        total = (busy_s + idle_s) or 1.0
        out.append(
            f"  [device_idle] {_short(r.get('chapter_path', '')):<28}"
            f" busy {busy_s:8.3f}s  idle {idle_s:8.3f}s "
            f"({100 * idle_s / total:.0f}% idle)"
        )
    if compiles:
        by_prog = {}
        for r in compiles:
            key = (r.get("fun_name", "?"), _short_path(r))
            row = by_prog.setdefault(key, [0.0, 0, 0])
            row[0] += float(r.get("seconds", 0.0))
            if r.get("stage") == "backend":
                row[1] += 1
                row[2] += bool(r.get("cache_hit"))
        total = sum(v[0] for v in by_prog.values())
        out.append(
            f"  compiles: {sum(v[1] for v in by_prog.values())} programs, "
            f"{total:.3f}s in trace + lower + backend; the longest:"
        )
        ranked = sorted(by_prog.items(), key=lambda kv: -kv[1][0])
        for (fun, path), (secs, n, hits) in ranked[:8]:
            out.append(
                f"  [compile] {fun:<36}{secs:>9.3f}s  x{n} "
                f"({hits} from the cache)  under {path}"
            )
    return out


def _fmt_bytes(b) -> str:
    if not isinstance(b, (int, float)):
        return "-"
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if abs(b) >= div:
            return f"{b / div:.1f}{unit}"
    return f"{int(b)}B"


def _memory_section(records, t0):
    """Memory-plane triage (ISSUE 14, docs/OBSERVABILITY.md "Memory
    plane"): the per-phase predicted-vs-peak waterfall from
    ``memory_watermark`` records, flagged under-estimates, a concrete
    recalibration suggestion for the ``obs/memmodel.py`` byte seeds,
    and every
    memory-attributed degrade — plan-time pre-degrades and reactive
    OOMs with their attached last watermark, joinable back to the full
    record by span path. Empty list = no memory-plane records
    (pre-ISSUE-14 stream)."""
    marks = [r for r in records if r.get("phase") == "memory_watermark"]
    # device-loss degrades (kind="device") also carry the mem context —
    # the driver attaches it to every degrade — but they belong to the
    # elastic ladder's triage (§3), not the memory section: labeling a
    # dead chip "OOM" would send the operator down the wrong runbook.
    mem_degrades = [
        r for r in records
        if r.get("phase") == "degrade"
        and r.get("kind") != "device"
        and (isinstance(r.get("mem"), dict) or r.get("kind") == "mem_plan"
             or isinstance(r.get("last_watermark"), dict))
    ]
    if not (marks or mem_degrades):
        return []
    out = []
    def _num(v):
        # non-numeric-tolerant (the r12 roofline discipline): schema
        # validation checks key presence, not types — a malformed
        # record must degrade to a hole in the table, never a crashed
        # report (the exit-3 path still names the violation)
        return int(v) if isinstance(v, (int, float)) else 0

    if marks:
        # grouped per (op, source): one transient rss-fallback sample
        # mid-run must never contaminate a device group's peak/ratio —
        # RSS vs HBM model is exactly the comparison the recalibration
        # rule below refuses to make
        groups: dict = {}
        for r in marks:
            key = (r.get("op", "?"), r.get("source", "?"))
            g = groups.setdefault(key, {
                "pred": 0, "peak": 0, "head": None, "n": 0,
            })
            g["pred"] = max(g["pred"], _num(r.get("predicted_bytes")))
            g["peak"] = max(g["peak"], _num(r.get("achieved_bytes")))
            h = r.get("headroom_frac")
            if isinstance(h, (int, float)):
                g["head"] = h if g["head"] is None else min(g["head"], h)
            g["n"] += 1
        out.append(
            "  op               predicted       peak  peak/model"
            "  headroom  src     marks"
        )
        peak_max = max(g["peak"] for g in groups.values()) or 1
        worst = None  # (ratio, op) over device-sourced groups
        for (op, src), g in sorted(groups.items()):
            ratio = g["peak"] / g["pred"] if g["pred"] else 0.0
            head = f"{g['head']:.2f}" if g["head"] is not None else "-"
            flag = ""
            if src == "device" and g["pred"] and ratio > 1.1:
                flag = "  << model under-estimates"
            if src == "device" and (worst is None or ratio > worst[0]):
                worst = (ratio, op)
            out.append(
                f"  {op:<15} {_fmt_bytes(g['pred']):>10}"
                f" {_fmt_bytes(g['peak']):>10}  {ratio:>9.2f}x"
                f"  {head:>8}  {src:<6}  {g['n']:>4}"
                f"  {_bar(g['peak'] / peak_max, 16)}{flag}"
            )
        # Recalibration suggestion: what the measured peaks mean for
        # the byte seeds the
        # planner AND the model read (one owner — obs/memmodel.py).
        try:
            from graphmine_tpu.obs.memmodel import BYTES_PER_EDGE
        except Exception:  # pragma: no cover — report must still render
            BYTES_PER_EDGE = None
        cur = (
            f"(current seed: BYTES_PER_EDGE={BYTES_PER_EDGE:.0f})"
            if BYTES_PER_EDGE is not None else ""
        )
        if worst is None:
            out.append(
                "  recalibration: watermarks carry host-RSS only (no "
                "device allocator on this backend) — RSS is not "
                "comparable to the HBM model; re-run on silicon to "
                f"recalibrate the obs/memmodel.py byte seeds {cur}"
            )
        elif worst[0] > 1.05:
            scaled = (
                f" (e.g. BYTES_PER_EDGE {BYTES_PER_EDGE:.0f} -> "
                f"{BYTES_PER_EDGE * worst[0]:.0f})"
                if BYTES_PER_EDGE is not None else ""
            )
            out.append(
                f"  recalibration: measured peak is {worst[0]:.2f}x the "
                f"modeled footprint for {worst[1]} — raise the "
                f"obs/memmodel.py byte seeds{scaled} so the planner "
                "stops accepting schedules the allocator rejects; the "
                "planner moves with the model (one owner)"
            )
        elif worst[0] < 0.7:
            out.append(
                f"  recalibration: measured peak is only {worst[0]:.2f}x "
                f"model for {worst[1]} — the seeds are conservative; "
                "lowering them (obs/memmodel.py) would admit larger "
                f"graphs per device {cur}"
            )
        else:
            out.append(
                f"  recalibration: measured peak within noise of model "
                f"(worst {worst[0]:.2f}x at {worst[1]}) — keep the "
                f"obs/memmodel.py byte seeds {cur}"
            )
    for r in mem_degrades:
        kind = (
            "PLAN PRE-DEGRADE" if r.get("kind") == "mem_plan"
            else "OOM DEGRADE"
        )
        mem = r.get("mem") if isinstance(r.get("mem"), dict) else {}
        line = (
            f"  {_fmt_offset(r, t0)}  {kind}  stage={r.get('stage', '?')}"
            f"  to={r.get('to', '?')}"
        )
        if mem:
            line += (
                f"  modeled={_fmt_bytes(mem.get('total_bytes'))}"
                f" ({mem.get('family', '?')})"
            )
        out.append(line)
        w = r.get("last_watermark")
        if isinstance(w, dict):
            out.append(
                f"      last watermark: "
                f"{_fmt_bytes(w.get('achieved_bytes'))} measured"
                f" ({w.get('source', '?')}) vs "
                f"{_fmt_bytes(w.get('predicted_bytes'))} model"
                f"  headroom={w.get('headroom_frac', '?')}"
                f"  @ {w.get('span_path', '?')}"
            )
        inv = mem.get("inventory")
        if isinstance(inv, dict) and inv:
            top = sorted(inv.items(), key=lambda kv: -_num(kv[1]))[:4]
            out.append(
                "      inventory: "
                + ", ".join(f"{k}={_fmt_bytes(v)}" for k, v in top)
                + (f", … ({len(inv)} components)" if len(inv) > 4 else "")
            )
    return out


def _telemetry_table(records):
    tele = [r for r in records if r.get("phase") == "superstep_telemetry"]
    if not tele:
        return ["  (no superstep_telemetry records)"]
    out = ["  it  frontier  shards  shard min/max  imbalance  variant"]
    for r in tele:
        out.append(
            f"  {r.get('iteration', '?'):>2}  {r.get('frontier', 0):>8}"
            f"  {r.get('devices', '?'):>6}"
            f"  {r.get('shard_min', '?'):>6}/{r.get('shard_max', '?'):<6}"
            f"  {r.get('imbalance', '?'):>9}  {r.get('variant', '?')}"
        )
    return out


_DETAIL_KEYS = {
    "retry": ("stage", "attempt", "backoff_s"),
    "retries_exhausted": ("stage", "attempts"),
    "degrade": ("stage", "to", "kind"),
    "mesh_degrade": ("from_devices", "to_devices", "iteration",
                     "resumed_from", "dead_devices"),
    "tripwire": ("kind", "shard", "iteration"),
    "watchdog_timeout": ("stage", "timeout_s", "checkpointed"),
    "resume": ("iteration", "reason"),
    "checkpoint_rollback": ("path",),
    "checkpoint_rollback_ok": ("path", "iteration"),
    "ivf_fallback": ("guard",),
    "quarantine": (),
    "repair_fallback": ("stage", "reason"),
    "breaker_transition": ("replica", "from_state", "to_state"),
    "fleet_degraded": ("read_only", "writer"),
    "wal_replay": ("entries", "from_seq", "source"),
    "writer_promote": ("epoch", "replica", "replayed"),
    "publish_fenced": ("attempted_epoch", "store_epoch"),
}

_SERVING_PHASES = ("snapshot_publish", "snapshot_load", "delta_apply",
                   "query_batch")


def _serving_table(records, t0):
    """Serving-layer timeline (r7): snapshot publishes/loads and delta
    applies as rows, query_batch records aggregated per endpoint —
    100k lookups must not become 100k report lines."""
    rows, queries = [], {}
    for r in records:
        phase = r.get("phase")
        if phase == "query_batch":
            agg = queries.setdefault(
                r.get("endpoint", "?"), {"batches": 0, "n": 0, "seconds": 0.0}
            )
            agg["batches"] += 1
            agg["n"] += int(r.get("n", 0))
            agg["seconds"] += float(r.get("seconds", 0.0))
        elif phase == "snapshot_publish":
            rows.append(
                f"  {_fmt_offset(r, t0)}  snapshot_publish  "
                f"v{r.get('version', '?')}  {r.get('bytes', 0):,} B  "
                f"{r.get('seconds', 0):.3f}s  arrays={len(r.get('arrays', []))}"
            )
        elif phase == "snapshot_load":
            rows.append(
                f"  {_fmt_offset(r, t0)}  snapshot_load     "
                f"v{r.get('version', '?')}  {r.get('seconds', 0):.3f}s"
            )
        elif phase == "delta_apply":
            q = r.get("quarantine", {})
            quarantined = sum(q.values()) if isinstance(q, dict) else 0
            rows.append(
                f"  {_fmt_offset(r, t0)}  delta_apply       "
                f"v{r.get('version', '?')}  +{r.get('inserts', 0)}/-"
                f"{r.get('deletes', 0)} edges  {r.get('method', '?')} "
                f"({r.get('iterations', '?')} supersteps)  "
                f"quarantined={quarantined}  {r.get('seconds', 0):.3f}s"
            )
    for endpoint, agg in sorted(queries.items()):
        qps = agg["n"] / agg["seconds"] if agg["seconds"] > 0 else 0.0
        rows.append(
            f"  queries[{endpoint}]: {agg['n']:,} lookups in "
            f"{agg['batches']} batch(es), {agg['seconds']:.3f}s resolve "
            f"time ({qps:,.0f}/s)"
        )
    return rows


def _percentile(sorted_vals, q):
    """Nearest-rank percentile over a sorted list — the stdlib-exact
    offline quantile the live bucket estimate (``/statusz``) is checked
    against (agreement within one histogram bucket, tests/test_slo.py)."""
    if not sorted_vals:
        return 0.0
    import math

    rank = max(1, math.ceil(q * len(sorted_vals)))
    return sorted_vals[min(rank, len(sorted_vals)) - 1]


def _slo_section(records, t0):
    """Serving SLO, reconstructed from the JSONL alone: per-endpoint
    latency quantiles + error rates from ``access_log`` records, and the
    repair-debt timeline from the ledger snapshots each ``delta_apply``
    carries. Empty list = no serving-SLO records (batch-only stream)."""
    access = [r for r in records if r.get("phase") == "access_log"]
    applies = [
        r for r in records
        if r.get("phase") == "delta_apply"
        and isinstance(r.get("repair_debt"), dict)
    ]
    out = []
    if access:
        per: dict = {}
        for r in access:
            d = per.setdefault(
                r.get("endpoint", "?"), {"secs": [], "errors": 0, "slow": 0}
            )
            d["secs"].append(float(r.get("seconds", 0.0)))
            if int(r.get("status", 0)) >= 400:
                d["errors"] += 1
            if r.get("slow"):
                d["slow"] += 1
        out.append(
            "  endpoint          n    err%  slow       p50       p95"
            "       p99"
        )
        for ep, d in sorted(per.items()):
            s = sorted(d["secs"])
            n = len(s)
            out.append(
                f"  {ep:<14} {n:>5}  {100.0 * d['errors'] / n:>5.1f}%"
                f"  {d['slow']:>4}"
                f"  {_percentile(s, 0.50) * 1e3:>7.2f}ms"
                f"  {_percentile(s, 0.95) * 1e3:>7.2f}ms"
                f"  {_percentile(s, 0.99) * 1e3:>7.2f}ms"
            )
    if applies:
        out.append("  repair-debt timeline:")
        for r in applies:
            debt = r["repair_debt"]
            budget = r.get("budget", "?")
            row = (
                f"  {_fmt_offset(r, t0)}  v{r.get('version', '?')}"
                f"  {r.get('method', '?'):<15}"
                f"  supersteps={r.get('iterations', '?')}/{budget}"
                f"  pending_rows={debt.get('pending_rows', '?')}"
                f"  lag={debt.get('ingest_lag_s', '?')}s"
                f"  warm_ratio={debt.get('warm_ratio', '?')}"
            )
            if int(r.get("batches", 1) or 1) > 1:
                row += f"  coalesced={r['batches']}"
            if r.get("lof_stale"):
                row += "  LOF-STALE"
            out.append(row)
    out.extend(_admission_timeline(records, t0))
    return out


def _admission_timeline(records, t0):
    """Admission-control timeline (r8, docs/SERVING.md "admission
    control"): every resolve verdict with the debt state that decided
    it, coalesce merges, and shed events — the first thing RUNBOOKS §8
    says to read when /delta starts returning 503s. Rendered next to the
    repair-debt timeline so "why did it shed" and "how far behind was
    repair" line up on one clock."""
    events = [
        r for r in records
        if r.get("phase") in ("admission", "delta_coalesce", "delta_shed")
    ]
    if not events:
        return []
    out = ["  admission timeline:"]
    verdicts: dict = {}
    for r in events:
        phase = r["phase"]
        debt = r.get("repair_debt") or {}
        if phase == "admission":
            verdicts[r.get("verdict", "?")] = (
                verdicts.get(r.get("verdict", "?"), 0) + 1
            )
            out.append(
                f"  {_fmt_offset(r, t0)}  admission  "
                f"{r.get('verdict', '?'):<8}"
                f"  rows={r.get('rows', '?')}"
                f"  queue={r.get('queue_depth', '?')}"
                f"  pending_rows={debt.get('pending_rows', '?')}"
                f"  lag={debt.get('ingest_lag_s', '?')}s"
                + (
                    f"  [{r.get('reason', '')}]"
                    if r.get("verdict") in ("shed",) else ""
                )
            )
        elif phase == "delta_coalesce":
            out.append(
                f"  {_fmt_offset(r, t0)}  coalesce   "
                f"{r.get('batches', '?')} batches -> "
                f"+{r.get('inserts', '?')}/-{r.get('deletes', '?')} rows "
                f"(cancelled={r.get('cancelled_pairs', 0)}, "
                f"rows {r.get('rows_in', '?')}->{r.get('rows_out', '?')})"
            )
        else:  # delta_shed
            out.append(
                f"  {_fmt_offset(r, t0)}  SHED       "
                f"stage={r.get('stage', '?')}  rows={r.get('rows', '?')}"
                f"  retry_after={r.get('retry_after_s', '?')}s"
                f"  [{r.get('reason', '')}]"
            )
    if verdicts:
        total = sum(verdicts.values())
        mix = "  ".join(f"{k}={v}" for k, v in sorted(verdicts.items()))
        out.append(f"  admission verdicts: {total} resolutions ({mix})")
    return out


def _fleet_section(records, t0):
    """Replicated-fleet timeline (r10, docs/SERVING.md "Fleet"): replica
    state-machine transitions, the breaker timeline, read-only flips and
    the route-verdict mix — RUNBOOKS §9's "read the fleet timeline
    before restarting anything" view. Empty list = no fleet records
    (single-process stream)."""
    health = [r for r in records if r.get("phase") == "replica_health"]
    breakers = [r for r in records if r.get("phase") == "breaker_transition"]
    degraded = [r for r in records if r.get("phase") == "fleet_degraded"]
    routes = [r for r in records if r.get("phase") == "fleet_route"]
    if not (health or breakers or degraded or routes):
        return []
    out = []
    if health:
        out.append("  replica health transitions:")
        for r in health:
            v = r.get("version")
            out.append(
                f"  {_fmt_offset(r, t0)}  {r.get('replica', '?'):<12}"
                f"  {r.get('from_state', '?'):>8} -> "
                f"{r.get('to_state', '?'):<8}"
                f"{f'  v{v}' if v is not None else ''}"
                f"  [{r.get('reason', '')}]"
            )
    if breakers:
        out.append("  breaker timeline:")
        for r in breakers:
            out.append(
                f"  {_fmt_offset(r, t0)}  {r.get('replica', '?'):<12}"
                f"  {r.get('from_state', '?'):>9} -> "
                f"{r.get('to_state', '?'):<9}"
                f"  [{r.get('reason', '')}]"
            )
    for r in degraded:
        verdict = (
            "FLEET READ-ONLY" if r.get("read_only") else "fleet writes restored"
        )
        out.append(
            f"  {_fmt_offset(r, t0)}  {verdict}  [{r.get('reason', '')}]"
        )
    if routes:
        verdicts: dict = {}
        attempts_total = 0
        retried = 0
        for r in routes:
            verdicts[r.get("verdict", "?")] = (
                verdicts.get(r.get("verdict", "?"), 0) + 1
            )
            a = int(r.get("attempts", 0) or 0)
            attempts_total += a
            if a > 1:
                retried += 1
        mix = "  ".join(f"{k}={v}" for k, v in sorted(verdicts.items()))
        out.append(
            f"  route verdicts: {len(routes)} requests ({mix}); "
            f"{attempts_total} replica attempts, {retried} needed retry"
        )
    return out


def _failover_section(records, t0):
    """Writer-failover timeline (r11, docs/SERVING.md "Replicated
    writers"): the WAL durability aggregate, ship-lag episodes, every
    promotion step and every fenced publish — RUNBOOKS §10's "read the
    promotion timeline before forcing writes" view. Empty list = no
    durable-write-path records in the stream."""
    appends = [r for r in records if r.get("phase") == "wal_append"]
    replays = [r for r in records if r.get("phase") == "wal_replay"]
    lags = [r for r in records if r.get("phase") == "ship_lag"]
    promotes = [r for r in records if r.get("phase") == "writer_promote"]
    fenced = [r for r in records if r.get("phase") == "publish_fenced"]
    if not (appends or replays or lags or promotes or fenced):
        return []
    out = []
    if appends:
        secs = sorted(float(r.get("seconds", 0.0)) for r in appends)
        rows = sum(int(r.get("rows", 0)) for r in appends)
        total = sum(int(r.get("bytes", 0)) for r in appends)
        out.append(
            f"  wal appends: {len(appends)} entries, {rows} rows, "
            f"{total:,} B; fsync p50 "
            f"{_percentile(secs, 0.50) * 1e3:.2f}ms / p99 "
            f"{_percentile(secs, 0.99) * 1e3:.2f}ms"
        )
    for r in replays:
        if r.get("torn_tail"):
            out.append(
                f"  {_fmt_offset(r, t0)}  WAL TORN TAIL  truncated at "
                f"{r.get('truncated_to', '?')} B  [{r['torn_tail']}]"
            )
            continue
        out.append(
            f"  {_fmt_offset(r, t0)}  wal_replay  "
            f"{r.get('entries', '?')} entr(ies) "
            f"seq {r.get('from_seq', '?')}..{r.get('to_seq', '?')}  "
            f"source={r.get('source', '?')}"
        )
    if lags:
        worst = max(lags, key=lambda r: float(r.get("lag_s", 0.0) or 0.0))
        out.append(
            f"  ship lag: {len(lags)} behind-sample(s); worst "
            f"{worst.get('lag_entries', '?')} entries / "
            f"{worst.get('lag_s', '?')}s behind "
            f"(primary seq {worst.get('primary_last_seq', '?')}, "
            f"shipped {worst.get('shipped_seq', '?')})"
        )
    for r in promotes:
        bits = [f"epoch {r.get('epoch', '?')}"]
        if r.get("replica"):
            bits.append(f"writer={r['replica']}")
        if r.get("deposed"):
            bits.append(f"deposed={r['deposed']}")
        if r.get("replayed") is not None:
            bits.append(f"replayed={r['replayed']}")
        if r.get("copied_tail") is not None:
            bits.append(f"copied_tail={r['copied_tail']}")
        if r.get("seconds") is not None:
            bits.append(f"{r['seconds']}s")
        out.append(
            f"  {_fmt_offset(r, t0)}  WRITER PROMOTE  {'  '.join(bits)}"
        )
    for r in fenced:
        out.append(
            f"  {_fmt_offset(r, t0)}  PUBLISH FENCED  attempted epoch "
            f"{r.get('attempted_epoch', '?')} < store epoch "
            f"{r.get('store_epoch', '?')}  [{r.get('reason', '')}]"
        )
    return out


def _writer_shards_section(records, t0):
    """Sharded-write-plane timeline (r17, docs/SERVING.md "Sharded write
    plane"): per-range admission verdict mix, every epoch commit, every
    per-shard stage publish, and each range's degrade/recover/promote
    line — the §17 runbook's "which range is read-only, which epoch is
    stuck" view. Empty list = no shard-plane records in the stream."""
    publishes = [r for r in records if r.get("phase") == "shard_publish"]
    commits = [r for r in records if r.get("phase") == "epoch_commit"]
    degraded = [r for r in records if r.get("phase") == "shard_degraded"]
    admissions = [
        r for r in records
        if r.get("phase") == "admission" and r.get("shard") is not None
    ]
    if not (publishes or commits or degraded):
        return []
    out = []
    if admissions:
        # per-range verdict mix: one line per shard, the range-level
        # answer to "who is shedding"
        by_shard: dict = {}
        for r in admissions:
            mix = by_shard.setdefault(int(r["shard"]), {})
            v = r.get("verdict", "?")
            mix[v] = mix.get(v, 0) + 1
        for shard in sorted(by_shard):
            mix = by_shard[shard]
            parts = "  ".join(
                f"{v}={mix[v]}" for v in sorted(mix)
            )
            out.append(f"  shard {shard} admission: {parts}")
    if publishes:
        by_shard = {}
        for r in publishes:
            by_shard.setdefault(int(r.get("shard", -1)), []).append(r)
        staged = ", ".join(
            f"shard {s}×{len(rs)}" for s, rs in sorted(by_shard.items())
        )
        out.append(f"  stage publishes: {len(publishes)} ({staged})")
    for r in commits:
        vec = r.get("version_vector") or {}
        vv = " ".join(
            f"{k}:{vec[k]}" for k in sorted(vec, key=lambda x: int(x))
        )
        tag = "  (recovered)" if r.get("recovered") else ""
        out.append(
            f"  {_fmt_offset(r, t0)}  EPOCH COMMIT  epoch "
            f"{r.get('epoch', '?')}  versions [{vv}]{tag}"
        )
    for r in degraded:
        status = str(r.get("status", "?")).upper()
        rng = r.get("range")
        rng_s = f" [{rng[0]},{rng[1]})" if isinstance(rng, list) else ""
        out.append(
            f"  {_fmt_offset(r, t0)}  SHARD {status}  shard "
            f"{r.get('shard', '?')}{rng_s}  [{r.get('reason', '')}]"
        )
    return out


def _sketch_quantiles(state) -> str:
    """p50/p90/p99 of a sketch state dict — rebuilt through the one
    shared QuantileSketch machinery so the report's numbers can never
    drift from the live /statusz estimates."""
    try:
        from graphmine_tpu.obs.sketch import QuantileSketch

        sk = QuantileSketch.from_state(state)
        if not sk.count:
            return "(empty)"
        return (
            f"p50 {sk.quantile(0.50):.3g} / p90 {sk.quantile(0.90):.3g}"
            f" / p99 {sk.quantile(0.99):.3g}"
        )
    except (ValueError, KeyError, TypeError):
        return "(malformed sketch)"


def _quality_section(records, t0):
    """Result-quality timeline (ISSUE 13, docs/OBSERVABILITY.md "Result
    quality"): one row per published version joining quality_snapshot /
    quality_drift / canary_score, then every alert transition — the
    RUNBOOKS §13 "read the quality timeline before blaming the data"
    view, rendered from the JSONL shards alone. Empty = no quality
    records in the stream."""
    snaps = [r for r in records if r.get("phase") == "quality_snapshot"]
    drifts = {
        r.get("version"): r for r in records
        if r.get("phase") == "quality_drift"
    }
    canaries = {
        r.get("version"): r for r in records
        if r.get("phase") == "canary_score"
    }
    alerts = [r for r in records if r.get("phase") == "alert"]
    if not (snaps or alerts):
        return []
    out = []
    if snaps:
        out.append(
            "  version  communities  anomaly%   churn   lof_psi  size_psi"
            "  canary@k  pass_s"
        )
        for r in snaps:
            ver = r.get("version")
            d = drifts.get(ver, {})
            c = canaries.get(ver, {})

            def num(src, key, fmt, absent="      -"):
                v = src.get(key)
                if not isinstance(v, (int, float)):
                    return absent
                return fmt.format(v)

            out.append(
                f"  v{ver!s:<7} {r.get('num_communities', '?'):>11}  "
                f"{num(r, 'anomaly_rate', '{:7.2%}')} "
                f"{num(d, 'churn_frac', '{:7.2%}')} "
                f"{num(d, 'lof_psi', '{:9.3f}')} "
                f"{num(d, 'size_psi', '{:9.3f}')} "
                f"{num(c, 'recall_at_k', '{:9.2f}')} "
                f"{num(r, 'seconds', '{:7.3f}')}"
            )
        last = snaps[-1]
        for key, label in (("lof_sketch", "lof scores"),
                           ("size_sketch", "community sizes")):
            state = last.get(key)
            if isinstance(state, dict):
                out.append(
                    f"  latest {label:<16} {_sketch_quantiles(state)}"
                )
    for r in alerts:
        mark = "ALERT FIRING" if r.get("state") == "firing" else "resolved"
        out.append(
            f"  {_fmt_offset(r, t0)}  {mark:<12} {r.get('name', '?')}"
            f"  [{r.get('severity', '?')}]  {r.get('metric', '?')}"
            f" {r.get('op', '')} {r.get('threshold', '?')}"
            f"  value={r.get('value', '?')}"
        )
    return out


def _tenant_section(records, t0):
    """Per-tenant serving rollup (ISSUE 16, docs/SERVING.md "Multi-tenant
    serving"): group the write-path and alert records by the ``tenant``
    they carry — one row per namespace with its admission verdict mix,
    applied volume, sheds and firing alerts, so a noisy-neighbor
    incident reads as "tenant A shed, tenant B clean" instead of one
    blended stream. Records without a tenant stamp are the default
    namespace. Empty when the stream is single-tenant (no record
    carries a tenant key)."""
    phases = ("admission", "delta_apply", "delta_shed", "delta_coalesce",
              "access_log", "alert", "quality_drift", "canary_score")
    tagged = [r for r in records if r.get("phase") in phases]
    if not any("tenant" in r for r in tagged):
        return []
    groups: dict = {}
    for r in tagged:
        groups.setdefault(r.get("tenant") or "default", []).append(r)
    out = [
        "  tenant            deltas    rows  sheds  admission verdicts"
        "        firing"
    ]
    for tenant in sorted(groups):
        rs = groups[tenant]
        applies = [r for r in rs if r["phase"] == "delta_apply"]
        rows = sum(
            int(r.get("inserts", 0) or 0) + int(r.get("deletes", 0) or 0)
            for r in applies
        )
        verdicts: dict = {}
        for r in rs:
            if r["phase"] == "admission":
                v = str(r.get("verdict", "?"))
                verdicts[v] = verdicts.get(v, 0) + 1
        mix = " ".join(
            f"{k}:{n}" for k, n in sorted(verdicts.items())
        ) or "-"
        sheds = sum(1 for r in rs if r["phase"] == "delta_shed")
        last_alert: dict = {}
        for r in rs:
            if r["phase"] == "alert" and r.get("name"):
                last_alert[r["name"]] = r.get("state")
        firing = sorted(
            n for n, st in last_alert.items() if st == "firing"
        )
        out.append(
            f"  {tenant:<16} {len(applies):>7} {rows:>7} {sheds:>6}  "
            f"{mix:<24}  {', '.join(firing) or '-'}"
        )
    transitions = [
        r for r in tagged
        if r["phase"] == "alert" and "tenant" in r
    ]
    for r in transitions:
        mark = "ALERT FIRING" if r.get("state") == "firing" else "resolved"
        out.append(
            f"  {_fmt_offset(r, t0)}  [{r.get('tenant', '?')}]  {mark:<12}"
            f" {r.get('name', '?')}  value={r.get('value', '?')}"
        )
    return out


def gating_alerts(records) -> list:
    """Alert names whose LAST transition in the stream is a firing
    page-severity alert (the canary rule is the built-in page) — the CI
    gate: ``main`` exits 4 when this is non-empty, alongside the
    schema-violation exit 3 (docs/OBSERVABILITY.md "Result quality")."""
    last: dict = {}
    for r in records:
        if r.get("phase") == "alert" and r.get("name"):
            last[r["name"]] = r
    return sorted(
        name for name, r in last.items()
        if r.get("state") == "firing" and r.get("severity") == "page"
    )


def _recovery_timeline(records, t0):
    events = [r for r in records if r.get("phase") in RECOVERY_PHASES]
    if not events:
        return ["  (clean run: no recovery events)"]
    out = []
    for r in events:
        keys = _DETAIL_KEYS.get(r["phase"], ())
        detail = "  ".join(
            f"{k}={r[k]}" for k in keys if k in r and r[k] is not None
        )
        err = r.get("error")
        if err and r["phase"] in ("retry", "retries_exhausted", "degrade"):
            err = str(err)
            detail += f"  error={err[:70]}{'…' if len(err) > 70 else ''}"
        where = _short_path(r)
        out.append(
            f"  {_fmt_offset(r, t0)}  {r['phase']:<22}"
            f"{('[' + where + ']  ') if where else ''}{detail}"
        )
    return out


def _liveness(records, t0):
    end = next((r for r in records if r.get("phase") == "run_end"), None)
    if end is not None:
        if end.get("ok"):
            return "ok", f"completed in {end.get('t', t0) - t0:.2f}s"
        detail = end.get("error_detail", end.get("error", ""))
        return "error", f"failed ({end.get('error', '?')}): {detail}"
    # no run_end: the process died or hung. Heartbeats disambiguate.
    beats = [r for r in records if r.get("phase") == "heartbeat"]
    others = [r for r in records if r.get("phase") not in ("heartbeat",)]
    last_t = max((r.get("t", t0) for r in others), default=t0)
    if beats and beats[-1].get("t", t0) > last_t + 1.0:
        busy = beats[-1].get("busy", "?")
        return "HUNG", (
            f"no run_end, but heartbeats continued {beats[-1]['t'] - last_t:.1f}s "
            f"past the last phase record (last busy: {busy}) — the process "
            "was alive but stuck"
        )
    return "DEAD", (
        "no run_end and no trailing heartbeats — the process was killed "
        "(preemption / OOM-kill) or crashed without cleanup"
    )


def _heartbeat_summary(records, t0):
    beats = [r for r in records if r.get("phase") == "heartbeat"]
    if not beats:
        return ["  (heartbeat disabled)"]
    ts = [r.get("t", t0) for r in beats]
    gaps = [b - a for a, b in zip(ts, ts[1:])]
    rss = [r["rss_mb"] for r in beats if "rss_mb" in r]
    line = (f"  {len(beats)} beats, last +{ts[-1] - t0:.2f}s,"
            f" max gap {max(gaps):.2f}s" if gaps else
            f"  {len(beats)} beat(s)")
    if rss:
        line += f", peak RSS {max(rss):.0f} MiB"
    return [line]


def _fleet_trace_section(records, max_traces: int = 4):
    """Cross-process trace timelines (ISSUE 11): the ``trace_stitch``
    join rendered inline — complete per-delta timelines first (each with
    its COMPLETE/partial verdict), then the failover epoch-fence
    sequence. Empty list when no record carries a delta or failover
    trace; records from a single-process stream render with their one
    shard name, a merged ``--obs-dir`` view attributes every line to the
    emitting process."""
    recs = [dict(r) for r in records if r.get("trace_id") is not None
            or r.get("phase") in trace_stitch._FAILOVER_PHASES]
    if not recs:
        return []
    for r in recs:
        r.setdefault("_src", "this-process")
    traces = trace_stitch.stitch(recs)
    deltas = trace_stitch.delta_traces(traces)
    lines: list = []
    complete = sorted(
        tid for tid, (_, st) in deltas.items() if all(st.values())
    )
    if deltas:
        lines.append(
            f"complete per-delta timelines: {len(complete)}/{len(deltas)}"
        )
        ordered = complete + [t for t in deltas if t not in set(complete)]
        for tid in ordered[:max_traces]:
            trecs, stages = deltas[tid]
            lines.extend(trace_stitch.render_trace(tid, trecs, stages))
        if len(deltas) > max_traces:
            lines.append(
                f"({len(deltas) - max_traces} more delta trace(s); "
                "tools/trace_stitch.py renders them all)"
            )
    lines.extend(trace_stitch.failover_section(recs))
    return lines


def build_report(
    records, source: str = "", bad_lines: int = 0,
    roofline_min_frac: float = 0.5,
) -> str:
    """Render one run's records (already filtered to a single run_id)."""
    start = next((r for r in records if r.get("phase") == "run_start"), None)
    t0 = records[0].get("t", 0.0) if records else 0.0
    run_id = records[0].get("run_id", "?") if records else "?"
    unknown = sum(
        1 for r in records
        if any("unknown phase" in p for p in validate_record(r))
    )
    status, verdict = _liveness(records, t0)
    import time as _time

    started = _time.strftime("%Y-%m-%d %H:%M:%S UTC", _time.gmtime(t0))
    lines = ["== graphmine_tpu run report =="]
    if source:
        lines.append(f"source: {source}")
    lines.append(f"run_id: {run_id}    started: {started}")
    if start is not None:
        cfgbits = "  ".join(
            f"{k}={start[k]}" for k in
            ("backend", "schedule", "community_method", "max_iter", "pid")
            if k in start
        )
        lines.append(f"config: {cfgbits}")
        lines.append(f"data:   {start.get('data_path', '?')}")
    lines.append(f"status: {status} — {verdict}")
    note = []
    if bad_lines:
        note.append(f"{bad_lines} unparseable line(s)")
    if unknown:
        note.append(f"{unknown} unknown-schema record(s)")
    lines.append(
        f"records: {len(records)}" + (f"  ({', '.join(note)})" if note else "")
    )
    lines.append("")
    lines.append("-- phase waterfall --")
    lines.extend(_phase_waterfall(records, t0))
    lines.append("")
    lines.append("-- lpa supersteps --")
    lines.extend(_superstep_table(records))
    lines.append("")
    lines.append("-- superstep telemetry (load imbalance) --")
    lines.extend(_telemetry_table(records))
    roofline = _roofline_section(records, roofline_min_frac)
    if roofline:  # pre-ISSUE-12 streams carry no superstep_timing
        lines.append("")
        lines.append("-- roofline (achieved vs cost model) --")
        lines.extend(roofline)
    device = _device_section(records)
    if device:  # a profile_dir capture and/or the run's compiles
        lines.append("")
        lines.append("-- device timeline (scopes / idle / compiles) --")
        lines.extend(device)
    memory = _memory_section(records, t0)
    if memory:  # pre-ISSUE-14 streams carry no memory_watermark
        lines.append("")
        lines.append("-- memory (predicted vs peak) --")
        lines.extend(memory)
    serving = _serving_table(records, t0)
    if serving:  # serving is opt-in; batch-only streams skip the section
        lines.append("")
        lines.append("-- serving (snapshots / deltas / queries) --")
        lines.extend(serving)
    slo = _slo_section(records, t0)
    if slo:
        lines.append("")
        lines.append("-- serving SLO (latency / errors / repair debt) --")
        lines.extend(slo)
    fleet = _fleet_section(records, t0)
    if fleet:
        lines.append("")
        lines.append("-- fleet (replica health / breakers / routing) --")
        lines.extend(fleet)
    qual = _quality_section(records, t0)
    if qual:
        lines.append("")
        lines.append("-- quality & alerts (result drift / canary) --")
        lines.extend(qual)
    tenants = _tenant_section(records, t0)
    if tenants:  # single-tenant streams carry no tenant stamps
        lines.append("")
        lines.append("-- tenants (per-namespace serving rollup) --")
        lines.extend(tenants)
    ftrace = _fleet_trace_section(records)
    if ftrace:
        lines.append("")
        lines.append("-- fleet traces (cross-process timelines) --")
        lines.extend(ftrace)
    failover = _failover_section(records, t0)
    if failover:
        lines.append("")
        lines.append("-- writer failover (WAL / promotion / fencing) --")
        lines.extend(failover)
    shards = _writer_shards_section(records, t0)
    if shards:
        lines.append("")
        lines.append(
            "-- writer shards (ranges / epochs / per-range failover) --"
        )
        lines.extend(shards)
    lines.append("")
    lines.append("-- recovery timeline --")
    lines.extend(_recovery_timeline(records, t0))
    lines.append("")
    lines.append("-- heartbeats --")
    lines.extend(_heartbeat_summary(records, t0))
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("metrics", help="metrics JSONL (--metrics-out of a "
                    "run) or a fleet --obs-dir directory of shards")
    ap.add_argument("--run-id", default=None,
                    help="report this run (default: the most recent)")
    ap.add_argument("--out", default=None, help="write the report here "
                    "instead of stdout")
    ap.add_argument("--lenient", action="store_true",
                    help="note schema/trace-stamping violations instead "
                    "of failing with exit code 3")
    ap.add_argument("--roofline-min-frac", type=float, default=0.5,
                    help="flag superstep_timing windows whose achieved "
                    "throughput is below this fraction of the cost "
                    "model (default 0.5)")
    args = ap.parse_args(argv)
    if os.path.isdir(args.metrics):
        # A fleet --obs-dir: merge every process shard into ONE report
        # view (each record keeps its shard under _src, so the fleet-
        # trace section attributes lines to the emitting process). The
        # fleet is one logical run — per-process run_ids would each
        # select a sliver, so run splitting is skipped.
        records, bad, dir_problems = trace_stitch.load_shards(
            [args.metrics]
        )
        if not records:
            print(
                f"obs_report: no records in {args.metrics}",
                file=sys.stderr,
            )
            return 2
        runs, order = {"fleet": records}, ["fleet"]
        rid = "fleet"
    else:
        dir_problems = None
        try:
            records, bad = load_records(args.metrics)
        except OSError as e:
            print(
                f"obs_report: cannot read {args.metrics}: {e}",
                file=sys.stderr,
            )
            return 2
        if not records:
            print(
                f"obs_report: no records in {args.metrics}",
                file=sys.stderr,
            )
            return 2
        runs, order = split_runs(records)
        rid = args.run_id or order[-1]
    if rid not in runs:
        print(
            f"obs_report: run_id {rid!r} not in {args.metrics} "
            f"(have: {', '.join(order)})", file=sys.stderr,
        )
        return 2
    report = build_report(
        runs[rid], source=args.metrics, bad_lines=bad,
        roofline_min_frac=args.roofline_min_frac,
    )
    if len(order) > 1:
        report += (f"\n({len(order)} runs in this file: "
                   + ", ".join(order) + ")\n")
    if args.out:
        with open(args.out, "w") as f:
            f.write(report)
    else:
        sys.stdout.write(report)
    # The post-e2e gate (ISSUE 11 satellite): a stream whose selected
    # run carries unknown phases, records missing required keys, or
    # HALF-STAMPED trace identity (some of run/trace/span ids, not all —
    # those records silently fall out of every timeline join) fails
    # loudly so schema rot can't accumulate between e2e runs.
    # Directory mode reuses the violations load_shards already computed
    # ("shard:line: problem" — _src-stripped there); a single file runs
    # the shared schema sweep once here.
    problems = (
        dir_problems if dir_problems is not None
        else validate_records(runs[rid])
    )
    if problems:
        print(
            f"obs_report: {len(problems)} schema/trace-stamping "
            f"violation(s) in run {rid!r}:", file=sys.stderr,
        )
        for p in problems[:20]:
            print(f"  {p}", file=sys.stderr)
        if len(problems) > 20:
            print(f"  ... and {len(problems) - 20} more", file=sys.stderr)
        if not args.lenient:
            return 3
    # The quality CI gate (ISSUE 13): a stream that ENDS with a firing
    # page-severity alert (the canary scorer-regression rule is the
    # built-in page) fails with exit 4 — distinct from the schema exit 3
    # so CI can tell "the telemetry rotted" from "the scorer regressed".
    # --lenient downgrades both.
    firing = gating_alerts(runs[rid])
    if firing:
        print(
            f"obs_report: {len(firing)} page-severity alert(s) still "
            f"firing at end of stream: {', '.join(firing)}",
            file=sys.stderr,
        )
        if not args.lenient:
            return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
