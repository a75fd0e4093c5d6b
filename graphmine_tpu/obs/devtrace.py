"""From one ``profile_dir`` capture to records: device seconds by named
scope, by compiled program and by the program span that launched them,
and busy/idle seconds per chapter.

Everything above :func:`read_xplane` is pure functions over
``(name, start_s, end_s, stats)`` tuples, tested on hand-made lists.
:func:`read_xplane` is the only part that knows the profiler's file.

It reads the ``.xplane.pb`` itself, with a protobuf wire reader of a few
dozen lines and no import: the profiler keeps an operation's ``op_name``
in the statistic ``tf_op`` of the operation's *event metadata* (seen in
a v5e trace of jax 0.9.0: ``jit(f)/outer/while/body/closed_call/inner/
gather:``), and ``jax.profiler.ProfileData`` hands out an event's own
statistics only (``device_offset_ps``, ``device_duration_ps``), not its
metadata's. So this module stays free of jax altogether.

A fusion carries the ``op_name`` of its root instruction: the seconds of
a fusion that the compiler built across two scopes are all booked to the
root's scope.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct

Event = tuple  # (name, start_s, end_s, stats: dict)

OP_NAME_STAT = "tf_op"
UNSCOPED = "unscoped"
_DEVICE_PLANE = "/device:TPU:"
_HOST_PLANE = "/host:CPU"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


# -- the reduction -----------------------------------------------------------


def scope_of(op_name: str, registered) -> str:
    """The first two ``registered`` levels of an ``op_name``:
    ``jit(f)/lpa_bucketed/while/body/row_gather/w8/gather:`` gives
    ``lpa_bucketed/row_gather``. The last component is the primitive (and
    after a colon its type), never a scope; what jit, scan and the other
    transforms put between the levels is not registered and drops out.
    No registered level gives ``unscoped``."""
    parts = op_name.split(":", 1)[0].split("/")[:-1]
    levels = [p for p in parts if p in registered][:2]
    return "/".join(levels) if levels else UNSCOPED


def leaf_events(events) -> list[Event]:
    """Events of one timeline that contain no other event: a ``while``
    spans its whole body, and counting both would count the body twice."""
    ordered = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    leaves, stack = [], []  # stack of [event, has_child]
    for ev in ordered:
        while stack and stack[-1][0][2] <= ev[1]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and ev[2] <= stack[-1][0][2]:
            stack[-1][1] = True
        stack.append([ev, False])
    leaves.extend(ev for ev, has_child in stack if not has_child)
    return sorted(leaves, key=lambda e: e[1])


def innermost_span(spans, t: float) -> str:
    """Name of the shortest span whose interval contains ``t``; ``""``
    when none does."""
    best = None
    for name, start, end, _ in spans:
        if start <= t <= end and (best is None or end - start < best[0]):
            best = (end - start, name)
    return best[1] if best else ""


def containing(events, starts, t: float):
    """The event, of a list sorted by start with ``starts`` its start
    times, whose interval contains ``t``; ``None`` when none does."""
    i = bisect.bisect_right(starts, t) - 1
    return events[i] if i >= 0 and t <= events[i][2] else None


def reduce_capture(device_ops: dict, modules: dict, host_spans,
                   registered) -> dict:
    """``device_ops`` / ``modules``: per device plane, the events of its
    ``XLA Ops`` / ``XLA Modules`` line. ``host_spans``: the program's own
    spans on the host plane, named by their paths. Leaf operations are
    grouped by the program that contains them, by their scope, and by the
    innermost span open while their program ran. Gives the
    ``device_scope`` rows (seconds summed over devices), the
    ``device_idle`` rows (seconds averaged over devices, one row per
    chapter: a span one level under the root) and the busy seconds the
    rows of the first add up to."""
    groups: dict[tuple, list] = {}
    per_device_leaves = []
    for plane, events in device_ops.items():
        leaves = leaf_events(events)
        per_device_leaves.append(leaves)
        mods = sorted(modules.get(plane, ()), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        # A program is booked to the span open at the middle of its run:
        # the device's clock ran about a millisecond ahead of the host's
        # in the v5e trace this was written against, so the first
        # operation of a program can seem to start before the span that
        # launched it; the middle of a program that its span waits for
        # lies inside that span either way.
        span_of = [innermost_span(host_spans, (m[1] + m[2]) / 2) for m in mods]
        for name, start, end, stats in leaves:
            mod = containing(mods, starts, start)
            key = (
                _PROGRAM_ID.sub("", mod[0]) if mod else "",
                scope_of(stats.get(OP_NAME_STAT) or "", registered),
                span_of[bisect.bisect_right(starts, start) - 1] if mod
                else innermost_span(host_spans, (start + end) / 2),
            )
            row = groups.setdefault(key, [0.0, 0])
            row[0] += end - start
            row[1] += 1
    scopes = [
        {"module": m, "scope": s, "stage_path": p,
         "device_seconds": sec, "events": n}
        for (m, s, p), (sec, n) in sorted(
            groups.items(), key=lambda kv: -kv[1][0])
    ]
    idle = []
    n_dev = max(len(per_device_leaves), 1)
    for name, start, end, _ in sorted(host_spans, key=lambda e: e[1]):
        if name.count("/") != 1 or not per_device_leaves:
            continue  # not a chapter, or no device in the capture
        busy = sum(
            min(e, end) - max(s, start)
            for leaves in per_device_leaves for _, s, e, _ in leaves
            if min(e, end) > max(s, start)
        ) / n_dev
        idle.append({"chapter_path": name, "busy_seconds": busy,
                     "idle_seconds": (end - start) - busy})
    return {
        "scopes": scopes, "idle": idle, "devices": len(per_device_leaves),
        "busy_seconds": sum(r["device_seconds"] for r in scopes),
    }


# -- the profiler's file -----------------------------------------------------


def newest_xplane(profile_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def _fields(buf, pos: int, end: int):
    """``(field number, value)`` of one protobuf message in
    ``buf[pos:end]``: an int for a varint, ``(start, end)`` for a
    length-delimited field, the raw 8 bytes for a fixed64; fixed32 fields
    (the format has none that matter here) are skipped."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if wire == 2:
                yield key >> 3, (pos, pos + val)
                pos += val
            else:
                yield key >> 3, val
        elif wire == 1:
            yield key >> 3, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names) -> tuple:
    """One XStat as ``(name, value)``."""
    name, value = "", None
    for field, val in _fields(buf, *span):
        if field == 1:
            name = stat_names.get(val, "")
        elif field == 2:
            value = struct.unpack("<d", val)[0]
        elif field in (3, 4):
            value = val
        elif field == 5:
            value = _text(buf, val)
        elif field == 7:  # a string kept once, among the stat names
            value = stat_names.get(val, "")
    return name, value


def _plane(buf, span, want_lines, want_name, short_names: bool) -> tuple:
    """``(plane name, {line name: [Event]})`` of one XPlane; only lines
    ``want_lines`` accepts and, in them, events whose metadata name
    ``want_name`` accepts are built. ``short_names``: name an event by
    its display name where it has one (a device operation's metadata
    name is its whole HLO line, its display name ``fusion.7``; a host
    span's display name is cut at a colon, ``rung:primary`` to
    ``primary``, so those keep their name)."""
    name, lines, metadata, stat_meta = "", [], [], []
    for field, val in _fields(buf, *span):
        if field == 2:
            name = _text(buf, val)
        elif field == 3:
            lines.append(val)
        elif field == 4:
            metadata.append(val)
        elif field == 5:
            stat_meta.append(val)
    stat_names = {}
    for entry in stat_meta:  # map<int64, XStatMetadata>
        for field, val in _fields(buf, *entry):
            if field == 2:
                meta = dict(_fields(buf, *val))
                stat_names[meta.get(1, 0)] = (
                    _text(buf, meta[2]) if 2 in meta else "")
    events_meta = {}
    for entry in metadata:  # map<int64, XEventMetadata>
        for field, val in _fields(buf, *entry):
            if field != 2:
                continue
            ident, ev_name, display, stats = 0, "", "", {}
            for f2, v2 in _fields(buf, *val):
                if f2 == 1:
                    ident = v2
                elif f2 == 2:
                    ev_name = _text(buf, v2)
                elif f2 == 4:
                    display = _text(buf, v2)
                elif f2 == 5:
                    k, v = _stat(buf, v2, stat_names)
                    stats[k] = v
            if want_name(ev_name):
                events_meta[ident] = (
                    display if short_names and display else ev_name, stats)
    out = {}
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for field, val in _fields(buf, *line):
            if field == 2:
                line_name = _text(buf, val)
            elif field == 3:
                t0_ns = val
            elif field == 4:
                events.append(val)
        if not want_lines(line_name):
            continue
        built = out.setdefault(line_name, [])
        for ev in events:
            ident = offset_ps = duration_ps = 0
            for field, val in _fields(buf, *ev):
                if field == 1:
                    ident = val
                elif field == 2:
                    offset_ps = val
                elif field == 3:
                    duration_ps = val
            meta = events_meta.get(ident)
            if meta is None or duration_ps <= 0:
                continue
            start = t0_ns * 1e-9 + offset_ps * 1e-12
            built.append((meta[0], start, start + duration_ps * 1e-12, meta[1]))
    return name, out


def read_xplane(path: str, span_prefix: str):
    """``(device_ops, modules, host_spans)`` of one trace file, as
    :func:`reduce_capture` takes them. Host spans are the host plane's
    events whose name is ``span_prefix`` or lies under it: the paths that
    ``MetricsSink.span`` annotates the profiler's timeline with."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    under = span_prefix + "/"
    device_ops, modules, host_spans = {}, {}, []
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        # the plane's name is its second field: peek before building it
        head = next((v for f, v in _fields(buf, *span) if f == 2), None)
        plane_name = _text(buf, head) if head else ""
        if plane_name.startswith(_DEVICE_PLANE):
            _, lines = _plane(
                buf, span, lambda n: n in (_OPS_LINE, _MODULES_LINE),
                lambda n: True, short_names=True)
            device_ops[plane_name] = lines.get(_OPS_LINE, [])
            modules[plane_name] = lines.get(_MODULES_LINE, [])
        elif plane_name == _HOST_PLANE:
            _, lines = _plane(
                buf, span, lambda n: True,
                lambda n: n == span_prefix or n.startswith(under),
                short_names=False)
            for events in lines.values():
                host_spans.extend(events)
    return device_ops, modules, host_spans
