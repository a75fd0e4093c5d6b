"""From a profiler trace to numbers: device busy and idle seconds, the
device operations that took most time, and the longest idle gaps named by
what the host was doing in them.

Everything below ``read_xplane`` works on plain ``(name, start_s, end_s)``
tuples, so the arithmetic is tested on hand-made lists. ``read_xplane``
is the only part that knows the profiler's file.
"""

from __future__ import annotations

import glob
import os

Event = tuple  # (name, start_s, end_s)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping (start, end) pairs."""
    out: list[list[float]] = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def leaf_events(events) -> list[Event]:
    """Events of one timeline that contain no other event. A ``while`` or a
    ``call`` on the device spans its whole body, idle time between the
    body's operations included: counting it as busy would hide that time,
    so only what runs innermost counts. An event that ends where it starts
    holds no time: it is neither a leaf nor a child, so the operation
    around it stays a leaf."""
    ordered = sorted((e for e in events if e[2] > e[1]),
                     key=lambda e: (e[1], -(e[2] - e[1])))
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


def clip(events, window) -> list[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def busy_and_gaps(events, window):
    """Seconds in which some event ran inside ``window``, and the idle
    stretches between them, longest first, as (start, end)."""
    lo, hi = window
    merged = union((s, e) for _, s, e in clip(events, window))
    busy = sum(e - s for s, e in merged)
    edges = [lo] + [t for pair in merged for t in pair] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return busy, sorted(gaps, key=lambda g: g[0] - g[1])


def top_ops(events, k: int) -> list[list]:
    """[name, seconds] of the k names with most summed time."""
    total: dict[str, float] = {}
    for name, start, end in events:
        total[name] = total.get(name, 0.0) + (end - start)
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[name, seconds] for name, seconds in ranked[:k]]


def label_gap(gap, host_events) -> str:
    """What the host was doing in an idle gap: the shortest host span that
    covers at least half of it (the innermost of nested spans), else the
    span overlapping it most, else ``untraced``."""
    lo, hi = gap
    best_cover, best_overlap = None, None
    for name, start, end in host_events:
        overlap = min(end, hi) - max(start, lo)
        if overlap <= 0:
            continue
        if overlap >= 0.5 * (hi - lo):
            if best_cover is None or end - start < best_cover[0]:
                best_cover = (end - start, name)
        if best_overlap is None or overlap > best_overlap[0]:
            best_overlap = (overlap, name)
    if best_cover is not None:
        return best_cover[1]
    return best_overlap[1] if best_overlap is not None else "untraced"


def reduce_events(device_lines: dict, host_events, window,
                  k_ops: int = 10, k_gaps: int = 5) -> dict:
    """``device_lines``: one event list per device, operations possibly
    nested. Busy seconds are averaged over the devices; operations are
    summed over them; gaps are those of the device that idled longest."""
    lo, hi = window
    busy, all_leaves, gaps = [], [], []
    for events in device_lines.values():
        leaves = clip(leaf_events(events), window)
        b, g = busy_and_gaps(leaves, window)
        busy.append(b)
        all_leaves.extend(leaves)
        if not gaps or b == min(busy):
            gaps = g
    busy_s = sum(busy) / len(busy) if busy else 0.0
    return {
        "busy_s": busy_s,
        "window_s": hi - lo,
        "devices": len(busy),
        "device_ops": top_ops(all_leaves, k_ops),
        "idle_gaps": [[label_gap(g, host_events), g[1] - g[0]]
                      for g in gaps[:k_gaps]],
    }


# -- the profiler's file ----------------------------------------------------

WINDOW_ANNOTATION = "bench_window"
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE = "XLA Ops"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_op_name(name: str) -> str:
    """``%fusion.570 = s32[128310830]{0:T(1024)} fusion(...)`` becomes
    ``fusion.570 s32[128310830]`` (a tuple result gives its first shape):
    the trace names a device operation by its whole HLO line, which no
    ledger line should carry."""
    op, _, rest = name.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return (op.lstrip("%") + " " + shape).strip()[:120]


def read_xplane(path: str):
    """``(device_lines, host_events, window)`` of one trace file. Device
    operations are the ``XLA Ops`` line of each ``/device:TPU:n`` plane;
    host events are every span of the ``/host:CPU`` plane with a duration;
    the window is the harness's own ``bench_window`` annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_lines, host_events, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(_DEVICE_PLANE):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    device_lines[plane.name] = [
                        (short_op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    span = (ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                    if ev.name == WINDOW_ANNOTATION:
                        window = (span[1], span[2])
                    else:
                        host_events.append(span)
    if window is None:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in {path}")
    return device_lines, host_events, window
