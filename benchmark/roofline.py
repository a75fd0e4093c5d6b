"""The least work a kernel needs, from its shapes, and the chip's published
peaks. Kept with the benchmark so that no change to the program moves the
yardstick. A device that ``peaks.json`` does not list is an error."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in peaks.json; "
            f"have {sorted(table)}"
        )
    return table[device_kind]


def lpa_superstep_min_bytes(num_vertices: int, num_messages: int) -> int:
    """One synchronous label-propagation superstep cannot move less than:
    every message's sender index read (int32), every message's label
    gathered (int32), every vertex's new label written (int32). The
    receiver grouping is free in this count (messages sorted by receiver
    need only the V+1 offsets, left out), and so is the mode itself: the
    superstep is bound by memory traffic, not arithmetic."""
    return 4 * (2 * int(num_messages) + int(num_vertices))


def roofline_share_percent(min_bytes: float, device_seconds: float,
                           device_kind: str) -> float:
    """100 x (least seconds the chip's HBM could take) / (device seconds)."""
    least_seconds = min_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_seconds / device_seconds
