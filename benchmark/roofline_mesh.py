"""The least work one chip of a mesh needs for a superstep that is shared
by vertex range, from shapes. Kept with the benchmark beside
``roofline.py``, whose table of published peaks it reads."""

from __future__ import annotations

from roofline import roofline_share_percent


def lpa_superstep_min_bytes_per_chip(num_vertices: int, num_messages: int,
                                     chips: int) -> float:
    """One of ``chips`` chips that share a synchronous label-propagation
    superstep by vertex range cannot move less than its share of
    ``roofline.lpa_superstep_min_bytes`` (a sender index read and a label
    gathered per message it receives, a new label written per vertex it
    owns: 4 (2 M + V) / D) plus the new labels of the other chips'
    vertices, which every chip has to be handed before the next superstep
    can gather from them (written once on arrival: 4 V (D - 1) / D). The
    bytes on the interconnect are not HBM bytes and are left out, as is
    the mode itself."""
    d = int(chips)
    own = 4 * (2 * int(num_messages) + int(num_vertices)) / d
    received = 4 * int(num_vertices) * (d - 1) / d
    return own + received


def share_percent(min_bytes_per_chip: float, busy_seconds_per_chip: float,
                  device_kind: str) -> float:
    """100 x (least seconds one chip's HBM could take) / (mean device-busy
    seconds of one chip): each chip against its own peak, so four chips'
    work is never set against one chip's."""
    return roofline_share_percent(min_bytes_per_chip, busy_seconds_per_chip,
                                  device_kind)
