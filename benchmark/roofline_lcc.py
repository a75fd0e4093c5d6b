"""The least bytes one exact LCC pass needs, from its shapes. It counts the
work, not the implementation: a wedge list, bit rows or a matrix product are
read against the same yardstick. The peaks and the share's arithmetic stay
``roofline.py``'s."""

from __future__ import annotations


def lcc_min_bytes(num_vertices: int, num_messages: int) -> int:
    """One LCC pass cannot move less than: every neighbour list read once
    each way (int32 a message), every vertex's degree read and its
    coefficient written (int32, float32). Closing the wedges is free in
    this count, as the receiver grouping is in ``roofline.
    lpa_superstep_min_bytes``, so the share reads thousandths of a percent:
    LCC is bound by its wedges (7.2e9 pairs on graph500-22 against 128 M
    messages), not by these bytes."""
    return 4 * (int(num_messages) + 2 * int(num_vertices))
