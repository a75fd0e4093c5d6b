"""The least bytes one PageRank iteration needs, from its shapes. It counts
the work, not the implementation: gathered rows, a ``segment_sum`` or a
hand-written kernel are read against the same yardstick. The peaks and the
share's arithmetic stay ``roofline.py``'s."""

from __future__ import annotations


def pagerank_iteration_min_bytes(num_vertices: int, num_messages: int) -> int:
    """One synchronous PageRank iteration cannot move less than: every
    message's sender index read (int32) and that sender's contribution
    gathered (float32); every vertex's rank and inverse out-degree read and
    its new rank written (float32). The receiver grouping is free in this
    count, as in ``roofline.lpa_superstep_min_bytes``, and so are the sums:
    the iteration is bound by memory traffic, not arithmetic."""
    return 4 * (2 * int(num_messages) + 3 * int(num_vertices))
