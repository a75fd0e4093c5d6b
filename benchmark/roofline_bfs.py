"""The least bytes one exact BFS job needs, from its shapes. It counts the
work, not the implementation: a full-width relaxation, carried rows behind
a frontier, a bottom-up level or a bit-map frontier are read against the
same yardstick. The peaks and the share's arithmetic stay ``roofline.py``'s."""

from __future__ import annotations


def bfs_job_min_bytes(num_vertices: int, num_messages: int) -> int:
    """One whole breadth-first search cannot move less than: every
    neighbour id read once (int32 a message: each edge is looked along once
    from each end), and every vertex's depth written once and read once
    (int32 each). The levels are free in this count, and so is finding the
    frontier: whatever the search does a level, the job as a whole reads
    the adjacency once. It reads well under 1 % on a chip whose gather is
    bound by issue and not by bytes, and does not move with the
    implementation."""
    return 4 * int(num_messages) + 8 * int(num_vertices)


def bfs_level_share_of_job_min_bytes(num_vertices: int, num_messages: int,
                                     levels: int) -> float:
    """:func:`bfs_job_min_bytes` spread evenly over the job's ``levels``,
    for the reader ``roofline``, which divides the device-busy seconds by
    a count of calls that it reads from a fact (``calls_per_job``) and has
    no fact that reads 1: with ``calls_per_job: iterations`` the share is
    (job bytes / levels) over (busy seconds / levels), the whole job's
    bytes over the whole job's device-busy seconds."""
    return bfs_job_min_bytes(num_vertices, num_messages) / max(int(levels), 1)
