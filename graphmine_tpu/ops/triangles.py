"""Triangle counting and local clustering coefficients.

Engine-surface parity with GraphFrames' ``triangleCount`` (exposed on the
object built at ``Graphframes.py:78``; semantics there: direction and
duplicate edges ignored — triangles of the underlying simple undirected
graph). Also feeds the clustering-coefficient feature of the LOF outlier
scorer (SURVEY §7.5) and is LDBC Graphalytics' LCC (``benchmark/algorithms/
lcc.py``, cell ``lcc-g500-22``).

TPU design — a plan in place of a wedge list. No wedge is ever listed on
the host, and none is looked up one by one on the device (an element
gather issues 69 M indices a second on a v5e, a binary search of eight
steps answers 7.7 M pairs a second: 7.2e9 pairs of graph500-22 would take a
quarter of an hour). Rows are fetched instead, and compared whole:

1. host, once per graph (:func:`_lcc_plan`, O(E log E), cached as the
   superstep plans are): simple undirected edges, vertices renamed by
   their (degree, id) rank, every edge oriented from the lower rank to the
   higher, the oriented CSR sorted by rank. Every triangle ``u < v < w``
   is then found once, from its lowest corner ``u``, as a pair ``(v, w)``
   of ``u``'s row that is an edge. The top ``K`` ranks are the *core*
   (``K`` a power of two, its bitmap at most 32 B an edge and 2 GiB:
   131,072 vertices at graph500-22, where it holds 98.6 % of the pairs).
2. device, stage ``lcc_core``: the core's symmetric adjacency as bit rows
   (``[K, K/32]`` uint32, built on the device from the core's edges and kept
   with the plan). A centre's neighbours in the core are the end of its row
   of the CSR; the plan lays them out once, a padded row of ranks a centre
   in the order the class programs walk (:func:`_build_plan`), so a job
   reads a block's slab with one slice and cuts no window of the CSR (a
   gather of windows of width 6 or more compiles to a loop of one window a
   trip, 1.16 us each on a v5e: 1.14 M trips, 1.32 s of a 6.6 s job at
   graph500-22). For a centre ``u`` anywhere, ``b_u`` is the bit row of its
   neighbours in the core; for each such neighbour ``v`` the row ``A[v]``
   is fetched whole and ``popcount(A[v] & b_u)`` is the number of ``u``'s
   core neighbours adjacent to ``v``: every triangle of ``u`` with its other
   two corners in the core, credited to ``v`` directly and to ``u`` by half
   the row's sum. One row fetch an oriented edge into the core, 16 KB each,
   no lookup.
3. device, stage ``lcc_tail``: the triangles whose middle corner ``v`` is
   outside the core. For each oriented edge ``(u, v)`` outside it, ``u``'s
   row and ``v``'s own row are compared all against all; a match is a
   triangle, credited to its three corners. The rows of the vertices these
   edges join are kept padded to whole tiles of 128 in a table of their own
   and fetched whole (:func:`_tail_table_class`). The third corner is a
   slot of ``v``'s row, and the edges that share a middle share that row:
   the plan puts a middle's edges side by side (:func:`_runs_by_middle`),
   a block sums their matches slot by slot and the row is credited once a
   middle, not once an edge (a scatter of a tenth of the slots at
   graph500-22, where a middle of width 96 lies under 17 edges). Where the
   table would pass 2 GiB the two rows are cut out of the CSR as windows
   instead (:func:`_tail_class`: 1.3 us a window on a v5e, whatever its
   width; credited through ``u``'s window, once an edge).
4. counts are two uint32 words a vertex (:func:`_add64`: a hub of degree
   163,352 may close 1.3e10 pairs), and the coefficient is one float32
   division of them.

Centres and edges are grouped by row width on a half-octave ladder, each
class one compiled program looping over blocks of bounded size; a block's
inner loops run to its longest row, the rows of a class being sorted by
length. ``ops/ktruss.py`` keeps the host wedge list (:func:`_oriented_csr`):
it needs the edge ids of every triangle's three sides.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphmine_tpu.graph.container import (
    Graph, simple_undirected_edges, sorted_pair_keys, split_pair_keys,
)
from graphmine_tpu.obs.spans import stage_span


# -- the plan -----------------------------------------------------------------

_CORE_MAX = 1 << 17         # core vertices at most: bit rows of 16 KB, 2 GiB of them
_CORE_BITS_PER_EDGE = 256   # the core's bitmap may take 32 B an edge
_CORE_BLOCK_ROWS = 1 << 16  # bit rows one core block fetches (centres x width)
_TAIL_BLOCK_COMPARES = 1 << 26  # comparisons one tail block makes (edges x width^2)
_TAIL_TABLE_MAX = 1 << 31   # bytes the tail's table of padded rows may take


def _ladder(longest: int) -> np.ndarray:
    """Row widths 2, 3, 4, 6, 8, 12, ... (half octaves) up to ``longest``.
    Coarser than the supersteps' 1.10x ladder on purpose: a block's loops
    stop at its longest row, so padding costs the elementwise passes alone,
    and every width is a program to compile."""
    widths = [2, 3]
    while widths[-1] < longest:
        widths.append(2 * widths[-2])
    return np.asarray(widths, np.int64)


def _core_size(num_edges: int, num_vertices: int) -> int:
    """Vertices in the core, by the graph: the smallest power of two whose
    bitmap holds ``_CORE_BITS_PER_EDGE`` bits an edge, at most ``_CORE_MAX``
    and no more than cover the vertex space (32 at least: one word a row)."""
    k = 32
    while (k < _CORE_MAX and k < num_vertices
           and k * k < _CORE_BITS_PER_EDGE * num_edges):
        k *= 2
    return k


def _width_classes(width: np.ndarray):
    """``(ladder width, positions)`` for each width class of the rows whose
    lengths are ``width``, positions in the order given."""
    widths = _ladder(int(width.max()))
    classes = np.searchsorted(widths, width)
    order = np.argsort(classes, kind="stable")
    cuts = np.flatnonzero(np.diff(classes[order])) + 1
    for rows in np.split(order, cuts):
        yield int(widths[classes[rows[0]]]), rows


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


@dataclass
class _LccPlan:
    """What :func:`_lcc_plan` keeps per graph: the rank-ordered oriented CSR
    and the core's bit rows on the device, and per width class the rows
    each compiled program walks (padded to whole blocks): a core class is
    ``(w, nb, blocks, ranks, lens, centres)``, ``ranks`` the centres' core
    neighbours, ``w`` slots a centre, flat ``[blocks * nb * w]``."""

    num_vertices: int
    core_start: int              # ranks from here up are the core
    rank: jax.Array              # int32 [V]: id -> rank
    degree: jax.Array            # int32 [V]: simple undirected degree, by id
    col: jax.Array               # int32 [E + pad]: higher neighbours, by rank
    bits: jax.Array | None       # uint32 [K, K / 32]: the core's adjacency
    tail_table: jax.Array | None = None  # int32 [rows, 128 n]: the tail's rows, padded with -1
    core_classes: list = field(default_factory=list)
    tail_classes: list = field(default_factory=list)
    stats: dict = field(default_factory=dict)


def _blocked(arrays, size: int):
    """Each int32 array padded with zeros to whole blocks of ``size`` and put
    on the device; the number of blocks."""
    n = len(arrays[0])
    blocks = -(-n // size)
    out = []
    for x in arrays:
        padded = np.zeros(blocks * size, np.int32)
        padded[:n] = x
        out.append(jnp.asarray(padded))
    return out, blocks


def _order(keys: np.ndarray) -> np.ndarray:
    """``np.argsort(keys, kind="stable")`` of keys from 0 to under 2**31, by
    one in-place sort of the words ``key << 32 | position`` (a seventh of the
    stable argsort's time at 2.5 M keys)."""
    words = (keys.astype(np.int64) << 32) | np.arange(len(keys))
    words.sort()
    return words & 0xFFFFFFFF


def _runs_by_middle(mid: np.ndarray, ne: int):
    """A tail class's edges put in an order in which the edges of one middle
    are neighbours (a *run*), and the runs dealt over the class's blocks of
    ``ne`` edges so that every block holds a like number of them: the
    middles sorted by their edges, most first, and dealt round the blocks as
    cards are, so each block gets its share of long runs and of short ones.
    A run that straddles a block's end counts in both blocks. One sort of
    the edges (by middle) and two of the middles.

    Returns ``(order, seg, ns, lead)``: the edges' order; for every edge
    slot the number of its run within its block (ascending from 0); the runs
    a block may hold (the fullest block's, rounded up to whole eights, ``ne``
    at most); and where in that order each block's runs begin, ``[blocks,
    ns]``, -1 where a block has fewer."""
    order = _order(mid)
    by_mid = mid[order]
    starts = np.flatnonzero(np.r_[True, by_mid[1:] != by_mid[:-1]])
    edges = np.diff(np.append(starts, len(mid)))
    blocks = -(-len(mid) // ne)
    dealt = _order(edges.max() - edges)
    dealt = dealt[_order(np.arange(len(starts)) % blocks)]
    edges = edges[dealt]
    run = np.repeat(np.arange(len(starts)), edges)  # ascending along the class
    opens = np.cumsum(edges) - edges
    # a run's edges move together: by where the run lay, less where it lies now
    order = order[np.arange(len(mid)) + np.repeat(starts[dealt] - opens, edges)]
    first = run[::ne]  # the run each block opens with
    seg = run - np.repeat(first, ne)[:len(run)]
    held = np.append(run[ne - 1::ne], run[-1])[:blocks] - first + 1
    ns = min(-(-int(held.max()) // 8) * 8, ne)
    slot = np.arange(ns)[None, :]
    at = np.minimum(first[:, None] + slot, len(starts) - 1)
    return order, seg, ns, np.where(slot < held[:, None], opens[at], -1)


def _tail_table_arrays(columns, ne: int):
    """What :func:`_tail_table_class` walks for one width class, from its
    edges' ``columns`` (the table rows of ``low`` and ``mid``, the length of
    ``mid``'s row, ``low``, ``mid``), put in runs by :func:`_runs_by_middle`:
    per edge slot the first four and the edge's run within its block; per
    run slot (``ns`` a block) its middle's table row, the length of it and
    the middle. Returns ``(arrays, blocks, ns)``."""
    order, seg, ns, lead = _runs_by_middle(columns[-1], ne)
    columns = [x[order] for x in columns]
    edges, blocks = _blocked((*columns[:4], seg), ne)
    held = (lead >= 0).ravel()
    lead = np.where(held, lead.ravel(), 0)
    runs, _ = _blocked([x[lead] * held for x in columns[1:3] + columns[4:]], ns)
    return edges + runs, blocks, ns


@partial(jax.jit, static_argnames=("k",))
def _core_bits(row, column, k: int):
    """The core's symmetric adjacency as bit rows ``[k, k / 32]`` from its
    edges (core indices, each edge once): distinct bits, so adding is or."""
    with jax.named_scope("triangles"), jax.named_scope("core_bits"):
        words = k // 32
        one = jnp.uint32(1)
        flat = jnp.zeros((k * words,), jnp.uint32)
        flat = flat.at[row * words + (column >> 5)].add(one << (column & 31).astype(jnp.uint32))
        flat = flat.at[column * words + (row >> 5)].add(one << (row & 31).astype(jnp.uint32))
        return flat.reshape(k, words)


def _build_plan(graph: Graph, simple_edges=None, core_vertices=None) -> _LccPlan:
    """Host side of the exact kernel (the module's note, step 1) and the
    hand-over to the device. ``core_vertices`` overrides the rule of
    :func:`_core_size` (tests: no core, everything core)."""
    v = graph.num_vertices
    a, b = simple_edges or simple_undirected_edges(graph)
    num_edges = len(a)
    degree = (np.bincount(a, minlength=v) + np.bincount(b, minlength=v)).astype(np.int32)
    order = np.lexsort((np.arange(v), degree))
    rank = np.empty(v, np.int32)
    rank[order] = np.arange(v, dtype=np.int32)
    bits = max(int(v - 1).bit_length(), 1)
    keys = sorted_pair_keys(rank[a], rank[b], bits)
    low, col = split_pair_keys(keys, bits)
    del keys
    above = np.bincount(low, minlength=v).astype(np.int64)  # d+ by rank
    ptr = np.zeros(v + 1, np.int64)
    np.cumsum(above, out=ptr[1:])

    k = _core_size(num_edges, v) if core_vertices is None else int(core_vertices)
    core_start = max(v - k, 0) if k else v
    to_core = col >= core_start
    in_core = np.bincount(low[to_core], minlength=v).astype(np.int64)  # by centre

    plan = _LccPlan(num_vertices=v, core_start=core_start,
                    rank=jnp.asarray(rank), degree=jnp.asarray(degree),
                    col=None, bits=None)
    # a window never runs off the end: the widest one fits after the last edge
    # (a class's is a ladder width, the tail table's whole tiles of 128)
    widest = int(above.max(initial=0))
    padded = np.concatenate(
        [col, np.zeros(max(_ladder(widest)[-1], -(-widest // 128) * 128), np.int32)])
    core_slots = slots = 0

    # core classes: centres with two core neighbours or more, longest first
    centres = np.flatnonzero(in_core >= 2)
    centres = centres[np.argsort(-in_core[centres], kind="stable")]
    if len(centres):
        for w, rows in _width_classes(in_core[centres]):
            rows = centres[rows]
            nb = min(int(np.clip(_CORE_BLOCK_ROWS // w, 512, 8192)),
                     _pow2_at_least(len(rows)))
            # a centre's neighbours in the core are the end of its row: a strided
            # view of the CSR's windows, copied out once a plan and not once a job
            ranks = np.lib.stride_tricks.sliding_window_view(padded, w)[
                ptr[rows + 1] - in_core[rows]]
            (ranks,), blocks = _blocked((ranks.ravel(),), nb * w)
            arrays, _ = _blocked((in_core[rows], rows), nb)
            plan.core_classes.append((w, nb, blocks, ranks, *arrays))
            core_slots += blocks * nb * w

    # tail classes: oriented edges (u, v) with v outside the core
    at = np.flatnonzero(~to_core)
    u, mid = low[at], col[at]
    rest = ptr[u + 1] - (at + 1)  # what is left of u's row after v
    keep = (rest > 0) & (above[mid] > 0)
    at, u, mid, rest = at[keep], u[keep], mid[keep], rest[keep]
    compares = table_width = middles = credit_slots = 0
    in_tail = None
    if len(at):
        # the rows of the vertices these edges join, padded to whole tiles of
        # 128, are fetched whole where that table fits; where it does not,
        # the two rows of an edge are cut out of the CSR as windows
        in_tail = np.unique(np.concatenate([u, mid]))
        table_width = -(-int(above[in_tail].max()) // 128) * 128
        if len(in_tail) * table_width * 4 <= _TAIL_TABLE_MAX:
            width = above[mid]  # a class is a width of the middle's row
            columns = (np.searchsorted(in_tail, u), np.searchsorted(in_tail, mid),
                       above[mid], u, mid)
            middles = int(np.count_nonzero(np.bincount(mid, minlength=v)))
        else:
            in_tail, table_width = None, 0
            width = np.maximum(rest, above[mid])
            columns = (at + 1, rest, ptr[mid], above[mid], u, mid)
        for w, rows in _width_classes(width):
            left = table_width or w  # the low vertex's whole row, or a window of it
            ne = min(int(np.clip(_TAIL_BLOCK_COMPARES // (left * w), 8, 1 << 16)),
                     _pow2_at_least(len(rows)))
            if in_tail is None:
                arrays, blocks = _blocked([x[rows] for x in columns], ne)
                ns = ne  # credited through the low vertex's window, once an edge
            else:
                arrays, blocks, ns = _tail_table_arrays([x[rows] for x in columns], ne)
            plan.tail_classes.append((w, ne, blocks, *arrays))
            credit_slots += blocks * ns * w
            slots += blocks * ne * (w + left)
            compares += len(rows) * left * w

    plan.col = jnp.asarray(padded)
    if in_tail is not None:
        plan.tail_table = _tail_rows(
            plan.col, jnp.asarray(ptr[in_tail].astype(np.int32)),
            jnp.asarray(above[in_tail].astype(np.int32)), width=table_width)
    inside = low >= core_start
    core_edges = int(np.count_nonzero(inside))
    if k:
        plan.bits = _core_bits(jnp.asarray(low[inside] - core_start),
                               jnp.asarray(col[inside] - core_start), k=k)
    wedges = int((above * (above - 1) // 2).sum())
    wedges_core = int((in_core * (in_core - 1) // 2).sum())
    held = [x for x in (plan.rank, plan.degree, plan.col, plan.bits, plan.tail_table)
            if x is not None]
    held += [x for c in plan.core_classes + plan.tail_classes for x in c[3:]]
    jax.block_until_ready(held)
    plan.stats = {
        "core_vertices": k, "core_edges": core_edges,
        "classes": len(plan.core_classes) + len(plan.tail_classes),
        "wedges_core": wedges_core, "wedges_tail": wedges - wedges_core,
        "core_rows": int(in_core[centres].sum()), "tail_edges": len(at),
        "tail_compares": compares, "tail_table_rows": 0 if in_tail is None else len(in_tail),
        "tail_middles": middles, "tail_credit_slots": credit_slots,
        "core_slots": core_slots,
        "padded_slots_per_edge": (core_slots + slots) / max(num_edges, 1),
        "resident_bytes": int(sum(x.nbytes for x in held)),
    }
    return plan


_plan_cache: dict = {}


def _lcc_plan(graph: Graph, simple_edges=None):
    """The graph's LCC plan, built once per graph as the superstep plans are
    (``ops/lpa.py:_cached_auto_plan``: keyed by the identity of the graph's
    ``msg_ptr``, evicted with it). Returns ``(plan, build seconds,
    cached)``."""
    key = id(graph.msg_ptr)
    hit = _plan_cache.get(key)
    if hit is not None and hit[0]() is graph.msg_ptr:
        return hit[1], 0.0, True
    from graphmine_tpu.ops.superstep_policy import timed_plan_build

    plan, seconds = timed_plan_build(lambda: _build_plan(graph, simple_edges))
    ref = weakref.ref(graph.msg_ptr, lambda _, k=key: _plan_cache.pop(k, None))
    _plan_cache[key] = (ref, plan)
    return plan, seconds, False


# -- the device side ----------------------------------------------------------


def _add64(lo, hi, part):
    """``(lo, hi) + part`` for uint32 words: per-vertex triangle counts are
    two words, since one does not hold a hub's (a vertex of degree 163,352
    closes up to 1.3e10 pairs). ``part`` is one block's credits, which the
    block sizes keep under 2**32 a vertex."""
    new = lo + part
    return new, hi + (new < part).astype(jnp.uint32)


def _windows(flat, starts, width: int):
    """``flat[start : start + width]`` for every start, as ``[n, width]``:
    one slice a row, not ``width`` lookups."""
    dims = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    return lax.gather(flat, starts[:, None], dims, slice_sizes=(width,),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _block(arrays, i, size: int):
    return [lax.dynamic_slice_in_dim(x, i * size, size) for x in arrays]


@partial(jax.jit, static_argnames=("w", "nb", "core_start"), donate_argnums=(0, 1))
def _core_class(lo, hi, bits, blocks, rows, lens, centres, *,
                w: int, nb: int, core_start: int):
    """One width class of stage ``lcc_core`` (the module's note, step 2):
    ``blocks`` blocks of ``nb`` centres, longest first, whose core
    neighbours are the first ``lens`` ranks of their ``w`` slots of ``rows``
    (laid out by the plan, flat; a block's slab is one slice; what the slots
    past a centre's length hold is never read)."""
    words = bits.shape[1]
    word_ids = jnp.arange(words, dtype=jnp.int32)[None, :]
    slots = jnp.arange(w, dtype=jnp.int32)[None, :]

    def block(i, counts):
        length, centre = _block((lens, centres), i, nb)
        with jax.named_scope("core_bits"):
            ranks = lax.dynamic_slice_in_dim(rows, i * (nb * w), nb * w).reshape(nb, w)
            valid = slots < length[:, None]
            index = jnp.where(valid, ranks - core_start, 0)
            word = index >> 5
            bit = jnp.where(valid, jnp.uint32(1) << (index & 31).astype(jnp.uint32),
                            jnp.uint32(0))
            longest = length[0]  # the class is sorted by length

            def mark(k, b):  # the centre's own bit row, a neighbour at a time
                at = lax.dynamic_slice_in_dim(word, k, 1, axis=1)
                return b | jnp.where(at == word_ids,
                                     lax.dynamic_slice_in_dim(bit, k, 1, axis=1), 0)

            mine = lax.fori_loop(0, longest, mark, jnp.zeros((nb, words), jnp.uint32))
        with jax.named_scope("bit_rows"):

            def probe(k, shared):  # A[v] & b_u, a neighbour at a time
                v = lax.dynamic_slice_in_dim(index, k, 1, axis=1)[:, 0]
                both = lax.population_count(bits[v] & mine).astype(jnp.int32).sum(-1)
                return lax.dynamic_update_slice_in_dim(shared, both[:, None], k, axis=1)

            shared = lax.fori_loop(0, longest, probe, jnp.zeros((nb, w), jnp.int32))
            shared = jnp.where(valid, shared, 0).astype(jnp.uint32)
        with jax.named_scope("credit"):
            part = jnp.zeros(lo.shape, jnp.uint32)
            part = part.at[jnp.where(valid, ranks, 0)].add(shared)
            # every triangle of the centre shows at both of its other corners
            part = part.at[centre].add(shared.sum(-1) >> 1)
            return _add64(*counts, part)

    with jax.named_scope("triangles"):
        return lax.fori_loop(0, blocks, block, (lo, hi))


@partial(jax.jit, static_argnames=("width",))
def _tail_rows(col, starts, lens, width: int):
    """The tail's table: the rows of the CSR that start at ``starts``, cut
    out once per plan and padded with -1 to ``width`` (whole tiles of 128),
    so that a job fetches a row whole, at the rate of a row and not of a
    window (1.3 us a window of the CSR, whatever its width, on a v5e)."""
    with jax.named_scope("triangles"), jax.named_scope("row_compare"):
        slots = jnp.arange(width, dtype=jnp.int32)[None, :]
        return jnp.where(slots < lens[:, None], _windows(col, starts, width), -1)


@partial(jax.jit, static_argnames=("w", "ne"), donate_argnums=(0, 1))
def _tail_table_class(lo, hi, table, blocks, row_low, row_mid, mid_len, low, run,
                      run_row, run_len, middle, *, w: int, ne: int):
    """One width class of stage ``lcc_tail`` on the table of padded rows:
    ``blocks`` blocks of ``ne`` oriented edges ``(low, mid)`` whose rows are
    ``table[row_low]`` and the first ``w`` of ``table[row_mid]``. Whatever
    of ``low``'s row is in ``mid``'s ranks above ``mid``, so the whole row
    stands for what is left of it after ``mid``; a match is credited through
    ``mid``'s row, which starts at slot 0, once a middle: the edges of a
    block lie grouped by their middle in at most ``ns`` runs (``run``: each
    edge slot's run; per run its middle's table row ``run_row``, the length
    of it and the ``middle``), their matches are summed along each run (a
    0/1 product on the MXU, exact in int32; its left operand, the compare
    ``[ns, ne]``, the chip's compiler folds into the product and never
    writes out, which ``tests/test_chip_compile.py`` holds it to) and the
    sum scattered through the middle's row, ``ns x w`` slots a block and
    not ``ne x w``. A run of one edge is summed like any other: the product
    costs what the slots it saves do even at 1.1 edges a run."""
    slots = jnp.arange(w, dtype=jnp.int32)[None, :]
    ns = len(middle) // (len(low) // ne)
    runs = jnp.arange(ns, dtype=jnp.int32)[:, None]

    def block(i, counts):
        at_low, at_mid, length, u, of = _block((row_low, row_mid, mid_len, low, run), i, ne)
        with jax.named_scope("row_compare"):
            left = table[at_low]  # [ne, 128 n], padded with -1
            right = jnp.where(slots < length[:, None], table[at_mid][:, :w], -2)
            closes = (left[:, :, None] == right[:, None, :]).sum(1, dtype=jnp.uint32)
            triangles = closes.sum(-1)
        with jax.named_scope("credit"):
            at_run, length, v = _block((run_row, run_len, middle), i, ns)
            closes = lax.dot_general(
                (of[None, :] == runs).astype(jnp.int8), closes.astype(jnp.int8),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
            ).astype(jnp.uint32)
            ranks = jnp.where(slots < length[:, None], table[at_run][:, :w], 0)
            part = jnp.zeros(lo.shape, jnp.uint32)
            part = part.at[ranks].add(closes)
            part = part.at[v].add(closes.sum(-1)).at[u].add(triangles)
            return _add64(*counts, part)

    with jax.named_scope("triangles"):
        return lax.fori_loop(0, blocks, block, (lo, hi))


@partial(jax.jit, static_argnames=("w", "ne"), donate_argnums=(0, 1))
def _tail_class(lo, hi, col, blocks, rest_start, rest_len, row_start, row_len,
                low, mid, *, w: int, ne: int):
    """One width class of stage ``lcc_tail`` (the module's note, step 3):
    ``blocks`` blocks of ``ne`` oriented edges ``(low, mid)``; what is left
    of ``low``'s row after ``mid`` against ``mid``'s own row."""
    slots = jnp.arange(w, dtype=jnp.int32)[None, :]

    def block(i, counts):
        a_start, a_len, b_start, b_len, u, v = _block(
            (rest_start, rest_len, row_start, row_len, low, mid), i, ne)
        with jax.named_scope("row_compare"):
            a_valid = slots < a_len[:, None]
            ranks = _windows(col, a_start, w)
            left = jnp.where(a_valid, ranks, -1)
            right = jnp.where(slots < b_len[:, None], _windows(col, b_start, w), -2)
            closes = (left[:, :, None] == right[:, None, :]).sum(-1, dtype=jnp.uint32)
            triangles = closes.sum(-1)
        with jax.named_scope("credit"):
            part = jnp.zeros(lo.shape, jnp.uint32)
            part = part.at[jnp.where(a_valid, ranks, 0)].add(closes)
            part = part.at[u].add(triangles).at[v].add(triangles)
            return _add64(*counts, part)

    with jax.named_scope("triangles"):
        return lax.fori_loop(0, blocks, block, (lo, hi))


@jax.jit
def _by_id(lo, hi, rank):
    return lo[rank], hi[rank]


def _triangles(graph: Graph, simple_edges=None, sink=None):
    """Shared pipeline: the plan (built or found), then the two device
    stages.

    Returns ``(lo, hi, simple_degree)``, each ``[V]`` by vertex id: the
    triangles through a vertex are ``hi * 2**32 + lo`` (uint32 words).
    ``sink``: optional MetricsSink; the plan is then the stage span
    ``triangles_host`` and a ``plan_build`` record (``op: lcc``), the device
    work ``triangles_device`` with the stages ``lcc_core`` and ``lcc_tail``
    under it.
    """
    with stage_span(sink, "triangles_host") as stage:
        plan, seconds, cached = _lcc_plan(graph, simple_edges)
        stats = plan.stats
        stage.note(wedges=stats["wedges_core"] + stats["wedges_tail"], cached=cached)
        if sink is not None:
            sink.emit("plan_build", op="lcc", family="lcc", seconds=round(seconds, 4),
                      cached=cached, **stats)
    return _count(plan, sink)


def _count(plan: _LccPlan, sink=None):
    """The device side on a built plan; under a sink also one
    ``program_memory`` record a width class and stage (asked of the
    executables by the plan's first job, copied by the later ones)."""
    from graphmine_tpu.ops.superstep_policy import (
        emit_program_memory,
        noting,
        program_log,
    )

    programs = program_log(sink, plan.col)
    core = noting(programs, "core", _core_class)
    tail_table = noting(programs, "tail", _tail_table_class)
    tail = noting(programs, "tail", _tail_class)
    v, stats = plan.num_vertices, plan.stats
    with stage_span(sink, "triangles_device",
                    wedges=stats["wedges_core"] + stats["wedges_tail"]) as device:
        lo, hi = jnp.zeros((v,), jnp.uint32), jnp.zeros((v,), jnp.uint32)
        with stage_span(sink, "lcc_core", blocks=sum(c[2] for c in plan.core_classes),
                        rows=stats["core_rows"],
                        bit_products=stats["core_rows"] * stats["core_vertices"]) as stage:
            for w, nb, blocks, *arrays in plan.core_classes:
                lo, hi = core(lo, hi, plan.bits, blocks, *arrays,
                              w=w, nb=nb, core_start=plan.core_start)
            stage.sync((lo, hi))
        with stage_span(sink, "lcc_tail", blocks=sum(c[2] for c in plan.tail_classes),
                        wedges=stats["wedges_tail"], edges=stats["tail_edges"],
                        compares=stats["tail_compares"],
                        credit_slots=stats["tail_credit_slots"]) as stage:
            for w, ne, blocks, *arrays in plan.tail_classes:
                if plan.tail_table is not None:
                    lo, hi = tail_table(lo, hi, plan.tail_table, blocks, *arrays,
                                        w=w, ne=ne)
                else:
                    lo, hi = tail(lo, hi, plan.col, blocks, *arrays, w=w, ne=ne)
            stage.sync((lo, hi))
        lo, hi = device.sync(noting(programs, "by_id", _by_id)(lo, hi, plan.rank))
    emit_program_memory(sink, "lcc", programs)
    return lo, hi, plan.degree


def triangle_count(graph: Graph):
    """Per-vertex triangle counts ``[V]`` (int32) and the global triangle
    total (a Python int).

    GraphFrames ``triangleCount`` semantics (simple undirected graph). A
    vertex in 2**31 triangles or more does not fit the answer's type and
    raises; :func:`clustering_coefficient` reads both words.
    """
    return _as_counts(_triangles(graph))


def _as_counts(cached):
    lo, hi, _ = cached
    words = np.asarray(lo)
    if np.asarray(hi).any() or (words >> 31).any():
        raise OverflowError("a vertex is in 2**31 triangles or more; "
                            "triangle_count answers in int32")
    return lo.astype(jnp.int32), int(words.sum(dtype=np.uint64)) // 3


def oriented_wedge_count(graph: Graph, simple_edges=None) -> int:
    """Exact count of the oriented wedges ``sum d+^2`` of the degree-ordered
    orientation, without listing them (O(E log E) host work, O(E) memory).

    It was the feasibility probe of the host wedge list, which the exact
    counts no longer build (:func:`_oriented_csr` is k-truss's now): 28 B a
    wedge, ~10^10 wedges on a mega-hub power-law graph at 25M edges, an e2e
    run OOM-killed at 130 GB RSS. The pipeline driver's LOF feature phase
    still compares it with ``GRAPHMINE_WEDGE_BUDGET`` and falls back to
    :func:`sampled_clustering_coefficient` past it (ROADMAP Queue 3: the
    budget guards a host cost that is gone). ``simple_edges``: optional
    precomputed :func:`simple_undirected_edges` pair.
    """
    v = graph.num_vertices
    a, b = simple_edges or simple_undirected_edges(graph)
    if len(a) == 0:
        return 0
    deg = np.bincount(a, minlength=v) + np.bincount(b, minlength=v)
    rank = deg.astype(np.int64) * v + np.arange(v)
    lo = np.where(rank[a] <= rank[b], a, b)
    counts = np.bincount(lo, minlength=v).astype(np.int64)
    # each oriented edge (u, v) expands against u's whole oriented row
    return int(counts[lo].sum())


@jax.jit
def _coefficient(lo, hi, degree):
    """``2 T / (d (d - 1))`` in float32 from the two count words."""
    triangles = hi.astype(jnp.float32) * 4294967296.0 + lo.astype(jnp.float32)
    d = degree.astype(jnp.float32)
    pairs = d * (d - 1.0)
    return jnp.where(pairs > 0, 2.0 * triangles / jnp.maximum(pairs, 1.0), 0.0)


def clustering_coefficient(
    graph: Graph, _cached=None, simple_edges=None, sink=None
) -> jax.Array:
    """Local clustering coefficient ``[V]`` (float32): triangles through a
    vertex over its wedge count on the simplified graph, exact (integer
    counts, one division; LDBC Graphalytics' LCC on an undirected graph).

    ``_cached`` optionally takes a prior :func:`_triangles` result so a
    caller needing both counts and coefficients pays the pipeline once;
    ``simple_edges`` forwards a precomputed dedup (see
    :func:`_build_plan`); ``sink`` the stage spans of :func:`_triangles`.
    """
    lo, hi, degree = (
        _triangles(graph, simple_edges, sink) if _cached is None else _cached
    )
    return _coefficient(lo, hi, degree)


def _oriented_csr(graph: Graph, simple_edges=None):
    """Host-side wedge list for :mod:`~graphmine_tpu.ops.ktruss`, which needs
    every triangle's three edge ids (the exact counts above list no wedge):
    simple undirected edges oriented by (degree, id) rank, every oriented
    wedge expanded, ~28 B a wedge of host memory.

    Returns ``(ptr, col, wedge_u, wedge_v, wedge_w, simple_degree,
    wedge_e1, wedge_e2)`` — the last two are per-wedge *edge indices*
    (into the ``col`` order, which IS the edge order): the generating
    edge ``(u, v)`` and the ``(u, w)`` row entry. Consumers that close a
    wedge (k-truss) get the third side's index from their binary-search
    hit, so every triangle knows all three edges from one shared build.

    ``simple_edges``: optional precomputed
    :func:`simple_undirected_edges` result — callers that already paid
    the O(E log E) dedup (the driver's wedge-budget probe) pass it so
    the pipeline runs it once per graph, not once per consumer.
    """
    v = graph.num_vertices
    a, b = simple_edges or simple_undirected_edges(graph)

    deg = np.bincount(a, minlength=v) + np.bincount(b, minlength=v)
    # orient small rank -> large rank; rank = (degree, id)
    rank = deg.astype(np.int64) * v + np.arange(v)
    lo = np.where(rank[a] <= rank[b], a, b)
    hi = np.where(rank[a] <= rank[b], b, a)

    order = np.lexsort((hi, lo))
    lo, hi = lo[order], hi[order]
    counts = np.bincount(lo, minlength=v)
    ptr = np.zeros(v + 1, np.int64)
    np.cumsum(counts, out=ptr[1:])

    # wedge expansion: edge (u, v) x each w in N+(u)
    d_u = counts[lo]
    wedge_u = np.repeat(lo, d_u)
    wedge_v = np.repeat(hi, d_u)
    # w indices: for each edge e with endpoint u, the whole row of u;
    # within-run offsets computed vectorized (no per-edge host loop)
    total = int(d_u.sum())
    starts = np.cumsum(d_u) - d_u
    offsets = np.arange(total, dtype=np.int64) - np.repeat(starts, d_u)
    wedge_e2 = np.repeat(ptr[lo], d_u) + offsets
    wedge_w = hi[wedge_e2]
    wedge_e1 = np.repeat(np.arange(len(lo), dtype=np.int64), d_u)
    return (
        ptr.astype(np.int64), hi.astype(np.int32),
        wedge_u.astype(np.int32), wedge_v.astype(np.int32), wedge_w.astype(np.int32),
        deg.astype(np.int32),
        wedge_e1.astype(np.int32), wedge_e2.astype(np.int32),
    )



def _splitmix64(x):
    """Vectorized splitmix64 finalizer (uint64 in, uint64 out) — the
    stateless per-(vertex, sample) RNG of the wedge sampler. uint64
    wraparound is the intended modular arithmetic."""
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _hash_u01(key, seed_mix):
    """Hash uint64 keys + a pre-mixed seed to float64 uniforms in [0, 1)."""
    with np.errstate(over="ignore"):
        z = _splitmix64(key ^ seed_mix)
    return (z >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))


def sampled_clustering_coefficient(
    graph: Graph, samples: int = 64, seed: int = 0,
    chunk_vertices: int = 1 << 20, simple_edges=None,
) -> np.ndarray:
    """Wedge-sampled approximate local clustering coefficient ``[V]``
    (float32, HOST NumPy) — the at-scale replacement for the exact wedge
    pipeline (VERDICT r3 item 5).

    For every vertex with simple-undirected degree >= 2, draws ``samples``
    uniform unordered neighbor pairs (distinct within each pair, drawn
    with replacement across pairs) and reports the closed fraction — an
    unbiased estimator of the exact coefficient with binomial standard
    error ``<= 1 / (2 * sqrt(samples))`` per vertex (~0.0625 at the
    default 64; the error-bound test pins a 4-sigma envelope against the
    exact pipeline). Work is O(V * samples * log E) membership binary
    searches + one O(E log E) host CSR build — independent of the wedge
    count, which is what makes the clustering feature (and therefore the
    full 8-feature LOF set) survive at the scale where the exact
    O(sum d+^2) wedge expansion is infeasible.

    Processes vertices in ``chunk_vertices`` blocks so peak scratch memory
    stays ~``chunk_vertices * samples`` words regardless of V. Draws are a
    stateless splitmix64 hash of ``(seed, vertex, sample)``, so the result
    is a pure function of the seed — changing ``chunk_vertices`` to fit
    host RAM cannot change the estimates (pinned in tests).
    ``simple_edges`` forwards a precomputed dedup (see
    :func:`_oriented_csr`).
    """
    v = graph.num_vertices
    a, b = simple_edges or simple_undirected_edges(graph)
    # full undirected adjacency CSR of the simple graph (both directions)
    nodes = np.concatenate([a, b])
    nbrs = np.concatenate([b, a])
    order = np.argsort(nodes, kind="stable")
    nbrs = nbrs[order]
    deg = np.bincount(a, minlength=v) + np.bincount(b, minlength=v)
    ptr = np.zeros(v + 1, np.int64)
    np.cumsum(deg, out=ptr[1:])
    # membership oracle: composite keys of the (a < b) edge list — already
    # sorted by construction (simple_undirected_edges unpacks a sorted
    # np.unique key array, and a*v+b reconstructs it exactly)
    edge_keys = a.astype(np.int64) * v + b.astype(np.int64)

    out = np.zeros(v, np.float32)
    seed_mix = _splitmix64(np.uint64(seed & 0xFFFFFFFFFFFFFFFF))
    active = np.flatnonzero(deg >= 2)
    for lo in range(0, len(active), chunk_vertices):
        vs = active[lo:lo + chunk_vertices]
        d = deg[vs].astype(np.int64)[:, None]           # [c, 1]
        # uniform unordered distinct pair (i, j) per sample: i uniform in
        # [0, d), j = (i + 1 + uniform[0, d-1)) mod d
        s_idx = np.arange(samples, dtype=np.uint64)[None, :]
        key = vs.astype(np.uint64)[:, None] * np.uint64(2 * samples)
        r1 = _hash_u01(key + 2 * s_idx, seed_mix)
        r2 = _hash_u01(key + 2 * s_idx + np.uint64(1), seed_mix)
        i = (r1 * d).astype(np.int64)
        j = (i + 1 + (r2 * (d - 1)).astype(np.int64)) % d
        base = ptr[vs][:, None]
        n1 = nbrs[base + i].astype(np.int64)
        n2 = nbrs[base + j].astype(np.int64)
        key = np.minimum(n1, n2) * v + np.maximum(n1, n2)
        pos = np.searchsorted(edge_keys, key)
        closed = (pos < len(edge_keys)) & (
            edge_keys[np.minimum(pos, len(edge_keys) - 1)] == key
        )
        out[vs] = closed.mean(axis=1, dtype=np.float64).astype(np.float32)
    return out
