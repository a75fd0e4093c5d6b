"""String → dense int32 vertex-id factorization.

The reference assigns vertex IDs with ``sha1(x)[:8]`` (a 32-bit hex string,
``Graphframes.py:57-58``), which collides near ~80K vertices and forces
string-keyed joins. We instead factorize to *dense* int32 indices — the
device-friendly representation every downstream kernel indexes with.

A native C++ fast path (``native/graph_builder.cpp``, loaded via ctypes in
:mod:`graphmine_tpu.io.native`) accelerates edge-list parsing + interning for
large text files; this module is the canonical NumPy implementation and the
fallback.
"""

from __future__ import annotations

import numpy as np


def factorize(*columns: np.ndarray) -> tuple[list[np.ndarray], np.ndarray]:
    """Map string columns to dense int32 codes over their *union* of values.

    Mirrors the vertex-dictionary build of the reference
    (``Graphframes.py:53``: flatMap over both domain columns + distinct),
    but produces contiguous indices instead of hash strings.

    Returns ``(codes, uniques)`` where ``codes[i]`` is the int32 code array
    for ``columns[i]`` and ``uniques`` is the vocabulary (np object/str
    array). Codes are assigned in first-appearance order over the
    concatenated columns — deterministic and stable across runs.
    """
    if not columns:
        raise ValueError("factorize() needs at least one column")
    flat = np.concatenate([np.asarray(c) for c in columns])
    codes_flat, uniques = _factorize_first_appearance(flat)
    out, off = [], 0
    for c in columns:
        n = len(c)
        out.append(codes_flat[off : off + n].astype(np.int32))
        off += n
    return out, uniques


def _factorize_first_appearance(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    # np.unique sorts; remap so codes follow first appearance (matches the
    # insertion-order semantics of a hash-map interner, and keeps golden
    # tests independent of locale/collation).
    uniq_sorted, first_idx, inv = np.unique(values, return_index=True, return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    codes = rank[inv].astype(np.int32)
    return codes, uniq_sorted[order]


class IncrementalFactorizer:
    """Streaming string -> dense int32 interner for batched ingestion.

    Each :meth:`add` call encodes one column batch, assigning new codes in
    first-appearance order *within the batch* (the batch's unique values
    are looked up / inserted via a dict — O(batch uniques), vectorized
    decode). Peak memory is the vocabulary plus one batch, which is what
    the reference's abandoned data slicer (``Graphframes.py:34-47``) was
    groping toward.
    """

    def __init__(self):
        self._index: dict = {}
        self._names: list = []

    def add(self, column: np.ndarray) -> np.ndarray:
        column = np.asarray(column)
        codes_batch, uniques = _factorize_first_appearance(column)
        return self._intern_uniques(codes_batch, uniques)

    def add_dictionary(self, indices: np.ndarray, dictionary: np.ndarray) -> np.ndarray:
        """Encode a batch given as ``dictionary[indices]`` WITHOUT
        materializing the per-row strings (r5 ingest fast path).

        Equivalent to ``add(dictionary[indices])`` by construction — an
        Arrow dictionary's values are unique, so first-appearance order
        over the int index stream is first-appearance order over the
        value stream, and only the batch's distinct values (``|D|``, not
        ``|rows|``) touch Python. The e2e capture measured the per-row
        string path at ~300K rows/s (84 s of a 196 s pipeline on 25M
        rows); this path moves the per-row work to int32 numpy.
        """
        codes_batch, uniq_idx = _factorize_first_appearance(
            np.asarray(indices)
        )
        return self._intern_uniques(codes_batch, np.asarray(dictionary)[uniq_idx])

    def _intern_uniques(self, codes_batch, uniques) -> np.ndarray:
        lut = np.empty(len(uniques), dtype=np.int32)
        index, names = self._index, self._names
        for i, val in enumerate(uniques.tolist()):
            code = index.get(val)
            if code is None:
                code = len(names)
                index[val] = code
                names.append(val)
            lut[i] = code
        return lut[codes_batch].astype(np.int32)

    def __len__(self) -> int:
        return len(self._names)

    def names(self) -> np.ndarray:
        return np.asarray(self._names, dtype=object)
