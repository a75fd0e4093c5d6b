"""The CDLP cells' programs, pinned by their lowered text (ISSUE 30).

The masked pass of ``ops/outliers.py`` calls the row reduce of
``ops/bucketed_mode.py`` and must not edit it: the benchmark's CDLP cells
and the pipeline's LPA chapter run ``lpa_superstep_bucketed`` and
``_label_propagation`` over a ``BucketedModePlan``, and a program whose
text moved is compiled again (219 s on one chip, 251 s on four) and is a
different program to measure. The digests below are of the StableHLO
these calls lowered to at the parent commit of PR 30 (``b319ad1``), on
a graph with narrow, pairwise, sorted and histogram rows. Whoever means
to change these programs (ROADMAP S4) replaces the digests in that PR.
PR 31 did for ``_connected_components`` alone: its ``while_loop`` carries
the per-superstep changed counts of the ``fixpoint`` record (ISSUE 31).
PR 32 did for ``_label_propagation`` alone: over a fused plan with its slot
index the scan carries the gathered rows and rewrites the changed senders'
slots (ISSUE 32); the other two digests stand, which is the proof that the
pipeline's and WCC's programs did not move.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.ops.bucketed_mode import (
    _HIST_MIN_DEG,
    BucketedModePlan,
    lpa_superstep_bucketed,
    with_slot_index,
)
from graphmine_tpu.ops.cc import _connected_components
from graphmine_tpu.ops.lpa import _label_propagation


def _graph_and_plan():
    v = _HIST_MIN_DEG + 64  # vertex 0 is a hub: the histogram rows are there
    rng = np.random.default_rng(30)
    src = np.concatenate([np.zeros(v - 1, np.int64), rng.integers(1, v, 9000)])
    dst = np.concatenate([np.arange(1, v), rng.integers(1, v, 9000)])
    g = build_graph(src, dst, num_vertices=v)
    return g, BucketedModePlan.from_graph(g, with_send=True)


def _lowered(name):
    g, plan = _graph_and_plan()
    if name == "lpa_superstep_bucketed":
        labels = jnp.arange(g.num_vertices, dtype=jnp.int32)
        return jax.jit(lpa_superstep_bucketed).lower(labels, g, plan)
    if name == "_label_propagation":
        return _label_propagation.lower(
            g, max_iter=10, plan=with_slot_index(plan)
        )
    return _connected_components.lower(g, plan=plan)


_PARENT_DIGESTS = {
    "lpa_superstep_bucketed":
        "f6997c7ecbe220e9bdbd9b8f2be3c5fd7205ec611cdfd6460eb9e5c1b125dace",
    "_label_propagation":
        "66d099320083c8cb2c5a2e009aa9074f81be462008719609e1255471b9c75061",
    "_connected_components":
        "c65ca4a6c2759859380430036393bd2d46d3bc1d496320a65d7eff2f851a16ac",
}


@pytest.mark.parametrize("name", sorted(_PARENT_DIGESTS))
def test_the_cdlp_programs_lower_to_the_parent_s_text(name):
    text = _lowered(name).as_text()  # no source locations in this form
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_DIGESTS[name]


def test_the_classes_gathers_are_in_the_carried_rows_program_once():
    """The first superstep takes the full branch by its carry (K = M + 1),
    not by a copy of the gathers peeled before the loop: one ``case`` in
    the scan's body, and each class's ``[n, w]`` row gather in it once."""
    _, plan = _graph_and_plan()
    text = _lowered("_label_propagation").as_text()
    assert text.count("stablehlo.case") == 1
    gathers = [ln for ln in text.splitlines() if "stablehlo.gather" in ln]
    for idx in plan.send_idx:
        n, w = idx.shape
        rows = [ln for ln in gathers if ln.endswith(f"-> tensor<{n}x{w}xi32>")]
        assert len(rows) == 1, (n, w, len(rows))
