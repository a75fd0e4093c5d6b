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
slots (ISSUE 32). PR 36 replaced that one digest by six: the scan and its
``switch`` are gone, and a carried job steps from the host through
``_gather_program``, ``_rewrite_program`` (one a rung) and ``_modes_program``
of ``ops/lpa.py``, each pinned here; ``_label_propagation`` is the stateless
scan alone (the text the parent's lowered to over a plan without its index,
which the ``plain`` admission ran: pinned too). The
other two digests stand, which is the proof that the pipeline's and WCC's
programs did not move.
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
    row_slots,
    with_slot_index,
)
from graphmine_tpu.ops.cc import _connected_components
from graphmine_tpu.ops.lpa import (
    _gather_program,
    _label_propagation,
    _modes_program,
    _rewrite_program,
)
from graphmine_tpu.ops.superstep_policy import delta_rungs


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
        return _label_propagation.lower(g, max_iter=10, plan=plan)
    if name == "_connected_components":
        return _connected_components.lower(g, plan=plan)
    # the carried job's programs: shapes are all a lowering reads
    plan = with_slot_index(plan)
    rows = jax.ShapeDtypeStruct((row_slots(plan),), jnp.int32)
    labels = jax.ShapeDtypeStruct((g.num_vertices,), jnp.int32)
    if name == "_gather_program":
        return _gather_program.lower(rows, labels, plan)
    if name == "_modes_program":
        return _modes_program.lower(rows, labels, plan)
    changed = jax.ShapeDtypeStruct((g.num_vertices,), jnp.bool_)
    rung = delta_rungs(g.num_messages)[int(name.rsplit(":", 1)[1])]
    return _rewrite_program.lower(rows, labels, changed, plan, cap=rung)


_PARENT_DIGESTS = {
    "lpa_superstep_bucketed":
        "f6997c7ecbe220e9bdbd9b8f2be3c5fd7205ec611cdfd6460eb9e5c1b125dace",
    "_label_propagation":
        "a2ba5004c2c1483a70bf8d46dd9716cdeaa254c5ad5e15c3037b35eb63b9daf9",
    "_connected_components":
        "c65ca4a6c2759859380430036393bd2d46d3bc1d496320a65d7eff2f851a16ac",
    "_gather_program":
        "1e713dce6aa57bd64d11d8dd3c27a7431abf543c911336e84f36bcf1cee8d3af",
    "_modes_program":
        "b69dc9867c6e86f00844a1470059cddf9eb035a1292a578f801905726ec23dc4",
    "_rewrite_program:0":
        "1e1c513ed232b5f7cbf2c60519a81ae4f95dbb14b9a2a7636f14105106e5f0ed",
    "_rewrite_program:1":
        "4ce2a940455086c6f4111759feef2010ddb76565f6d4d2f657fa7693afe4ae80",
    "_rewrite_program:2":
        "bfa41019117a6942bb1f37c04b145be552b4f725ac2f89874d067a88ccd9a777",
    "_rewrite_program:3":
        "f76dd23541cb9296c6dd4e4296cad7b168df6ec5639b6908ddeb61afdebf2d7d",
}


@pytest.mark.parametrize("name", sorted(_PARENT_DIGESTS))
def test_the_cdlp_programs_lower_to_the_parent_s_text(name):
    text = _lowered(name).as_text()  # no source locations in this form
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_DIGESTS[name]


def test_each_class_s_gather_is_in_the_gather_program_once_and_in_no_other():
    """The full gather is a program of its own: each class's ``[n, w]`` row
    gather is in ``_gather_program`` once, and neither the rewrites nor the
    row modes hold one (they read the rows they are handed). No program of
    the job picks a branch on the device: the host does."""
    _, plan = _graph_and_plan()
    texts = {name: _lowered(name).as_text() for name in _PARENT_DIGESTS
             if name.endswith("_program") or "_program:" in name}
    assert len(texts) == 6
    for name, text in texts.items():
        assert "stablehlo.case" not in text and "stablehlo.while" not in text
        gathers = [ln for ln in text.splitlines() if "stablehlo.gather" in ln]
        for idx in plan.send_idx:
            n, w = idx.shape
            rows = [ln for ln in gathers if ln.endswith(f"-> tensor<{n}x{w}xi32>")]
            assert len(rows) == (name == "_gather_program"), (name, n, w, len(rows))
