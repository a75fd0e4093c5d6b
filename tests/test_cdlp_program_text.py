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
programs did not move. PR 39 added the mesh job's programs
(``parallel/sharded.py``: ``_mesh_gather_program``, ``_mesh_rewrite_program``
at the top and the lowest rung, ``_mesh_modes_program``, over four devices)
and the one-program mesh scan ``_sharded_lpa_jit``, which a ``plain``
admission still runs and whose text that PR did not move; the nine
one-chip digests stand: the mesh job calls the one-chip row functions and
edits none. PR 42 replaced the five mesh digests and no other: the width
ladder keeps its 1.10x step past 2048 (``_extend_widths``), and on a mesh,
which has no histogram path, this graph's hub of degree 2,111 is a row, of
width 2253 where it was 3072; no function of ``parallel/sharded.py`` was
edited. On one chip the hub is a histogram and no row is past 2048, so the
one-chip digests stand but one, which is the proof that the programs of a
plan with no row past 2048 (GAP Urand's, the pipeline's) did not move. The
one: ``_modes_program`` gained an ``optimization_barrier`` around the
slice of a class that starts at a multiple of its width ``w`` in rows whose
length is a multiple of ``w`` too, because for such a class the chip's
compiler cuts the slice out of a ``[S / w, w]`` view of ALL the rows, tiled
to 128 lanes (42 times the rows at w = 3: step 0 of PR 42 could not compile
graph500-24's ``modes``, 92.6 GB). This graph has three such classes, so
its digest moved; lowered with the barrier taken out the text is the
parent's to the byte (``_modes_program:no-barrier``), and a plan with no
such class (Urand's, graph500-22's, a shard of graph500-25) lowers no
barrier at all (``test_a_barrier_stands_where_the_rows_would_be_viewed_whole``).
PR 43 added two digests and moved none: after a rewrite on the lowest rung
the one-chip job reduces only the rows the rewrite wrote to, so
``_rewrite_program`` at that rung is lowered ``marked`` (the same scatter,
then the list of the rows it touched: ``_rewrite_program:0:marked``) and
``_dirty_modes_program`` reduces that list. ``marked`` is a
static argument that defaults to what the program was, so the four
``_rewrite_program`` digests STAND (ISSUE 43 expected them to move: the top
rungs' programs are the ones a cache already holds), as do ``_modes_program``,
``_gather_program``, the stateless scan, WCC's loop and all five mesh digests:
``rewrite_rows`` was split (``_rewrite_rows_and_slots`` is the body it had
and also hands back the slots it wrote to) and ``_modes_program``'s count of K
moved into a helper, and both lower to the text they lowered to.
"""

import dataclasses
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
    _dirty_modes_program,
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


def _lowered_on_a_mesh(name):
    """The mesh job's programs over four devices, from the same graph
    kept on the host: shapes and shardings are all a lowering reads."""
    import graphmine_tpu as gm
    from jax.sharding import NamedSharding, PartitionSpec as P
    from graphmine_tpu.parallel import sharded

    g, _ = _graph_and_plan()
    host = build_graph(np.asarray(g.src), np.asarray(g.dst),
                       num_vertices=g.num_vertices, to_device=False)
    mesh = gm.make_mesh(4)
    part = sharded.partition_graph(host, mesh=mesh, build_bucket_plan=True)
    if name == "_sharded_lpa_jit":
        placed = sharded.shard_graph_arrays(part, mesh)
        return sharded._sharded_lpa_jit.lower(placed, mesh, 10, None, 0, False)
    counts = np.diff(sharded._shard_message_offsets(
        np.asarray(host.msg_ptr), 4, part.chunk_size))
    part = dataclasses.replace(
        sharded.with_shard_slot_index(part, counts),
        msg_recv_local=None, msg_send=None, degrees=None)
    sg = sharded.shard_graph_arrays(part, mesh, lpa_only=True)
    put = lambda x, spec: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, spec))
    rows = put(np.empty(4 * sharded.shard_row_slots(sg), np.int32),
               P(sharded._vertex_axes(mesh)))
    labels = put(np.empty(sg.padded_vertices, np.int32), P())
    if name == "_mesh_gather_program":
        return sharded._mesh_gather_program.lower(rows, labels, sg, mesh)
    if name == "_mesh_modes_program":
        return sharded._mesh_modes_program.lower(rows, labels, sg, mesh)
    changed = put(np.empty(sg.padded_vertices, np.bool_), P())
    rung = delta_rungs(int(counts.max()))[int(name.rsplit(":", 1)[1])]
    return sharded._mesh_rewrite_program.lower(
        rows, labels, changed, sg, mesh, cap=rung)


def _lowered(name):
    if name.startswith("_mesh_") or name == "_sharded_lpa_jit":
        return _lowered_on_a_mesh(name)
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
    if name == "_modes_program:no-barrier":
        # a jit of a function object of its own, so that no trace is shared
        # with (or left in the cache of) the program the job runs
        def _modes_program_(rows, labels, plan):
            return _modes_program.__wrapped__(rows, labels, plan)

        _modes_program_.__name__ = "_modes_program"
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
            return jax.jit(_modes_program_).lower(rows, labels, plan)
    changed = jax.ShapeDtypeStruct((g.num_vertices,), jnp.bool_)
    rung = delta_rungs(g.num_messages)[int(name.split(":")[1])]
    total = sum(idx.shape[0] for idx in plan.send_idx)
    if name.startswith("_dirty_modes_program"):  # after the marked rewrite at that rung
        dirty = jax.ShapeDtypeStruct((min(rung, total),), jnp.int32)
        return _dirty_modes_program.lower(rows, labels, dirty, plan)
    return _rewrite_program.lower(
        rows, labels, changed, plan, cap=rung, marked=name.endswith(":marked"))


_PARENT_DIGESTS = {
    "lpa_superstep_bucketed":
        "f6997c7ecbe220e9bdbd9b8f2be3c5fd7205ec611cdfd6460eb9e5c1b125dace",
    "_label_propagation":
        "a2ba5004c2c1483a70bf8d46dd9716cdeaa254c5ad5e15c3037b35eb63b9daf9",
    "_connected_components":
        "c65ca4a6c2759859380430036393bd2d46d3bc1d496320a65d7eff2f851a16ac",
    "_gather_program":
        "1e713dce6aa57bd64d11d8dd3c27a7431abf543c911336e84f36bcf1cee8d3af",
    # PR 42: three of this graph's classes ([2, 2], [144, 6], [261, 8]) start
    # at a multiple of their width in rows whose length (20,112) is one too,
    # and `lpa_modes_from_rows` cuts such a class behind a barrier; with the
    # barrier taken out the text is the parent's, to the byte
    "_modes_program":
        "ba132ac488bef5d200dc16f1894878aa91c2b94a4a97e2beacf97a2d4bb004c5",
    "_modes_program:no-barrier":
        "b69dc9867c6e86f00844a1470059cddf9eb035a1292a578f801905726ec23dc4",
    "_rewrite_program:0":
        "1e1c513ed232b5f7cbf2c60519a81ae4f95dbb14b9a2a7636f14105106e5f0ed",
    "_rewrite_program:1":
        "4ce2a940455086c6f4111759feef2010ddb76565f6d4d2f657fa7693afe4ae80",
    "_rewrite_program:2":
        "bfa41019117a6942bb1f37c04b145be552b4f725ac2f89874d067a88ccd9a777",
    "_rewrite_program:3":
        "f76dd23541cb9296c6dd4e4296cad7b168df6ec5639b6908ddeb61afdebf2d7d",
    # PR 43: the lowest rung's rewrite that also lists the rows it wrote to,
    # and the reduce over that list
    "_rewrite_program:0:marked":
        "482a7f996bb1b157c234a272e5acb420c90e9587534a7d315c8769380f13afcc",
    "_dirty_modes_program:0":
        "5763fe003223880463d6a1c0ecb85d21256d05d3ffddc2867b9edbd2aba70b0e",
    # on a mesh of four (PR 39's programs; PR 42: the hub's row is 2253 wide)
    "_sharded_lpa_jit":
        "f131e22a2d2e795f147a58e6e9d0e1ae9ed043393d5e5d18406dac8143158112",
    "_mesh_gather_program":
        "6e2d7d99a50ed9da2f4c5a6ebe87654422ff31e392ef41d9967d730f7549b009",
    "_mesh_modes_program":
        "4b21300e7f05ca04a8a85c72025f061d4a61ebd62a5ef52523f4689f6ae9d88e",
    "_mesh_rewrite_program:0":
        "fed2fa1decc8587ab7c5965b89516fd319911b7c5256e63ab5bbb9902de2148b",
    "_mesh_rewrite_program:3":
        "6a3e5325db379920ec04a5336e46aa282f8f5264e5719d1d0e5cf7f5cb2e8313",
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
             if name.startswith(("_gather", "_modes", "_rewrite")) and ":no-" not in name}
    assert len(texts) == 7  # the lowest rung's rewrite also as `marked`
    # this graph's three classes that would be viewed whole, and no other
    assert texts["_modes_program"].count("stablehlo.optimization_barrier") == len(
        _viewed_whole([idx.shape for idx in plan.send_idx])) == 3
    for name, text in texts.items():
        assert "stablehlo.case" not in text and "stablehlo.while" not in text
        gathers = [ln for ln in text.splitlines() if "stablehlo.gather" in ln]
        for idx in plan.send_idx:
            n, w = idx.shape
            rows = [ln for ln in gathers if ln.endswith(f"-> tensor<{n}x{w}xi32>")]
            assert len(rows) == (name == "_gather_program"), (name, n, w, len(rows))


def _viewed_whole(classes):
    """The classes the chip's compiler would cut from a view of all the
    rows: those that start at a multiple of their width in rows whose
    length is a multiple of it."""
    s, off, hit = sum(n * w for n, w in classes), 0, []
    for n, w in classes:
        if w > 1 and off % w == 0 and s % w == 0:
            hit.append((n, w))
        off += n * w
    return hit


@pytest.mark.parametrize("shapes,hit", [
    ("urand_24", []), ("g500_22", []), ("g500_24", [(681618, 3)]), ("g500_25_x4", []),
])
def test_a_barrier_stands_where_the_rows_would_be_viewed_whole(shapes, hit):
    """``_modes_program`` over the benchmark's plans, by shapes
    (``_proof/*_shapes.json``; a shard's, for the mesh): one barrier for
    each class that starts at a multiple of its width in rows whose length
    is one too, and none in a plan that has no such class: GAP Urand's
    program is the text it was."""
    import json
    import os

    said = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "_proof", shapes + "_shapes.json")))
    classes = [tuple(c) for c in said["classes"]]
    assert _viewed_whole(classes) == hit
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)
    v = said["num_vertices"]
    plan = BucketedModePlan(
        vertex_ids=tuple(i32(n) for n, _ in classes), msg_idx=None,
        num_vertices=v, num_messages=said["num_messages"],
        send_idx=tuple(i32(n, w) for n, w in classes),
        out_ptr=i32(v + 1), out_slot=i32(1),
    )
    text = _modes_program.lower(i32(said["slots"]), i32(v), plan).as_text()
    assert text.count("stablehlo.optimization_barrier") == len(hit)
