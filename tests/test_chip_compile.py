"""Compiles of the main path's programs for a TPU v5e that is described,
not attached (the chip's own compiler, no chip): what it refuses here it
would refuse on the chip — tile misalignment, VMEM or HBM overflow, a
program it cannot partition — which the CPU backend and the Pallas
interpreter never show. Nothing runs, so nothing here is a result or a
time. The full-size (2^18 vertices / 25 M edges) compiles take minutes
and belong to the no-chip rehearsal before a chip call, not to tier-1.

Everything built from the topology is built inside fixtures
(``tests/chip_compile_fixtures.py``) or tests: only one process may hold
libtpu, and under pytest-xdist every worker imports every test module. The
BFS job's programs are compiled by ``tests/test_chip_compile_bfs.py``: under
``--dist loadfile`` a file is one worker's, and this one is the run's wall.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chip_compile_fixtures import (  # noqa: F401  (fixtures, by name)
    _compile,
    _shape_on,
    _shapes,
    flat_plan,
    fused_plan,
    one_chip,
    planted,
    topo,
)


@pytest.mark.parametrize("impl, k", [("pallas", 8), ("xla", 128)])
def test_knn_compiles_for_v5e(one_chip, impl, k):
    from graphmine_tpu.ops.knn import _knn_xla
    from graphmine_tpu.pallas_kernels.knn_pallas import knn_pallas

    points = jax.ShapeDtypeStruct((65536, 8), jnp.float32, sharding=one_chip)
    compiled = _compile(knn_pallas if impl == "pallas" else _knn_xla, points, k=k)
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")


@pytest.mark.parametrize("site", ["search", "merge"])
def test_ivf_selection_compiles_without_a_gather(one_chip, site):
    """The IVF search's selection at the pipeline cell's shapes (a
    ``[4096, 8] x [1024, 8]`` chunk, a ``[16384, 18 * 128]`` merge tile,
    k = 128): the sort carries the ids, so no gather turns positions into
    ids afterwards. That gather was 3.6 s of a 16.3 s job (PR 28); this
    keeps it from coming back. The merge's row gather of the pair tables
    (``ivf/merge_gather``) is a different operation and stays."""
    from graphmine_tpu.ops.ann import _merge_tiles, _search_clusters

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    k, chunk_b, l_max, p_max, merge_t = 128, 4096, 1024, 18, 16384
    if site == "search":
        compiled = _compile(
            _search_clusters,
            shape((chunk_b, 8), jnp.float32), shape((chunk_b,), jnp.int32),
            shape((l_max, 8), jnp.float32), shape((l_max,), jnp.int32),
            shape((l_max,), jnp.bool_), k=k,
        )
        scope = ""
    else:
        rows = 1295 * chunk_b + 1  # the cell's chunk rows + the junk row
        compiled = _compile(
            _merge_tiles,
            shape((rows, k), jnp.float32), shape((rows, k), jnp.int32),
            shape((16, merge_t, p_max), jnp.int32), k=k,
        )
        scope = "merge_topk"
    hlo = compiled.as_text()
    assert " sort(" in hlo
    gathers = [
        line for line in hlo.splitlines()
        if (" gather(" in line or "AssumeGatherIndicesInBound" in line)
        and scope in line
    ]
    assert not gathers, gathers[:2]


def test_ivf_list_programs_compile_for_v5e(one_chip):
    """The three programs that build the IVF index on the device (ISSUE
    34) at the pipeline cell's shapes: 262,144 x 16 probes of 512
    clusters, 640 sublists of 1,024 members, 1,295 chunks, 18 pairs a
    query. They sort twice where a scatter would invert the order, and
    cut the member and query tables as runs, not index by index: the
    only gathers of ``n * n_probe`` indices left are the three lookups in
    the 512-entry cluster tables."""
    from graphmine_tpu.ops.ann import _index_tables, _probe_census, _take_table

    def i32(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    n, n_probe, c, n_sub, l_max, r_rows, p_max = 262144, 16, 512, 640, 1024, 1295, 18
    cell = i32(n, n_probe)
    programs = {
        "census": _compile(_probe_census, cell, n_clusters=c, l_cap=1024),
        "tables": _compile(
            _index_tables, i32(n), i32(n * n_probe), cell, i32(n_sub),
            i32(n_sub), i32(r_rows), i32(r_rows), i32(c), i32(c),
            l_max=l_max, chunk_b=4096,
        ),
        "take": _compile(
            _take_table, cell, cell, cell, p_max=p_max, junk=r_rows * 4096,
            merge_t=16384,
        ),
    }
    wide = {}
    for name, compiled in programs.items():
        hlo = compiled.as_text()
        assert " scatter(" not in hlo, name
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30, name
        wide[name] = sum(
            " gather(" in line and f"s32[{n},{n_probe}]" in line.split(" gather(")[0]
            for line in hlo.splitlines()
        )
    assert wide == {"census": 1, "tables": 2, "take": 0}


def test_lpa_superstep_bucketed_compiles_for_v5e(one_chip, fused_plan, planted):
    from graphmine_tpu.ops.bucketed_mode import lpa_superstep_bucketed

    graph, plan = fused_plan
    labels = jax.ShapeDtypeStruct((planted[2],), jnp.int32, sharding=one_chip)
    _compile(
        jax.jit(lpa_superstep_bucketed), labels,
        _shapes(graph, one_chip), _shapes(plan, one_chip),
    )


@pytest.mark.parametrize(
    "program", ["gather", "rewrite", "modes", "rewrite:marked", "dirty_modes"]
)
@pytest.mark.parametrize("graph", ["kronecker", "flat"])
def test_carried_rows_programs_compile_for_v5e(
    one_chip, fused_plan, flat_plan, planted, program, graph
):
    """The one-chip CDLP job's three kinds of program (ISSUE 36), each
    compiled alone: the classes' full gathers and the top rung's rewrite
    through the slot index update the rows IN PLACE, because the rows are
    their donated argument: the compiler aliases the whole ``s32[S]``
    buffer to the result and keeps no temporary of its size. The admission
    (``obs/memmodel.carried_rows_inventory``) counts the rows once on the
    strength of this, and each program's other temporaries from the plan's
    shapes and the chip's tiles (ISSUE 38): at or above what the compiler
    assigns, on a skewed plan with hubs and on a flat one of narrow
    classes, where a class passes through a 128-lane form of 3.9 times
    its size. ISSUE 43's two: the rewrite that also lists the rows it wrote
    to still updates the rows in place (two results, the rows aliased as
    before), and the dirty reduce holds a trip's rows and never the flat
    rows: its temporaries are the labels, the ids and the hubs'
    histograms, under a tenth of the rows where there is no hub."""
    from graphmine_tpu.obs.memmodel import carried_job_transients
    from graphmine_tpu.ops import lpa
    from graphmine_tpu.ops.bucketed_mode import row_slots, with_slot_index
    from graphmine_tpu.ops.superstep_policy import delta_rungs

    plan = fused_plan[1] if graph == "kronecker" else flat_plan
    plan = _shapes(with_slot_index(plan), one_chip)
    v, rows_bytes = planted[2], 4 * row_slots(plan)
    top_rung = delta_rungs(plan.num_messages)[-1]
    rows = jax.ShapeDtypeStruct((row_slots(plan),), jnp.int32, sharding=one_chip)
    labels = jax.ShapeDtypeStruct((v,), jnp.int32, sharding=one_chip)
    if program == "gather":
        compiled = _compile(lpa._gather_program, rows, labels, plan)
    elif program == "rewrite":
        changed = jax.ShapeDtypeStruct((v,), jnp.bool_, sharding=one_chip)
        compiled = _compile(
            lpa._rewrite_program, rows, labels, changed, plan, cap=top_rung,
        )
    elif program == "modes":
        compiled = _compile(lpa._modes_program, rows, labels, plan)
    else:  # ISSUE 43: at the highest rung that takes the dirty reduce
        from graphmine_tpu.ops.superstep_policy import DIRTY_REDUCE_TOP_PLACE

        cap = delta_rungs(plan.num_messages)[DIRTY_REDUCE_TOP_PLACE]
        total = sum(idx.shape[0] for idx in plan.send_idx)
        if program == "rewrite:marked":
            changed = jax.ShapeDtypeStruct((v,), jnp.bool_, sharding=one_chip)
            compiled = _compile(
                lpa._rewrite_program, rows, labels, changed, plan, cap=cap,
                marked=True,
            )
        else:
            dirty = jax.ShapeDtypeStruct(
                (min(cap, total),), jnp.int32, sharding=one_chip
            )
            compiled = _compile(lpa._dirty_modes_program, rows, labels, dirty, plan)
    held = compiled.memory_analysis()
    assert " conditional(" not in compiled.as_text()
    counted = carried_job_transients(plan, top_rung=top_rung)[program.split(":")[0]]
    if program in ("modes", "dirty_modes"):  # reads the rows, writes V-sized results
        assert held.alias_size_in_bytes == 0
        hubs = 0 if plan.hist_vertex_ids is None else plan.hist_vertex_ids.shape[0]
        assert held.temp_size_in_bytes <= max(counted, 8 * hubs * v + 8 * v)
    else:
        assert held.alias_size_in_bytes >= rows_bytes
        assert held.temp_size_in_bytes <= counted
    if program == "dirty_modes" and graph == "flat":  # a trip's rows, not the rows
        assert held.temp_size_in_bytes < rows_bytes // 10
    if graph == "kronecker":  # wide classes: well under the rows
        assert held.temp_size_in_bytes < (
            rows_bytes if program in ("modes", "dirty_modes") else rows_bytes // 4)


@pytest.mark.parametrize("graph", ["kronecker", "flat"])
def test_pagerank_iteration_compiles_for_v5e_and_the_loop_holds_every_class(
    one_chip, fused_plan, flat_plan, planted, graph
):
    """The one compiled iteration the message reading of ``gm.pagerank``
    steps from the host (ISSUE 41): its temporaries are its largest
    class's, at or under ``obs/memmodel.row_sum_transients``' count from
    the plan's shapes. The same body inside a ``while_loop`` holds several
    times that (every class's rows and indices at once: 5.26 GB against
    1.13 GB at graph500-24's shapes, 11.0 against 2.43 GB at GAP Urand's),
    which is why the job steps from the host and is not one program."""
    import importlib

    from graphmine_tpu.obs.memmodel import row_sum_transients

    pagerank = importlib.import_module("graphmine_tpu.ops.pagerank")
    host_graph, plan = fused_plan[0], fused_plan[1] if graph == "kronecker" else flat_plan
    plan = _shapes(plan, one_chip)
    v = planted[2]
    f32 = jax.ShapeDtypeStruct((v,), jnp.float32, sharding=one_chip)
    stepped = _compile(
        pagerank._bucketed_iteration, f32, f32, f32, plan, 0.85, with_delta=False,
    )
    held = stepped.memory_analysis()
    assert held.alias_size_in_bytes >= 4 * v  # the ranks are written where they were read
    assert held.temp_size_in_bytes <= row_sum_transients(plan)
    if graph == "kronecker":  # the loop needs a graph of the plan's own shapes
        looped = _compile(
            pagerank._pagerank_messages_jit, _shapes(host_graph, one_chip), plan,
            0.85, 10, None, None,
        )
        assert looped.memory_analysis().temp_size_in_bytes > \
            3 * held.temp_size_in_bytes


def test_masked_lpa_plan_mask_compiles_for_v5e(one_chip, fused_plan, planted):
    """The recursive outlier pass's one program of its own size: the
    community mask over the plan's rows (ISSUE 30). Its supersteps are
    ``lpa_superstep_bucketed``, compiled above."""
    from graphmine_tpu.ops.outliers import _mask_plan_rows

    _, plan = fused_plan
    comm = jax.ShapeDtypeStruct((planted[2],), jnp.int32, sharding=one_chip)
    _compile(_mask_plan_rows, _shapes(plan, one_chip), comm)


def test_cc_superstep_bucketed_compiles_for_v5e(one_chip, fused_plan, planted):
    from graphmine_tpu.ops.cc import cc_superstep_bucketed

    _, plan = fused_plan
    labels = jax.ShapeDtypeStruct((planted[2],), jnp.int32, sharding=one_chip)
    _compile(jax.jit(cc_superstep_bucketed), labels, _shapes(plan, one_chip))


@pytest.mark.parametrize("v, w, ne, ns", [
    (1 << 22, 96, 5461, 328),     # graph500-22's widest class: 468 of the 539 tail blocks
    (1 << 18, 6, 65536, 31032),   # the pipeline cell's planted graph, width 6
    (1 << 22, 2, 65536, 65536),   # the most a block may hold, every run one edge
])
def test_lcc_tail_class_compiles_for_v5e_and_writes_no_runs_by_edges_operand(one_chip, v, w, ne, ns):
    """LCC's tail class program (ISSUE 47) sums a block's matches along its
    ``ns`` runs by a 0/1 product whose left operand, ``[ns, ne]``, is a
    compare the chip's compiler folds into the product: nothing of it is
    written out. If it were, that is 1.9 GiB at the pipeline cell's width 6
    and 4 GiB at the most a block may hold, in a cell that sits at 84 % of
    the chip's memory: the program's temporaries stay under the two fetched
    rows of every edge and the block's fresh count words."""
    from graphmine_tpu.ops.triangles import _tail_table_class

    shape = _shape_on(one_chip)
    blocks, table_width = 2, 128
    compiled = _compile(
        _tail_table_class, shape((v,), jnp.uint32), shape((v,), jnp.uint32),
        shape((v // 4, table_width)), shape(()),
        *[shape((blocks * ne,))] * 5, *[shape((blocks * ns,))] * 3, w=w, ne=ne,
    )
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= 2 * ne * table_width * 4 + 4 * v * 4, temporaries


@pytest.mark.parametrize("w, nb, blocks", [
    (6, 8192, 22),    # graph500-22's narrow classes: 1.15 M of its 1.74 M padded centres
    (256, 512, 132),  # and one of its three widest by rows fetched
])
def test_lcc_core_class_compiles_for_v5e_with_no_loop_over_centres(one_chip, w, nb, blocks):
    """LCC's core class program (ISSUE 48) reads a block's neighbour ranks as
    one slice of the rows the plan laid out. Cut out of the CSR as ``nb``
    windows, they were a gather the chip's compiler expands, from width 6
    up, into a fourth ``while`` of ``nb`` trips (one ``s32[1,1]`` start and
    one ``s32[1,w]`` window a trip, 1.16 us each: 1.32 s of a 6.6 s job at
    graph500-22). The
    program keeps the three loops of its source (blocks, ``mark``,
    ``probe``), takes nothing of the CSR's size, and holds the ranks at four
    bytes a slot: flat, not ``[n, w]`` padded to tiles of 128 lanes."""
    from graphmine_tpu.ops.triangles import _core_class

    shape = _shape_on(one_chip)
    v, k = 1 << 22, 1 << 17
    arguments = (
        shape((v,), jnp.uint32), shape((v,), jnp.uint32), shape((k, k // 32), jnp.uint32),
        shape(()), shape((blocks * nb * w,)), shape((blocks * nb,)), shape((blocks * nb,)),
    )
    compiled = _compile(_core_class, *arguments, w=w, nb=nb, core_start=v - k)
    assert compiled.as_text().count(" while(") == 3
    declared = sum(int(np.prod(a.shape)) * 4 for a in arguments)
    held = compiled.memory_analysis().argument_size_in_bytes
    assert declared <= held < declared + 4096, (held, declared)


def test_query_engine_gather_compiles_for_v5e(one_chip):
    """The served batched read: the engine's own jitted gather, at the
    smoke's table width and its largest batch bucket."""
    from graphmine_tpu.serve.query import QueryEngine
    from graphmine_tpu.serve.snapshot import Snapshot

    tiny = Snapshot(
        arrays={
            "src": np.array([0, 1], np.int32), "dst": np.array([1, 2], np.int32),
            "labels": np.zeros(3, np.int32),
        },
        meta={"version": 1},
    )
    v = 1 << 18
    _compile(
        QueryEngine(tiny)._gather,
        jax.ShapeDtypeStruct((3, v), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((v,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip),
    )


def test_sharded_lpa_superstep_compiles_for_four_chips(topo, planted):
    """One replicated-schedule LPA superstep over a 4-device mesh of the
    described chips. ``shard_graph_arrays`` would place arrays, and a
    described device can hold none: the jitted inner program gets the
    host partition's shapes with the shardings it would have placed."""
    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.sharded import (
        _sharded_lpa_jit,
        _vertex_axes,
        partition_graph,
    )

    src, dst, v = planted
    mesh = make_mesh(4, devices=topo.devices)
    axes = _vertex_axes(mesh)
    sg = partition_graph(
        build_graph(src, dst, num_vertices=v, to_device=False),
        mesh=mesh, build_bucket_plan=True,
    )
    sg = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), a.dtype,
            sharding=NamedSharding(mesh, P(axes, *[None] * (np.ndim(a) - 1))),
        ),
        sg,
    )
    compiled = _compile(_sharded_lpa_jit, sg, mesh, 1, None, 0, False)
    assert "all-gather" in compiled.as_text()


@pytest.fixture(scope="module")
def mesh_partition(topo):
    """``(mesh of the four described chips, a Kronecker graph's partition
    with its slot index as shapes placed the way the entry places them,
    the largest shard's messages)``: the benchmark's own generator at
    scale 17 (the cell's R-MAT at 1/256 of its vertices): wide classes,
    whose reduce is what a program holds, as at the cell's size; on the
    planted graph's sixty classes of under 2,000 rows the compiler keeps
    many at once, which it cannot at a size that matters."""
    import sys

    from graphmine_tpu.graph.container import build_graph
    from graphmine_tpu.parallel.mesh import make_mesh
    from graphmine_tpu.parallel.sharded import (
        _shard_message_offsets,
        _vertex_axes,
        partition_graph,
        with_shard_slot_index,
    )

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    import generators

    src, dst = generators.rmat_undirected(17, 16, 0.57, 0.19, 0.19, seed=7)
    v = 1 << 17
    mesh = make_mesh(4, devices=topo.devices)
    axes = _vertex_axes(mesh)
    host = build_graph(src, dst, num_vertices=v, to_device=False)
    sg = partition_graph(host, mesh=mesh, lpa_only=True, build_bucket_plan=True)
    counts = np.diff(_shard_message_offsets(np.asarray(host.msg_ptr), 4, sg.chunk_size))
    sg = with_shard_slot_index(sg, counts)
    assert sg.out_slot.shape == (4 * counts.max(),)
    sg = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), a.dtype,
            sharding=NamedSharding(mesh, P(axes, *[None] * (np.ndim(a) - 1))),
        ),
        sg,
    )
    return mesh, sg, int(counts.max())


@pytest.mark.parametrize("program", ["gather", "rewrite:top", "rewrite:low", "modes"])
def test_mesh_carried_rows_programs_compile_for_four_chips(mesh_partition, program):
    """The mesh CDLP job's three kinds of program (ISSUE 39), each compiled
    alone for four described chips. The rows are the donated argument of
    ``gather`` and of every ``rewrite``, through ``jit`` and ``shard_map``:
    the compiler aliases a chip's whole ``s32[S]`` buffer to the result
    and copies none of its size in the chip's memory, so a chip holds its
    rows once (flat ``[D * S]`` rows: as ``[D, S]`` the squeeze to ``[S]``
    was a copy in and a copy out, 2.46 GB of temporaries at graph500-25). The
    ``all_gather`` of the labels is in ``modes`` and in no other. The
    admission counts each program's temporaries from ONE shard's shapes
    (``parallel/sharded.shard_plan_shapes``): at or above the compiler's."""
    from graphmine_tpu.obs.memmodel import carried_job_transients
    from graphmine_tpu.ops.superstep_policy import delta_rungs
    from graphmine_tpu.parallel import sharded

    mesh, sg, largest = mesh_partition
    axes = sharded._vertex_axes(mesh)
    slots, v_pad = sharded.shard_row_slots(sg), sg.padded_vertices
    rungs = delta_rungs(largest)
    rows = jax.ShapeDtypeStruct(
        (4 * slots,), jnp.int32, sharding=NamedSharding(mesh, P(axes)))
    rep = NamedSharding(mesh, P())
    labels = jax.ShapeDtypeStruct((v_pad,), jnp.int32, sharding=rep)
    changed = jax.ShapeDtypeStruct((v_pad,), jnp.bool_, sharding=rep)
    if program == "gather":
        compiled = _compile(sharded._mesh_gather_program, rows, labels, sg, mesh)
    elif program == "modes":
        compiled = _compile(sharded._mesh_modes_program, rows, labels, sg, mesh)
    else:
        cap = rungs[-1] if program == "rewrite:top" else rungs[1]
        compiled = _compile(
            sharded._mesh_rewrite_program, rows, labels, changed, sg, mesh, cap=cap)
    held, text = compiled.memory_analysis(), compiled.as_text()
    assert " conditional(" not in text and " while(" not in text
    assert ("all-gather" in text) == (program == "modes")
    counted = carried_job_transients(
        sharded.shard_plan_shapes(sg, largest), top_rung=rungs[-1], shards=4
    )[program.split(":")[0]]
    assert held.temp_size_in_bytes <= counted
    if program == "modes":  # reads the rows, writes V-sized results
        assert held.alias_size_in_bytes == 0
        return
    assert held.alias_size_in_bytes >= 4 * slots
    # (a prefetch into fast memory, `S(1)`, is no copy in the chip's HBM:
    # at this size the rows fit there, at the cell's they do not)
    copies = [ln for ln in text.splitlines()
              if (" copy(" in ln or " copy-start(" in ln)
              and f"s32[{slots}]" in ln and "S(1)" not in ln]
    assert copies == []
