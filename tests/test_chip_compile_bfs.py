"""The BFS job's programs (``ops/paths.py``) compiled for a TPU v5e that is
described, not attached: ``tests/test_chip_compile.py``'s way, in a file of
its own so that a worker of the tier-1 run takes these compiles while
another takes that file's (``--dist loadfile``: a file is one worker's).
Nothing runs, so nothing here is a result or a time."""

import jax.numpy as jnp
import pytest

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


@pytest.mark.parametrize("graph, program", [
    *[("kronecker", p) for p in ("start", "gather", "rewrite", "level", "full_level",
                                 "unreached", "bottom_up")],
    # the rewrite is CDLP's under another scope, a minute a compile: once is enough
    *[("flat", p) for p in ("start", "gather", "level", "full_level")],
])
def test_bfs_job_programs_compile_for_v5e(
    one_chip, fused_plan, flat_plan, planted, program, graph
):
    """The BFS job's programs (ISSUE 49: ``ops/paths.py``), each compiled
    alone, beside CDLP's: the rows are the donated argument of the gather
    and of the rewrite, so both update the whole ``s32[S]`` buffer IN PLACE
    (aliased to the result, no copy and no temporary of its size), and the
    start program lays them out by a fill, with no gather and no
    temporary. The level reads the rows and writes V-sized results. Each
    program's temporaries are at or under what the admission counts for
    it (``carried_job_transients(..., reduce="min")``), on a skewed plan
    with hubs, whose histograms this job never builds, and on a flat one.
    ISSUE 50's two, as ISSUE 53 left them: the compaction of the unreached
    vertices' spans of the message CSR holds the one sort of a bottom-up
    level, and the level itself is ONE program whatever the level's size: a
    loop over chunks of places (the job's one ``while``), no sort (its
    scatters' indices are stated sorted), no row and no plan among its
    arguments, V-sized results."""
    from graphmine_tpu.obs.memmodel import carried_job_transients
    from graphmine_tpu.ops import paths
    from graphmine_tpu.ops.bucketed_mode import row_slots, with_slot_index
    from graphmine_tpu.ops.superstep_policy import bottom_up_chunk, delta_rungs

    plan = fused_plan[1] if graph == "kronecker" else flat_plan
    plan = _shapes(with_slot_index(plan), one_chip)
    v, slots = planted[2], row_slots(plan)
    top_rung = delta_rungs(plan.num_messages)[-1]
    chunk = bottom_up_chunk(plan.num_messages)
    shape = _shape_on(one_chip)
    rows, depth = shape((slots,)), shape((v,))
    counted = carried_job_transients(
        plan, top_rung=top_rung, reduce="min", bottom_up_chunk=chunk)
    if program == "start":
        compiled = _compile(paths._start_program, shape((1,)), plan.out_ptr,
                            slots=slots, num_vertices=v)
        limit = 8 * v
    elif program == "gather":
        compiled = _compile(paths._gather_program, rows, depth, plan)
        limit = counted["gather"]
    elif program == "rewrite":
        compiled = _compile(paths._rewrite_program, rows, depth,
                            shape((v,), jnp.bool_), plan, cap=top_rung)
        limit = counted["rewrite"]
    elif program == "level":
        compiled = _compile(paths._level_program, rows, depth, plan)
        limit = counted["row_min"]
    elif program == "unreached":  # the rewrite's sort less an operand
        compiled = _compile(paths._unreached_program, depth, shape((v + 1,)))
        limit = counted["rewrite"]
    elif program == "bottom_up":
        compiled = _compile(paths._bottom_up_program, depth, depth, depth, depth,
                            shape((plan.num_messages,)), shape((v + 1,)), chunk=chunk)
        limit = counted["bottom_up"]
    else:  # where the rows were not admitted: gathers, mins, keeps nothing
        compiled = _compile(paths._full_level_program, depth, plan)
        limit = counted["row_min"]
    held = compiled.memory_analysis()
    text = compiled.as_text()
    assert " conditional(" not in text
    # the bottom-up level's loop over chunks of places: no other program loops
    assert (" while(" in text) == (program == "bottom_up")
    assert held.temp_size_in_bytes <= limit
    if program in ("gather", "rewrite"):
        assert held.alias_size_in_bytes >= 4 * slots
    else:
        assert held.alias_size_in_bytes == 0
    if program == "start":
        assert " gather(" not in text and held.output_size_in_bytes >= 4 * slots
    assert (" sort(" in text) == (program in ("rewrite", "unreached"))
    if program == "bottom_up":  # a chunk's vectors, whatever the graph
        assert held.temp_size_in_bytes < 4 * 8 * chunk
    elif graph == "kronecker":  # wide classes, four hubs: well under the rows
        assert held.temp_size_in_bytes < 4 * slots // (4 if program != "level" else 1)


def test_the_bottom_up_level_fits_its_count_at_graph500_24s_shapes(one_chip):
    """The BFS cell's own shapes (``_proof/g500_24_shapes.json``): the one
    bottom-up program, a chunk of 2^19 places a trip, holds the level's
    V-vectors and a trip's chunk-long ones whatever U is (ISSUE 53:
    202,003,968 B compiled against 285,212,688 counted; the level it takes
    over from the full gather would have held 6 x 273 MB at a cap fitted to
    its 68.2 M places), at or under what the admission counts for it and
    far under the top rung's rewrite, so the job's largest program is what
    it was. One loop, the trips: a trip finds its first span where the one
    before it stopped."""
    import json
    import os

    from graphmine_tpu.obs.memmodel import carried_job_transients
    from graphmine_tpu.ops import paths
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan
    from graphmine_tpu.ops.superstep_policy import bottom_up_chunk, delta_rungs

    said = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "_proof", "g500_24_shapes.json")))
    shape = _shape_on(one_chip)
    v, m = said["num_vertices"], said["num_messages"]
    chunk = bottom_up_chunk(m)
    assert chunk == 1 << 19
    depth = shape((v,))
    compiled = _compile(paths._bottom_up_program, depth, depth, depth, depth,
                        shape((m,)), shape((v + 1,)), chunk=chunk)
    held, text = compiled.memory_analysis(), compiled.as_text()
    plan = BucketedModePlan(  # by shapes: what the admission counts from
        vertex_ids=tuple(shape((n,)) for n, _ in said["classes"]), msg_idx=None,
        num_vertices=v, num_messages=m,
        send_idx=tuple(shape((n, w)) for n, w in said["classes"]),
    )
    counted = carried_job_transients(
        plan, top_rung=delta_rungs(m)[-1], reduce="min", bottom_up_chunk=chunk)
    assert held.temp_size_in_bytes <= counted["bottom_up"] < counted["rewrite"] // 4
    assert held.temp_size_in_bytes < 4 * (4 * v + 8 * chunk)
    assert held.alias_size_in_bytes == 0
    assert text.count(" while(") == 1 and " sort(" not in text
