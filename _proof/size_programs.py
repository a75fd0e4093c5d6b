"""Step 0 (ISSUE 36): each program of the host-stepped carried-rows job
compiled for a described v5e from a plan of SHAPES (no data, no chip), one
program a process so that the peak RSS is that program's compile alone.

    python _proof/size_programs.py _proof/g500_24_shapes.json            # all, one child each
    python _proof/size_programs.py _proof/g500_24_shapes.json modes      # one, in this process
    python _proof/size_programs.py _proof/urand_24_shapes.json gather out.txt  # and its compiled text
    python _proof/size_programs.py _proof/g500_24_shapes.json pagerank_step  # gm.pagerank's iteration
    python _proof/size_programs.py _proof/g500_22_shapes.json wcc            # WCC's while_loop
    python _proof/size_programs.py _proof/g500_24_shapes.json rewrite:0:marked  # the rewrite that says which rows it wrote to
    python _proof/size_programs.py _proof/g500_24_shapes.json dirty_modes:0     # the reduce over those rows (ISSUE 43)
    python _proof/size_programs.py _proof/g500_24_shapes.json bfs_level         # the BFS job's row min (ISSUE 49);
                                                # also bfs_gather, bfs_rewrite:<place>, bfs_start, bfs_full_level
    python _proof/size_programs.py _proof/g500_24_shapes.json bfs_bottom_up   # the bottom-up level's one program (ISSUE 53);
                                                # bfs_unreached is its compaction, one program whatever the level

A shapes file of a MESH partition (``shards`` in it: _proof/mesh_shapes_and_k.py,
ISSUE 39) compiles the mesh job's programs (``parallel/sharded.py``) for the
described 2x2 instead; the bytes are then one chip's.

    python _proof/size_programs.py _proof/g500_25_x4_shapes.json

Prints one JSON line a program: temp / alias / argument / output / code
bytes of memory_analysis(), the count of copy-done in the compiled text,
compile seconds, peak RSS."""
import json, os, resource, subprocess, sys, time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def plan_of_shapes(said, sharding):
    import jax, jax.numpy as jnp
    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    def i32(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=sharding)

    v, m = said["num_vertices"], said["num_messages"]
    hubs = said["hubs"]
    return BucketedModePlan(
        vertex_ids=tuple(i32(n) for n, _ in said["classes"]), msg_idx=None,
        num_vertices=v, num_messages=m,
        send_idx=tuple(i32(n, w) for n, w in said["classes"]),
        hist_vertex_ids=i32(hubs) if hubs else None,
        hist_send=i32(said["hist_send"]) if hubs else None,
        hist_row_offset=i32(said["hist_row_offset"]) if hubs else None,
        out_ptr=i32(v + 1), out_slot=i32(m),
    )


def mesh_lowered(said, name, topo):
    """The mesh job's program ``name`` lowered over a partition of shapes."""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from graphmine_tpu.ops.superstep_policy import delta_rungs
    from graphmine_tpu.parallel import sharded
    from graphmine_tpu.parallel.mesh import make_mesh

    d, vc = said["shards"], said["chunk_size"]
    mesh = make_mesh(d, devices=topo.devices)
    axes = sharded._vertex_axes(mesh)

    def i32(*dims, dtype=jnp.int32, spec=None):
        spec = P(axes, *[None] * (len(dims) - 1)) if spec is None else spec
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    m_max, s = max(said["messages_per_shard"]), said["slots"]
    sg = sharded.ShardedGraph(
        msg_recv_local=None, msg_send=None, degrees=None,
        num_vertices=said["num_vertices"], chunk_size=vc, num_shards=d,
        bucket_send=tuple(i32(d, n, w) for n, w in said["classes"]),
        bucket_target=tuple(i32(d, n) for n, _ in said["classes"]),
        out_ptr=i32(d * (d * vc + 1)), out_slot=i32(d * m_max),
    )
    rows = i32(d * s)
    labels = i32(d * vc, spec=P())
    changed = i32(d * vc, dtype=jnp.bool_, spec=P())
    if name == "gather":
        return sharded._mesh_gather_program.lower(rows, labels, sg, mesh)
    if name == "modes":
        return sharded._mesh_modes_program.lower(rows, labels, sg, mesh)
    if name.startswith("rewrite:"):
        cap = delta_rungs(m_max)[int(name.split(":")[1])]
        return sharded._mesh_rewrite_program.lower(rows, labels, changed, sg, mesh, cap=cap)
    raise SystemExit(f"no program {name!r}")


def one(said, name, text_out=None):
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from graphmine_tpu.ops import lpa
    from graphmine_tpu.ops.superstep_policy import delta_rungs

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    v, s = said["num_vertices"], said["slots"]
    t0 = time.time()
    plan = rows = labels = changed = None
    if "shards" not in said:
        plan = plan_of_shapes(said, chip)
        rows = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=chip)
        labels = jax.ShapeDtypeStruct((v,), jnp.int32, sharding=chip)
        changed = jax.ShapeDtypeStruct((v,), jnp.bool_, sharding=chip)
    if plan is None:
        lowered = mesh_lowered(said, name, topo)
    elif name == "gather":
        lowered = lpa._gather_program.lower(rows, labels, plan)
    elif name == "modes":
        lowered = lpa._modes_program.lower(rows, labels, plan)
    elif name.startswith("rewrite:"):  # rewrite:<place>[:marked] (ISSUE 43)
        cap = delta_rungs(said["num_messages"])[int(name.split(":")[1])]
        lowered = lpa._rewrite_program.lower(
            rows, labels, changed, plan, cap=cap, marked=name.endswith(":marked"))
    elif name.startswith("dirty_modes:"):  # after the marked rewrite at <place> (ISSUE 43)
        cap = delta_rungs(said["num_messages"])[int(name.split(":")[1])]
        total = sum(n for n, _ in said["classes"])
        dirty = jax.ShapeDtypeStruct((min(cap, total),), jnp.int32, sharding=chip)
        lowered = lpa._dirty_modes_program.lower(rows, labels, dirty, plan)
    elif name.startswith("bfs_"):  # the BFS job's programs (ops/paths.py, ISSUE 49)
        from graphmine_tpu.ops import paths

        if name == "bfs_level":
            lowered = paths._level_program.lower(rows, labels, plan)
        elif name == "bfs_gather":
            lowered = paths._gather_program.lower(rows, labels, plan)
        elif name == "bfs_full_level":
            lowered = paths._full_level_program.lower(labels, plan)
        elif name == "bfs_start":
            one_source = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
            lowered = paths._start_program.lower(
                one_source, plan.out_ptr, slots=s, num_vertices=v)
        elif name == "bfs_unreached":
            lowered = paths._unreached_program.lower(labels, plan.out_ptr)
        elif name == "bfs_bottom_up":  # ISSUE 53: one program whatever the level's size
            from graphmine_tpu.ops.superstep_policy import bottom_up_chunk

            m = said["num_messages"]
            msg_send = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=chip)
            lowered = paths._bottom_up_program.lower(
                labels, labels, labels, labels, msg_send, plan.out_ptr,
                chunk=bottom_up_chunk(m))
        else:  # bfs_rewrite:<place>
            cap = delta_rungs(said["num_messages"])[int(name.split(":")[1])]
            lowered = paths._rewrite_program.lower(rows, labels, changed, plan, cap=cap)
    elif name in ("pagerank", "pagerank_step", "wcc"):
        import dataclasses
        import importlib

        from graphmine_tpu.graph.container import Graph

        # ops/__init__ exports the function `pagerank` under the module's name
        pagerank = importlib.import_module("graphmine_tpu.ops.pagerank")
        i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
        m = said["num_messages"]
        graph = Graph(src=i32(m // 2), dst=i32(m // 2), msg_recv=i32(m),
                      msg_send=i32(m), msg_ptr=i32(v + 1), num_vertices=v)
        plan = dataclasses.replace(plan, out_ptr=None, out_slot=None)
        if name == "pagerank":  # ISSUE 41: ten iterations as ONE program (not run)
            lowered = pagerank._pagerank_messages_jit.lower(
                graph, plan, 0.85, 10, None, None)
        elif name == "pagerank_step":  # the one iteration gm.pagerank steps from the host
            f32 = jax.ShapeDtypeStruct((v,), jnp.float32, sharding=chip)
            lowered = pagerank._bucketed_iteration.lower(
                f32, f32, f32, plan, 0.85, with_delta=False)
        else:  # WCC's loop, as connected_components(return_iterations=True) runs it
            from graphmine_tpu.ops import cc

            lowered = cc._connected_components.lower(graph, 0, True, plan)
    else:
        raise SystemExit(f"no program {name!r}")
    compiled = lowered.compile()
    secs = time.time() - t0
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    if text_out:
        with open(text_out, "w") as f:
            f.write(text)
    print(json.dumps({
        "program": name, "rows_bytes": 4 * s,
        "temp": ma.temp_size_in_bytes, "alias": ma.alias_size_in_bytes,
        "argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
        "code": ma.generated_code_size_in_bytes,
        "temp_over_rows": round(ma.temp_size_in_bytes / (4 * s), 4),
        "copy_done": text.count(" copy-done("), "compile_s": round(secs, 1),
        "peak_rss_gb": round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
    }), flush=True)


if __name__ == "__main__":
    said = json.load(open(sys.argv[1]))
    if len(sys.argv) > 2:
        one(said, *sys.argv[2:4])
    else:
        for name in ["gather", "rewrite:0", "rewrite:1", "rewrite:2", "rewrite:3", "modes",
                     "rewrite:0:marked", "dirty_modes:0"]:
            subprocess.run([sys.executable, os.path.abspath(__file__), sys.argv[1], name])
