"""REVIEW of PR 43: what a trip of the dirty reduce costs in its two forms on the
narrow coarse widths (32, 64, 128): many rows a trip by one gather
(``_DIRTY_GATHER_SLOTS = 2048``: 64 / 32 / 16 rows) against 8 rows a trip by
``dynamic_slice`` (the form every wider width takes).

    python _proof/dirty_trip_forms.py _proof/g500_24_shapes.json '{"32": 1200, "64": 300, ...}'

``lpa_modes_from_dirty_rows`` alone, on a plan of the shapes file's classes
(no hubs: their histograms are the same program in both forms), flat rows of
random labels (a sort network and a pairwise count do not read the values) and
a dirty list with the given count of rows a coarse width (the replay's,
``rows_by_coarse_width``), drawn at random inside the width's classes. Host
clock around N back-to-back calls ended by one wait; a list with one width's
rows alone, less the empty list's seconds, is that width's loop. One JSON line
a form and list."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    said = json.load(open(sys.argv[1]))
    counts = {int(k): int(n) for k, n in json.loads(sys.argv[2]).items()}
    calls = int(sys.argv[3]) if len(sys.argv) > 3 else 20

    import jax
    import jax.numpy as jnp
    import numpy as np

    import importlib

    bm = importlib.import_module("graphmine_tpu.ops.bucketed_mode")  # ops exports a function of that name
    from graphmine_tpu.ops.superstep_policy import delta_rungs

    v, m, s = said["num_vertices"], said["num_messages"], said["slots"]
    classes = [(int(n), int(w)) for n, w in said["classes"]]
    widths = [w for _, w in classes]
    rowoffs = np.concatenate([[0], np.cumsum([n for n, _ in classes])])
    total = int(rowoffs[-1])
    length = min(delta_rungs(m)[0], total)
    groups = bm._dirty_groups(widths)
    print(json.dumps({"device": str(jax.devices()[0]), "v": v, "slots": s, "rows": total,
                      "list": length, "coarse_widths": [g[0] for g in groups],
                      "counts": counts}), flush=True)

    rows = jax.random.randint(jax.random.PRNGKey(0), (s,), 0, v, jnp.int32)
    labels = jnp.arange(v, dtype=jnp.int32)
    # a class's vertices: any distinct ones do
    vertex_ids = tuple(jnp.arange(int(lo), int(hi), dtype=jnp.int32)
                       for lo, hi in zip(rowoffs[:-1], rowoffs[1:]))
    shapes = tuple(jax.ShapeDtypeStruct(c, jnp.int32) for c in classes)

    def a_list(only=None):
        rng = np.random.default_rng(7)
        picked = []
        for coarse, c0, c1 in groups:
            lo, hi = int(rowoffs[c0]), int(rowoffs[c1])
            n = min(counts.get(coarse, 0), hi - lo)
            drawn = np.sort(rng.choice(hi - lo, size=n, replace=False)) + lo
            if only is None or coarse in only:
                picked.append(drawn)
        flat = np.concatenate(picked) if picked else np.zeros(0, np.int64)
        out = np.full(length, total, np.int32)
        out[:len(flat)] = flat
        return jnp.asarray(out), len(flat)

    narrow = [g[0] for g in groups if g[0] < 256]
    lists = {"all": a_list(), "none": a_list(only=()), "wide": a_list(
        only=[g[0] for g in groups if g[0] >= 256])}
    lists.update({f"w{c}": a_list(only=[c]) for c in narrow})

    results = {}
    for form, slots in (("gathered", 2048), ("sliced", 0)):
        bm._DIRTY_GATHER_SLOTS = slots  # read when the program is traced, below

        def reduce(rows, labels, dirty, vertex_ids):
            plan = bm.BucketedModePlan(
                vertex_ids=vertex_ids, msg_idx=None, num_vertices=v, num_messages=m,
                send_idx=shapes)
            return bm.lpa_modes_from_dirty_rows(rows, labels, dirty, plan)

        t0 = time.perf_counter()
        program = jax.jit(reduce).lower(rows, labels, lists["all"][0], vertex_ids).compile()
        compile_s = time.perf_counter() - t0
        ma = program.memory_analysis()
        print(json.dumps({"form": form, "compile_s": compile_s,
                          "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                          "code_bytes": getattr(ma, "generated_code_size_in_bytes", None)}),
              flush=True)
        for name, (dirty, n) in lists.items():
            out = program(rows, labels, dirty, vertex_ids)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(calls):
                out = program(rows, labels, dirty, vertex_ids)
            jax.block_until_ready(out)
            secs = (time.perf_counter() - t0) / calls
            results[form, name] = (secs, np.asarray(out[0]))
            print(json.dumps({"form": form, "list": name, "rows": n, "seconds": secs,
                              "dirty_rows": int(out[1]), "dirty_slots": int(out[2])}),
                  flush=True)
    for name in lists:
        g, c = results["gathered", name], results["sliced", name]
        none = results["gathered", "none"][0], results["sliced", "none"][0]
        print(json.dumps({"list": name, "equal": bool((g[1] == c[1]).all()),
                          "gathered_s": g[0], "sliced_s": c[0], "sliced_minus_gathered_s": c[0] - g[0],
                          "gathered_less_none_s": g[0] - none[0], "sliced_less_none_s": c[0] - none[1]}),
              flush=True)


if __name__ == "__main__":
    main()
