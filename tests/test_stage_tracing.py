"""Names on the device timeline and stages inside the chapters (ISSUE 25,
docs/OBSERVABILITY.md "Device timeline"): registered named scopes in the
compiled programs, stage spans where host and device alternate, a record
per compile, and a ``profile_dir`` capture that reduces itself.

All on the CPU backend. What only a chip can show (device seconds by
scope) is tested on hand-made event lists.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs import devtrace, schema
from graphmine_tpu.obs.costmodel import timed_fixpoint
from graphmine_tpu.obs.spans import Tracer, stage_span
from graphmine_tpu.pipeline.config import PipelineConfig
from graphmine_tpu.pipeline.driver import run_pipeline
from graphmine_tpu.pipeline.metrics import MetricsSink, maybe_profile

pytestmark = pytest.mark.obs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(_REPO, "tools") not in sys.path:
    sys.path.insert(0, os.path.join(_REPO, "tools"))


# ---- (1) the scope lint ----------------------------------------------------


def test_scope_lint_package_is_clean():
    import schema_lint

    assert schema_lint.scope_violations() == []
    used = {name for name, _, _ in schema_lint.scan_scopes()}
    assert used == set(schema.DEVICE_SCOPES)  # every registered scope is used
    assert {"lpa_bucketed", "row_gather", "ivf", "search_topk"} <= used


def test_scope_lint_catches_unregistered_and_computed_names(tmp_path):
    import schema_lint

    (tmp_path / "mod.py").write_text(
        "import jax\n"
        "def f(x, name, ridx):\n"
        '    with jax.named_scope("lpa_bucketed"):\n'
        '        with jax.named_scope("not_a_registered_scope"):\n'
        "            x = x + 1\n"
        "        with jax.named_scope(name):\n"
        "            x = x + 1\n"
        '        width = f"w{ridx.shape[1]}"\n'
        "        with jax.named_scope(width):\n"
        "            return x + 1\n"
    )
    out = schema_lint.scope_violations(str(tmp_path), check_unused=False)
    assert len(out) == 2, out
    assert "not_a_registered_scope" in out[0] and "mod.py:4" in out[0]
    assert "computed" in out[1] and "mod.py:6" in out[1]


def test_stage_lint_package_is_clean():
    import schema_lint

    assert schema_lint.stage_violations() == []
    used = {arg.strip("\"'") for arg, _, _ in schema_lint.scan_stages()}
    assert used == set(schema.STAGE_SPANS)  # every registered stage is opened
    assert {"ivf_search", "triangles_host", "publish_write", "delta_repair"} <= used


def test_stage_lint_catches_unregistered_and_computed_names(tmp_path):
    import schema_lint

    (tmp_path / "mod.py").write_text(
        "from graphmine_tpu.obs.spans import stage_span\n"
        "def f(sink, name):\n"
        '    with stage_span(sink, "publish_write", rows=3):\n'
        "        pass\n"
        "    with stage_span(\n"
        '        self.sink, "not_a_registered_stage"\n'
        "    ) as stage:\n"
        "        pass\n"
        "    with stage_span(sink, name):\n"
        "        pass\n"
    )
    out = schema_lint.stage_violations(str(tmp_path), check_unused=False)
    assert len(out) == 2, out
    assert "not_a_registered_stage" in out[0] and "mod.py:5" in out[0]
    assert "computed" in out[1] and "mod.py:9" in out[1]
    # the whole-package lint that tier-1 runs holds them too
    assert [v for v in schema_lint.violations(str(tmp_path)) if "stage" in v] == out


# ---- (2) the compiled programs carry their scopes --------------------------


def _graph():
    rng = np.random.default_rng(5)
    v, e = 96, 700
    src = rng.integers(0, v, e)
    dst = (src + rng.integers(1, v, e)) % v
    return build_graph(src, dst, num_vertices=v)


def _op_names(fn, *args, **static):
    text = jax.jit(fn, **static).lower(*args).compile().as_text()
    return {line.split('op_name="', 1)[1].split('"', 1)[0]
            for line in text.splitlines() if 'op_name="' in line}


def _scopes_in(op_names) -> set:
    return {devtrace.scope_of(n + "/op", schema.DEVICE_SCOPES | {"op"})
            for n in op_names}


def _superstep_case(family, algorithm):
    from graphmine_tpu.ops import cc, lpa
    from graphmine_tpu.ops.bucketed_mode import (
        BucketedModePlan,
        lpa_superstep_bucketed,
    )

    g = _graph()
    labels = jnp.arange(g.num_vertices, dtype=jnp.int32)
    if family == "sort":
        fn = lpa.lpa_superstep if algorithm == "lpa" else cc.cc_superstep
        return fn, (labels, g)
    plan = BucketedModePlan.from_graph(g, with_send=True)
    if algorithm == "lpa":
        return lpa_superstep_bucketed, (labels, g, plan)
    return cc.cc_superstep_bucketed, (labels, plan)


@pytest.mark.parametrize("family,algorithm,want", [
    ("bucketed", "lpa", {"lpa_bucketed/row_gather", "lpa_bucketed/row_mode",
                         "lpa_bucketed/write_back"}),
    ("bucketed", "cc", {"cc_bucketed/row_gather", "cc_bucketed/row_min",
                        "cc_bucketed/pointer_jump"}),
    ("sort", "lpa", {"lpa_sort/msg_gather", "lpa_sort/segment_mode"}),
    ("sort", "cc", {"cc_sort/msg_gather", "cc_sort/segment_min",
                    "cc_sort/pointer_jump"}),
])
def test_one_superstep_of_each_family_carries_its_scopes(family, algorithm, want):
    fn, args = _superstep_case(family, algorithm)
    names = _op_names(fn, *args)
    assert want <= _scopes_in(names), sorted(names)
    if family != "sort":  # the degree class rides as the third level
        assert any("/row_gather/w" in n for n in names), sorted(names)
    else:
        assert algorithm == "cc" or any(
            "/segment_mode/sort/" in n for n in names), sorted(names)


@pytest.mark.parametrize("family,want", [
    # its supersteps are the LPA chapter's program: lpa_bucketed/*, above
    ("bucketed", {"masked_lpa/mask", "masked_lpa/write_back"}),
    ("sort", {"masked_lpa/mask", "masked_lpa/msg_gather",
              "masked_lpa/segment_mode", "masked_lpa/write_back"}),
])
def test_the_masked_pass_of_each_family_carries_its_scopes(family, want):
    from graphmine_tpu.ops import outliers
    from graphmine_tpu.ops.bucketed_mode import _HIST_MIN_DEG, BucketedModePlan

    v = _HIST_MIN_DEG + 64  # vertex 0 is a hub: the histogram rows are there
    rng = np.random.default_rng(5)
    src = np.concatenate([np.zeros(v - 1, np.int64), rng.integers(1, v, 900)])
    dst = np.concatenate([np.arange(1, v), rng.integers(1, v, 900)])
    g = build_graph(src, dst, num_vertices=v)
    comm = jnp.asarray(np.arange(v) % 2, jnp.int32)
    if family == "sort":
        names = _op_names(
            lambda *a: outliers._masked_lpa_sort(*a, max_iter=2), g, comm)
    else:
        plan = BucketedModePlan.from_graph(g, with_send=True)
        assert plan.hist_vertex_ids is not None
        names = _op_names(outliers._mask_plan_rows, plan, comm) | _op_names(
            outliers._keep_unreached, comm, comm, plan.hist_vertex_ids,
            jnp.ones((1,), bool))
    assert want <= _scopes_in(names), sorted(names)


def test_the_loop_around_a_superstep_names_its_own_bookkeeping():
    from graphmine_tpu.ops.cc import _connected_components
    from graphmine_tpu.ops.lpa import _label_propagation

    g = _graph()
    lpa = _scopes_in(_op_names(lambda gg: _label_propagation(gg, 2), g))
    assert {"superstep/changed_count", "lpa_sort/segment_mode"} <= lpa
    cc = _scopes_in(_op_names(lambda gg: _connected_components(gg), g))
    assert {"superstep/changed_count", "superstep/converged"} <= cc


@pytest.mark.parametrize("program,want", [
    ("gather", {"lpa_bucketed/row_gather"}),
    ("rewrite", {"delta/compact", "delta/expand", "delta/scatter"}),
    ("modes", {"lpa_bucketed/row_mode", "lpa_bucketed/write_back",
               "superstep/changed_count"}),
])
def test_the_carried_rows_programs_name_their_scopes(program, want):
    """Each program of the host-stepped job carries its own scopes and none
    of another's: a capture books a superstep's update and its reduce apart."""
    from graphmine_tpu.ops import lpa
    from graphmine_tpu.ops.bucketed_mode import (
        BucketedModePlan,
        row_slots,
        with_slot_index,
    )

    g = _graph()
    plan = with_slot_index(BucketedModePlan.from_graph(g, with_send=True))
    rows = jnp.zeros((row_slots(plan),), jnp.int32)
    labels = jnp.arange(g.num_vertices, dtype=jnp.int32)
    if program == "gather":
        names = _op_names(lpa._gather_program, rows, labels, plan)
    elif program == "rewrite":
        names = _op_names(
            lambda *a: lpa._rewrite_program(*a, cap=64), rows, labels,
            labels > 90, plan)
    else:
        names = _op_names(lpa._modes_program, rows, labels, plan)
    got = _scopes_in(names)
    assert want <= got, sorted(got)
    others = {"lpa_bucketed/row_gather", "delta/scatter", "lpa_bucketed/row_mode"} - want
    assert not others & got, sorted(got)


def test_ivf_search_and_merge_and_lof_carry_their_scopes():
    from graphmine_tpu.ops.ann import _merge_tiles, _search_clusters
    from graphmine_tpu.ops.lof import lof_from_knn

    k, f = 4, 8
    q = jnp.ones((16, f), jnp.float32)
    m = jnp.ones((32, f), jnp.float32)
    search = _scopes_in(_op_names(
        lambda *a: _search_clusters(*a, k=k), q, jnp.arange(16, dtype=jnp.int32),
        m, jnp.arange(32, dtype=jnp.int32), jnp.ones((32,), bool)))
    assert {"ivf/search_distance", "ivf/search_topk"} <= search
    merge = _scopes_in(_op_names(
        lambda *a: _merge_tiles(*a, k=k), jnp.ones((65, k), jnp.float32),
        jnp.zeros((65, k), jnp.int32), jnp.zeros((2, 8, 3), jnp.int32)))
    assert {"ivf/merge_gather", "ivf/merge_topk"} <= merge
    lof = _scopes_in(_op_names(
        lambda d2, idx: lof_from_knn(d2, idx, k), jnp.ones((16, k), jnp.float32),
        jnp.zeros((16, k), jnp.int32)))
    assert {"lof/reach", "lof/lrd", "lof/score"} <= lof


# ---- (3) stage spans of a whole run ----------------------------------------


def _parquet(tmp_path, v=1536, e=24000, seed=3):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    block = v // 12
    src = rng.integers(0, v, e)
    near = np.minimum((src // block) * block + rng.integers(0, block, e), v - 1)
    far = rng.integers(0, v, e)
    dst = np.where(rng.random(e) < 0.85, near, far)
    names = np.array([f"d{i:05d}.example" for i in range(v)])
    path = str(tmp_path / "outlinks.parquet")
    pq.write_table(pa.table({
        "_c1": pa.array(names[src]).dictionary_encode(),
        "_c2": pa.array(names[dst]).dictionary_encode(),
    }), path)
    return path


def _spans(records):
    return [r for r in records if r["phase"] == "span"]


def _by_name(records, name):
    return [r for r in _spans(records) if r["name"] == name]


_LOF_STAGES_EXACT = ("lof_features", "knn_exact", "lof_formula")
_LOF_STAGES_IVF = ("lof_features", "ivf_train", "ivf_probe", "ivf_lists",
                   "ivf_search", "ivf_merge", "lof_formula")


@pytest.mark.parametrize("ivf", [False, True], ids=["exact", "ivf"])
def test_run_pipeline_yields_every_stage_span_and_the_same_answers(
    tmp_path, monkeypatch, ivf,
):
    if ivf:  # the crossover lowered, as tests/test_lof_policy.py pins it
        monkeypatch.setenv("GRAPHMINE_LOF_IVF_MIN_N", "1024")
    res = run_pipeline(PipelineConfig(
        data_path=_parquet(tmp_path), batch_rows=10000, max_iter=3,
        outlier_method="both", lof_k=16, num_devices=1,
    ))
    recs = res.metrics.records
    assert schema.validate_records(recs) == []
    assert not res.metrics.of_phase("ivf_fallback")
    by_id = {r["span_id"]: r for r in _spans(recs)}

    def chapter_of(span):
        path = span["span_path"].split("/")
        return path[1]

    # ingest: a decode and an intern per batch, one concat, all under load
    decodes = [r for r in _by_name(recs, "ingest_decode") if "rows" in r]
    interns = _by_name(recs, "ingest_intern")
    assert [r["batch"] for r in decodes] == [0, 1, 2]
    assert [r["batch"] for r in interns] == [0, 1, 2]
    assert sum(r["rows"] for r in decodes) == 24000
    assert interns[-1]["names_so_far"] == res.edge_table.num_vertices
    (concat,) = _by_name(recs, "ingest_concat")
    assert concat["rows"] == 24000
    assert {chapter_of(r) for r in decodes + interns + [concat]} == {"load"}

    # recursive-LPA outliers: the device pass, then the host report
    (masked,) = _by_name(recs, "masked_lpa")
    (decile,) = _by_name(recs, "decile_report")
    assert chapter_of(masked) == chapter_of(decile) == "outliers_recursive_lpa"
    assert decile["sub_communities"] == len(res.outliers.sub_sizes)
    # the masked pass ran on the LPA chapter's bucket rows; every message
    # is a slot of them, and the mask left the intra-community ones
    assert masked["family"] == "bucketed"
    g = res.graph
    intra = int((res.labels[np.asarray(g.msg_send)]
                 == res.labels[np.asarray(g.msg_recv)]).sum())
    assert masked["kept_slots"] == intra
    assert g.num_messages <= masked["padded_slots"] <= 1.5 * g.num_messages
    # ... through the LPA chapter's compiled superstep: the pass compiles a
    # mask and a fix-up, and no superstep program of its own to hold in HBM
    compiled = {r["fun_name"] for r in res.metrics.of_phase("compile")
                if r["span_path"].startswith(masked["span_path"])}
    assert not any("superstep" in name for name in compiled), compiled

    # LOF: every stage of the path that ran, with parent and counts
    (lof,) = _by_name(recs, "outliers_lof")
    under_lof = [r for r in _spans(recs)
                 if r["span_path"].startswith(lof["span_path"] + "/")]
    names = [r["name"] for r in under_lof]
    for stage in _LOF_STAGES_IVF if ivf else _LOF_STAGES_EXACT:
        assert stage in names, (stage, names)
    (features,) = _by_name(under_lof, "lof_features")
    assert features["parent_span_id"] == lof["span_id"]
    for child in ("triangles_host", "triangles_device", "features_device"):
        (span,) = _by_name(under_lof, child)
        assert by_id[span["parent_span_id"]]["name"] == "lof_features"
    assert _by_name(under_lof, "triangles_host")[0]["wedges"] > 0
    n = res.edge_table.num_vertices
    if ivf:
        assert names.count("ivf_lists") == 2  # before the search, and after
        (train,) = _by_name(under_lof, "ivf_train")
        assert (train["n"], train["k"]) == (n, 16) and train["n_clusters"] >= 8
        lists, take = _by_name(under_lof, "ivf_lists")
        assert lists["n_sub"] >= train["n_clusters"] and lists["l_max"] > 16
        # the index is built where the probe table lies: no table of n or
        # n_pairs rows is fetched or handed on as a host array (4 n bytes
        # is one int32 column of the probe table)
        assert 0 < lists["host_bytes"] < 4 * n < 1 << 20
        assert take["host_bytes"] == 0 and take["p_max"] == lists["p_max"]
        (search,) = _by_name(under_lof, "ivf_search")
        assert search["n_pairs"] == lists["n_pairs"] >= n
        assert search["chunk_rows"] == lists["chunk_rows"] >= 1
        assert _by_name(under_lof, "ivf_merge")[0]["p_max"] == lists["p_max"]
    else:
        (exact,) = _by_name(under_lof, "knn_exact")
        assert (exact["n"], exact["k"]) == (n, 16)
    # the chapter's children cover it: what no stage names stays small
    top = [r for r in under_lof
           if r["name"] in _LOF_STAGES_IVF + _LOF_STAGES_EXACT]
    assert sum(r["seconds"] for r in top) >= 0.9 * lof["seconds"]

    # the same answers as the ops give with sink=None, bit for bit
    from graphmine_tpu.ops.features import standardize, vertex_features
    from graphmine_tpu.ops.lof import lof_scores
    from graphmine_tpu.ops.lpa import label_propagation
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers

    labels = np.asarray(label_propagation(g, max_iter=3))
    assert (labels == res.labels).all()
    # no plan here: the sort family, against the pipeline's bucketed one
    sub = recursive_lpa_outliers(g, jnp.asarray(labels)).sub_labels
    assert (sub == res.outliers.sub_labels).all()
    scores = np.asarray(lof_scores(
        standardize(vertex_features(g, jnp.asarray(labels))), k=16))
    assert scores.tobytes() == np.asarray(res.lof).tobytes()


def test_masked_lpa_span_names_the_sort_family_when_no_plan_is_passed():
    from graphmine_tpu.ops.outliers import recursive_lpa_outliers

    g = _graph()
    sink = MetricsSink(tracer=Tracer())
    comm = jnp.asarray(np.arange(g.num_vertices) % 3, jnp.int32)
    recursive_lpa_outliers(g, comm, max_iter=2, sink=sink)
    (masked,) = _by_name(sink.records, "masked_lpa")
    assert masked["family"] == "sort"
    assert masked["padded_slots"] == g.num_messages
    comm_np = np.asarray(comm)
    assert masked["kept_slots"] == int(
        (comm_np[np.asarray(g.msg_send)] == comm_np[np.asarray(g.msg_recv)]).sum())


def test_stage_span_without_a_sink_records_nothing_and_syncs_nothing():
    class Lazy:
        def block_until_ready(self):
            raise AssertionError("synced without a sink")

    with stage_span(None, "ivf_search", n_pairs=3) as stage:
        stage.note(k=1)
        assert isinstance(stage.sync(Lazy()), Lazy)
    assert stage.seconds is None  # no span, no seconds: not a clock of its own
    sink = MetricsSink()  # a sink without a tracer: still nothing to record
    with stage_span(sink, "ivf_search") as stage:
        stage.note(k=1)
    assert sink.records == [] and stage.seconds is None
    traced = MetricsSink(tracer=Tracer())
    synced = []

    class Ready:
        def block_until_ready(self):
            synced.append(1)

    with stage_span(traced, "ivf_search", n_pairs=3) as stage:
        stage.sync((Ready(), Ready()))
        stage.note(chunk_rows=2)
    (rec,) = traced.of_phase("span")
    assert (rec["n_pairs"], rec["chunk_rows"], len(synced)) == (3, 2, 2)
    # a caller that writes a summary record reads the span's seconds
    assert rec["seconds"] == round(stage.seconds, 4)
    traced.span_attrs(ignored=True)  # the root span writes no record
    assert "ignored" not in traced.tracer.root.attrs


# ---- (4) the reduction of a capture, on hand-made events -------------------

_REG = frozenset({"lpa_bucketed", "hist", "row_gather", "superstep",
                  "changed_count", "sort"})


def _op(name, start, end, op_name=None):
    return (name, start, end, {} if op_name is None else {"tf_op": op_name})


def test_scope_of_takes_the_first_two_registered_levels():
    f = devtrace.scope_of
    assert f("jit(f)/lpa_bucketed/while/body/closed_call/row_gather/w8/gather:",
             _REG) == "lpa_bucketed/row_gather"
    assert f("jit(f)/lpa_bucketed/hist/gather", _REG) == "lpa_bucketed/hist"
    # the primitive is never a scope, even when a scope shares its name
    assert f("jit(f)/lpa_bucketed/sort", _REG) == "lpa_bucketed"
    assert f("jit(f)/lpa_bucketed/sort/sort", _REG) == "lpa_bucketed/sort"
    assert f("jit(_mean)/div:", _REG) == "unscoped"
    assert f("", _REG) == "unscoped"


def test_reduce_capture_groups_counts_a_loop_body_once_and_books_by_span():
    ops = {"/device:TPU:0": [
        _op("while.1", 1.0, 9.0, "jit(f)/lpa_bucketed/while"),  # spans its body
        _op("fusion.1", 1.0, 4.0, "jit(f)/lpa_bucketed/while/body/hist/gather:"),
        _op("fusion.2", 4.0, 6.0, "jit(f)/lpa_bucketed/while/body/row_gather/w4/gather:"),
        _op("fusion.3", 6.0, 7.0, "jit(f)/lpa_bucketed/while/body/row_gather/w8/gather:"),
        _op("fusion.4", 7.0, 8.5, "jit(f)/superstep/while/body/changed_count/reduce_sum:"),
        _op("copy.1", 8.5, 9.0),                              # no op_name at all
        _op("fusion.9", 20.0, 21.0, "jit(g)/div:"),           # no registered level
    ]}
    modules = {"/device:TPU:0": [
        ("jit_f(123)", 1.0, 9.0, {}), ("jit_g(77)", 20.0, 21.0, {})]}
    spans = [("run/lpa", 0.5, 30.0, {}), ("run/lpa/stage", 1.5, 10.0, {}),
             ("run/census", 40.0, 50.0, {})]
    out = devtrace.reduce_capture(ops, modules, spans, _REG)
    rows = {(r["module"], r["scope"], r["stage_path"]):
            (r["device_seconds"], r["events"]) for r in out["scopes"]}
    assert rows == {
        ("jit_f", "lpa_bucketed/hist", "run/lpa/stage"): (3.0, 1),
        ("jit_f", "lpa_bucketed/row_gather", "run/lpa/stage"): (3.0, 2),
        ("jit_f", "superstep/changed_count", "run/lpa/stage"): (1.5, 1),
        ("jit_f", "unscoped", "run/lpa/stage"): (0.5, 1),
        ("jit_g", "unscoped", "run/lpa"): (1.0, 1),
    }
    # the while is not a leaf: 9 s of leaves, not 17
    assert out["busy_seconds"] == pytest.approx(9.0)
    assert out["scopes"][0]["device_seconds"] == 3.0  # longest first
    idle = {r["chapter_path"]: r for r in out["idle"]}
    assert set(idle) == {"run/lpa", "run/census"}  # chapters only, not stages
    assert idle["run/lpa"]["busy_seconds"] == pytest.approx(9.0)
    assert idle["run/lpa"]["idle_seconds"] == pytest.approx(20.5)
    assert idle["run/census"]["busy_seconds"] == 0.0


def test_a_program_is_booked_to_the_span_open_at_its_middle():
    # the device's clock a little ahead of the host's: the program seems
    # to start before the span that launched it
    ops = {"d": [_op("fusion.1", 0.9990, 1.5, "jit(f)/lpa_bucketed/hist/gather:")]}
    modules = {"d": [("jit_f(1)", 0.9990, 1.5, {})]}
    spans = [("run/a", 0.0, 1.0, {}), ("run/b", 1.0, 2.0, {})]
    (row,) = devtrace.reduce_capture(ops, modules, spans, _REG)["scopes"]
    assert row["stage_path"] == "run/b"


def test_reduce_capture_without_a_device_plane_reads_nothing():
    out = devtrace.reduce_capture({}, {}, [("run/load", 0.0, 1.0, {})], _REG)
    assert out == {"scopes": [], "idle": [], "devices": 0, "busy_seconds": 0}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _event_metadata(ident, name, display="", tf_op=None):
    body = _field(1, ident) + _field(2, name) + _field(4, display)
    if tf_op is not None:  # XStat{metadata_id=9, str_value}
        body += _field(5, _field(1, 9) + _field(5, tf_op))
    return _field(4, _field(1, ident) + _field(2, body))


def _line(name, t0_ns, events):
    body = _field(2, name) + _field(3, t0_ns)
    for ident, offset_ps, duration_ps in events:
        body += _field(4, _field(1, ident) + _field(2, offset_ps)
                       + _field(3, duration_ps))
    return _field(3, body)


def test_read_xplane_reads_a_hand_made_file(tmp_path):
    stat_names = _field(5, _field(1, 9) + _field(2, _field(1, 9) + _field(2, "tf_op")))
    device = _field(1, (
        _field(2, "/device:TPU:0") + stat_names
        + _event_metadata(1, "%fusion.7 = s32[8] fusion(...)", "fusion.7",
                          "jit(f)/lpa_bucketed/hist/gather:")
        + _event_metadata(2, "jit_f(55)")
        + _line("XLA Ops", 1_000_000_000, [(1, 500_000_000_000, 250_000_000_000)])
        + _line("XLA Modules", 1_000_000_000, [(2, 500_000_000_000, 300_000_000_000)])
        + _line("Steps", 0, [(2, 0, 5)])))
    host = _field(1, (
        _field(2, "/host:CPU")
        + _event_metadata(1, "run/cdlp") + _event_metadata(2, "PjitFunction(f)")
        + _event_metadata(3, "runner")
        + _line("python", 1_000_000_000, [(1, 0, 2_000_000_000_000),
                                          (2, 10, 20), (3, 10, 20)])))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + device)
    ops, modules, spans = devtrace.read_xplane(str(path), "run")
    assert ops == {"/device:TPU:0": [
        ("fusion.7", 1.5, 1.75, {"tf_op": "jit(f)/lpa_bucketed/hist/gather:"})]}
    assert modules == {"/device:TPU:0": [("jit_f(55)", 1.5, 1.8, {})]}
    assert spans == [("run/cdlp", 1.0, 3.0, {})]  # "runner" is not under "run/"
    (row,) = devtrace.reduce_capture(ops, modules, spans, _REG)["scopes"]
    assert (row["module"], row["scope"], row["stage_path"]) == (
        "jit_f", "lpa_bucketed/hist", "run/cdlp")


# ---- (5) a record per compile ----------------------------------------------


def test_a_forced_retrace_emits_compile_records_and_marks_the_window_cold():
    x = jnp.arange(8.0)

    def retraced():  # a fresh closure: lax.map traces and compiles it anew
        return jax.lax.map(lambda t: t * 3.0 + x[0], x)

    retraced()  # whatever else a first call compiles is compiled here
    sink = MetricsSink(tracer=Tracer())
    with sink.span("ivf_search"):
        out, _, cold = timed_fixpoint(retraced)
    assert cold is True and out.shape == (8,)
    recs = sink.of_phase("compile")
    assert schema.validate_records(recs) == []
    backend = [r for r in recs if r["stage"] == "backend"]
    assert [r["fun_name"] for r in backend] == ["jit(scan)"]
    assert backend[0]["cache_hit"] in (False, True)
    assert {r["stage"] for r in recs} == {"trace", "lower", "backend"}
    assert all(r["span_path"] == "run/ivf_search" for r in recs)
    assert all(r["cache_hit"] is None for r in recs if r["stage"] != "backend")
    reg = sink.registry
    assert reg.counter("graphmine_compiles_total").value == 1
    assert reg.counter("graphmine_compile_seconds_total").value == pytest.approx(
        sum(r["seconds"] for r in recs), abs=1e-4)

    # a warm window of a cached program is not cold, and a closed sink
    # hears of no compile
    step = jax.jit(lambda v: v + 1.0)
    step(x).block_until_ready()
    before = len(sink.records)
    _, _, cold = timed_fixpoint(lambda: step(x))
    assert cold is False and len(sink.records) == before
    sink.finalize(os.devnull)
    retraced()
    assert len(sink.records) == before


# ---- (7) profile_dir around a whole run ------------------------------------


def test_profile_dir_covers_the_whole_run_on_a_backend_with_no_device_plane(
    tmp_path,
):
    prof = tmp_path / "prof"
    mo = str(tmp_path / "m.jsonl")
    res = run_pipeline(PipelineConfig(
        data_path=_parquet(tmp_path, v=256, e=3000), max_iter=2,
        outlier_method="recursive_lpa", num_devices=1,
        profile_dir=str(prof), metrics_out=mo,
    ))
    (cap,) = res.metrics.of_phase("profile_capture")
    assert cap["ok"] is True and cap["dir"] == str(prof)
    assert cap["devices"] == 0 and cap["busy_seconds"] == 0
    assert cap["trace_bytes"] == os.path.getsize(devtrace.newest_xplane(str(prof)))
    assert not res.metrics.of_phase("device_scope")
    assert not res.metrics.of_phase("device_idle")
    # one capture, stopped after the last chapter and before run_end
    phases = [json.loads(line)["phase"] for line in open(mo)]
    assert phases.index("profile_capture") > phases.index("outliers_recursive_lpa")
    assert phases[-1] == "run_end"
    # the capture holds the program's spans, named by their paths
    _, _, spans = devtrace.read_xplane(devtrace.newest_xplane(str(prof)), "run")
    assert {"run/load", "run/lpa", "run/outliers_recursive_lpa/rung:primary/masked_lpa"
            } <= {s[0] for s in spans}
    assert schema.validate_records(res.metrics.records) == []


def test_a_capture_that_cannot_be_reduced_is_still_recorded(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    sink = MetricsSink()
    with maybe_profile(str(tmp_path), sink=sink):  # nothing was written there
        pass
    (cap,) = sink.of_phase("profile_capture")
    assert cap["ok"] is True and "FileNotFoundError" in cap["reduce_error"]
