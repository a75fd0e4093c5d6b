"""What the files that compile programs for a described TPU v5e share
(``tests/test_chip_compile.py``, ``tests/test_chip_compile_bfs.py``): the
described topology, the graphs and plans the programs are compiled over,
and the helpers that turn arrays into shapes placed on a described chip.

Everything built from the topology is built inside fixtures, and a file
that imports them gets its own (``scope="module"``): only one process may
hold libtpu, and under pytest-xdist every worker imports every test
module, so nothing here describes a chip at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip can be written to the
    # persistent cache but not read back without one: keep it off here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def planted():
    from graphmine_tpu.datasets import planted_anomaly_graph

    v = 1 << 16
    src, dst, _, _ = planted_anomaly_graph(v, 1_000_000, seed=0)
    return src, dst, v


@pytest.fixture(scope="module")
def fused_plan(planted):
    from graphmine_tpu.ops.bucketed_mode import build_graph_and_plan

    src, dst, v = planted
    return build_graph_and_plan(src, dst, num_vertices=v)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding),
        tree,
    )


def _shape_on(sharding):
    return lambda dims, dtype=jnp.int32: jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 15 << 30
    return compiled


@pytest.fixture(scope="module")
def flat_plan():
    """GAP Urand's plan at scale 16 (the benchmark's own generator at
    a = b = c = 0.25, as ``gap-urand-24``): two dozen narrow classes of
    like size, no hub."""
    import sys

    from graphmine_tpu.ops.bucketed_mode import BucketedModePlan

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    import generators

    u, v = generators.rmat_undirected(16, 16, 0.25, 0.25, 0.25, seed=2147483659)
    plan = BucketedModePlan.from_edges(u, v, 1 << 16)
    assert plan.hist_vertex_ids is None and len(plan.send_idx) > 20
    return plan
