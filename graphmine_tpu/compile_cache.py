"""Persistent XLA compile-cache setup: the one owner of where it lives.

One superstep program takes tens of seconds to compile for the TPU at
scale, a cold pipeline minutes; every entry point (``run_pipeline``, the
snapshot server, ``benchmark/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` first so a repeat invocation finds what the
last one compiled.
"""

from __future__ import annotations

import os

# The cache directory is part of what makes an entry findable again, so it
# is a fixed path derived from this file's own location — never $HOME, a
# temp name, a pid or the time.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, and this
    function sets no directory in code. Unset: ``<checkout>/.jax_cache``.
    Idempotent.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir
