"""Virtual-CPU-mesh environment (single source of truth, no jax).

Any code that needs an N-device virtual CPU mesh — the TPU analog of the
reference's ``SparkContext("local[*]")`` (``Graphframes.py:12``) — must fix
the environment *before* jax is imported: the platform and the host device
count are read once, at backend start-up. This module builds that
environment for a child process (or for the current one, pre-import); it is
deliberately standalone (stdlib-only) so callers that must not trigger the
package ``__init__`` (which imports jax) can load it by file path::

    from importlib import util
    spec = util.spec_from_file_location("_envscrub", path_to_this_file)
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)

Used by ``__graft_entry__.dryrun_multichip`` and ``tests/conftest.py``.
"""

import os


def virtual_cpu_env(n_devices, base=None, override_count=True):
    """Return an environment dict for an ``n_devices`` virtual CPU mesh.

    - Forces ``JAX_PLATFORMS=cpu``.
    - Ensures ``--xla_force_host_platform_device_count=n_devices`` is in
      ``XLA_FLAGS``. With ``override_count=False`` an existing count flag
      (e.g. a caller's explicit device-count choice) is preserved.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "").split()
    has_count = any("xla_force_host_platform_device_count" in f for f in flags)
    if override_count:
        flags = [
            f for f in flags
            if "xla_force_host_platform_device_count" not in f
        ]
        has_count = False
    if not has_count:
        flags.append(f"--xla_force_host_platform_device_count={int(n_devices)}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
