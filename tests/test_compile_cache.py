"""The persistent compile cache has one owner and one place
(graphmine_tpu/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` when the
operator set it, else ``<checkout>/.jax_cache`` — a fixed path, because
the directory is part of what makes an entry findable again."""

import os
import subprocess
import sys

import jax
import pytest

from graphmine_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, ".jax_cache")
# the two knobs PR 22 removed, spelled in halves so that a grep for a
# leftover reader of either finds none
OLD_DIR_KNOB = "GRAPHMINE_" + "COMPILE_CACHE"
OLD_OFF_KNOB = "GRAPHMINE_NO_" + "COMPILE_CACHE"


@pytest.fixture
def config_updates(monkeypatch):
    """Record what the code sets on jax's config, without setting it."""
    calls = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.__setitem__(name, value)
    )
    for name in ("JAX_COMPILATION_CACHE_DIR", OLD_DIR_KNOB, OLD_OFF_KNOB):
        monkeypatch.delenv(name, raising=False)
    return calls


def test_env_var_set_means_code_sets_no_directory(config_updates, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/operator/dir")
    compile_cache.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in config_updates


def test_unset_means_the_checkout_cache_everywhere(config_updates, tmp_path):
    compile_cache.enable_compile_cache()
    first = config_updates.pop("jax_compilation_cache_dir")
    compile_cache.enable_compile_cache()
    assert first == config_updates["jax_compilation_cache_dir"] == CHECKOUT_CACHE
    # another process, another cwd, another $HOME: the same directory
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(HOME=str(tmp_path), PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from graphmine_tpu.compile_cache import enable_compile_cache; "
         "print(enable_compile_cache())"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env, check=True,
    )
    assert out.stdout.strip() == CHECKOUT_CACHE
    assert os.listdir(tmp_path) == []  # nothing under $HOME or the cwd


def test_removed_knobs_are_ignored(config_updates, monkeypatch, tmp_path):
    monkeypatch.setenv(OLD_DIR_KNOB, str(tmp_path / "elsewhere"))
    monkeypatch.setenv(OLD_OFF_KNOB, "1")
    compile_cache.enable_compile_cache()
    assert config_updates["jax_compilation_cache_dir"] == CHECKOUT_CACHE
    with pytest.raises(TypeError):
        compile_cache.enable_compile_cache(str(tmp_path))  # no default_dir
