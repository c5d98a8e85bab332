"""Persistent-compile-cache plumbing (core/compile_cache.py)."""

import os
import subprocess
import sys

import jax
import pytest

from deep_vision_tpu.core import compile_cache
from deep_vision_tpu.core.compile_cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_unset_env_uses_checkout_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    p = enable_compile_cache()
    assert p == os.path.join(REPO, ".jax_cache") == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == p


def test_env_set_names_no_directory_in_code(tmp_path, monkeypatch,
                                            restore_cache_config):
    """With the variable set the function reports it and leaves the
    directory to JAX — no ``jax_compilation_cache_dir`` update happens."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    assert enable_compile_cache() == str(tmp_path / "outer")
    assert "jax_compilation_cache_dir" not in updates


def test_env_dir_is_the_one_jax_writes(tmp_path):
    """End to end in a fresh process: JAX itself picks the variable up,
    and a compile that clears the persistence threshold lands there."""
    outer = tmp_path / "outer"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from deep_vision_tpu.core.compile_cache import enable_compile_cache\n"
        "p = enable_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == p, p\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
        ".block_until_ready()\n"
        "print(p)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(outer))
    env.pop("DEEP_VISION_TPU_NO_COMPILE_CACHE", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(outer)
    assert any(outer.iterdir()), "no cache entry written under the env dir"


def test_env_opt_out(monkeypatch):
    monkeypatch.setenv("DEEP_VISION_TPU_NO_COMPILE_CACHE", "1")
    assert enable_compile_cache() is None
