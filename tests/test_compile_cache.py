"""The compile-cache helper: JAX_COMPILATION_CACHE_DIR wins when set,
otherwise compiled programs land in the checkout's fixed .jax_cache/."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

import repro.compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(cc.ENV, raising=False)
    path = cc.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compiled_programs_land_in_the_default_dir(
    monkeypatch, tmp_path, restore_cache_config
):
    monkeypatch.delenv(cc.ENV, raising=False)
    monkeypatch.setattr(cc, "CACHE_DIR", str(tmp_path / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    path = cc.use_compile_cache()
    jax.jit(lambda x: jnp.cos(x) * 7)(jnp.ones((5,))).block_until_ready()
    assert os.listdir(path)
