"""``repro.compile_cache``: where the persistent compile cache lands, and
that a second compile of a program loads it from there."""
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cache_dir_from_env(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_defaults_to_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.enable() == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(
        checkout / ".jax_cache")


def test_second_compile_loads_from_cache(monkeypatch, tmp_path,
                                         restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable()
    cc.reset_cache()
    x = jnp.arange(37, dtype=jnp.float32)
    fn = jax.jit(lambda v: jnp.cumsum(jnp.sin(v) * 0.731))

    before = compile_cache.stats()
    fn.lower(x).compile()
    mid = compile_cache.stats()
    assert mid["written"] == before["written"] + 1
    assert any(tmp_path.iterdir())
    jax.clear_caches()           # drop the in-memory executable
    fn.lower(x).compile()
    after = compile_cache.stats()
    assert after["loaded"] == mid["loaded"] + 1
    assert after["written"] == mid["written"]
