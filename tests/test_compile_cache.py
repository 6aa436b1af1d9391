"""The entry points' persistent-compilation-cache location."""
import os

import jax

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.compile_cache_dir() == str(tmp_path)
    assert cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is configured
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    first, second = cache.compile_cache_dir(), cache.compile_cache_dir()
    assert first == second == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
