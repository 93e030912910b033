"""The persistent compilation cache helper (launch/compile_cache.py)."""
from pathlib import Path

import jax

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_leaves_jax_config_untouched(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/host")
    assert enable_compile_cache() == "/set/by/the/host"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path      # same place every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
