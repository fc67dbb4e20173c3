"""The entry points' persistent compile cache: JAX_COMPILATION_CACHE_DIR
wins when set; otherwise the cache lives at the fixed ``.jax_cache/`` at
the checkout root."""
from pathlib import Path

import jax

from repro.utils import COMPILE_CACHE_DIR, enable_compile_cache


def test_compile_cache_follows_env_or_fixed_dir(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", prev)
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev   # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(COMPILE_CACHE_DIR)
        root = Path(__file__).resolve().parent.parent
        assert COMPILE_CACHE_DIR == root / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
