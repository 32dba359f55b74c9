"""repro.compile_cache: the persistent compilation cache stays where the
caller's environment puts it, or at one fixed directory."""
import os

import jax

from repro import compile_cache


def test_env_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    # the environment is JAX's own setting: nothing else is set in code
    assert jax.config.jax_compilation_cache_dir == was


def test_default_directory_is_fixed(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # restored after
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    was = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        first = compile_cache.use_compile_cache()
        assert first == os.path.join(repo, ".jax_cache")
        assert compile_cache.use_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
