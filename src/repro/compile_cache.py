"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles the engine (chip_smoke.py and the
benchmark scripts) calls `use_compile_cache()` first, so a second run
reuses the executables of the first instead of compiling again. The
directory is fixed: JAX keys its entries by the program, and a directory
that changed from run to run would never be hit.
"""
from __future__ import annotations

import os

import jax

#: `<repo>/.jax_cache` — the directory tests/conftest.py uses too, and
#: listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to `DEFAULT_DIR`, and
    the variable is exported so that child processes share the cache.
    Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
