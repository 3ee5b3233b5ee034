"""JAX's persistent compilation cache, placed from outside the program.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise a fixed ``.jax_cache`` at the root of the checkout (listed in
``.gitignore``).  Entry points call ``enable_compile_cache()``; importing
the package turns nothing on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on at ``compile_cache_dir()``; returns
    the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
