"""JAX's persistent compilation cache for the entry points.

The cache directory is part of each entry's lookup, so it must not move
between runs: a temporary or per-process path would never be hit again.
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself);
otherwise the cache lives at ``<checkout>/.jax_cache``.  Entry points call
:func:`enable_compile_cache` from ``main()``; importing this module changes
nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache goes."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (before the first compile) and return the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
