"""JAX's persistent compilation cache, placed from outside or at a fixed path."""
from __future__ import annotations

import os
import pathlib

import jax

#: The variable JAX itself reads the cache directory from.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache lives when nothing outside places it: a fixed directory
#: of the checkout (gitignored).  Fixed, because the directory is part of
#: what a later run must find again.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
