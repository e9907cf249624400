"""JAX's persistent compilation cache for the launchers.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it itself
and nothing here overrides it.  Otherwise the cache lives in one fixed
directory of the checkout, ``<repo>/.jax_cache`` (gitignored): a fixed path,
because the path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
