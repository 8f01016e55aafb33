"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is left to JAX, which reads
    it.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
    because the directory is part of every entry's key and a cache that
    moves never hits.  Call it from an entry point's ``main()``, never at
    import time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
