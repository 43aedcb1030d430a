"""Where compiled programs are cached between runs.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing. JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing else is
configured here. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a later run must find
again (a directory named after a tempdir, pid or time never hits).
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
