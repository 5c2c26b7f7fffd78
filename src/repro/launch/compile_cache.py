"""JAX's persistent compilation cache, kept at one fixed path.

A full-width train or serve step takes tens of seconds to compile on a
TPU; with the cache on, a second process (or a second run of the same
command) reads the compiled programs back. The cache key includes the
directory, so the directory never moves: it is either the one
``JAX_COMPILATION_CACHE_DIR`` names — which JAX reads by itself — or
``<checkout>/.jax_cache``, never a temporary, per-process or dated path.

Call :func:`enable_compile_cache` at the start of an entry point's
``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` (this file is ``src/repro/launch/…``)."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and nothing else is set here; otherwise the cache goes to
    :func:`default_cache_dir`.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
