"""JAX's persistent compilation cache, kept at one fixed place.

A cache entry is only found again under the same directory, so the path
must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set,
otherwise ``<checkout>/.jax_cache`` (git-ignored).  Entry points call
:func:`enable_compile_cache` from their ``main()``; importing ``repro``
never turns it on.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """The directory the persistent cache lives in."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
