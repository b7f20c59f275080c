"""JAX's persistent compilation cache for the repo's entry points.

Entry points that run on an accelerator call ``enable_compile_cache()``
once, before their first compile; importing the package never does, so
tests compile without touching a cache.  The cache is keyed by its path,
so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself), otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
