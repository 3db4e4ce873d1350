"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing this module does nothing. Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and the cache goes
there — this code sets no other. Otherwise the cache goes to the fixed
path ``<checkout>/.cache/jax_compile`` (gitignored): the directory is part
of the cache key, so it must not move between runs. It is kept apart from
the rate-distortion tables (``REPRO_CACHE``, ``core/rate_distortion.py``).
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".cache", "jax_compile"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the environment's
    directory or at ``DEFAULT_DIR``; returns the directory in use."""
    path = os.environ.get(ENV)
    if path:
        return path
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
