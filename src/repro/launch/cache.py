"""Placement of JAX's persistent compilation cache for the entry points.

The cache key includes its directory, so the directory must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads that variable itself) or the fixed ``<checkout>/.jax_cache``.
Called from entry points' ``main()`` before anything compiles, never
at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (then nothing is changed
    here), else ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
