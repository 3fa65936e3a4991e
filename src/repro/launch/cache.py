"""Persistent XLA compilation cache for the entry points.

Compiling the training step at the paper's sizes takes a large share of a
cold run; JAX's persistent cache lets later processes load the compiled
programs instead.  ``enable_compile_cache`` is called by
``launch/train_gnn.py`` and ``chip_smoke.py`` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed directory inside the checkout: a cache whose path moved between
# runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing else is configured.  Otherwise the cache
    goes to ``DEFAULT_DIR`` (``<checkout>/.jax_cache``, git-ignored).
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
