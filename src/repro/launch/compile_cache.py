"""JAX's persistent compilation cache, placed where a run can find it again.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
nothing else is configured. Otherwise the cache lives at a fixed directory
inside the checkout (``.jax_cache/``, git-ignored) — a fixed path, because
the path is part of the cache key, so a temporary or per-run directory
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
