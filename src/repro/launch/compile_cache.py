"""JAX's persistent compilation cache, placed where a run can find it again,
and a count of the executables a process builds.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
nothing else is configured. Otherwise the cache lives at a fixed directory
inside the checkout (``.jax_cache/``, git-ignored) — a fixed path, because
the path is part of the cache key, so a temporary or per-run directory
would never hit.

`compile_count` counts the executables lowered since this module was
imported (``/jax/core/compile/jaxpr_to_mlir_module_duration`` events): one
per new executable variant (a jitted function, or an eager primitive, met
at a new shape or static argument), whether XLA then compiles it or reads
it from the persistent cache. A serving loop that holds its shapes adds
none once warm. (The jaxpr-trace event would count every nested jit of a
trace — ``jnp`` functions are jitted — so one new shape reads several.)
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_compiles = 0


def _on_event(event: str, duration_s: float, **kwargs) -> None:
    global _compiles
    if event == LOWER_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


def compile_count() -> int:
    """Executable variants lowered in this process since start-up."""
    return _compiles


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
