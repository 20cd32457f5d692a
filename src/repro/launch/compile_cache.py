"""Persistent compilation cache placement for entry points.

Called once from an entry point's ``main`` (``launch.serve``,
``chip_smoke.py``), never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here. Otherwise the cache lives at
a fixed path inside the checkout (``.jax_cache/``, git-ignored): the path is
part of what a later run must find again, so it is never built from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
