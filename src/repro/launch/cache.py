"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once at start-up (never at
import).  A ``JAX_COMPILATION_CACHE_DIR`` set by whoever runs the program
wins: JAX reads it itself and this module sets nothing.  Otherwise the
cache lives at ``<checkout>/.jax_cache``, a fixed path, so a second run in
the same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
