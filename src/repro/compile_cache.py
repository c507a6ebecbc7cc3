"""Persistent XLA compilation cache for the repository's entry points.

``chip_smoke.py`` and the ``benchmarks/`` mains call :func:`enable` once,
before their first compile; library modules set nothing at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set.  Otherwise the cache lives at one fixed path in the checkout,
``<repo>/.jax_cache`` (gitignored), so every run of the same checkout finds
what earlier runs compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``."""
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
