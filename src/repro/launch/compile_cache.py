"""Persistent XLA compilation cache for the entry points.

``enable()`` is called by ``launch.train.main``, ``launch.serve.main`` and
``chip_smoke.py`` before their first compile, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and it is
left alone. Otherwise the cache goes to a fixed directory inside the
checkout: the path is part of the cache key, so a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
