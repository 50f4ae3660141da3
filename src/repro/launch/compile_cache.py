"""Persistent JAX compilation cache for the command-line entry points.

A full-width step compiles for tens of seconds; the persistent cache lets
the next process on the same machine load it instead. Called from the
``main()`` of the launchers and from ``chip_smoke.py``, never at import,
so library users and tests keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set. Otherwise the cache goes to
    ``<repo>/.jax_cache``: a fixed path, so every run finds what the last
    one wrote.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
