"""Persistent XLA compilation cache for the entry points.

JAX keys its persistent cache on the program and on the cache directory,
so the directory must not move between runs: it is either the one the
environment names (``JAX_COMPILATION_CACHE_DIR``, which JAX reads on its
own) or the fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    stands and no other directory is set. Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`. Called by the ``main()`` of the train and
    serve entry points and by ``chip_smoke.py`` — never on import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
