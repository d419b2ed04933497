"""Persistent XLA compilation cache, shared by every entry point.

JAX keys a cache entry by the program and the cache directory, so the
directory must not move between runs: a temporary or per-process path
never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX
itself and wins; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
