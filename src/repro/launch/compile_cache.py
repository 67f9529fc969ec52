"""Placement of JAX's persistent compilation cache for the launchers."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache key includes it, so a moving directory never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself, and no other directory is set here), else ``<repo>/.jax_cache``.
    Call before the first compile; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
