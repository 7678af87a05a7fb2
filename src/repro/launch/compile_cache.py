"""Persistent compilation cache for the entry points that run on a chip
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``).

A cold TPU process spends minutes compiling a full-width train step; the
persistent cache lets the next process of the same program load it instead.
Tests never call this: the suite runs with the cache off.
"""
from __future__ import annotations

import os

import jax

# Fixed, repo-relative: the cache only hits when the directory stays put.
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX
    already uses it and nothing else is configured. Otherwise the cache
    lives at ``<repo>/.jax_cache`` (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
