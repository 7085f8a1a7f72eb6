"""Persistent XLA compilation cache: the one place that decides where it lives.

Every entry point that compiles real programs (``chip_smoke.py``, the
benchmarks, the examples, ``scripts/worker.py``) calls
:func:`enable_compile_cache` once, before its first compile, so processes that
run one after another — and the workers of one fleet — share compiled code.

The cache key includes the cache directory, so the directory must be the same
in every run: a fixed path inside the checkout, never a temporary name, a
process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache goes to :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
