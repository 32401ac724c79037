"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a run only hits what an earlier
run wrote when both use the same fixed path. ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself); otherwise the cache lives at
``.jax_cache`` in the root of the checkout (git-ignored), never at a
temporary or per-process path.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
