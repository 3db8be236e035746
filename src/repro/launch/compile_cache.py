"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads it
itself) and no other directory is configured.  Otherwise the cache lives
at one fixed path in the checkout, ``.jax_cache/`` (git-ignored): the
path is part of what makes an entry reusable, so it never varies by
process or run.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory → that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
