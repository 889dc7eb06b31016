"""Where JAX's persistent compilation cache lives, for every entry point.

The cache key includes its directory, so the directory must not move
between runs: a temporary, per-process or time-stamped path never hits.
`JAX_COMPILATION_CACHE_DIR`, when set, wins (JAX reads it itself and
nothing else is set here); otherwise the cache goes to `.jax_cache/` at
the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call once at program start, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
