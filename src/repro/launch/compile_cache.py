"""JAX's persistent compilation cache for the entry points.

A cold run compiles a prefill and a decode program per stage and batch
bucket, plus the heads; the persistent cache lets the next process that
compiles the same programs load them instead.  The cache key includes the
directory, so it lives at one fixed place: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself), otherwise
``.jax_cache`` at the root of the checkout.

Call ``enable_compile_cache()`` from a ``main``, never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
