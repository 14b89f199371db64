"""Where JAX keeps compiled programs between processes.

A TPU compile of one superstep takes about a minute, so a second process
that runs the same programs should find them on disk. The cache key
includes the directory, so the directory never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), and otherwise ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
