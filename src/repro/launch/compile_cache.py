"""Where JAX keeps its persistent compilation cache, for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is: JAX reads it
itself and nothing here sets another directory.  Otherwise the cache goes
to ``.jax_cache`` at the root of the checkout — a fixed path, because the
path is part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
