"""Persistent JAX compilation cache at a fixed path.

Cold Mosaic/XLA compiles are a large share of a short serving run, and the
cache only hits when its directory is stable: the path is part of the key.
``enable_compile_cache`` keeps an operator's ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself) and otherwise points the
cache at ``.jax_cache/`` in the checkout.  Call it before the first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
