"""JAX's persistent compilation cache, kept at one fixed path.

A cold process on the chip recompiles every train and serve executable;
the persistent cache lets the next process (or the next command sharing
the checkout) load them instead.  The cache key includes the directory, so
the directory must not move between runs: it is either what
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or
``<checkout>/.jax_cache``, found from this file's own location.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: src/repro/common/compile_cache.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """Where compiled executables persist: ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; call first in an entry point, before
    anything compiles.  Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()
