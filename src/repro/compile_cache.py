"""Where JAX's persistent compilation cache lives.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before anything compiles:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
  and this sets no other;
* otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
  because the path is part of the cache key: a directory that moves never
  hits.

``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the cache off (the test
suite sets it, so tests never write one).
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

#: the in-checkout default (listed in .gitignore)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
