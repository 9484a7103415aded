"""Where JAX keeps compiled programs between processes.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable_compile_cache` once before they compile anything; importing
the package never does. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it by itself and this module sets no other directory. Otherwise the cache
lives at ``<repo>/.jax_cache``: a fixed path, because the path is part of
what a later process must find again (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Every program is cached, however quick its compile, so a second run
    of the same shapes compiles nothing.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
