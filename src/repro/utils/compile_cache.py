"""Where this repo's scripts keep JAX's persistent compilation cache.

Only entry-point scripts call :func:`use_compile_cache` (``chip_smoke.py``,
``benchmarks/``, ``examples/``); importing the library never turns the
cache on.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root: src/repro/utils/compile_cache.py -> three levels up
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, so the next run
    from the same checkout finds what this one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
