"""JAX's persistent compilation cache, turned on by the entry points.

Called from ``main`` of ``launch/submod.py``, ``benchmarks/run.py`` and
``chip_smoke.py`` — never at import, so tests and library users keep JAX's
defaults.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir, os.pardir))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and no other
    directory is set.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
    a fixed path, so that the next process finds what this one cached.
    """
    # cache every program: a cold start pays dozens of sub-second compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
