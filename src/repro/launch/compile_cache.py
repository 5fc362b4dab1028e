"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.serving``, ``experiments/run_paper_eval.py``) call
:func:`enable_compile_cache` before anything compiles. Library modules never
do: importing them sets no jax config.
"""
from __future__ import annotations

import os
import pathlib

#: The cache of this checkout. A fixed path: the directory is part of the
#: cache key, so a path that moved from run to run would never hit.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`, ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
