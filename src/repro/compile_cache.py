"""Where compiled programs are kept between runs.

Both persistent caches default to one fixed directory inside the checkout,
derived from this file's path, so that every process of a run — and the
next run of the same checkout — finds what an earlier one compiled:

  * JAX's persistent compilation cache, under ``<checkout>/.cache/jax``
    (``JAX_COMPILATION_CACHE_DIR`` wins where it is set; JAX reads that
    variable itself);
  * the planner's fused-program cache (``core.exprops``), under
    ``<checkout>/.cache/exprops`` (``REPRO_COMPILE_CACHE`` wins where it is
    set).

JAX keys its cache entries partly by path, so the directory must not move
between runs: no temporary name, pid or time goes into it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.cache`` (this file is ``src/repro/compile_cache.py``)
CACHE_ROOT = pathlib.Path(__file__).resolve().parents[2] / ".cache"


def enable_jax_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
    changes nothing.  Otherwise the cache goes to ``CACHE_ROOT / "jax"``.
    Call before the first compile that should be cached.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CACHE_ROOT / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
