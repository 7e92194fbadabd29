"""Fixed on-disk cache locations inside the checkout.

Compiled programs and the edge-backend calibration table live under
``<checkout>/.cache/`` (gitignored), so every process started from one
checkout — ``chip_smoke.py``, the benchmarks, a second run of either —
finds what an earlier one stored. A cache directory that moved between
runs would never hit: the path is part of the compilation cache's key.

``JAX_COMPILATION_CACHE_DIR`` (read by jax itself) and
``DRONE_AUTOTUNE_DIR`` (read by ``core/autotune.py``) override the two
locations.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_ROOT", "enable_compile_cache"]

#: ``<checkout>/.cache`` — this file is ``<checkout>/src/repro/caches.py``
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache")


def enable_compile_cache() -> str:
    """Turn jax's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is changed. Otherwise the cache goes to ``CACHE_ROOT/jax``.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CACHE_ROOT, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
