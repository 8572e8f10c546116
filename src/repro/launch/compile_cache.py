"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py``, ``launch/serve_sim.py`` and ``repro.launch.train_sim``
call :func:`enable_compile_cache` before their first compile, so a second
run of the same programs reads its executables back instead of compiling.
"""
from __future__ import annotations

import os

import jax

#: root of the checkout this module runs from (``src/repro/launch/..``)
CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the cache: JAX reads the
    variable itself and nothing here overrides it. Otherwise the cache is
    the fixed ``<checkout>/.jax_cache``; a path made per run (a temp name,
    a pid, the time) would never be found again. Call before the first
    compile of the process.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
