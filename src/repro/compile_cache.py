"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.experiments.sweep``) call :func:`use_compile_cache` from
their ``main()``. Nothing calls it at import, so importing ``repro`` — as
the tests do — never writes a cache into the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset. The
#: path is part of each entry's key, so it is fixed: a moving directory
#: would never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`. Every compile is cached, however short: a run
    loads dozens of small serving executables that each take well under
    JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
