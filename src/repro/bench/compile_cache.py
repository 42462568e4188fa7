"""JAX's persistent compilation cache, turned on by the entry points only
(``python -m repro.bench``, ``chip_smoke.py``, the ``benchmarks/`` scripts) —
never as a side effect of importing a library module.

Every (mix, size, passes) case is its own program, so a cold process spends
much of a short run compiling.  The cache keeps compiled programs on disk,
keyed among other things by the directory, so the directory never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache inside the checkout (listed in .gitignore), used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Every compile is cached, however short:
    a kernel compiles in well under JAX's default one-second threshold,
    and a characterize sweep compiles hundreds of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
