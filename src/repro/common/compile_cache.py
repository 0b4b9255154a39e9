"""Where JAX keeps its persistent compilation cache.

The cache's path is part of its key, so it must not move between runs:
a directory that changes never hits. Entry points (``chip_smoke.py``,
``repro.launch.*``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` first; importing ``repro`` never does.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and nothing is changed here. Without it the cache goes to
    ``<checkout>/.jax_cache``, resolved from this file's location.
    """
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
