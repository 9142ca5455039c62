"""Where JAX keeps compiled programs between runs of an entry point.

``use_compile_cache()`` is called at the start of every entry point's
``main()`` — ``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run`` and ``benchmarks.serve_bench`` — and never at import,
so tests keep JAX's defaults.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here.  Otherwise the cache is the
fixed, gitignored ``<repo>/.jax_cache``: the directory is part of the
cache key, so it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
