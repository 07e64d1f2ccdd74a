"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``repro.launch.serve``, ``repro.launch.gateway``,
``benchmarks/bench_serve.py`` and ``chip_smoke.py``).

``use_compile_cache()`` is called by those ``main``s, never at import and
never from tests.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to one fixed,
git-ignored directory in the checkout, so a later run from the same
checkout finds what an earlier one compiled; a directory named after a temp
dir, a pid or the time would start empty every run.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
