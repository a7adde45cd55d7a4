"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
``use_compile_cache()`` once under their ``__main__`` guard; library
imports and tests never do.  The cache key includes the directory, so the
location must not move between runs: it is either the one the environment
names or one fixed path inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/artifacts/jax_cache (src/repro/launch/ -> repo root is 3 up);
# artifacts/ is git-ignored
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
