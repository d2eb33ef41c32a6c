"""JAX's persistent compilation cache, set up in one place for every entry
point (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout (git-ignored).  The path is fixed — never built from a temp
name, a pid or the time — so a second run of the same checkout finds what
the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
