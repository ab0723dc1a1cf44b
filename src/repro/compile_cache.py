"""Where JAX keeps its persistent compilation cache.

The one rule for every entry point (chip_smoke.py, benchmarks/run.py,
python -m repro.serve_db, the examples): if JAX_COMPILATION_CACHE_DIR is
set, JAX already reads it and nothing here overrides it; otherwise the
cache goes to a FIXED directory, <checkout>/.jax_cache. A fixed path
matters: what a temporary or per-process directory holds is never found
again by the next run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
