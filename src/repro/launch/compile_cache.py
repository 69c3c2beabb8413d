"""JAX's persistent compilation cache for the entry points.

Each entry point (`launch/serve.py`, `launch/train.py`, `chip_smoke.py`)
calls `use_compile_cache()` before its first compile; importing this
module touches no JAX state.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# fixed, inside the checkout: a cache directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return it.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and it is
    left alone; otherwise the cache goes to `.jax_cache/` at the repo root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
