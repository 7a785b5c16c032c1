"""JAX's persistent compilation cache, for the entry points only.

`chip_smoke.py`, `repro.launch.serve` and `repro.launch.train` call
:func:`enable_compile_cache` first thing; no library module does at import,
so tests and embedding programs keep whatever cache setting they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout (listed in .gitignore): a cache
# directory that moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory. Where
    `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no other
    directory is set here; otherwise the cache lives at `DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
