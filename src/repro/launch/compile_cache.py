"""JAX's persistent compilation cache, placed from outside.

Every entry point (`pic_run`, `pic_fit`, `sim_serve`, `chip_smoke.py`)
calls `enable_compile_cache()` before its first compile, so compiled
windows and kernels outlive the process:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
  and no other path is set in code;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  resolved from this file, not from the working directory (the path is
  part of a cache entry's identity, so a moving directory never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
