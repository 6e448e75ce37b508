"""JAX's persistent compilation cache, placed by the entry points.

A cold failover on the testbed is a compile (`InferenceEngine.warmup`),
so a run that finds its programs already compiled loads faster. Entry
points (`chip_smoke.py`, `launch/serve.py`, the experiment CLI) call
`enable_compile_cache()` once at start-up; importing a library module
never turns the cache on.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
sets no other directory. Otherwise the cache lives in `.jax_cache/` at
the repository root: a fixed path, since the directory is part of what
a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
