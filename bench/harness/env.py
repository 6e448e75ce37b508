"""Process set-up shared by the benchmark's entry points."""

from __future__ import annotations

import os
from pathlib import Path


def start_jax(root: Path):
    """Point JAX's persistent compile cache and the TPU runtime's logs
    at fixed directories inside the checkout, turn the cache on through
    the program's own entry (`repro.launch.compile_cache`), and return
    JAX's devices. Every program is cached, the sub-second ones too, so
    that from a checkout's second run on nothing the window uses is
    compiled: JAX's default leaves out compiles under one second, and
    the backup's first requests after a failover pay for those."""
    bench = Path(root) / "bench"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = str(bench / ".tpu_logs")
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.devices()
