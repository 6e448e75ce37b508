"""jit'd public wrapper for the RG-LRU scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas


@partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def rglru_scan(a, b, h0, *, block_s=128, block_w=256, interpret=False):
    return rglru_scan_pallas(a, b, h0, block_s=block_s, block_w=block_w,
                             interpret=interpret)
