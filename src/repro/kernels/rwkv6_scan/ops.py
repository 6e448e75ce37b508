"""jit'd public wrapper for the WKV-6 chunked kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, lw, u, *, chunk=32, interpret=False):
    return wkv6_pallas(r, k, v, lw, u, chunk=chunk, interpret=interpret)
