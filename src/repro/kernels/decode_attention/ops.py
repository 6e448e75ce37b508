"""jit'd public wrapper for split-KV decode attention."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import \
    decode_attention_bhd


@partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, lens, *, window=0, block_k=256,
                     interpret=False):
    """q: (B,1,H,hd); caches (B,Smax,KVH,hd); lens (B,) -> (B,1,H,hd)."""
    qt = q[:, 0]                                  # (B,H,hd)
    kt = jnp.swapaxes(k_cache, 1, 2)              # (B,KVH,Smax,hd)
    vt = jnp.swapaxes(v_cache, 1, 2)
    o = decode_attention_bhd(qt, kt, vt, lens, window=window,
                             block_k=block_k, interpret=interpret)
    return o[:, None]
