"""jit'd public wrapper for the flash attention kernel.

Accepts the model's (B, S, H, hd) layout, handles the transpose to the
kernel's (B, H, S, hd) layout. It compiles for the TPU unless the
caller asks for interpret mode (the CPU tests do).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    """q: (B,S,H,hd); k,v: (B,S,KVH,hd) -> (B,S,H,hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return jnp.swapaxes(ot, 1, 2)
