"""Pallas TPU split-KV decode attention (flash-decoding, arXiv:2311.01282).

One query token per sequence attends to a long KV cache.  The GPU
flash-decoding kernel splits KV across SMs and reduces partials in a
second kernel; on TPU the KV-chunk axis is the sequential last grid
dimension and the partial (m, l, acc) reduction lives in VMEM scratch —
one kernel, no inter-core reduction.  Grid: (B, KVH, n_kv_chunks): each
step scores the G = H // KVH query heads that share one KV head against
one KV chunk, so the (G, hd) query block spans the array's last two dims
(the TPU block rule) and every KV tile is read once per group.

Layouts: q (B, H, hd); k/v caches (B, KVH, Smax, hd); lens (B,) valid
entries.  Ring-buffer (sliding-window) caches pass window=0 and a
pre-clamped `lens` since the buffer holds exactly the window.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale, block_k, nk, window):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    n_valid = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    needed = (ik * block_k) < n_valid
    if window > 0:
        needed &= (ik * block_k + block_k) > (n_valid - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos < n_valid
        if window > 0:
            mask &= k_pos >= n_valid - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                               # (G, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_bhd(q, k_cache, v_cache, lens, *, window=0,
                         block_k=256, interpret=False):
    """q: (B,H,hd); caches (B,KVH,Smax,hd); lens (B,). Returns (B,H,hd)."""
    B, H, hd = q.shape
    _, KVH, Smax, _ = k_cache.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_k = min(block_k, max(8, Smax))
    pad = (-Smax) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (Smax + pad) // block_k

    kernel = functools.partial(_dec_kernel, scale=scale, block_k=block_k,
                               nk=nk, window=window)
    group = pl.BlockSpec((1, 1, G, hd), lambda b, g, ik: (b, g, 0, 0))
    kv = pl.BlockSpec((1, 1, block_k, hd), lambda b, g, ik: (b, g, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, KVH, nk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),    # lens
                  group, kv, kv],
        out_specs=group,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(lens.astype(jnp.int32), q.reshape(B, KVH, G, hd), k_cache, v_cache)
    return out.reshape(B, H, hd)
