"""Pallas TPU chunked RWKV-6 WKV recurrence (Finch, arXiv:2404.05892).

Per head: S_t = diag(w_t) S_{t-1} + k_t^T v_t;  y_t = r_t (S_{t-1} + u k_t^T v_t).

The CUDA kernel in the paper runs one thread per channel, sequential over
time.  The TPU adaptation uses the chunk-parallel form (as in GLA,
arXiv:2312.06635): grid = (B, NH, n_chunks) with chunks sequential; the
(hs x hs) state lives in VMEM scratch; intra-chunk work is two MXU
matmuls plus a (C x C) decay-masked score matmul, with all cross-step
decay exponents kept <= 0 for fp32 stability.

Inputs per head: r,k,v (B,NH,S,hs) fp32; lw (B,NH,S,hs) log-decay <= 0;
u (NH,hs) bonus.  Returns (y (B,NH,S,hs), S_out (B,NH,hs,hs)).
u is passed to the kernel as (NH, 1, hs): a (1, hs) block of a 2-D
(NH, hs) array breaks the TPU rule that a block's last two dims tile by
(8, 128) or span the array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every in-kernel matmul asks for full fp32 precision, as the reference
# computes
_HI = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, sout_ref,
                s_scr, *, chunk, nc):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0]                       # (C, hs) fp32
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    lw = lw_ref[0, 0]
    u = u_ref[0]                          # (1, hs)
    s = s_scr[...]                        # (hs, hs)

    # inclusive cumsum over time as a lower-triangular ones matmul:
    # Mosaic has no cumsum lowering
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (ti >= si).astype(jnp.float32)
    cum = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                              precision=_HI,
                              preferred_element_type=jnp.float32)
    cum_prev = cum - lw                   # exclusive
    cum_last = cum[-1:]                   # (1, hs)

    # inter-chunk: y += (r * e^{cum_prev}) @ S_in
    r_dec = r * jnp.exp(cum_prev)
    y = jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)
    # intra-chunk strict-lower part: A[t,s] = sum_k r_t k_s e^{cum_prev_t - cum_s}
    k_div = k * jnp.exp(-cum)             # NOTE: may be large; masked below
    a = jax.lax.dot_general(r_dec, k_div, (((1,), (1,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)
    a = jnp.where(ti > si, a, 0.0)
    y = y + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                precision=_HI,
                                preferred_element_type=jnp.float32)
    # diagonal bonus: y_t += (r_t . u*k_t) v_t
    diag = jnp.sum(r * (u * k), axis=-1, keepdims=True)
    y = y + diag * v
    y_ref[0, 0, ...] = y.astype(y_ref.dtype)

    # state update: S_out = e^{cum_last} ⊙ S_in + sum_s (k_s e^{cum_last-cum_s})^T v_s
    k_dec = k * jnp.exp(cum_last - cum)
    s_new = jnp.exp(cum_last).reshape(-1, 1) * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ic == nc - 1)
    def _finalize():
        sout_ref[0, 0, ...] = s_new


def wkv6_pallas(r, k, v, lw, u, *, chunk=32, interpret=False):
    """r,k,v,lw: (B,NH,S,hs) fp32; u: (NH,hs). Zero initial state.

    The intra-chunk two-factor decomposition (r e^{cum_prev}) @ (k e^{-cum})
    requires |cum| within a chunk to stay in fp32 range; chunk<=64 with
    lw >= -20 is safe (e^{1280} overflow is masked out but Inf*0 = NaN is
    not, so lw is clamped here).
    """
    B, NH, S, hs = r.shape
    lw = jnp.maximum(lw, -40.0 / chunk)   # stability clamp (see docstring)
    pad = (-S) % chunk
    if pad:
        r = jnp.pad(r, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        lw = jnp.pad(lw, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, nc=nc)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(B, NH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hs), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hs), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hs), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hs), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, hs), lambda b, h, ic: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hs), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, hs, hs), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NH, S + pad, hs), jnp.float32),
            jax.ShapeDtypeStruct((B, NH, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u.reshape(NH, 1, hs))
    return y[:, :, :S], s_out
