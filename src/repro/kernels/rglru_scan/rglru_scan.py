"""Pallas TPU blocked RG-LRU scan (Griffin, arXiv:2402.19427).

The recurrence h_t = a_t*h_{t-1} + b_t is elementwise over the width dim,
so the GPU implementation uses a warp-level Blelloch scan.  The TPU
adaptation: grid = (B blocks, W blocks, S blocks) with the sequence axis
last (sequential); each grid step loads a (block_s, block_w) tile of
(a, b) into VMEM, runs the short sequential scan over block_s with the
8x128-lane VPU vectorizing the width dim, and carries h across grid
steps in VMEM scratch. h0 enters as (B, 1, W) so its (1, block_w) block
meets the TPU rule that a block's last two dims tile by (8, 128) or
span the array.  Wall-clock depth is S/block_s instead of S.

Inputs are the precomputed gate products: a = exp(log_a), b (both fp32,
shape (B, S, W)); initial state h0 (B, W).  Returns (h (B,S,W), h_last).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hs_scr, *, block_s, ns):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        hs_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):               # h: (1, block_w) fp32
        # one-row loads/stores straight from the refs: Mosaic lowers a
        # dynamic ref index, not a dynamic_slice of a loaded value
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]
        h_ref[0, pl.ds(t, 1), :] = h
        return h

    hs_scr[...] = jax.lax.fori_loop(0, block_s, step, hs_scr[...])


def rglru_scan_pallas(a, b, h0, *, block_s=128, block_w=256,
                      interpret=False):
    """a, b: (B, S, W) fp32; h0: (B, W) fp32 -> (h (B,S,W), h_last (B,W))."""
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    pad_s = (-S) % block_s
    if pad_s:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, 0)))
    ns = (S + pad_s) // block_s
    nw = W // block_w
    assert W % block_w == 0, (W, block_w)

    kernel = functools.partial(_rglru_kernel, block_s=block_s, ns=ns)
    h = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w),
                         lambda bb, iw, isq: (bb, isq, iw)),
            pl.BlockSpec((1, block_s, block_w),
                         lambda bb, iw, isq: (bb, isq, iw)),
            pl.BlockSpec((1, 1, block_w), lambda bb, iw, isq: (bb, 0, iw)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda bb, iw, isq: (bb, isq, iw)),
        out_shape=jax.ShapeDtypeStruct((B, S + pad_s, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, W))
    h = h[:, :S]
    return h, h[:, -1]
