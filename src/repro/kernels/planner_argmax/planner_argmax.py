"""Pallas TPU tiled masked argmax — the planner's worst-fit reduction.

FailLite's Algorithm 1 answers every placement attempt with one masked
argmax over the per-server headroom column: "the feasible alive server
of maximal normalized headroom, FIRST row on ties" (state.py:183 /
vectorized.py:196 — the first-maximum rule is what makes the vectorized,
sharded, and jax planner backends bit-identical). This kernel is that
reduction as a tiled one-pass scan: values stream HBM->VMEM one
(1, block) tile at a time, each tile reduces to (tile max, first index
achieving it), and a scalar carry in SMEM combines tiles in ascending
order — a later tile only wins on a STRICT improvement, so the global
winner is the first maximum, exactly `np.argmax(np.where(mask, v, -inf))`.

Returns (idx int32, val) with idx = -1 / val = -inf when the mask is
empty — callers branch on feasibility the same way the numpy path
branches on `feas.any()`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _masked_argmax_kernel(v_ref, m_ref, idx_ref, val_ref, *, block, n):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        idx_ref[0, 0] = jnp.int32(-1)
        val_ref[0, 0] = jnp.array(-jnp.inf, val_ref.dtype)

    v = v_ref[...]                                     # (1, block)
    m = m_ref[...]
    # typed constants: under `jax.enable_x64` a bare Python scalar is
    # 64-bit, and Mosaic's lowering of the f64->f32 / i64->i32 convert
    # recurses until RecursionError (TPU kernels have no 64-bit types)
    vv = jnp.where(m, v, jnp.array(-jnp.inf, v.dtype))
    tile_max = vv.max()
    # first in-tile column achieving the max (iota ascending, min wins)
    col = jax.lax.broadcasted_iota(jnp.int32, vv.shape, 1)
    tile_idx = jnp.where(vv == tile_max, col, jnp.int32(n)).min() + i * block

    # ascending-tile combine: strict improvement only, so ties keep the
    # earlier (smaller-index) tile — the first-maximum rule
    best = val_ref[0, 0]
    take = tile_max > best
    val_ref[0, 0] = jnp.where(take, tile_max, best)
    idx_ref[0, 0] = jnp.where(take, tile_idx.astype(jnp.int32),
                              idx_ref[0, 0])


def masked_argmax_pallas(values, mask, *, block: int = 512,
                         interpret: bool = False):
    """(S,) values + (S,) bool mask -> (idx int32, val): the first
    maximum among masked-in entries; (-1, -inf) when none."""
    n = values.shape[0]
    block = max(128, min(block, max(128, n)))
    pad = (-n) % block
    if pad:
        values = jnp.pad(values, (0, pad), constant_values=0)
        mask = jnp.pad(mask, (0, pad), constant_values=False)
    nt = (n + pad) // block
    v2 = values.reshape(1, n + pad)
    m2 = mask.reshape(1, n + pad)

    kernel = functools.partial(_masked_argmax_kernel, block=block, n=n)
    # every index map returns int32 zeros, never Python ints: the planner
    # traces this under `jax.enable_x64`, where a bare 0 (or a default
    # index map) becomes an i64 that Mosaic refuses
    smem = pl.BlockSpec((1, 1), lambda i: (jnp.int32(0), jnp.int32(0)),
                        memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((1, block), lambda i: (jnp.int32(0), i))
    idx, val = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[tile, tile],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), values.dtype),
        ],
        interpret=interpret,
    )(v2, m2)
    return idx[0, 0], val[0, 0]
