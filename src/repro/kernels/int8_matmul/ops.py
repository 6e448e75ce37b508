"""jit'd public wrapper for the int8 weight-only matmul."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.int8_matmul.int8_matmul import (int8_matmul_pallas,
                                                   quantize_int8)


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "interpret"))
def int8_matmul(x, w_q, scale, *, block_m=128, block_n=128, block_k=512,
                interpret=False):
    return int8_matmul_pallas(x, w_q, scale, block_m=block_m,
                              block_n=block_n, block_k=block_k,
                              interpret=interpret)


__all__ = ["int8_matmul", "quantize_int8"]
