"""Dispatching wrapper for the planner's masked-argmax reduction.

Two implementations, one contract (first maximum among masked-in rows,
(-1, -inf) on an empty mask — see ref.py):

  * ``pallas`` — the tiled TPU kernel (planner_argmax.py): used when
    the default JAX backend is a TPU, or forced via ``impl="pallas"``
    (with ``interpret=True`` on CPU — the parity tests run it this way);
  * ``jnp``    — the jittable jnp equivalent: the CPU fast path the
    jax planner backend inlines into its fused placement scan.

Both are exact — comparisons and argmax only, no accumulation — so the
choice never changes a placement, only where the reduction runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.planner_argmax.planner_argmax import masked_argmax_pallas


def masked_argmax_jnp(values, mask):
    """Jittable jnp implementation of the ref contract.

    Formulated as a max-reduce plus a first-index min-reduce over iota
    rather than one variadic argmax reduce: XLA:CPU vectorizes plain
    min/max reductions but emits scalar code for index-carrying
    reductions, which made `argmax` the dominant cost of the planner's
    placement scan (~40us vs ~10us per step at S=10000). The min over
    iota of positions attaining the max IS numpy's first-occurrence
    argmax, so the tie rule is unchanged; the `mask &` term keeps the
    empty-mask case on the ref contract. Values must be finite (-inf is
    reserved as the mask sentinel) — true of every planner call site,
    where values are normalized headroom."""
    n = values.shape[0]
    masked = jnp.where(mask, values, -jnp.inf)
    mx = masked.max()
    iota = jax.lax.iota(jnp.int32, n)
    i = jnp.where(mask & (masked == mx), iota, jnp.int32(n)).min()
    found = i < n
    return (jnp.where(found, i, -1).astype(jnp.int32),
            jnp.where(found, mx, -jnp.inf))


def masked_argmax(values, mask, *, impl: str | None = None,
                  block: int = 512, interpret: bool = False):
    """(S,) values + (S,) bool mask -> (idx int32, val).

    ``impl=None`` auto-selects: the Pallas kernel on TPU, the jnp path
    everywhere else (the kernel still runs anywhere via
    ``impl="pallas", interpret=True``). The kernel needs 32-bit values:
    TPU kernels have no 64-bit types."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "jnp":
        return masked_argmax_jnp(values, mask)
    return masked_argmax_pallas(values, mask, block=block,
                                interpret=interpret)


__all__ = ["masked_argmax", "masked_argmax_jnp"]
