"""Seeded weights for a dense (qwen-style) model, made on the device.

The benchmark makes the weights the system serves, so that they come
from `--seed` and the reference can make the same ones again without
taking anything from the program. One jitted call builds every leaf in
the type it is served in (bf16), each element a hash of (seed, rung,
leaf, index): no host copy, no per-leaf dispatch, no random-number
state. Values are uniform with the standard deviations of the usual
fan-in initialisation; norm scales lie in [0.9, 1.1] and biases have a
standard deviation of 0.1, so that a fault in either shows in the
logits.

The tree has the layout the serving engine takes (`embed`, `cycles` of
layers stacked on axis 0, `final_norm`, `unembed` when untied). The
reference reads the same tree by the same names.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_HALF_RANGE = 0.1
BIAS_STD = 0.1


@dataclass(frozen=True)
class Shape:
    """Sizes of one dense rung: what the weights and the reference need."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, cfg) -> "Shape":
        """From any object with these attributes (a model config)."""
        return cls(**{k: getattr(cfg, k) for k in (
            "num_layers", "d_model", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "vocab_size", "tie_embeddings", "qkv_bias",
            "rope_theta", "norm_eps")}, dtype=str(cfg.param_dtype))


def leaf_specs(s: Shape) -> Dict[str, Tuple[tuple, str, float]]:
    """path -> (shape, kind, std). Kinds: "w" uniform, "norm", "bias"."""
    L, d, h, kv, hd, ff, V = (s.num_layers, s.d_model, s.num_heads,
                              s.num_kv_heads, s.head_dim, s.d_ff,
                              s.vocab_size)
    out = {
        "embed/table": ((V, d), "w", 1 / math.sqrt(d)),
        "final_norm/scale": ((d,), "norm", 0.0),
        "layers/norm1/scale": ((L, d), "norm", 0.0),
        "layers/attn/wq": ((L, d, h, hd), "w", 1 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, kv, hd), "w", 1 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, kv, hd), "w", 1 / math.sqrt(d)),
        "layers/attn/wo": ((L, h, hd, d), "w", 1 / math.sqrt(h * hd)),
        "layers/norm2/scale": ((L, d), "norm", 0.0),
        "layers/ffn/w_gate": ((L, d, ff), "w", 1 / math.sqrt(d)),
        "layers/ffn/w_up": ((L, d, ff), "w", 1 / math.sqrt(d)),
        "layers/ffn/w_down": ((L, ff, d), "w", 1 / math.sqrt(ff)),
    }
    if s.qkv_bias:
        out["layers/attn/bq"] = ((L, h, hd), "bias", BIAS_STD)
        out["layers/attn/bk"] = ((L, kv, hd), "bias", BIAS_STD)
        out["layers/attn/bv"] = ((L, kv, hd), "bias", BIAS_STD)
    if not s.tie_embeddings:
        out["unembed/table"] = ((V, d), "w", 1 / math.sqrt(d))
    return out


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform01(key, salt: int, shape):
    """Uniform [0, 1) float32 from (key, salt, element index)."""
    n = int(np.prod(shape))
    if n >= 2**32:
        raise ValueError(f"leaf of {n} elements exceeds the 32-bit index")
    i = jax.lax.iota(jnp.uint32, n).reshape(shape)
    x = i * jnp.uint32(0x9E3779B1) + jnp.uint32(salt)
    x = _fmix32(x ^ key[0])
    x = _fmix32(x ^ key[1])
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


@partial(jax.jit, static_argnames=("specs", "dtype"))
def _make(key, specs, dtype):
    out = []
    for shape, kind, std, salt in specs:
        u = _uniform01(key, salt, shape) * 2.0 - 1.0
        if kind == "norm":
            v = 1.0 + NORM_HALF_RANGE * u
        else:                           # uniform on [-a, a] has std a/sqrt(3)
            v = u * (std * math.sqrt(3.0))
        out.append(v.astype(dtype))
    return out


def seed_key(seed: int, rung: str) -> np.ndarray:
    """Two 32-bit words from a seed of any size and the rung's name."""
    s = seed & (2**64 - 1)
    c = zlib.crc32(rung.encode())
    return np.asarray([(s & 0xFFFFFFFF) ^ c,
                       ((s >> 32) & 0xFFFFFFFF) ^ (c * 0x9E3779B1 & 0xFFFFFFFF)],
                      np.uint32)


def make_flat(s: Shape, seed: int, rung: str) -> Dict[str, jax.Array]:
    """path -> array, on JAX's default device."""
    specs = leaf_specs(s)
    paths = sorted(specs)
    packed = tuple((specs[p][0], specs[p][1], specs[p][2],
                    zlib.crc32(p.encode())) for p in paths)
    leaves = _make(jnp.asarray(seed_key(seed, rung)), packed,
                   jnp.dtype(s.dtype))
    return dict(zip(paths, leaves))


def engine_tree(flat: Dict[str, jax.Array]) -> dict:
    """The serving engine's param layout: one stacked cycle of layers."""
    layer: dict = {}
    for path, a in flat.items():
        parts = path.split("/")
        if parts[0] != "layers":
            continue
        node = layer
        for p in parts[1:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = a
    tree = {"embed": {"table": flat["embed/table"]},
            "final_norm": {"scale": flat["final_norm/scale"]},
            "cycles": [layer], "tail": []}
    if "unembed/table" in flat:
        tree["unembed"] = {"table": flat["unembed/table"]}
    return tree
