"""Plain float32 reference of a dense qwen-style decoder, and its control.

Independent of the program: it imports nothing of it and reads only the
benchmark's own weights (`weights.py`), by name. The forward pass is the
published architecture written out straight: RMSNorm, Q/K/V with bias,
rotary embedding (rotate-half), causal grouped-query attention, SwiGLU,
final RMSNorm, logits against the (tied or separate) output table. Every
matrix product runs at `Precision.HIGHEST` in float32.

`gaps` teacher-forces one request, its prompt and the tokens the system
served, and returns at each served position the gap by which the served
token's reference logit lies below the reference's best. For a control
it also runs the same pass with every matrix product computed in a lower
precision (int8: weights per output channel and activations per token,
int32 accumulation; fp8: both rounded to float8_e4m3fn under the same
scaling, float32 accumulation) and returns the gap of the token that pass
puts first: the reading a program that slipped to it would give.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("norm1/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
              "attn/bq", "attn/bk", "attn/bv", "norm2/scale",
              "ffn/w_gate", "ffn/w_up", "ffn/w_down")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


FP8_MAX = 448.0              # largest float8_e4m3fn


def _quant(a, axis):
    """Symmetric int8 along `axis`; returns (int8 values, float scale)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8), s


def _fp8(a, axis):
    """a rounded to float8_e4m3fn under a scale that maps its largest
    magnitude along `axis` to the format's largest, back in float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low):
    """(T, K) @ (K, N) in float32; with `low` "int8", operands in int8
    (activations per row, weights per column) and int32 accumulation;
    with "fp8", operands rounded to float8_e4m3fn, float32 accumulation."""
    if low is None:
        return jnp.dot(x, w, precision=HI)
    if low == "fp8":
        return jnp.dot(_fp8(x, 1), _fp8(w, 0), precision=HI)
    xq, sx = _quant(x, 1)
    wq, sw = _quant(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rope(x, positions, theta):
    """x: (T, H, hd); rotate-half rotary embedding."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, dims, low):
    d, h, kv, hd, theta, eps, bias = dims
    T = x.shape[0]
    f = {k: w[k].astype(jnp.float32) for k in w}
    pos = jnp.arange(T)
    a = _rmsnorm(x, f["norm1/scale"], eps)
    q = _mm(a, f["attn/wq"].reshape(d, h * hd), low).reshape(T, h, hd)
    k = _mm(a, f["attn/wk"].reshape(d, kv * hd), low).reshape(T, kv, hd)
    v = _mm(a, f["attn/wv"].reshape(d, kv * hd), low).reshape(T, kv, hd)
    if bias:
        q, k, v = q + f["attn/bq"], k + f["attn/bk"], v + f["attn/bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, h * hd)
    x = x + _mm(o, f["attn/wo"].reshape(h * hd, d), low)
    a = _rmsnorm(x, f["norm2/scale"], eps)
    g = _mm(a, f["ffn/w_gate"], low)
    u = _mm(a, f["ffn/w_up"], low)
    return x + _mm(jax.nn.silu(g) * u, f["ffn/w_down"], low)


def _final(x, rows, norm, table, eps, low):
    xr = _rmsnorm(x[rows], norm.astype(jnp.float32), eps)
    return _mm(xr, table.astype(jnp.float32).T, low)


@partial(jax.jit, static_argnames=("dims", "low"))
def _logits(flat, tokens, rows, dims, low):
    eps = dims[5]
    table = flat["embed/table"]
    out_table = flat.get("unembed/table", table)
    x = table[tokens].astype(jnp.float32)
    if low == "int8":           # lookups read the low-precision table too
        q, s = _quant(table.astype(jnp.float32), 1)
        x = q[tokens].astype(jnp.float32) * s[tokens]
    elif low == "fp8":
        x = _fp8(x, 1)
    stacked = {k: flat["layers/" + k] for k in LAYER_KEYS
               if "layers/" + k in flat}

    def body(x, w):
        return _layer(x, w, dims, low), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _final(x, rows, flat["final_norm/scale"], out_table, eps, low)


def _dims(shape) -> Tuple:
    return (shape.d_model, shape.num_heads, shape.num_kv_heads,
            shape.head_dim, float(shape.rope_theta), float(shape.norm_eps),
            bool(shape.qkv_bias))


@partial(jax.jit, static_argnames=("dims", "controls"))
def _gaps(flat, tokens, rows, served, dims, controls):
    ref = _logits(flat, tokens, rows, dims, None)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    out = {"gap": best - got}
    for low in controls:
        pick = jnp.argmax(_logits(flat, tokens, rows, dims, low), axis=-1)
        out[low] = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return out


def pad_len(n: int, step: int = 512) -> int:
    return step * max(1, -(-n // step))


def gaps(flat: Dict[str, jax.Array], shape, prompt: np.ndarray,
         served: list, *, pad_to: int, rows_to: int,
         controls: Tuple[str, ...] = ()) -> Dict[str, np.ndarray]:
    """Per served token: the reference's best logit minus the served
    token's ("gap"), and for each control precision in `controls`
    ("int8", "fp8") the reference's best minus the logit of the token
    that pass puts first. The sequence is padded to `pad_to` and the rows
    to `rows_to`, so that a few compiled programs serve every request of
    a cell; padding lies after the last real position and cannot reach
    it under the causal mask."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    if len(seq) > pad_to or n > rows_to:
        raise ValueError(f"request of {len(seq)} tokens, {n} served, over "
                         f"the pads {pad_to}, {rows_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(rows_to, p - 1, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    tok = np.zeros(rows_to, np.int32)
    tok[:n] = served
    out = _gaps(flat, jnp.asarray(tokens), jnp.asarray(rows),
                jnp.asarray(tok), _dims(shape), tuple(controls))
    return {k: np.asarray(v, np.float64)[:n] for k, v in out.items()}
