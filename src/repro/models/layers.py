"""Core transformer layers, pure JAX (no flax).

Params are plain nested dicts of jnp arrays.  Every layer comes as an
``init_*`` returning a param tree and an ``apply`` function.

Attention is implemented *flash-style in jnp*: a `lax.scan` over KV blocks
with an online-softmax carry.  This keeps the traced memory footprint
O(q_block x kv_block) instead of O(S^2), which is what lets the 32k-prefill
and 500k-decode dry-run cells compile and fit; it also doubles as the
numerical oracle for the Pallas flash_attention kernel.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import logical_constraint

NEG_INF = -1e30

# Dry-run "exact cost" mode: XLA's cost_analysis counts a lax.scan body
# once regardless of trip count, so the dry-run unrolls intra-layer scans
# (flash KV blocks, CE chunks) to make HLO FLOP/byte counts exact.
# The WKV chunk scan is too long to unroll (1024 bodies at 32k prefill);
# instead the dry-run probes it at WKV_UNROLL ∈ {1, 2} and recovers the
# exact per-chunk cost from the difference (see dryrun.lower_cell).
EXACT_COST_MODE = False
WKV_UNROLL = 1


def set_exact_cost_mode(on: bool, wkv_unroll: int = 1):
    global EXACT_COST_MODE, WKV_UNROLL
    EXACT_COST_MODE = bool(on)
    WKV_UNROLL = int(wkv_unroll)


def _he(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(dt)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope(x, positions, theta=10_000.0):
    """x: (..., S, H, hd); positions broadcastable to (..., S); theta may
    be a traced scalar (uniform layer scan)."""
    hd = x.shape[-1]
    log_theta = (math.log(theta) if isinstance(theta, (int, float))
                 else jnp.log(theta))
    freqs = jnp.exp(-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd * log_theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / bidirectional / cross)
# --------------------------------------------------------------------------

def init_attention(key, cfg, dtype=None):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = dtype or cfg.pdtype
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(h * hd)
    p = {
        "wq": _he(ks[0], (d, h, hd), s_in, dtype),
        "wk": _he(ks[1], (d, kv, hd), s_in, dtype),
        "wv": _he(ks[2], (d, kv, hd), s_in, dtype),
        "wo": _he(ks[3], (h, hd, d), s_out, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _qkv(params, cfg, x, positions, rope_on=True, theta=None):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if rope_on:
        theta = cfg.rope_theta if theta is None else theta
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def flash_attention_jnp(q, k, v, *, causal=True, window=0, q_offset=0,
                        kv_block=1024, kv_len_mask=None):
    """Online-softmax attention, scanned over KV blocks.

    q: (B, Sq, H, hd); k, v: (B, Skv, KVH, hd) with H % KVH == 0.
    window > 0 => sliding-window causal attention (each q attends to the
    last `window` kv positions, inclusive of itself).
    q_offset: absolute position of q[0] relative to kv[0] (decode: Skv - Sq).
    kv_len_mask: optional (B, Skv) bool validity mask (ragged batches).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)

    # Flat-head layout: KV broadcast to H query heads. Keeping the head
    # axis whole lets the "model" sharding propagate cleanly through the
    # score/grad ops (a (KVH, G) split is inexpressible in a PartitionSpec
    # and forces GSPMD into full-tensor regathers in the backward pass).
    def expand(t):
        if G == 1:
            return t
        Bt, St = t.shape[0], t.shape[1]
        t = jnp.broadcast_to(t[:, :, :, None, :], (Bt, St, KVH, G, hd))
        return t.reshape(Bt, St, H, hd)

    # operands stay in the model dtype; dots accumulate in fp32 via
    # preferred_element_type (avoids materializing fp32 copies of K/V)
    qf = q * jnp.asarray(scale, q.dtype)
    nblk = -(-Skv // kv_block)
    pad = nblk * kv_block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len_mask is None:
            kv_len_mask = jnp.arange(Skv + pad) < Skv
            kv_len_mask = jnp.broadcast_to(kv_len_mask, (B, Skv + pad))
        else:
            kv_len_mask = jnp.pad(kv_len_mask, ((0, 0), (0, pad)))
    kb = k.reshape(B, nblk, kv_block, KVH, hd)
    vb = v.reshape(B, nblk, kv_block, KVH, hd)
    mb = (None if kv_len_mask is None
          else kv_len_mask.reshape(B, nblk, kv_block))

    q_pos = q_offset + jnp.arange(Sq)
    S_SPEC = P(("pod", "data"), None, "model", None)

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        kblk, vblk, vmask, start = blk
        kblk = expand(kblk)
        vblk = expand(vblk)
        kv_pos = start + jnp.arange(kv_block)
        # (B, Sq, H, kv_block), fp32 accumulation over bf16 operands
        s = jnp.einsum("bqhk,bshk->bqhs", qf, kblk,
                       preferred_element_type=jnp.float32)
        s = logical_constraint(s, S_SPEC)
        mask = jnp.ones((Sq, kv_block), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if isinstance(window, (int, float)):
            if window > 0:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
        else:
            # traced per-layer window (uniform layer scan); <= 0 = global
            eff = jnp.where(window > 0, window, Skv + 1)
            mask &= kv_pos[None, :] > q_pos[:, None] - eff
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        if vmask is not None:
            s = jnp.where(vmask[:, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhs,bshk->bqhk", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        acc = logical_constraint(acc, S_SPEC)
        return (m_new, l_new, acc), None

    # without this, the backward pass stacks per-trip score tensors
    # (B,Sq,H,block) across the whole KV scan — checkpointing the body
    # keeps only the (m,l,acc) carries and recomputes scores in bwd
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)

    m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    starts = jnp.arange(nblk) * kv_block
    kb = jnp.moveaxis(kb, 1, 0)
    vb = jnp.moveaxis(vb, 1, 0)
    xs = (kb, vb,
          None if mb is None else jnp.moveaxis(mb, 1, 0),
          starts)
    unroll = nblk if EXACT_COST_MODE else 1
    if mb is None:
        (m, l, acc), _ = jax.lax.scan(
            lambda c, b: body(c, (b[0], b[1], None, b[2])), (m0, l0, a0),
            (kb, vb, starts), unroll=unroll)
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs, unroll=unroll)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def banded_local_attention_jnp(q, k, v, *, window, q_block=512):
    """Sliding-window self-attention over a (window + q_block) KV band.

    The generic flash path visits every KV block and masks — for
    gemma3's window-1024 local layers at 32k prefill that is 21x more
    score FLOPs/bytes than the band actually needs.  Here each q-block
    slices only its [i*bq - window, i*bq + bq) KV band (left-padded so
    slices stay in range).  Causal, full-sequence (Sq == Skv) only.
    """
    B, S, H, hd = q.shape
    _, _, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    q_block = min(q_block, S)
    pad_s = (-S) % q_block
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    Sp = S + pad_s
    nq = Sp // q_block
    band = window + q_block
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    def expand(t):
        if G == 1:
            return t
        Bt, St = t.shape[0], t.shape[1]
        t = jnp.broadcast_to(t[:, :, :, None, :], (Bt, St, KVH, G, hd))
        return t.reshape(Bt, St, H, hd)

    qs = (q * jnp.asarray(scale, q.dtype))

    def body(_, i):
        qb = jax.lax.dynamic_slice_in_dim(qs, i * q_block, q_block, 1)
        kb = expand(jax.lax.dynamic_slice_in_dim(kp, i * q_block, band, 1))
        vb = expand(jax.lax.dynamic_slice_in_dim(vp, i * q_block, band, 1))
        q_pos = i * q_block + jnp.arange(q_block)
        kv_pos = i * q_block - window + jnp.arange(band)
        s = jnp.einsum("bqhk,bshk->bqhs", qb, kb,
                       preferred_element_type=jnp.float32)
        s = logical_constraint(s, P(("pod", "data"), None, "model", None))
        mask = ((q_pos[:, None] >= kv_pos[None, :])
                & (kv_pos[None, :] > q_pos[:, None] - window)
                & (kv_pos[None, :] >= 0) & (q_pos[:, None] < S))
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ob = jnp.einsum("bqhs,bshk->bqhk", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return None, ob.astype(q.dtype)

    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    _, out = jax.lax.scan(body, None, jnp.arange(nq),
                          unroll=nq if EXACT_COST_MODE else 1)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sp, H, hd)[:, :S]
    return out


def decode_attention_jnp(q, k_cache, v_cache, cache_len, *, window=0):
    """Single-token attention against a (possibly longer, padded) KV cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, Smax, KVH, hd);
    cache_len: (B,) int32 number of valid cache entries INCLUDING this step.

    NOTE (measured, §Perf log): routing this through the chunked flash
    path regressed decode memory/collectives (scan stash + per-block mask
    machinery with q_len=1); the direct whole-cache dot is better here.
    The fp32 operand copies XLA:CPU materializes for bf16 dots are a
    host-backend artifact — the TPU MXU consumes bf16 natively.
    """
    B, _, H, hd = q.shape
    _, Smax, KVH, _ = k_cache.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    # Grouped-KV einsum against the UNEXPANDED cache: broadcasting KV to
    # H heads forced GSPMD to all-gather the sequence-sharded cache
    # (measured: 268MB x 2 x L per decode step — the entire collective
    # term of the decode cells). Q is one token, so regrouping it is
    # free; scores (B, KVH, G, S) keep S on the "model" axis.
    qg = (q * jnp.asarray(scale, q.dtype))[:, 0].reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(Smax)
    valid = pos[None, :] < cache_len[:, None]
    if window > 0:
        valid &= pos[None, :] > cache_len[:, None] - 1 - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def decode_attention_append_jnp(q, k_cache, v_cache, valid, k_new, v_new):
    """Single-token attention over a read-only KV cache and the token's
    own K/V in one softmax: the attention of writing the token into the
    cache and attending over it, with the cache left as it lies.

    q: (B, 1, H, hd); k_cache/v_cache: (B, Smax, KVH, hd); valid:
    (B, Smax) bool, the cache entries the token sees; k_new/v_new:
    (B, KVH, hd). Grouped-KV einsums against the unexpanded cache, as in
    `decode_attention_jnp`. The softmax's max and sum over the cache are
    reductions, and the token's own score joins them after, so the
    scores of a sequence-sharded cache are never gathered to one piece.
    """
    B, _, H, hd = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    qg = (q * jnp.asarray(scale, q.dtype))[:, 0].reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new,
                       preferred_element_type=jnp.float32)[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    p = jnp.exp(s - m)
    p_new = jnp.exp(s_new - m)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = ((out + p_new * v_new[:, :, None].astype(jnp.float32))
           / (jnp.sum(p, axis=-1, keepdims=True) + p_new))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def init_cross_attention(key, cfg, dtype=None):
    """Cross-attention (whisper decoder): kv from encoder states."""
    return init_attention(key, cfg, dtype)


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def init_swiglu(key, d, ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _he(k1, (d, ff), 1 / math.sqrt(d), dtype),
        "w_up": _he(k2, (d, ff), 1 / math.sqrt(d), dtype),
        "w_down": _he(k3, (ff, d), 1 / math.sqrt(ff), dtype),
    }


def swiglu(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = logical_constraint(h, P(("pod", "data"), None, "model"))
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


def init_gelu_mlp(key, d, ff, dtype):
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_in": _he(k1, (d, ff), 1 / math.sqrt(d), dtype),
        "b_in": jnp.zeros((ff,), dtype),
        "w_out": _he(k2, (ff, d), 1 / math.sqrt(ff), dtype),
        "b_out": jnp.zeros((d,), dtype),
    }


def gelu_mlp(params, x):
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"]) + params["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = logical_constraint(h, P(("pod", "data"), None, "model"))
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"]) + params["b_out"]


# --------------------------------------------------------------------------
# Embedding / LM head
# --------------------------------------------------------------------------

def init_embedding(key, vocab, d, dtype):
    # 1/sqrt(d) keeps tied-unembed logits O(1); gemma-style configs restore
    # O(1) activations via scale_embedding (x * sqrt(d)) after lookup.
    return {"table": _he(key, (vocab, d), 1.0 / math.sqrt(d), dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, softcap=0.0):
    logits = jnp.einsum("bsd,vd->bsv", x, params["table"])
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logical_constraint(logits, P(("pod", "data"), None, "model"))
