"""Model assembly: layer stacks, scan-over-cycles, caches, fwd/prefill/decode.

The layer stack is grouped into *cycles* of ``cfg.block_pattern``; cycles are
jnp-stacked and iterated with ``lax.scan`` (small HLO, fast multi-pod
compiles), any remainder layers run unrolled as the tail.  One code path
serves all ten assigned architectures; encoder-decoder (whisper) lives in
``encdec.py`` and is dispatched from the public API here.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import rwkv6 as W
from repro.models.config import ModelConfig
from repro.parallel.sharding import current_mesh, logical_constraint

ACT_SPEC = P(("pod", "data"), None, None)
HEAD_SPEC = P(("pod", "data"), None, "model", None)
# Megatron-style sequence parallelism: the residual stream (and therefore
# the scan/remat activation stash) lives sequence-sharded over "model";
# GSPMD turns the TP all-reduces into all-gather + reduce-scatter pairs at
# the attention/FFN boundaries. 16x smaller stash; same collective bytes.
RESID_SPEC = P(("pod", "data"), "model", None)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype
    d = cfg.d_model
    if kind in ("global", "local"):
        return {
            "norm1": L.init_rmsnorm(d, dt),
            "attn": L.init_attention(ks[0], cfg),
            "norm2": L.init_rmsnorm(d, dt),
            "ffn": L.init_swiglu(ks[1], d, cfg.d_ff, dt),
        }
    if kind in ("moe", "moe_dense"):
        return {
            "norm1": L.init_rmsnorm(d, dt),
            "attn": L.init_attention(ks[0], cfg),
            "norm2": L.init_rmsnorm(d, dt),
            "moe": M.init_moe(ks[1], cfg),
        }
    if kind == "rglru":
        return {
            "norm1": L.init_rmsnorm(d, dt),
            "rec": R.init_rglru(ks[0], cfg),
            "norm2": L.init_rmsnorm(d, dt),
            "ffn": L.init_swiglu(ks[1], d, cfg.d_ff, dt),
        }
    if kind == "rwkv":
        return {
            "norm1": L.init_layernorm(d, dt),
            "tm": W.init_time_mix(ks[0], cfg),
            "norm2": L.init_layernorm(d, dt),
            "cm": W.init_channel_mix(ks[1], cfg),
        }
    raise ValueError(kind)


def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


@partial(jax.jit, static_argnames=("cfg", "kind"))
def _init_stack(keys, cfg: ModelConfig, kind: str):
    """Stacked params of len(keys) layers of one kind: a loop over one
    compiled layer body, written straight into the stacked arrays (no
    per-layer copies to stack, so peak memory stays near the params'
    size). A loop, not a `vmap`: the TPU compile of the random draws
    grows with the batched size (qwen2.5-3b: 28 s vmapped, 5 s looped,
    compiled for a v5e)."""
    return jax.lax.map(lambda k: init_layer(k, cfg, kind), keys)


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.init_params(key, cfg)
    kinds = cfg.layer_kinds()
    pat = cfg.block_pattern
    plen = len(pat)
    n_cycles = cfg.num_layers // plen if cfg.scan_layers else 0

    # layer i draws from keys[i] whether it is stacked or in the tail
    keys = jax.random.split(key, cfg.num_layers + 2)
    cycles = []
    if n_cycles > 0:
        for pos in range(plen):
            cycles.append(_init_stack(keys[pos:n_cycles * plen:plen], cfg,
                                      pat[pos]))
    tail = [init_layer(keys[i], cfg, kinds[i])
            for i in range(n_cycles * plen, cfg.num_layers)]

    p = {
        "embed": L.init_embedding(keys[-1], cfg.vocab_size, cfg.d_model, cfg.pdtype),
        "final_norm": (L.init_layernorm(cfg.d_model, cfg.pdtype)
                       if "rwkv" in pat else L.init_rmsnorm(cfg.d_model, cfg.pdtype)),
        "cycles": cycles,
        "tail": tail,
    }
    if not cfg.tie_embeddings:
        p["unembed"] = L.init_embedding(keys[-2], cfg.vocab_size, cfg.d_model, cfg.pdtype)
    return p


# ---------------------------------------------------------------------------
# Per-layer apply: full-sequence (train / prefill) and single-step (decode)
# ---------------------------------------------------------------------------

def _attn_common(params, cfg, kind, x, positions, theta_override=None):
    if theta_override is not None:
        theta = theta_override
    else:
        theta = (cfg.rope_theta_local
                 if (kind == "local" and cfg.rope_theta_local)
                 else cfg.rope_theta)
    q, k, v = L._qkv(params["attn"], cfg, x, positions, theta=theta)
    # NOTE (§Perf log, refuted): for head counts that don't divide the TP
    # axis (qwen1.5: 20 on 16) we tried sequence-parallel attention
    # (q/scores seq-sharded, K/V gathered). With MHA the per-layer K/V
    # gathers are as large as Q and the collective term got 2.6-7x WORSE
    # (24.5s -> 63.6s train; 20.5s -> 157s prefill); head-parallel with
    # replicated remainder is the better baseline. The real remedy is
    # padding heads to the axis size (documented in EXPERIMENTS.md).
    q = logical_constraint(q, HEAD_SPEC)
    return q, k, v


def layer_forward(params, cfg, kind, x, positions, cache=None,
                  window_override=None, theta_override=None):
    """Full-sequence layer apply.

    Returns (x, aux_loss, new_cache). cache=None means train (no caching).
    window_override/theta_override: traced per-layer values for the
    uniform attention scan (gemma3-style interleaves).
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    if window_override is not None:
        window = window_override
    else:
        window = cfg.window_size if kind == "local" else 0

    if kind == "rwkv":
        st = cache or {}
        xn = logical_constraint(
            L.layernorm(params["norm1"], x, cfg.norm_eps), ACT_SPEC)
        h, tm_state = W.time_mix(params["tm"], cfg, xn, st.get("tm"))
        x = x + logical_constraint(h, RESID_SPEC)
        xn = logical_constraint(
            L.layernorm(params["norm2"], x, cfg.norm_eps), ACT_SPEC)
        h, cm_state = W.channel_mix(params["cm"], cfg, xn, st.get("cm"))
        x = x + logical_constraint(h, RESID_SPEC)
        if cache is not None:
            new_cache = {"tm": tm_state, "cm": cm_state}
        return x, aux, new_cache

    if kind == "rglru":
        xn = logical_constraint(
            L.rmsnorm(params["norm1"], x, cfg.norm_eps), ACT_SPEC)
        h, rec_state = R.recurrent_block(params["rec"], cfg, xn,
                                         cache if cache else None)
        x = x + logical_constraint(h, RESID_SPEC)
        xn = logical_constraint(
            L.rmsnorm(params["norm2"], x, cfg.norm_eps), ACT_SPEC)
        x = x + logical_constraint(L.swiglu(params["ffn"], xn), RESID_SPEC)
        if cache is not None:
            new_cache = rec_state
        return x, aux, new_cache

    # attention-bearing kinds
    xn = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    xn = logical_constraint(xn, ACT_SPEC)            # SP all-gather
    q, k, v = _attn_common(params, cfg, kind, xn, positions,
                           theta_override)
    if (isinstance(window, int) and window > 0 and q.shape[1] > window):
        # static sliding window: banded attention touches only the
        # (window + q_block) KV band per q block instead of masking the
        # full sequence (21x fewer score FLOPs at 32k prefill)
        o = L.banded_local_attention_jnp(q, k, v, window=window)
    else:
        o = L.flash_attention_jnp(q, k, v, causal=True, window=window,
                                  kv_block=min(1024, max(128, q.shape[1])))
    o = jnp.einsum("bshk,hkd->bsd", o, params["attn"]["wo"])
    x = x + logical_constraint(o, RESID_SPEC)        # SP reduce-scatter

    xn = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    xn = logical_constraint(xn, ACT_SPEC)
    if kind in ("moe", "moe_dense"):
        h, aux = M.moe_ffn(params["moe"], cfg, xn)
    else:
        h = L.swiglu(params["ffn"], xn)
    x = x + logical_constraint(h, RESID_SPEC)

    if cache is not None:
        new_cache = _write_kv_prefill(cache, cfg, kind, k, v, positions)
    return x, aux, new_cache


def _kv_cache_len(cfg, kind, max_len):
    return min(cfg.window_size, max_len) if kind == "local" else max_len


def _write_kv_prefill(cache, cfg, kind, k, v, positions):
    """Write prefill K/V into the (ring-)buffer cache."""
    S = k.shape[1]
    W_ = cache["k"].shape[1]
    if kind == "local" and S > W_:
        # keep only the last window tokens; absolute slot = t % W
        tail_idx = jnp.arange(S - W_, S)
        slots = tail_idx % W_
        knew = cache["k"].at[:, slots].set(k[:, S - W_:])
        vnew = cache["v"].at[:, slots].set(v[:, S - W_:])
    else:
        knew = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
        vnew = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
    return {"k": knew, "v": vnew}


def _decode_slot(kind, pos, cache_len):
    """The cache entry the token at `pos` takes: a ring's slot for
    `local`, the position itself otherwise."""
    return (pos % cache_len) if kind == "local" else pos


def layer_decode(params, cfg, kind, x, pos, cache):
    """Single-token layer apply. x: (B,1,d); pos: (B,) absolute position.

    Returns (x, state): the whole new state of a recurrent layer; the
    token's own {"k", "v"} (B, KVH, hd) of an attention layer, which
    reads its cache as it lies and leaves the write to
    `_write_kv_decode`."""
    if kind == "rwkv":
        h, tm_state = W.time_mix(params["tm"], cfg,
                                 L.layernorm(params["norm1"], x, cfg.norm_eps),
                                 cache["tm"], use_chunked=False)
        x = x + h
        h, cm_state = W.channel_mix(params["cm"], cfg,
                                    L.layernorm(params["norm2"], x, cfg.norm_eps),
                                    cache["cm"])
        x = x + h
        return x, {"tm": tm_state, "cm": cm_state}

    if kind == "rglru":
        h, rec_state = R.recurrent_block(
            params["rec"], cfg, L.rmsnorm(params["norm1"], x, cfg.norm_eps),
            cache)
        x = x + h
        x = x + L.swiglu(params["ffn"],
                         L.rmsnorm(params["norm2"], x, cfg.norm_eps))
        return x, rec_state

    xn = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _attn_common(params, cfg, kind, xn, pos[:, None])
    W_ = cache["k"].shape[1]
    # the entries written before this token, less the ring slot it is
    # about to take (that token has left the window); the token's own
    # K/V join the softmax as one more score and value
    idx = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], W_), 1)
    valid = ((idx < jnp.minimum(pos, W_)[:, None])
             & (idx != _decode_slot(kind, pos, W_)[:, None]))
    o = L.decode_attention_append_jnp(q, cache["k"], cache["v"], valid,
                                      k[:, 0], v[:, 0])
    o = jnp.einsum("bshk,hkd->bsd", o, params["attn"]["wo"])
    x = x + o

    xn = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    if kind in ("moe", "moe_dense"):
        h, _ = M.moe_ffn(params["moe"], cfg, xn)
    else:
        h = L.swiglu(params["ffn"], xn)
    x = x + h
    return x, {"k": k[:, 0], "v": v[:, 0]}


def _write_kv_decode(kind, cache, new, pos):
    """A layer's cache after a decode step, from what `layer_decode`
    returned: a recurrent state replaces the old one whole; each slot's
    new token {"k", "v"} (..., B, KVH, hd) goes into the K/V cache
    (..., B, S, KVH, hd) at its `_decode_slot`, for every layer of a
    leading layer dim at once."""
    if kind in ("rwkv", "rglru"):
        return new
    S = cache["k"].shape[-3]
    slot = _decode_slot(kind, pos, S)
    if current_mesh() is not None:
        # one-hot masked write instead of a scatter: GSPMD handles the
        # elementwise select shard-locally on the (batch, seq)-sharded
        # cache, where a scatter forced a full-cache regather (measured:
        # dominant collective term of the decode cells).
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (pos.shape[0], S), 1)
                  == slot[:, None])[..., None, None]
        return {n: jnp.where(onehot, new[n][..., None, :, :], cache[n])
                for n in ("k", "v")}
    # one position per slot, in place where the cache is donated; a slot
    # past the cache (a free one, which admission resets) is clamped to
    # the last entry, which it does not read before rewriting it
    out = {}
    for n in ("k", "v"):
        c = cache[n]
        bax = c.ndim - 4
        for b in range(pos.shape[0]):
            start = [0] * c.ndim
            start[bax], start[bax + 1] = b, slot[b]
            row = jax.lax.slice_in_dim(new[n], b, b + 1, axis=bax)
            c = jax.lax.dynamic_update_slice(
                c, row[..., None, :, :].astype(c.dtype), start)
        out[n] = c
    return out


# ---------------------------------------------------------------------------
# Cache allocation
# ---------------------------------------------------------------------------

def init_layer_cache(cfg, kind, batch, max_len):
    dt = cfg.adtype
    if kind == "rwkv":
        return W.init_rwkv_state(cfg, batch)
    if kind == "rglru":
        return R.init_rglru_state(cfg, batch, dt)
    S = _kv_cache_len(cfg, kind, max_len)
    kv = cfg.num_kv_heads
    hd = cfg.head_dim
    shape = (batch, S, kv, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.init_cache(cfg, batch, max_len)
    kinds = cfg.layer_kinds()
    pat = cfg.block_pattern
    plen = len(pat)
    n_cycles = (cfg.num_layers // plen) if cfg.scan_layers else 0
    cycles = []
    for pos in range(plen):
        if n_cycles:
            per = [init_layer_cache(cfg, pat[pos], batch, max_len)
                   for _ in range(n_cycles)]
            cycles.append(_tree_stack(per))
    tail_kinds = kinds[n_cycles * plen:]
    tail = [init_layer_cache(cfg, k, batch, max_len) for k in tail_kinds]
    return {"pos": jnp.zeros((batch,), jnp.int32), "cycles": cycles,
            "tail": tail}


# ---------------------------------------------------------------------------
# Whole-model forward / prefill / decode
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, tokens, prefix_embeds=None):
    x = L.embed(params["embed"], tokens).astype(cfg.adtype)
    if cfg.scale_embedding:
        x = x * math.sqrt(cfg.d_model)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.adtype), x], axis=1)
    return logical_constraint(x, RESID_SPEC)


def _unembed(params, cfg, x):
    x = (L.layernorm if "rwkv" in cfg.block_pattern else L.rmsnorm)(
        params["final_norm"], x, cfg.norm_eps)
    table = params["unembed" if "unembed" in params else "embed"]
    return L.unembed(table, x, cfg.logit_softcap)


def unembed_table(params):
    return params["unembed" if "unembed" in params else "embed"]


def _uniform_attention(cfg) -> bool:
    """True when every layer is plain attention (local/global) — the
    stack can then scan per-LAYER with traced (window, theta) inputs."""
    return (len(cfg.block_pattern) > 1 and
            all(k in ("local", "global") for k in cfg.block_pattern))


def _merge_attention_stack(params, cfg):
    """Interleave per-position cycle stacks (+tail) into one (L, ...)
    stack, with per-layer window/theta arrays.

    gemma3's 5-local:1-global cycle otherwise forces the remat scan body
    to hold SIX layers' backward intermediates at once (measured
    48 GiB/device on train_4k); a per-layer scan caps the peak at one.
    """
    kinds = cfg.layer_kinds()

    def interleave(*stacks):
        # stacks: plen arrays of (n_cycles, ...) -> (n_cycles*plen, ...)
        st = jnp.stack(stacks, axis=1)
        return st.reshape((-1,) + st.shape[2:])

    merged = jax.tree_util.tree_map(interleave, *params["cycles"])
    if params["tail"]:
        tail = _tree_stack(params["tail"])
        merged = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), merged, tail)
    windows = jnp.asarray(
        [cfg.window_size if k == "local" else 0 for k in kinds],
        jnp.int32)
    thetas = jnp.asarray(
        [(cfg.rope_theta_local if (k == "local" and cfg.rope_theta_local)
          else cfg.rope_theta) for k in kinds], jnp.float32)
    return merged, windows, thetas


def _stack_body(cfg, mode):
    """Build the scan body over cycles for `forward` or `prefill`."""
    pat = cfg.block_pattern

    def body(carry, xs):
        x, aux, positions = carry
        if mode == "forward":
            cycle_params = xs
            for i, kind in enumerate(pat):
                x, a, _ = layer_forward(cycle_params[i], cfg, kind, x,
                                        positions)
                aux = aux + a
            return (x, aux, positions), None
        cycle_params, cycle_cache = xs
        new_caches = []
        for i, kind in enumerate(pat):
            x, a, c = layer_forward(cycle_params[i], cfg, kind, x, positions,
                                    cache=cycle_cache[i])
            aux = aux + a
            new_caches.append(c)
        return (x, aux, positions), tuple(new_caches)
    return body


def forward(params, cfg: ModelConfig, tokens, prefix_embeds=None):
    """Training/eval forward. Returns (logits, aux_loss)."""
    x, aux = forward_features(params, cfg, tokens, prefix_embeds)
    table = params["unembed" if "unembed" in params else "embed"]
    return L.unembed(table, x, cfg.logit_softcap), aux


def forward_features(params, cfg: ModelConfig, tokens, prefix_embeds=None):
    """Forward up to (and incl.) the final norm; no unembed matmul.

    The training loss pairs this with a chunked cross-entropy so the
    (B, S, vocab) logits tensor is never materialized in full.
    """
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.forward_features(params, cfg, tokens, prefix_embeds)
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    aux = jnp.zeros((), jnp.float32)

    # banded local attention (static window) needs the cycle path; the
    # uniform merged scan only pays off when windows don't bind anyway
    banded_applicable = ("local" in cfg.block_pattern
                         and cfg.window_size < x.shape[1])
    if params["cycles"] and _uniform_attention(cfg) and not banded_applicable:
        # per-layer scan with traced (window, theta): one layer's backward
        # intermediates at a time instead of a whole pattern cycle's
        merged, windows, thetas = _merge_attention_stack(params, cfg)

        def ubody(carry, xs):
            x, aux, positions = carry
            p_l, w, th = xs
            x, a, _ = layer_forward(p_l, cfg, "global", x, positions,
                                    window_override=w, theta_override=th)
            return (x, aux + a, positions), None

        if cfg.remat:
            ubody = jax.checkpoint(
                ubody, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux, _), _ = jax.lax.scan(ubody, (x, aux, positions),
                                      (merged, windows, thetas))
    else:
        body = _stack_body(cfg, "forward")
        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        if params["cycles"]:
            (x, aux, _), _ = jax.lax.scan(body, (x, aux, positions),
                                          tuple(params["cycles"]))
        kinds = cfg.layer_kinds()
        tail_kinds = kinds[len(kinds) - len(params["tail"]):]
        for p_l, kind in zip(params["tail"], tail_kinds):
            x, a, _ = layer_forward(p_l, cfg, kind, x, positions)
            aux = aux + a
    norm = L.layernorm if "rwkv" in cfg.block_pattern else L.rmsnorm
    return norm(params["final_norm"], x, cfg.norm_eps), aux


def prefill(params, cfg: ModelConfig, tokens, cache, prefix_embeds=None,
            length=None):
    """Process a prompt, fill the cache. Returns (last-token logits, cache).

    `length` (decoder-only models; may be traced): the prompt's true
    length when `tokens` is padded after it. The logits are then those
    at `length - 1` and `pos` advances by `length`. Causal attention
    hides the pads from the prompt, and decode masks the cache beyond
    `pos` and overwrites it, so global attention never reads the pads'
    K/V; a ring buffer or a recurrent state would take them in."""
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.prefill(params, cfg, tokens, cache, prefix_embeds)
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    B, S, _ = x.shape
    positions = cache["pos"][:, None] + jnp.arange(S)[None, :]
    aux = jnp.zeros((), jnp.float32)

    new_cycles = []
    if params["cycles"]:
        body = _stack_body(cfg, "prefill")
        (x, aux, _), ys = jax.lax.scan(
            body, (x, aux, positions),
            (tuple(params["cycles"]), tuple(cache["cycles"])))
        new_cycles = list(ys)
    kinds = cfg.layer_kinds()
    tail_kinds = kinds[len(kinds) - len(params["tail"]):]
    new_tail = []
    for p_l, c_l, kind in zip(params["tail"], cache["tail"], tail_kinds):
        x, a, c = layer_forward(p_l, cfg, kind, x, positions, cache=c_l)
        new_tail.append(c)
    if length is None:
        length = S
    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = _unembed(params, cfg, last)
    new_cache = {"pos": cache["pos"] + length, "cycles": new_cycles,
                 "tail": new_tail}
    return logits[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache):
    """One decode step. tokens: (B,) int32. Returns (logits (B,V), cache)."""
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.decode_step(params, cfg, tokens, cache)
    pos = cache["pos"]
    x = _embed_inputs(params, cfg, tokens[:, None])
    pat = cfg.block_pattern

    # the layer scan reads the stacked caches and writes none: its ys are
    # each layer's new token (or recurrent state), written after it
    def body(x, xs):
        cycle_params, cycle_cache = xs
        new = []
        for i, kind in enumerate(pat):
            x, c = layer_decode(cycle_params[i], cfg, kind, x, pos,
                                cycle_cache[i])
            new.append(c)
        return x, tuple(new)

    new_cycles = []
    if params["cycles"]:
        x, ys = jax.lax.scan(body, x, (tuple(params["cycles"]),
                                       tuple(cache["cycles"])))
        new_cycles = [_write_kv_decode(kind, c, y, pos)
                      for kind, c, y in zip(pat, cache["cycles"], ys)]
    kinds = cfg.layer_kinds()
    tail_kinds = kinds[len(kinds) - len(params["tail"]):]
    new_tail = []
    for p_l, c_l, kind in zip(params["tail"], cache["tail"], tail_kinds):
        x, y = layer_decode(p_l, cfg, kind, x, pos, c_l)
        new_tail.append(_write_kv_decode(kind, c_l, y, pos))
    logits = _unembed(params, cfg, x)
    new_cache = {"pos": pos + 1, "cycles": new_cycles, "tail": new_tail}
    return logits[:, 0], new_cache


def param_shapes(cfg: ModelConfig):
    """Shape/dtype tree without allocation (for the dry-run)."""
    return jax.eval_shape(partial(init_params, cfg=cfg), jax.random.PRNGKey(0))


# -- per-slot cache views (serving engine continuous batching) -------------

_STACKED_KEYS = ("cycles", "self", "cross")   # leading dim = layer stack


def cache_take_slot(cache: Dict[str, Any], slot: int) -> Dict[str, Any]:
    """Length-1 batch view of one slot of a decode cache (`slot` may be
    traced)."""
    out = {}
    for k, v in cache.items():
        ax = 1 if k in _STACKED_KEYS else 0
        out[k] = jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, slot, 1, axis=ax), v)
    return out


def cache_put_slot(cache: Dict[str, Any], slot: int,
                   sub: Dict[str, Any]) -> Dict[str, Any]:
    """Write a length-1 batch view back into slot `slot`."""
    out = {}
    for k, v in cache.items():
        ax = 1 if k in _STACKED_KEYS else 0
        out[k] = jax.tree_util.tree_map(
            lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                full, part.astype(full.dtype), slot, axis=ax), v, sub[k])
    return out
