"""Peaks of each chip, and the operations and bytes of a decode step.

Peaks are keyed by JAX's `device_kind`; a device not in the table is an
error, never a default. Source for the TPU v5e row: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM).

A decode step of the serving engine at batch `slots` and cache length
`max_len` (`models/model.py:decode_step`):
  flops   2 per weight of every matrix used per token (embedding lookup
          excluded, output table included), plus attention's QK and PV
          products over the whole cache: the engine scores all `max_len`
          positions and masks the unfilled ones.
  bytes   every weight the step uses read once: the layers, the final
          norm and the output table (the embedding when tied), plus one
          embedding row per slot for the lookup (an untied input table
          is not read whole); and the K/V cache of every
          layer at full `max_len` read once and written once: the step
          rewrites the whole cache with a one-hot select and does not
          donate it. A read by attention on top of that is not counted,
          since the compiler may fuse it with the select's read; the
          count is the least the step's program must move.
The roofline time is max(flops / peak flops, bytes / peak bandwidth).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a "
                       f"row to bench/harness/roofline.py") from None


def weight_count(s) -> dict:
    """Parameters by role for a dense rung (`weights.Shape`)."""
    d, h, kv, hd, ff, V, L = (s.d_model, s.num_heads, s.num_kv_heads,
                              s.head_dim, s.d_ff, s.vocab_size, s.num_layers)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    bias = (h + 2 * kv) * hd if s.qkv_bias else 0
    layer = attn + bias + 3 * d * ff + 2 * d
    table = V * d
    return {"layers": L * layer, "matmul_per_token": L * (attn + 3 * d * ff)
            + table, "embed": table, "unembed": 0 if s.tie_embeddings
            else table, "final_norm": d}


def param_bytes(s, itemsize: int = 2) -> int:
    w = weight_count(s)
    return itemsize * (w["layers"] + w["embed"] + w["unembed"]
                       + w["final_norm"])


def kv_bytes_per_token(s, itemsize: int = 2) -> int:
    return s.num_layers * 2 * s.num_kv_heads * s.head_dim * itemsize


def decode_flops(s, slots: int, max_len: int) -> float:
    w = weight_count(s)
    attn = s.num_layers * 2 * 2 * s.num_heads * s.head_dim * max_len
    return float(slots * (2 * w["matmul_per_token"] + attn))


def decode_weight_bytes(s, slots: int, itemsize: int = 2) -> int:
    """Weight bytes one decode step reads."""
    w = weight_count(s)
    out_table = w["unembed"] if w["unembed"] else w["embed"]
    return itemsize * (w["layers"] + w["final_norm"] + out_table
                       + slots * s.d_model)


def decode_bytes(s, slots: int, max_len: int) -> float:
    cache = slots * max_len * kv_bytes_per_token(s)
    return float(decode_weight_bytes(s, slots) + 2 * cache)


def decode_bound_s(s, slots: int, max_len: int, device_kind: str) -> dict:
    """Least time of one decode step, and which term bounds it."""
    pk = peaks(device_kind)
    t_f = decode_flops(s, slots, max_len) / pk["flops"]
    t_b = decode_bytes(s, slots, max_len) / pk["hbm_bytes_s"]
    return {"s": max(t_f, t_b), "bound": "flops" if t_f > t_b else "bytes"}


def model_flops_per_token(s, context: int) -> float:
    """Operations one token needs: every matrix once, attention over the
    `context` tokens it sees (not the masked rest of the cache)."""
    w = weight_count(s)
    return float(2 * w["matmul_per_token"]
                 + s.num_layers * 4 * s.num_heads * s.head_dim * context)
