"""Sharding rules: DP / FSDP / TP / EP / SP over the production mesh.

Mesh axes (launch/mesh.py):
    single-pod : (data=16, model=16)
    multi-pod  : (pod=2, data=16, model=16)

Conventions
-----------
* batch           -> ("pod", "data")          (pure DP over pods)
* d_model of params -> "data"                  (FSDP / ZeRO-3 style)
* heads / d_ff / experts / vocab -> "model"    (TP / EP)
* decode KV sequence -> "model"                (flash-decoding split-KV)
* long-context KV sequence -> ("data","model") when batch == 1 (SP)

All helpers degrade gracefully: axes missing from the ambient mesh are
dropped from specs, as are axes that do not divide the dimension (so the
same model code runs on the 1-device CPU smoke tests and the 512-device
dry-run unchanged).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def current_mesh() -> Optional[Mesh]:
    """The ambient mesh set by `jax.set_mesh`, or None. A bare
    `with mesh:` scope does not set it and is not seen here."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def _axis_size(mesh, name) -> int:
    return dict(zip(mesh.axis_names, mesh.axis_sizes if hasattr(mesh, "axis_sizes") else mesh.devices.shape))[name]


def filter_spec(spec: P, mesh: Mesh, shape=None) -> P:
    """Drop mesh axes that are absent or do not divide the dimension."""
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names,
                     getattr(mesh, "axis_sizes", None) or mesh.devices.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in names)
        if shape is not None and axes:
            total = int(np.prod([sizes[a] for a in axes]))
            if shape[i] % total != 0:
                # try progressively shorter prefixes of the axis tuple
                while axes:
                    total = int(np.prod([sizes[a] for a in axes]))
                    if shape[i] % total == 0:
                        break
                    axes = axes[:-1]
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def logical_constraint(x, spec: P):
    """with_sharding_constraint that is a no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, filter_spec(spec, mesh, x.shape))


# ---------------------------------------------------------------------------
# Parameter partition specs
# ---------------------------------------------------------------------------

# Leaf-name -> spec template, by *trailing* path component. Templates are
# written for the full (pod, data, model) mesh; filter_spec() adapts them.
_PARAM_RULES = {
    # embedding / head
    "table":   P("model", "data"),
    # attention
    "wq":      P("data", "model", None),
    "wk":      P("data", "model", None),
    "wv":      P("data", "model", None),
    "wo":      P("model", None, "data"),
    "bq":      P("model", None),
    "bk":      P("model", None),
    "bv":      P("model", None),
    # mlp
    "w_gate":  P("data", "model"),
    "w_up":    P("data", "model"),
    "w_down":  P("model", "data"),
    "w_in":    P("data", "model"),
    "w_out":   P("model", "data"),
    "b_in":    P("model"),
    "b_out":   P(None),
    # MoE (leading expert dim)
    "we_gate": P("model", "data", None),
    "we_up":   P("model", "data", None),
    "we_down": P("model", None, "data"),
    "router":  P("data", None),
    # RG-LRU recurrent block
    "w_x":     P("data", "model"),
    "w_gate_rec": P("data", "model"),
    "conv_w":  P(None, "model"),
    "conv_b":  P("model"),
    "gate_a":  P("model", None, None),   # (blocks, w/b, w/b)
    "gate_x":  P("model", None, None),
    "log_lambda": P("model"),
    "w_out_rec": P("model", "data"),
    # RWKV-6
    "w_r":     P("data", "model"),
    "w_k":     P("data", "model"),
    "w_v":     P("data", "model"),
    "w_g":     P("data", "model"),
    "w_o":     P("model", "data"),
    "decay_w1": P("data", None),
    "decay_w2": P(None, "model"),
    "bonus_u": P("model", None),
    "mix":     P(None),
    # norms
    "scale":   P(None),
    "bias":    P(None),
}


def spec_for_param(path: str, shape) -> P:
    """Partition spec for one parameter, by path suffix.

    Stacked (scanned) block params carry a leading layer/cycle dim which
    is never sharded; we right-align the rule spec against the shape.
    """
    leaf = path.split("/")[-1]
    rule = _PARAM_RULES.get(leaf)
    if rule is None:
        return P(*([None] * len(shape)))
    rule_dims = len(rule)
    extra = len(shape) - rule_dims
    if extra < 0:
        return P(*([None] * len(shape)))
    return P(*([None] * extra + list(rule)))


def _tree_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _tree_paths(v, f"{prefix}/{k}" if prefix else str(k))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _tree_paths(v, f"{prefix}/{i}" if prefix else str(i))
    else:
        yield prefix, tree


def param_specs(shapes_tree) -> Any:
    """Map a tree of ShapeDtypeStructs/arrays to a tree of PartitionSpecs."""
    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else str(k))
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, f"{prefix}/{i}" if prefix else str(i))
                 for i, v in enumerate(tree)]
            return type(tree)(t)
        return spec_for_param(prefix, tree.shape)
    return walk(shapes_tree)


def _drop_axes(spec: P, axes: set) -> P:
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a not in axes)
            out.append(kept if len(kept) > 1 else (kept[0] if kept
                                                   else None))
        else:
            out.append(None if e in axes else e)
    return P(*out)


def param_shardings(shapes_tree, mesh: Mesh, *, serving: bool = False):
    """NamedShardings for a param tree, with divisibility-aware filtering.

    serving=True = weight-stationary layout: the FSDP ("data"/"pod")
    axes are dropped so weights are only TP-sharded — no per-step weight
    all-gathers at decode (used when params/TP-shard fit the cell HBM).
    """
    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else str(k))
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, f"{prefix}/{i}" if prefix else str(i))
                 for i, v in enumerate(tree)]
            return type(tree)(t)
        spec = spec_for_param(prefix, tree.shape)
        if serving:
            spec = _drop_axes(spec, {"data", "pod"})
        return NamedSharding(mesh, filter_spec(spec, mesh, tree.shape))
    return walk(shapes_tree)


# Common activation/data specs --------------------------------------------

BATCH = P(("pod", "data"))


def batch_spec(ndim: int, *, seq_axis: Optional[int] = None,
               shard_seq: bool = False) -> P:
    entries: list = [("pod", "data")] + [None] * (ndim - 1)
    if shard_seq and seq_axis is not None:
        entries[seq_axis] = "model"
    return P(*entries)


def data_shardings(tree, mesh: Mesh, spec: P):
    def walk(leaf):
        return NamedSharding(mesh, filter_spec(spec, mesh, leaf.shape))
    return jax.tree_util.tree_map(walk, tree)


def batch_shardings(batch_shapes, mesh: Mesh):
    """Shardings for input batches: leading batch dim over (pod, data)."""
    def walk(leaf):
        spec = P(*([("pod", "data")] + [None] * (len(leaf.shape) - 1)))
        return NamedSharding(mesh, filter_spec(spec, mesh, leaf.shape))
    return jax.tree_util.tree_map(walk, batch_shapes)


def _mesh_sizes(mesh):
    return dict(zip(mesh.axis_names,
                    getattr(mesh, "axis_sizes", None) or mesh.devices.shape))


def decode_cache_shardings(cache_shapes, mesh: Mesh):
    """Shardings for decode caches (KV buffers + recurrent states).

    KV (.../B, S, KVH, hd): batch over (pod, data), sequence over "model"
    (flash-decoding split-KV).  When the batch does not divide the data
    axes (long_500k, B=1) the sequence dim takes (pod, data, model) —
    sequence parallelism over the full mesh.
    """
    sizes = _mesh_sizes(mesh)
    dp = sizes.get("pod", 1) * sizes.get("data", 1)

    def walk(tree, name=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)([walk(v, name) for v in tree])
        shape = tree.shape
        nd = len(shape)
        if name in ("k", "v") and nd >= 4:
            B, S = shape[-4], shape[-3]
            lead = [None] * (nd - 4)
            if B % dp == 0 and dp > 1:
                spec = P(*lead, ("pod", "data"), "model", None, None)
            else:
                spec = P(*lead, None, ("pod", "data", "model"), None, None)
        elif name == "wkv" and nd >= 4:
            lead = [None] * (nd - 4)
            spec = P(*lead, ("pod", "data"), "model", None, None)
        elif name in ("h", "last") and nd >= 2:
            lead = [None] * (nd - 2)
            spec = P(*lead, ("pod", "data"), "model")
        elif name == "conv" and nd >= 3:
            lead = [None] * (nd - 3)
            spec = P(*lead, ("pod", "data"), None, "model")
        elif name == "pos":
            spec = P(*([None] * (nd - 1)), ("pod", "data"))
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, filter_spec(spec, mesh, shape))
    return walk(cache_shapes)


def replicated(tree, mesh: Mesh):
    def walk(leaf):
        return NamedSharding(mesh, P(*([None] * len(leaf.shape))))
    return jax.tree_util.tree_map(walk, tree)
