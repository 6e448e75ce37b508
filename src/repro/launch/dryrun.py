import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the full-size config, creates ShapeDtypeStruct
stand-ins for params / optimizer state / caches / batch (no allocation),
lowers the appropriate step under the production mesh with explicit
in/out shardings, compiles it, and records:

  * memory_analysis()  — proves the cell fits per-device HBM
  * cost_analysis()    — HLO FLOPs / bytes for the roofline
  * collective bytes   — parsed from the optimized HLO text

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json; the
roofline benchmark and EXPERIMENTS.md tables are generated from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--mesh-scale N]
"""

import argparse
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, cell_applicable, input_specs
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_train_step, make_prefill_step, \
    make_decode_step
from repro.models import model as MDL
from repro.parallel import sharding as SH
from repro.training.optimizer import AdamW

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mesh_name(mesh):
    return "x".join(str(s) for s in mesh.devices.shape)


def _named(mesh, spec_tree, shape_tree):
    def walk(spec, leaf):
        return NamedSharding(mesh, SH.filter_spec(spec, mesh, leaf.shape))
    return jax.tree_util.tree_map(walk, spec_tree, shape_tree)


def _scaled_cfg(cfg, k_cycles: int):
    """Config with k cycles (+ original tail) for 2-point cost extrapolation."""
    if cfg.is_encoder_decoder:
        return cfg.replace(num_layers=2 * k_cycles,
                           num_encoder_layers=k_cycles,
                           num_decoder_layers=k_cycles,
                           scan_layers=False)
    plen = len(cfg.block_pattern)
    tail = cfg.num_layers % plen
    return cfg.replace(num_layers=k_cycles * plen + tail,
                       scan_layers=False)


def _extrapolation_factor(cfg) -> float:
    """Number of scan trips N such that cost(L) = c1 + (N-1)*(c2-c1)."""
    if cfg.is_encoder_decoder:
        return cfg.num_encoder_layers  # enc and dec scale together
    plen = len(cfg.block_pattern)
    return cfg.num_layers // plen


SERVING_WEIGHT_BUDGET = 6e9      # bytes/device for weight-stationary


def _lower_one(cfg, shape, mesh, opt, microbatches: int = 1,
               serving_layout=None):
    """Lower + compile a single config at one shape. Returns artifacts."""
    param_shapes = MDL.param_shapes(cfg)
    # decode: weight-stationary layout when the TP-sharded weights fit
    # the cell (kills per-token FSDP weight gathers)
    if serving_layout is None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tp = sizes.get("model", 1)
        serving_layout = (shape.kind == "decode"
                          and cfg.param_bytes() / tp
                          < SERVING_WEIGHT_BUDGET)
    param_sh = SH.param_shardings(param_shapes, mesh,
                                  serving=serving_layout)
    batch_shapes = input_specs(cfg, shape)
    batch_sh = SH.batch_shardings(batch_shapes, mesh)

    if shape.kind == "train":
        opt_shapes = opt.state_shapes(param_shapes)
        opt_sh = jax.tree_util.tree_map(
            lambda s: (NamedSharding(mesh, P()) if s.ndim == 0 else None),
            opt_shapes)
        # m/v/master mirror the param tree shardings
        opt_sh = opt_sh._replace(
            m=SH.param_shardings(opt_shapes.m, mesh),
            v=SH.param_shardings(opt_shapes.v, mesh),
            master=SH.param_shardings(opt_shapes.master, mesh))
        step = make_train_step(cfg, opt, microbatches=microbatches)
        out_shapes = jax.eval_shape(step, param_shapes, opt_shapes,
                                    batch_shapes)
        metric_sh = SH.replicated(out_shapes[2], mesh)
        jitted = jax.jit(step,
                         in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh, metric_sh))
        args = (param_shapes, opt_shapes, batch_shapes)
    else:
        max_len = shape.seq_len
        cache_shapes = jax.eval_shape(
            partial(MDL.init_cache, cfg, shape.global_batch, max_len))
        cache_sh = SH.decode_cache_shardings(cache_shapes, mesh)
        if shape.kind == "prefill":
            step = make_prefill_step(cfg, max_len)
        else:
            step = make_decode_step(cfg)
        out_shapes = jax.eval_shape(step, param_shapes, cache_shapes,
                                    batch_shapes)
        logits_sh = NamedSharding(
            mesh, SH.filter_spec(P(("pod", "data"), "model"), mesh,
                                 out_shapes[0].shape))
        jitted = jax.jit(step,
                         in_shardings=(param_sh, cache_sh, batch_sh),
                         out_shardings=(logits_sh, cache_sh))
        args = (param_shapes, cache_shapes, batch_shapes)

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def _costs_of(compiled):
    cost = compiled.cost_analysis()
    coll = H.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll)


def lower_cell(arch: str, shape_name: str, mesh, *, opt=None,
               cfg_override=None, exact_costs: bool = True,
               microbatches: int = 1, serving_layout=None):
    """Lower + compile one cell. Returns (record dict, compiled).

    Cost accounting: XLA's cost_analysis is per-device and counts a scan
    body once, so (i) intra-layer scans are unrolled (EXACT_COST_MODE),
    (ii) layer-stack scan costs are recovered by compiling 1-cycle and
    2-cycle configs and extrapolating linearly, (iii) totals are scaled
    by chip count to report globals.  memory_analysis comes from the
    full-size compile (which is also the shardability proof).
    """
    from repro.models import layers as LAYERS
    cfg = cfg_override or configs.get_config(arch)
    shape = SHAPES[shape_name]
    opt = opt or AdamW()
    chips = mesh.devices.size
    t0 = time.time()

    lowered, compiled = _lower_one(cfg, shape, mesh, opt,
                                   microbatches=microbatches,
                                   serving_layout=serving_layout)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()

    has_wkv = ("rwkv" in cfg.block_pattern
               and shape.kind in ("train", "prefill"))
    if exact_costs:
        try:
            LAYERS.set_exact_cost_mode(True, wkv_unroll=1)
            _, c1 = _lower_one(_scaled_cfg(cfg, 1), shape, mesh, opt)
            _, c2 = _lower_one(_scaled_cfg(cfg, 2), shape, mesh, opt)
            if has_wkv:
                LAYERS.set_exact_cost_mode(True, wkv_unroll=2)
                _, c1b = _lower_one(_scaled_cfg(cfg, 1), shape, mesh, opt)
        finally:
            LAYERS.set_exact_cost_mode(False)
        f1, b1, coll1 = _costs_of(c1)
        f2, b2, coll2 = _costs_of(c2)
        n = _extrapolation_factor(cfg)
        flops = (f1 + (n - 1) * (f2 - f1)) * chips
        hbytes = (b1 + (n - 1) * (b2 - b1)) * chips
        coll = {k: int((coll1[k] + (n - 1) * (coll2[k] - coll1[k])) * chips)
                for k in coll1}
        if has_wkv:
            # chunk-scan correction: cost_analysis counts the WKV chunk
            # body once; the (unroll=2) - (unroll=1) delta is one chunk's
            # exact cost, multiplied out over all chunks and layers.
            nchunk = -(-shape.seq_len // 32)
            f1b, b1b, _ = _costs_of(c1b)
            # fusion differences can make the byte delta slightly
            # negative; clamp (flops are robust — validated against a
            # fully-unrolled compile within 5%).
            flops += n * (nchunk - 1) * max(0.0, f1b - f1) * chips
            hbytes += n * (nchunk - 1) * max(0.0, b1b - b1) * chips
    else:
        f1, b1, coll1 = _costs_of(compiled)
        flops, hbytes = f1 * chips, b1 * chips
        coll = {k: v * chips for k, v in coll1.items()}
    t_cost = time.time() - t0 - t_full

    roof = H.roofline_terms(
        arch=arch, shape=shape_name, mesh_name=_mesh_name(mesh),
        chips=chips, hlo_flops=flops, hlo_bytes=hbytes,
        coll_bytes=float(coll["total"]),
        model_flops=H.model_flops_for(cfg, shape),
        temp_bytes=float(mem.temp_size_in_bytes),
        arg_bytes=float(mem.argument_size_in_bytes))

    record = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_name(mesh),
        "chips": chips, "microbatches": microbatches,
        "lower_s": round(t_full, 2), "compile_s": round(t_cost, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "per_device_total": (mem.argument_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 + mem.generated_code_size_in_bytes),
        },
        "cost": {"global_flops": flops, "global_bytes": hbytes},
        "collectives": coll,
        "roofline": roof.to_dict(),
    }
    return record, compiled


HBM_BUDGET = 16 * 2**30          # v5e per-chip


def run_cell(arch, shape_name, mesh, save=True, verbose=True, tag="",
             exact_costs=True, skip_existing=False):
    if skip_existing:
        d = OUT_DIR / (_mesh_name(mesh) + tag)
        f = d / f"{arch}__{shape_name}.json".replace("/", "_")
        if f.exists() and "error" not in json.loads(f.read_text()):
            if verbose:
                print(f"[{_mesh_name(mesh)}] {arch:24s} {shape_name:12s} "
                      f"CACHED", flush=True)
            return json.loads(f.read_text()), True
    try:
        record, compiled = lower_cell(arch, shape_name, mesh,
                                      exact_costs=exact_costs)
        # train cells over HBM budget escalate to gradient accumulation;
        # the (exact) cost terms from the first record are kept — only
        # the memory analysis comes from the escalated compile.
        if (SHAPES[shape_name].kind == "train"
                and record["memory"]["per_device_total"] > HBM_BUDGET):
            rec1 = record
            mem1 = record["memory"]["per_device_total"]
            for mb in (2, 4):
                record, compiled = lower_cell(arch, shape_name, mesh,
                                              microbatches=mb,
                                              exact_costs=False)
                if record["memory"]["per_device_total"] <= HBM_BUDGET:
                    break
            record["cost"] = rec1["cost"]
            record["collectives"] = rec1["collectives"]
            record["roofline"] = dict(
                rec1["roofline"],
                per_device_temp_bytes=record["memory"]["temp_bytes"])
            record["memory_mb1_bytes"] = mem1
        # decode cells where weight-stationary overshoots the HBM budget
        # fall back to the FSDP weight layout (keep whichever fits /
        # is smaller)
        if (SHAPES[shape_name].kind == "decode"
                and record["memory"]["per_device_total"] > HBM_BUDGET):
            rec_fsdp, _ = lower_cell(arch, shape_name, mesh,
                                     exact_costs=exact_costs,
                                     serving_layout=False)
            if (rec_fsdp["memory"]["per_device_total"]
                    < record["memory"]["per_device_total"]):
                rec_fsdp["weight_stationary"] = False
                record = rec_fsdp
        ok = True
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        record = {"arch": arch, "shape": shape_name,
                  "mesh": _mesh_name(mesh), "error": str(e),
                  "traceback": traceback.format_exc()}
        ok = False
    if verbose:
        if ok:
            m = record["memory"]
            r = record["roofline"]
            print(f"[{record['mesh']}] {arch:24s} {shape_name:12s} "
                  f"OK  mem/dev={m['per_device_total']/2**30:.2f}GiB "
                  f"compute={r['compute_s']*1e3:.2f}ms "
                  f"memory={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms "
                  f"dom={r['dominant']} "
                  f"useful={r['useful_flop_frac']:.2f} "
                  f"(lower {record['lower_s']}s compile {record['compile_s']}s)",
                  flush=True)
        else:
            print(f"[{record['mesh']}] {arch:24s} {shape_name:12s} FAILED: "
                  f"{record['error'][:200]}", flush=True)
    if save:
        d = OUT_DIR / (record["mesh"] + tag)
        d.mkdir(parents=True, exist_ok=True)
        fname = f"{arch}__{shape_name}.json".replace("/", "_")
        (d / fname).write_text(json.dumps(record, indent=2))
    return record, ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--fast-costs", action="store_true",
                    help="skip the exact-cost probes (multi-pod sweep: "
                         "the roofline table is single-pod only)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    archs = configs.ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    n_ok = n_fail = n_skip = 0
    for mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                if not cell_applicable(arch, shape_name):
                    print(f"[{_mesh_name(mesh)}] {arch:24s} {shape_name:12s} "
                          f"SKIP (full-attention arch; see DESIGN.md)",
                          flush=True)
                    n_skip += 1
                    continue
                _, ok = run_cell(arch, shape_name, mesh,
                                 save=not args.no_save,
                                 exact_costs=not args.fast_costs,
                                 skip_existing=args.skip_existing)
                n_ok += ok
                n_fail += (not ok)
    print(f"\ndry-run summary: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
