#!/usr/bin/env python3
"""Chip smoke run: FailLite's serving main path on a TPU, end to end.

A smoke run, not a benchmark: every time it prints comes from one cold
(or compile-cache-warm) pass and says only that the path ran.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one host's four chips

One chip, in one process:
  1. device    JAX must see a TPU. There is no CPU branch.
  2. kernels   each Pallas kernel of src/repro/kernels, compiled
               (interpret=False) at a shape from a published config,
               against its ref.py.
  3. serving   qwen2.5-3b at its published widths (36 layers, d_model
               2048, vocab 151936; random weights from a fixed seed) as
               one critical app on a 2-worker MiniTestbed: deploy the
               full model and a narrower warm backup, check cached
               decode against one uncached forward, serve requests
               through the Router, crash the primary, and serve again
               from the backup after the controller's failover.

`--chips 4` runs only the replica path: four workers, one per chip.
Each engine's arrays must sit on its worker's chip; the primary's chip
is crashed, the backup on another chip serves, and its tokens must
equal the same rung replayed on chip 0 from the same seed.

The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}};
any failed phase exits non-zero before printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2.5-3b"
# alpha: the share of the cluster's free memory the planner holds back.
# With two workers it leaves the warm backup less room than the full
# model, so the planner picks a narrower rung (the 0.75-width one for
# qwen2.5-3b): full + warm stay near 10 GB of one chip's 16 GB.
ALPHA = 0.6
N_REQUESTS = 2                 # direct requests before and after the crash
CLIENT_HZ = 5.0                # the testbed's client during the failover
SEED = 0                       # testbed and request prompts
RESIDENT_LIMIT = 12e9          # bytes the one-chip run may keep resident
MAX_NEW_TOKENS = 8
PROMPT_LEN = 8                 # the engine's compiled prefill bucket
# cached decode vs one uncached forward, max |diff| over max |logit|:
# in bf16 the two paths round differently through 36 layers (1.6% on a
# v5e); a wrong cache position or mask shows as order-one differences
REF_TOL = 0.05


def log(*a):
    print(*a, flush=True)


def _wait_done(tb, reqs, timeout_s: float = 120.0):
    """Block until every request has finished; each must carry its
    prefill token plus max_new_tokens decoded ones. A fault the testbed
    records meanwhile (a failed decode step) is raised at once."""
    deadline = time.monotonic() + timeout_s
    for r in reqs:
        while r.done_at is None:
            tb.raise_errors()
            if time.monotonic() > deadline:
                raise TimeoutError(f"request {r.id} did not finish")
            time.sleep(0.005)
        if len(r.tokens) != r.max_new_tokens + 1:
            raise AssertionError(f"request {r.id}: {len(r.tokens)} tokens, "
                                 f"want {r.max_new_tokens + 1}")


def _serve_one(tb, app_id, req):
    """Submit through the router's current route; returns (sid, vname)."""
    sid, vname = tb.router.lookup(app_id)
    if not tb.workers[sid].submit(vname, req):
        raise AssertionError(f"{sid} refused {req.id} on {vname}")
    return sid, vname


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def _request_times(reqs):
    ttft = [r.first_token_at - r.submitted_at for r in reqs]
    step = [(r.done_at - r.first_token_at) / r.max_new_tokens for r in reqs]
    return sum(ttft) / len(ttft), sum(step) / len(step)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_phase():
    """Each Pallas kernel compiled for the chip at a published shape,
    against its ref.py (refs at `highest` matmul precision). Returns
    {kernel: max abs error relative to the reference's max magnitude}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.int8_matmul.ops import int8_matmul, quantize_int8
    from repro.kernels.int8_matmul.ref import int8_matmul_ref
    from repro.kernels.planner_argmax.ops import masked_argmax
    from repro.kernels.planner_argmax.ref import masked_argmax_ref
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    from repro.kernels.rwkv6_scan.ops import wkv6
    from repro.kernels.rwkv6_scan.ref import wkv6_ref

    ks = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    bf, f32 = jnp.bfloat16, jnp.float32

    def rnd(shape, dtype=f32, scale=1.0):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    def rel(out, ref):
        out = np.asarray(jnp.asarray(out, f32))
        ref = np.asarray(jnp.asarray(ref, f32))
        if not np.isfinite(out).all():
            raise AssertionError("non-finite kernel output")
        return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))

    errs, tols = {}, {}
    with jax.default_matmul_precision("highest"):
        # qwen2.5-3b decode: 16 query heads over 2 KV heads, hd 128
        q = rnd((2, 1, 16, 128), bf)
        kc, vc = rnd((2, 4096, 2, 128), bf), rnd((2, 4096, 2, 128), bf)
        lens = jnp.array([4096, 1500], jnp.int32)
        out = decode_attention(q, kc, vc, lens)
        ref = decode_attention_ref(q[:, 0], jnp.swapaxes(kc, 1, 2),
                                   jnp.swapaxes(vc, 1, 2), lens)
        errs["decode_attention"], tols["decode_attention"] = \
            rel(out[:, 0], ref), 2e-2

        # qwen2.5-3b prefill: 2048 tokens, causal
        q = rnd((1, 2048, 16, 128), bf)
        k, v = rnd((1, 2048, 2, 128), bf), rnd((1, 2048, 2, 128), bf)
        out = flash_attention(q, k, v, causal=True)
        ref = attention_ref(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                            causal=True)
        errs["flash_attention"], tols["flash_attention"] = \
            rel(out, jnp.swapaxes(ref, 1, 2)), 2e-2

        # qwen2.5-3b FFN up-projection, 16 tokens, int8 weights
        x = rnd((16, 2048), bf)
        wq, sc = quantize_int8(rnd((2048, 11008), scale=0.02))
        errs["int8_matmul"], tols["int8_matmul"] = \
            rel(int8_matmul(x, wq, sc), int8_matmul_ref(x, wq, sc)), 2e-2

        # recurrentgemma-2b RG-LRU: lru_width 2560, 2048 steps
        a = jax.nn.sigmoid(rnd((2, 2048, 2560))) * 0.2 + 0.8
        b = rnd((2, 2048, 2560), scale=0.1)
        h0 = rnd((2, 2560))
        h, hl = rglru_scan(a, b, h0)
        h_ref, hl_ref = rglru_scan_ref(a, b, h0)
        errs["rglru_scan"], tols["rglru_scan"] = \
            max(rel(h, h_ref), rel(hl, hl_ref)), 1e-4

        # rwkv6-3b WKV: 40 heads of 64, 512 steps. Log-decays inside the
        # kernel's domain: for fp32 stability it clamps lw at -40/chunk
        # (-1.25 at its chunk of 32), which its ref does not. A v5e
        # reads 4.6e-6 here; the tolerance leaves a 20x margin
        r, kk, vv = (rnd((1, 40, 512, 64)) for _ in range(3))
        lw = jnp.maximum(-jnp.exp(rnd((1, 40, 512, 64), scale=0.5) - 2.0),
                         -40.0 / 32)
        u = rnd((40, 64), scale=0.3)
        y, s = wkv6(r, kk, vv, lw, u)
        y_ref, s_ref = wkv6_ref(r, kk, vv, lw, u)
        errs["rwkv6_scan"], tols["rwkv6_scan"] = \
            max(rel(y, y_ref), rel(s, s_ref)), 1e-4

    # the planner's worst-fit reduction at 10k servers: exact, ties
    # included (values drawn from 64 levels)
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 64, 10_000).astype(np.float32) / 64
    mask = rng.random(10_000) < 0.5
    gi, gv = masked_argmax(jnp.asarray(vals), jnp.asarray(mask),
                           impl="pallas")
    wi, wv = masked_argmax_ref(vals, mask)
    errs["planner_argmax"], tols["planner_argmax"] = \
        float(int(gi) != wi or float(gv) != float(wv)), 0.0

    for name, err in errs.items():
        log(f"  kernel {name:17s} compiled  max|err|/max|ref| = {err!r}"
            f"  (tolerance {tols[name]!r})")
        if not err <= tols[name]:
            raise AssertionError(f"kernel {name} off its reference: {err}")
    return errs


# ---------------------------------------------------------------------------
# reference check: cached decode vs one uncached forward
# ---------------------------------------------------------------------------

def reference_check(params, cfg, prompt, steps: int = 4) -> dict:
    """Prefill then `steps` cached greedy decode steps (the engine's
    step functions) against one uncached `forward` over the same tokens.
    Raises on any non-finite logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as MDL

    prefill = jax.jit(lambda p, c, t: MDL.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, c, t: MDL.decode_step(p, cfg, t, c))
    forward = jax.jit(lambda p, t: MDL.forward(p, cfg, t)[0])

    cache = MDL.init_cache(cfg, 1, len(prompt) + steps)
    logits, cache = prefill(params, cache, jnp.asarray(prompt)[None])
    cached, seq = [logits[0]], list(prompt)
    for _ in range(steps):
        seq.append(int(jnp.argmax(cached[-1])))
        logits, cache = decode(params, cache, jnp.asarray(seq[-1:]))
        cached.append(logits[0])
    cached = np.asarray(jnp.stack(cached), np.float32)
    full = np.asarray(forward(params, jnp.asarray(seq)[None])[0],
                      np.float32)[len(prompt) - 1:]
    if not (np.isfinite(cached).all() and np.isfinite(full).all()):
        raise AssertionError("non-finite logits in the reference check")
    return {"max_abs_diff": float(np.abs(cached - full).max()),
            "max_abs_logit": float(np.abs(full).max()),
            "argmax_agree": int((cached.argmax(-1) == full.argmax(-1)).sum()),
            "positions": len(full)}


# ---------------------------------------------------------------------------
# serving: deploy, serve, crash, fail over, serve again
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, workers: int = 2,
                max_new_tokens: int = MAX_NEW_TOKENS,
                reference: bool = True) -> dict:
    """One critical app with `build_ladder(cfg)` on a MiniTestbed of
    `workers` workers, driven through the normal entry points. Raises
    on any failed check; returns what it measured. `reference` adds the
    cached-vs-uncached logits check on the primary's weights."""
    from repro.core.variants import Application, build_ladder
    from repro.serving.testbed import MiniTestbed
    from repro.serving.workload import make_request

    app = Application(id=f"{cfg.name}-app0", family=cfg.name,
                      variants=build_ladder(cfg), critical=True)
    rng = random.Random(SEED)

    def requests(tag):
        return [make_request(rng, f"{tag}{i}", cfg.vocab_size,
                             prompt_len=(PROMPT_LEN, PROMPT_LEN),
                             new_tokens=(max_new_tokens, max_new_tokens))
                for i in range(N_REQUESTS)]

    out = {}
    tb = MiniTestbed(n_sites=1, servers_per_site=workers, apps=[app],
                     alpha=ALPHA, seed=SEED)
    try:
        t0 = time.monotonic()
        tb.deploy()
        out["deploy_s"] = time.monotonic() - t0
        p_sid, p_name = tb.router.lookup(app.id)
        warm_v, w_sid, _key = tb.controller.warm[app.id]
        if w_sid == p_sid:
            raise AssertionError("warm backup shares the primary's server")
        out.update(primary=(p_sid, p_name), warm=(w_sid, warm_v.name))

        rungs = []
        for sid, w in tb.workers.items():
            for name, eng in list(w.engines.items()):
                for leaf in _leaves((eng.params, eng.cache)):
                    if leaf.devices() != {w.device}:
                        raise AssertionError(
                            f"{name} on {sid}: array on {leaf.devices()}, "
                            f"worker bound to {w.device}")
                v = app.variant_by_name(name)
                rungs.append({"server": sid, "device": str(w.device),
                              "variant": name, "mem_bytes": v.mem_bytes,
                              "device_bytes": eng.device_bytes(),
                              "load_s": w.load_s[name]})
                log(f"  rung {name:22s} on {sid} ({w.device}): "
                    f"Variant.mem_bytes={v.mem_bytes:.4g} "
                    f"device bytes={eng.device_bytes()} "
                    f"load+compile={w.load_s[name]!r} s")
        out["rungs"] = rungs

        if reference:
            # cached decode vs one uncached forward, on the primary
            prim = tb.workers[p_sid].engines[p_name]
            ref = reference_check(prim.params, prim.cfg,
                                  requests("ref")[0].prompt.tolist())
            del prim               # the crash must free the primary's HBM
            out["reference"] = ref
            log(f"  reference: cached vs uncached logits over "
                f"{ref['positions']} positions: max|diff|="
                f"{ref['max_abs_diff']!r} (max|logit|="
                f"{ref['max_abs_logit']!r}), argmax agrees at "
                f"{ref['argmax_agree']}/{ref['positions']}")
            if ref["max_abs_diff"] > REF_TOL * ref["max_abs_logit"]:
                raise AssertionError("cached decode is off the uncached "
                                     "forward")

        before = requests("pre")
        for r in before:
            _serve_one(tb, app.id, r)
        _wait_done(tb, before)
        out["ttft_s"], out["decode_step_s"] = _request_times(before)
        log(f"  before the crash: {len(before)} requests on {p_name}@"
            f"{p_sid}, all {max_new_tokens + 1} tokens each; mean "
            f"TTFT={out['ttft_s']!r} s, mean decode step="
            f"{out['decode_step_s']!r} s")

        res = tb.run_failure_experiment(victim=p_sid, client_hz=CLIENT_HZ)
        rec = res["records"].get(app.id)
        if rec is None or not rec.recovered:
            raise AssertionError(f"no recovery for {app.id}: {rec}")
        if rec.variant != warm_v.name:
            raise AssertionError(f"recovered on {rec.variant}, "
                                 f"not the warm {warm_v.name}")
        if tb.router.lookup(app.id) != (w_sid, warm_v.name):
            raise AssertionError(f"route is {tb.router.lookup(app.id)}")
        downtime = res["client_stats"][app.id].downtime
        out.update(detect_s=res["detect_latency_s"], mttr_s=rec.mttr,
                   mode=rec.mode, phases=dict(rec.phases),
                   client_downtime_s=downtime)
        log(f"  crash {p_sid}: detected in {res['detect_latency_s']!r} s; "
            f"recovery record: mode={rec.mode} variant={rec.variant} "
            f"controller MTTR={rec.mttr!r} s phases={rec.phases}; "
            f"client-observed downtime={downtime!r} s")

        # client traffic the backup served: requests made after the
        # detection, when the primary was down and could admit nothing
        t_detect = rec.t_fail + rec.phases["detect"]
        served = [req for _acc, req in tb.telemetry.served(app.id)
                  if req.submitted_at > t_detect]
        if not served:
            raise AssertionError("no client request completed on the backup")
        _wait_done(tb, served)
        after = requests("post")
        for r in after:
            _serve_one(tb, app.id, r)
            _wait_done(tb, [r])      # one at a time: a replayable batch
        out["after"] = [(r.prompt.tolist(), list(r.tokens)) for r in after]
        out["ttft_after_s"], out["decode_step_after_s"] = \
            _request_times(after)
        log(f"  after the crash: {len(served)} client requests and "
            f"{len(after)} direct ones completed on {warm_v.name}@{w_sid} "
            f"with all their tokens; mean TTFT={out['ttft_after_s']!r} s, "
            f"mean decode step={out['decode_step_after_s']!r} s")
        w = tb.workers[w_sid]
        out["engine_shape"] = (w.batch_slots, w.max_len)
    finally:
        tb.shutdown()          # re-raises any error a load or client hit
    out["warm_variant"] = warm_v
    return out


def replay_on(device, variant, batch_slots: int, max_len: int, served):
    """The same rung, built on `device` from the same seed, fed the same
    prompts one at a time; returns its tokens per prompt."""
    import jax
    import numpy as np

    from repro.serving.engine import InferenceEngine, Request
    from repro.serving.server import checkpoint_params

    with jax.default_device(device):
        eng = InferenceEngine(variant.config, checkpoint_params(variant),
                              batch_slots=batch_slots, max_len=max_len,
                              device=device)
        for leaf in _leaves((eng.params, eng.cache)):
            if leaf.devices() != {device}:
                raise AssertionError(f"replay array on {leaf.devices()}")
        tokens = []
        for i, (prompt, got) in enumerate(served):
            req = Request(id=f"replay{i}", prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=len(got) - 1)
            if not eng.try_admit(req):
                raise AssertionError("replay engine refused a request")
            while eng.active_count():
                eng.step()
            tokens.append(list(req.tokens))
    return tokens


def replica_phase(cfg, devices, **serve_kw) -> dict:
    """One worker per device: the primary and its backup must sit on
    different chips, and the backup's tokens after the crash must equal
    the same rung replayed on `devices[0]` from the same seed."""
    res = serve_phase(cfg, workers=len(devices), reference=False, **serve_kw)
    on = {r["server"]: r["device"] for r in res["rungs"]}
    p_dev, w_dev = on[res["primary"][0]], on[res["warm"][0]]
    if p_dev == w_dev:
        raise AssertionError("primary and backup share a chip")
    log(f"  failover across chips: {p_dev} -> {w_dev}")
    replay = replay_on(devices[0], res["warm_variant"],
                       *res["engine_shape"], res["after"])
    got = [t for _p, t in res["after"]]
    log(f"  backup tokens on {w_dev}: {got}")
    log(f"  replay tokens on {devices[0]}: {replay}")
    if replay != got:
        raise AssertionError("backup tokens differ from the chip-0 replay")
    return res


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, reference and serving on one chip; "
                         "4: one-chip replicas behind the router only")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    for d in devs:
        log(f"device {d.id}: platform={d.platform} kind={d.device_kind}")
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    log(f"smoke run (not a benchmark); compile cache: {cache_dir}")

    from repro import configs
    cfg = configs.get_config(ARCH)
    log(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.param_count()} params")

    t0 = time.monotonic()
    if args.chips == 1:
        log("[kernels]")
        kernel_phase()
        log(f"[serving] {ARCH} on a 2-worker testbed")
        res = serve_phase(cfg, workers=2)
        resident = sum(r["device_bytes"] for r in res["rungs"])
        full = next(r for r in res["rungs"]
                    if r["variant"] == res["primary"][1])
        warm = next(r for r in res["rungs"]
                    if r["variant"] == res["warm"][1])
        log(f"  resident before the crash: {resident} bytes "
            f"(limit {RESIDENT_LIMIT!r})")
        if resident > RESIDENT_LIMIT:
            raise AssertionError("resident set over the one-chip limit")
        if not warm["device_bytes"] < full["device_bytes"]:
            raise AssertionError("warm rung is not narrower than the full "
                                 "model")
    else:
        log(f"[replicas] {ARCH} on a 4-worker testbed, one chip each")
        replica_phase(cfg, devs[:4])
    for d in devs[:args.chips]:
        stats = d.memory_stats()
        peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
        log(f"peak HBM in use on {d}: {peak} bytes (limit {limit})")
        if peak >= limit:
            raise AssertionError(f"peak HBM at {d}'s limit")
    log(f"smoke wall {time.monotonic() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs[:args.chips])}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
