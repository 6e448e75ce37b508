"""CPU tests of the benchmark: its manifest, generator, arithmetic, trace
reduction, reference, control and the faults `correct` must catch.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import copy
import json
import math
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHAT = "qwen2.5-3b.chat.warm-crash"


def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# -- manifest ---------------------------------------------------------------

def test_manifest_keys_names_units_and_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["bench"]
    assert 1 <= m["run_seconds"] <= 51
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        used.add(w["config"])
    assert used == set(configs)
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    for p in m["per_layer"]:
        assert set(p) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        mv = e2e[p["moves"]]
        assert set(p["workloads"]) <= set(mv.get("workloads", cells))
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= cells
        assert (BENCH / "metrics" / f"{x['name']}.py").exists()
        if x["name"].endswith("_roofline") or "_roofline." in x["name"]:
            assert x["unit"] == "%"
    for cell in cells:        # every cell: setup_s, another end-to-end
        mine = [e for e in m["end_to_end"]
                if cell in e.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in p["workloads"] for p in m["per_layer"])


def test_config_files_hold_published_numbers():
    hf = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}
    for f in (BENCH / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        for k, mk in hf.items():
            assert c["model"][mk] == c[k], (f.name, k)
        assert c["model"]["head_dim"] * c["num_attention_heads"] \
            == c["hidden_size"]


# -- traffic ------------------------------------------------------------------

def test_schedule_is_the_same_work_for_every_seed():
    from bench.harness import traffic
    mix = json.loads((BENCH / "traffic" / "chat.warm-crash.json").read_text())
    a = traffic.schedule(mix, 2**33 + 5, 45, 151936)
    b = traffic.schedule(mix, 2**33 + 5, 45, 151936)
    c = traffic.schedule(mix, 7, 45, 151936)
    assert [p.offset_s for p in a] == [p.offset_s for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    n = mix["block"]
    full = len(a) // n * n
    # other seeds: the same sizes at the same due times, other tokens
    assert [(p.offset_s, len(p.prompt), p.max_new_tokens) for p in a] \
        == [(p.offset_s, len(p.prompt), p.max_new_tokens) for p in c]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, c))
    # every block holds the same multiset of sizes and gaps
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert sorted(map(key, a[:n])) == sorted(map(key, a[n:2 * n]))
    assert full >= n
    # mean rate of whole blocks is the mix's rate within rounding
    gaps = np.diff([0.0] + [p.offset_s for p in a[:full]])
    assert abs(full / gaps.sum() / mix["rate_hz"] - 1) < 0.15
    for p in a:
        assert len(p.prompt) + p.max_new_tokens + 1 <= 1024


def test_saturated_schedule_has_whole_blocks_due_at_start():
    from bench.harness import traffic
    mix = json.loads((BENCH / "traffic" / "longctx.steady.json").read_text())
    n = traffic.saturated_count(mix, 45)
    plan = traffic.schedule(mix, 3, 45, 151936, n)
    assert len(plan) == n and n % mix["block"] == 0
    assert all(p.offset_s == 0 for p in plan)
    assert all(len(p.prompt) + p.max_new_tokens + 1 <= 4096 for p in plan)


# -- metric arithmetic ----------------------------------------------------------

def _row(i, due, first, done, n, failed=False, server="s0-0", rung="a"):
    return {"index": i, "due": due, "prompt_len": 64, "want_tokens": n,
            "t_ready": due, "t_first_try": due + 0.001,
            "t_admit": None if failed else due + 0.01, "server": server,
            "rung": rung, "first": first, "done": done, "n_tokens": n,
            "failed": failed, "lost": failed}


def test_metric_arithmetic_on_a_synthetic_run():
    from bench.harness import derive, readers
    rows = [_row(i, float(i), i + 0.1 + 0.01 * i, i + 1.0, 11)
            for i in range(40)]
    rows[7] = _row(7, 7.0, None, None, 3, failed=True)
    rows[30] = _row(30, 30.0, 31.5, 32.0, 11, server="s0-1", rung="b")
    run = {"requests": rows, "kill": {"t": 29.9, "server": "s0-0"},
           "seconds": 45.0, "setup_s": 12.5, "tokens_in_window": 400,
           "recovery": {"recovered": True, "mttr_s": 0.05, "mode": "warm",
                        "variant": "b", "phases": {}},
           "loads": {}}
    ttft = derive.ttft_s(run)
    assert math.isinf(ttft[7])
    # 40 requests: the nearest-rank p95 is the 38th smallest
    assert readers.read(ROOT, "ttft_p95_ms", run) == pytest.approx(
        1e3 * sorted(ttft)[37])
    assert readers.read(ROOT, "client_mttr_ms", run) == pytest.approx(1600)
    assert readers.read(ROOT, "ctl_mttr_ms", run) == pytest.approx(50)
    assert readers.read(ROOT, "output_tok_s", run) == pytest.approx(400 / 45)
    assert sum(r["failed"] for r in rows) == 1
    # a p95 over too many failures is infinite and is not reported
    for r in rows[:3]:
        r["failed"] = True
    assert readers.read(ROOT, "ttft_p95_ms", run) is None


def test_percentile_is_nearest_rank():
    from bench.harness import stats
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_decode_counts_match_hand_counts():
    from bench.harness import roofline
    from bench.harness.cell import model_config
    from bench.harness.weights import Shape
    want = {"qwen2.5-3b": (6.17e9, 36_864), "qwen1.5-4b": (7.90e9, 409_600)}
    for name, (wbytes, kv) in want.items():
        cfg = model_config(json.loads(
            (BENCH / "configs" / f"{name}.json").read_text()))
        s = Shape.of(cfg)
        assert roofline.param_bytes(s) == pytest.approx(wbytes, rel=2e-3)
        assert roofline.param_bytes(s) == 2 * cfg.param_count()
        assert roofline.kv_bytes_per_token(s) == kv
    # weight bytes a decode step reads, by hand. qwen2.5-3b, tied: 36
    # layers of q, k, v, o (2048*2048 + 2*2048*256 + 2048*2048), qkv
    # bias 2560, SwiGLU 3*2048*11008, two norms; the table once, as the
    # output projection; the final norm; one embedding row
    layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 2560 + 3 * 2048 * 11008 \
        + 2 * 2048
    q25 = 2 * (36 * layer + 151936 * 2048 + 2048 + 2048)
    assert q25 == 6_171_881_472
    # qwen1.5-4b, untied: 40 MHA layers, the output table read whole and
    # only one row of the input table
    layer = 4 * 2560 * 2560 + 3 * 2560 + 3 * 2560 * 6912 + 2 * 2560
    q15 = 2 * (40 * layer + 151936 * 2560 + 2560 + 2560)
    assert q15 == 7_122_831_360
    for name, wb, max_len in (("qwen2.5-3b", q25, 1024),
                              ("qwen1.5-4b", q15, 4096)):
        s = Shape.of(model_config(json.loads(
            (BENCH / "configs" / f"{name}.json").read_text())))
        assert roofline.decode_weight_bytes(s, 1) == wb
        b = roofline.decode_bound_s(s, 1, max_len, "TPU v5 lite")
        assert b["bound"] == "bytes"
        kv = roofline.kv_bytes_per_token(s)
        assert b["s"] == pytest.approx((wb + 2 * max_len * kv) / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- trace reduction ------------------------------------------------------------

def test_trace_reduction_on_a_recorded_trace():
    from bench.harness import derive, trace
    rec = json.loads((BENCH / "tests" / "data" / "trace_chat.json")
                     .read_text())
    ev = {"devices": {k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()}
                      for k, v in rec["events"]["devices"].items()},
          "annotations": [tuple(e) for e in rec["events"]["annotations"]]}
    run = rec["run"]
    run["spans"] = [tuple(s) for s in run["spans"]]
    red = trace.reduce(ev, run)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["inflight_busy_s"] <= red["inflight_s"]
    assert red["decode_calls"]
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    run["trace"] = red
    roof = derive.decode_roofline_pct(run)
    assert 0 < roof <= 100
    # the step's share of the peak: model operations over the same
    # device time, so under the roofline share
    mfu = derive.decode_mfu_pct(run)
    assert 0 < mfu < roof
    assert derive.decode_mfu_pct({**run, "trace": {"decode_calls": []}}) \
        is None
    idle = derive.device_idle_pct(run)
    assert 0 <= idle < 100


def test_interval_arithmetic():
    from bench.harness import trace
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert trace.length(trace.intersect(u, [(2, 5.5)])) == pytest.approx(1.5)
    assert trace.clip(u, 1, 6) == [(1, 3), (5, 6)]


# -- the command ----------------------------------------------------------------

def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CHAT, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], capture_output=True, text=True, env=env,
                       timeout=300, cwd=ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# -- reference, control and faults, at a size a CPU holds -------------------------

TINY = dict(num_layers=8, d_model=512, num_heads=8, num_kv_heads=2,
            head_dim=64, d_ff=1408, vocab_size=8192)
# between this size's readings over 15 seeds (`engine_readings`): the
# program's widest gap (0.017-0.048) and the fp8 control's (0.50-0.88);
# the program's mean gap (0.00014-0.00041) and the int8 control's
# (0.0012-0.0045), which the widest gap (int8: 0.061-0.159) does not
# separate
TINY_LIMIT = 0.15
TINY_MEAN_LIMIT = 0.0012


def tiny(cell_name=CHAT, **mix_kw):
    m = manifest()
    cell = next(c for c in m["workloads"] if c["name"] == cell_name)
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    config = copy.deepcopy(config)
    config["model"].update(TINY)
    config["deployment"]["max_len"] = 128
    config["correct"] = {"sample_tokens": 120, "max_gap": TINY_LIMIT,
                         "mean_gap": TINY_MEAN_LIMIT}
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    # well under what a CPU serves at this size (~0.2 s a request), so
    # that no more than the request lost at the kill goes unserved and
    # the tails stay finite
    mix.update(rate_hz=3.0, prompt_buckets=[[16, 12], [32, 6], [64, 2]],
               output_tokens=[3, 10], **mix_kw)
    return m, cell, config, mix


def run_tiny(seed=11, seconds=10.0, **mix_kw):
    import jax

    from bench.run import run_once
    m, cell, config, mix = tiny(**mix_kw)
    return run_once(ROOT, m, cell, config, mix, seed, seconds, False,
                    jax.devices(), time.monotonic())


def test_reference_matches_the_program_forward_in_float32():
    import jax.numpy as jnp

    from bench.harness import reference
    from bench.harness.cell import model_config
    from bench.harness.weights import Shape, engine_tree, make_flat
    from repro.models import model as MDL
    _m, _c, config, _mix = tiny()
    config["model"].update(param_dtype="float32", activation_dtype="float32")
    cfg = model_config(config)
    s = Shape.of(cfg)
    flat = make_flat(s, 5, "r")
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    want = np.asarray(MDL.forward(engine_tree(flat), cfg,
                                  jnp.asarray(toks)[None])[0][0])
    got = np.asarray(reference._logits(flat, jnp.asarray(toks),
                                       jnp.arange(40), reference._dims(s),
                                       None))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # teacher-forced on the tokens the program would serve, gaps are 0
    prompt, seq = toks[:20], list(toks[:20])
    for _ in range(8):
        lg = MDL.forward(engine_tree(flat), cfg, jnp.asarray(seq)[None])[0]
        seq.append(int(np.asarray(lg[0, -1]).argmax()))
    g = reference.gaps(flat, s, prompt, seq[20:], pad_to=64, rows_to=32)
    assert g["gap"].max() < 1e-4


def engine_readings(seed: int, n_requests: int = 12) -> dict:
    """The program's engine (bf16, one slot, as the cells run it) serves
    prompts one at a time; the reference reads the gaps of its tokens,
    and of the tokens the int8 and fp8 controls put first at the same
    positions."""
    from bench.harness import reference
    from bench.harness.cell import model_config
    from bench.harness.weights import Shape, engine_tree, make_flat
    from repro.serving.engine import InferenceEngine, Request
    _m, _c, config, _mix = tiny()
    cfg = model_config(config)
    s = Shape.of(cfg)
    flat = make_flat(s, seed, "rung")
    eng = InferenceEngine(cfg, engine_tree(flat), batch_slots=1, max_len=128)
    rng = np.random.default_rng(seed)
    out = {"program": [], "int8": [], "fp8": []}
    for i in range(n_requests):
        req = Request(id=str(i), max_new_tokens=31, prompt=rng.integers(
            0, cfg.vocab_size, int(rng.choice([16, 32, 64])), np.int32))
        assert eng.try_admit(req)
        while eng.active_count():
            eng.step()
        g = reference.gaps(flat, s, req.prompt, req.tokens, pad_to=128,
                           rows_to=32, controls=("int8", "fp8"))
        out["program"].extend(g["gap"])
        out["int8"].extend(g["int8"])
        out["fp8"].extend(g["fp8"])
    return out


def test_controls_are_judged_not_correct_on_three_seeds():
    from bench.harness.cell import judge
    _m, _c, config, _mix = tiny()
    cfg = dict(config["correct"], sample_tokens=12 * 32)
    for seed in (21, 22, 23):
        r = engine_readings(seed)
        assert len(r["program"]) == 12 * 32
        assert judge(r["program"], 0, cfg)["correct"], seed
        for c in ("int8", "fp8"):
            v = judge(r[c], 0, cfg)
            assert not v["correct"], (seed, c, v["checks"])


def test_a_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "client_mttr_ms", "setup_s"}


def test_a_decode_that_leaves_its_cache_unchanged_is_not_correct(
        monkeypatch):
    from repro.models import model as MDL
    orig = MDL.decode_step

    def stale(params, cfg, tokens, cache):
        logits, _new = orig(params, cfg, tokens, cache)
        return logits, cache

    monkeypatch.setattr(MDL, "decode_step", stale)
    out = run_tiny(seed=12)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > TINY_LIMIT


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import InferenceEngine
    orig = InferenceEngine.step

    def altered(self):
        live = [r for r in self.slots if r is not None]
        out = orig(self)
        for r in live:
            if len(r.tokens) == 3:
                r.tokens[-1] = (r.tokens[-1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(InferenceEngine, "step", altered)
    out = run_tiny(seed=13)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > TINY_LIMIT
