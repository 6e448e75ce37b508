"""The engine's programs are a fixed set that `warmup()` builds: a warm
engine's first requests of every bucket trace nothing, whatever JAX
context its caller holds; padding a prompt up to its bucket gives the
exact-length tokens; configurations where padding is not exact keep
exact-length admission."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as MDL
from repro.serving import spans
from repro.serving.engine import InferenceEngine, Request, prefill_buckets

pytestmark = pytest.mark.slow  # JAX compile-heavy: full CI tier only

MAX_LEN = 528                   # buckets 256, 512, 528
NEW_TOKENS = 8


def _since(t0, names):
    return sorted((s for s in spans.snapshot().spans
                   if s.start >= t0 and s.name in names),
                  key=lambda s: s.start)


def _serve(eng, prompt, new_tokens, rid="r"):
    req = Request(id=rid, prompt=np.asarray(prompt, np.int32),
                  max_new_tokens=new_tokens)
    assert eng.try_admit(req)
    while eng.active_count():
        eng.step()
    return req.tokens


def _model_tokens(params, cfg, prompt, new_tokens, max_len):
    """Greedy tokens of the model's own exact-length prefill + decode."""
    prefill = jax.jit(lambda p, c, t: MDL.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, c, t: MDL.decode_step(p, cfg, t, c))
    logits, cache = prefill(params, MDL.init_cache(cfg, 1, max_len),
                            jnp.asarray(prompt, jnp.int32)[None])
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(new_tokens):
        logits, cache = decode(params, cache,
                               jnp.asarray([toks[-1]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_smoke("qwen2.5-3b")
    return cfg, MDL.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def warm_engine(qwen):
    cfg, params = qwen
    dev = jax.devices()[0]
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                          device=dev, tags={"server": "s0", "rung": "m"})
    with jax.default_device(dev):       # as a worker's load() runs it
        eng.warmup()
    return eng


def test_bucket_ladder():
    qwen = configs.get_smoke("qwen2.5-3b")
    assert prefill_buckets(qwen, 1024) == (256, 512, 1024)
    assert prefill_buckets(qwen, 4096) == (256, 512, 1024, 2048, 4096)
    assert prefill_buckets(qwen, 48) == (48,)
    assert prefill_buckets(qwen, 256) == (256,)
    assert prefill_buckets(qwen, MAX_LEN) == (256, 512, MAX_LEN)


def test_a_warm_engine_traces_nothing_from_another_thread(warm_engine):
    """warmup() under the caller's default-device context, serving from
    a thread outside it: every bucket's first admission and the decode
    steps find the programs warmup() built."""
    eng = warm_engine
    lengths = [1, 255, 256, 511, 512, MAX_LEN - 1, MAX_LEN]
    rng = np.random.default_rng(0)
    done = []

    def serve():
        for n in lengths:
            prompt = rng.integers(0, eng.cfg.vocab_size, n, np.int32)
            done.append(_serve(eng, prompt, 2, rid=f"w{n}"))

    t0 = time.monotonic()
    th = threading.Thread(target=serve)
    th.start()
    th.join(timeout=300)
    assert not th.is_alive() and len(done) == len(lengths)
    got = _since(t0, {"engine.admit", "jax.trace", "jax.lower",
                      "jax.compile"})
    assert [s.name for s in got] == ["engine.admit"] * len(lengths)
    assert [(s.attrs["prompt_len"], s.attrs["bucket"], s.attrs["warm"])
            for s in got] == [(1, 256, True), (255, 256, True),
                              (256, 256, True), (511, 512, True),
                              (512, 512, True), (MAX_LEN - 1, MAX_LEN, True),
                              (MAX_LEN, MAX_LEN, True)]


@pytest.mark.parametrize("n", [1, 255, 256, 257, 511, 512, 513])
def test_padded_admission_gives_the_exact_length_tokens(qwen, warm_engine,
                                                        n):
    cfg, params = qwen
    prompt = np.random.default_rng(n).integers(0, cfg.vocab_size, n,
                                               np.int32)
    assert _serve(warm_engine, prompt, NEW_TOKENS) == _model_tokens(
        params, cfg, prompt, NEW_TOKENS, MAX_LEN)


def test_configs_where_padding_is_not_exact_keep_exact_length():
    for arch in ("gemma3-27b", "rwkv6-3b", "recurrentgemma-2b",
                 "whisper-medium", "qwen3-moe-30b-a3b"):
        assert prefill_buckets(configs.get_smoke(arch), 1024) is None, arch


@pytest.mark.parametrize("arch", ["gemma3-27b", "rwkv6-3b",
                                  "recurrentgemma-2b"])
def test_exact_length_configs_serve_their_own_tokens(arch):
    """A local ring buffer (prompt longer than its window) and recurrent
    states: admission at the prompt's own length, the model's tokens."""
    cfg = configs.get_smoke(arch)
    params = MDL.init_params(jax.random.PRNGKey(1), cfg)
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=64)
    eng.warmup()
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 12,
                                               np.int32)
    t0 = time.monotonic()
    toks = _serve(eng, prompt, 4)
    admit, = _since(t0, {"engine.admit"})
    assert (admit.attrs["bucket"], admit.attrs["warm"]) == (None, False)
    assert toks == _model_tokens(params, cfg, prompt, 4, 64)
