"""A decode step reads each layer's cache where it lies and writes one
position per slot into a donated cache: the engine's decode consumes the
cache it is given, and the in-place write gives the logits of the
one-hot write that a mesh keeps, and of the full forward."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.models import model as MDL
from repro.serving import spans
from repro.serving.engine import InferenceEngine, Request, _admit

pytestmark = pytest.mark.slow  # JAX compile-heavy: full CI tier only

MAX_LEN = 32

# config, prompt length per slot, decode steps
CASES = {
    "global-mha": ("qwen1.5-4b", (7,), 6),
    "global-gqa": ("qwen2.5-3b", (7,), 6),
    # window 8: the ring wraps at position 8, in the cycle and the tail
    "local-ring-wrap": ("gemma3-27b", (5,), 12),
    "three-slots": ("qwen2.5-3b", (3, 9, 14), 6),
    # rglru tail layers after a cycle that holds a local ring
    "tail-layers": ("recurrentgemma-2b", (6, 11), 6),
}


def _prompts(cfg, lengths):
    rng = np.random.default_rng(len(lengths))
    return [rng.integers(0, cfg.vocab_size, n, np.int32) for n in lengths]


def _greedy(cfg, params, prompts, steps, mesh=None):
    """Admit each prompt into its own slot at its exact length, then
    decode greedily: the tokens each step was given, (steps, B), and its
    logits, (steps, B, V)."""
    admit = jax.jit(lambda p, c, t, s, n: _admit(cfg, p, c, t, s, n))
    decode = jax.jit(lambda p, c, t: MDL.decode_step(p, cfg, t, c),
                     donate_argnums=1)
    cache = MDL.init_cache(cfg, len(prompts), MAX_LEN)
    last = []
    for slot, prompt in enumerate(prompts):
        first, cache = admit(params, cache, jnp.asarray(prompt)[None],
                             slot, len(prompt))
        last.append(int(first))
    fed, out = [], []
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        for _ in range(steps):
            fed.append(last)
            logits, cache = decode(params, cache,
                                   jnp.asarray(last, jnp.int32))
            out.append(np.asarray(logits, np.float32))
            last = [int(t) for t in np.argmax(out[-1], axis=-1)]
    return np.asarray(fed), np.stack(out)


@pytest.mark.parametrize("case", list(CASES))
def test_inplace_write_matches_the_one_hot_write(case):
    arch, lengths, steps = CASES[case]
    cfg = configs.get_smoke(arch).replace(param_dtype="bfloat16",
                                          activation_dtype="bfloat16")
    params = MDL.init_params(jax.random.PRNGKey(3), cfg)
    prompts = _prompts(cfg, lengths)
    fed, inplace = _greedy(cfg, params, prompts, steps)
    fed_onehot, onehot = _greedy(cfg, params, prompts, steps,
                                 mesh=make_local_mesh())
    np.testing.assert_array_equal(fed, fed_onehot)
    np.testing.assert_array_equal(np.argmax(inplace, -1),
                                  np.argmax(onehot, -1))
    scale = float(np.max(np.abs(onehot)))
    np.testing.assert_allclose(inplace, onehot, rtol=0, atol=2e-2 * scale)


@pytest.mark.parametrize("case", list(CASES))
def test_inplace_decode_matches_the_full_forward(case):
    """Each step's logits are the full forward's at that position over
    the prompt and the tokens decoded so far (float32)."""
    arch, lengths, steps = CASES[case]
    cfg = configs.get_smoke(arch)
    params = MDL.init_params(jax.random.PRNGKey(3), cfg)
    prompts = _prompts(cfg, lengths)
    fed, got = _greedy(cfg, params, prompts, steps)
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, fed[:, b]])
        ref, _ = MDL.forward(params, cfg, jnp.asarray(seq, jnp.int32)[None])
        want = np.asarray(ref[0, len(prompt):], np.float32)
        np.testing.assert_allclose(got[:, b], want, rtol=0, atol=2e-4)


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_smoke("qwen2.5-3b")
    return cfg, MDL.init_params(jax.random.PRNGKey(0), cfg)


def _model_tokens(cfg, params, prompt, new_tokens, max_len):
    """Greedy tokens of the model's own prefill + decode."""
    cache = MDL.init_cache(cfg, 1, max_len)
    logits, cache = MDL.prefill(params, cfg, jnp.asarray(prompt)[None],
                                cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(new_tokens):
        logits, cache = MDL.decode_step(
            params, cfg, jnp.asarray([toks[-1]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0])))
    return toks


def test_engine_decode_consumes_its_cache(qwen):
    cfg, params = qwen
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=48)
    req = Request(id="donor", prompt=np.arange(1, 9, dtype=np.int32),
                  max_new_tokens=3)
    assert eng.try_admit(req)
    t0 = time.monotonic()
    while eng.active_count():
        before = jax.tree_util.tree_leaves(eng.cache)
        eng.step()
        assert all(x.is_deleted() for x in before)
        assert not any(x.is_deleted()
                       for x in jax.tree_util.tree_leaves(eng.cache))
    steps = [s for s in spans.snapshot().spans
             if s.start >= t0 and s.name == "engine.decode"]
    assert len(steps) == 3
    assert all(s.attrs["donated"] is True for s in steps)


def test_engine_cache_after_warmup_serves_the_model_tokens(qwen):
    cfg, params = qwen
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=48)
    eng.warmup()
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert not any(x.is_deleted() for x in leaves)
    np.testing.assert_array_equal(np.asarray(eng.cache["pos"]), [0, 0])
    for rid, n in (("a", 8), ("b", 13)):
        prompt = np.random.default_rng(n).integers(0, cfg.vocab_size, n,
                                                   np.int32)
        req = Request(id=rid, prompt=prompt, max_new_tokens=5)
        assert eng.try_admit(req)
        while eng.active_count():
            eng.step()
        assert req.tokens == _model_tokens(cfg, params, prompt, 5, 48)
