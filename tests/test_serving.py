"""Serving runtime: engine consistency, router semantics, and a compact
real-failure testbed integration test."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX compile-heavy: full CI tier only

from repro import configs
from repro.models import model as MDL
from repro.serving import spans
from repro.serving.engine import InferenceEngine, Request
from repro.serving.router import Router


def test_engine_matches_forward():
    cfg = configs.get_smoke("qwen2.5-3b")
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=48)
    prompt = np.arange(1, 9, dtype=np.int32)
    req = Request(id="r", prompt=prompt, max_new_tokens=3)
    assert eng.try_admit(req)
    while eng.active_count():
        eng.step()
    assert len(req.tokens) == 1 + 3
    # greedy decode must match the model's own prefill+decode
    cache = MDL.init_cache(cfg, 1, 48)
    logits, cache = MDL.prefill(params, cfg, jnp.asarray(prompt)[None],
                                cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits, cache = MDL.decode_step(
            params, cfg, jnp.asarray([toks[-1]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0])))
    assert req.tokens == toks


def test_engine_slot_reuse_and_concurrency():
    cfg = configs.get_smoke("qwen2.5-3b")
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=48)
    reqs = [Request(id=f"r{i}", prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=2) for i in range(4)]
    assert eng.try_admit(reqs[0])
    assert eng.try_admit(reqs[1])
    assert not eng.try_admit(reqs[2])       # slots full
    while eng.active_count():
        eng.step()
    assert eng.try_admit(reqs[2])           # slot freed
    assert eng.try_admit(reqs[3])
    while eng.active_count():
        eng.step()
    for r in reqs:
        assert len(r.tokens) == 3
    # same prompt, same params -> identical greedy outputs across slots
    assert reqs[0].tokens == reqs[1].tokens == reqs[2].tokens


def test_concurrent_admissions_and_steps_keep_every_request_exact():
    """Clients admit from their own threads while a server thread steps
    the engine, with the interpreter switching threads often: every
    request still gets the model's own greedy tokens (an admission and
    a decode step each read and replace the whole cache)."""
    import sys
    import threading
    cfg = configs.get_smoke("qwen2.5-3b")
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, batch_slots=3, max_len=48)
    eng.warmup()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n, np.int32)
               for n in (5, 9, 13) * 4]
    new_tokens = 6

    def reference(prompt):
        cache = MDL.init_cache(cfg, 1, 48)
        logits, cache = prefill(params, cache, jnp.asarray(prompt)[None])
        toks = [int(jnp.argmax(logits[0]))]
        for _ in range(new_tokens):
            logits, cache = decode(params, cache,
                                   jnp.asarray([toks[-1]], jnp.int32))
            toks.append(int(jnp.argmax(logits[0])))
        return toks

    prefill = jax.jit(lambda p, c, t: MDL.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, c, t: MDL.decode_step(p, cfg, t, c))
    want = [reference(p) for p in prompts]
    reqs = [Request(id=f"c{i}", prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    stop = threading.Event()

    def serve():
        while not stop.is_set():
            if not eng.step():
                time.sleep(0)

    def client(mine):
        for r in mine:
            while not eng.try_admit(r):
                time.sleep(0.0005)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        server = threading.Thread(target=serve)
        clients = [threading.Thread(target=client, args=(reqs[k::4],))
                   for k in range(4)]
        server.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=120)
        deadline = time.monotonic() + 120
        while eng.active_count() and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    server.join(timeout=30)
    assert not server.is_alive()
    assert not any(t.is_alive() for t in clients)
    assert [r.tokens for r in reqs] == want


def test_router_epoch_and_push():
    r = Router()
    seen = []
    r.subscribe(lambda a, s, v: seen.append((a, s, v)))
    r.set_route("app1", "s1", "m:full")
    assert r.lookup("app1") == ("s1", "m:full")
    e0 = r.epoch
    r.set_route("app1", "s2", "m:w050")
    assert r.epoch == e0 + 1
    assert seen[-1] == ("app1", "s2", "m:w050")


@pytest.mark.slow
def test_mini_testbed_failover_end_to_end():
    from repro.serving.testbed import MiniTestbed
    tb = MiniTestbed(apps_per_arch=1, archs=["qwen2.5-3b", "rwkv6-3b"],
                     seed=3, headroom=0.35)
    try:
        tb.deploy()
        t0 = time.monotonic()
        res = tb.run_failure_experiment(observe_s=25.0, client_hz=10.0)
        assert res["detect_latency_s"] < 0.5
        # the failover's spans, in the order the testbed runs them
        got = sorted((s for s in spans.snapshot().spans if s.start >= t0),
                     key=lambda s: s.start)
        kill, = [s for s in got if s.name == "testbed.kill"]
        victim = res["victim"]
        assert kill.attrs["servers"] == [victim] and kill.attrs["apps"]
        detect = next(s for s in got if s.name == "testbed.detect"
                      and victim in s.attrs["servers"])
        handle = next(s for s in got if s.name == "testbed.handle_failures"
                      and s.start >= detect.end)
        reroute = next(s for s in got if s.name == "router.set_route"
                       and s.start > kill.start
                       and s.attrs["app"] in kill.attrs["apps"]
                       and s.attrs["server"] != victim)
        assert detect.start == kill.start
        assert kill.start < detect.end <= handle.start < reroute.start
        s = res["summary"]
        assert s["n"] >= 1
        assert s["recovery_rate"] == 1.0
        # clients of unaffected apps kept being served
        healthy = [st for app_id, st in res["client_stats"].items()
                   if app_id not in res["records"]]
        assert all(st.ok > 0 for st in healthy)
    finally:
        tb.shutdown()
