"""The serving path's span recorder (repro.serving.spans): nesting across
threads, the ring's bound, JAX compiles as child spans, and the
engine's admission and decode spans."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import model as MDL
from repro.serving import spans
from repro.serving.engine import InferenceEngine, Request


def _since(t0, names=None):
    return sorted((s for s in spans.snapshot().spans if s.start >= t0
                   and (names is None or s.name in names)),
                  key=lambda s: s.start)


def test_parents_nest_per_thread():
    t0 = time.monotonic()
    seen = {}

    def worker():
        with spans.span("t.outer") as a:
            with spans.span("t.inner") as b:
                seen["thread"] = (a.parent, a.id, b.parent)

    with spans.span("m.outer") as outer:
        with spans.span("m.inner", k=1) as inner:
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
            kept = spans.record("m.interval", t0, time.monotonic())
        assert spans.current() == outer.id
    assert spans.current() is None
    assert outer.parent is None and inner.parent == outer.id
    assert kept.parent == inner.id
    # the other thread's spans do not nest under this thread's
    a_parent, a_id, b_parent = seen["thread"]
    assert a_parent is None and b_parent == a_id
    got = {s.name: s for s in _since(t0)}
    assert got["m.inner"].attrs == {"k": 1}
    assert got["t.outer"].thread != got["m.outer"].thread
    assert got["m.outer"].start <= got["m.inner"].start
    assert got["m.inner"].end <= got["m.outer"].end


def test_ring_is_bounded_and_counts_what_it_drops():
    before = spans.snapshot()
    t0 = time.monotonic()
    for i in range(spans.RING + 7):
        spans.record("ring.fill", t0, t0, i=i)
    snap = spans.snapshot()
    assert len(snap.spans) == spans.RING
    assert snap.recorded - before.recorded >= spans.RING + 7
    assert snap.dropped == snap.recorded - spans.RING
    assert snap.dropped >= 7
    # the oldest kept span is the 8th of this batch at the latest
    assert snap.spans[0].attrs["i"] <= 7
    assert snap.spans[-1].attrs["i"] == spans.RING + 6
    # a window the ring may have cut short reads as None
    assert spans.window(snap, t0, time.monotonic()) is None
    assert spans.window(snap, t0 + 1.0, t0 + 2.0) == []


def test_window_keeps_spans_that_start_inside():
    S = spans.Span
    snap = spans.Snapshot([S("a", 1.0, 5.0, 1, None, 0, {}),
                           S("b", 2.0, 3.0, 2, 1, 0, {}),
                           S("c", 6.0, 7.0, 3, None, 0, {})], 3, 0)
    assert [s.name for s in spans.window(snap, 1.5, 6.0)] == ["b", "c"]
    # dropped spans ended before the window: it is whole
    dropped = snap._replace(recorded=10, dropped=7)
    assert [s.name for s in spans.window(dropped, 5.5, 9.0)] == ["c"]
    assert spans.window(dropped, 2.5, 9.0) is None


def test_compile_inside_a_span_is_its_child():
    def tripled_plus_one(x):
        return 3 * x + 1

    t0 = time.monotonic()
    with spans.span("outer") as outer:
        y = jax.jit(tripled_plus_one)(jnp.arange(7, dtype=jnp.float32))
        y.block_until_ready()
    got = {s.name: s for s in _since(t0, {"jax.trace", "jax.lower",
                                          "jax.compile"})
           if s.attrs["fun_name"] in ("tripled_plus_one",
                                      "jit(tripled_plus_one)")}
    trace, lower, comp = got["jax.trace"], got["jax.lower"], \
        got["jax.compile"]
    assert trace.attrs == {"fun_name": "tripled_plus_one"}
    assert lower.attrs == {"fun_name": "jit(tripled_plus_one)"}
    assert comp.attrs["fun_name"] == "jit(tripled_plus_one)"
    assert comp.attrs["cache_hit"] in (True, False)
    for s in (trace, lower, comp):
        assert s.parent == outer.id
        assert outer.start <= s.start <= s.end <= time.monotonic()
    assert trace.end <= lower.end <= comp.start + 1e-3


def test_engine_spans_admission_and_decode():
    cfg = configs.get_smoke("qwen2.5-3b")
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=48,
                          tags={"server": "s0", "rung": "m:full"})
    prompt = np.arange(1, 9, dtype=np.int32)
    req = Request(id="spanned", prompt=prompt, max_new_tokens=3)
    t0 = time.monotonic()
    assert eng.try_admit(req)
    while eng.active_count():
        eng.step()
    got = _since(t0, {"engine.admit", "engine.first_token",
                      "engine.decode", "engine.sync"})
    admit, first = got[0], got[1]
    assert (admit.name, first.name) == ("engine.admit", "engine.first_token")
    # max_len 48 is the one bucket; warmup() never ran
    assert admit.attrs == {"id": "spanned", "prompt_len": 8, "bucket": 48,
                           "warm": False, "server": "s0", "rung": "m:full"}
    assert first.attrs == {"id": "spanned"} and first.parent == admit.id
    assert admit.start <= first.start <= first.end <= admit.end
    assert admit.end - admit.start > first.end - first.start
    assert first.end <= req.first_token_at <= admit.end
    steps = got[2:]
    assert [s.name for s in steps] == ["engine.decode", "engine.sync"] * 3
    for dec, syn in zip(steps[::2], steps[1::2]):
        assert dec.attrs == {"ids": ("spanned",), "donated": True}
        assert syn.attrs == {"ids": ("spanned",)}
        assert dec.end <= syn.start
    assert steps[-1].end <= req.done_at
    # tokens are the model's own greedy prefill + decode
    cache = MDL.init_cache(cfg, 1, 48)
    logits, cache = MDL.prefill(params, cfg, jnp.asarray(prompt)[None],
                                cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits, cache = MDL.decode_step(
            params, cfg, jnp.asarray([toks[-1]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0])))
    assert req.tokens == toks
    # the benchmark finds the decode program in the trace by this name
    tok = jnp.zeros((2,), jnp.int32)
    assert "@jit__lambda" in eng._decode.lower(
        eng.params, eng.cache, tok).as_text().splitlines()[0]


def test_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("probe.outer"):
            with spans.span("probe.inner"):
                jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    got = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("probe.")}
    (a0, a1), (b0, b1) = got["probe.outer"], got["probe.inner"]
    assert a0 <= b0 <= b1 <= a1
    # no trace running: no annotation, and the span is still kept
    with spans.span("probe.untraced") as s:
        assert s._ann is None
    assert spans.snapshot().spans[-1].name == "probe.untraced"
