"""The readers of the program's spans (`repro.serving.spans`), each on a
hand-built span list: what they read, and when they read nothing."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

from bench.harness import readers
from repro.serving.spans import Snapshot, Span

ROOT = Path(__file__).resolve().parents[2]
RUN = {"window": {"t0": 10.0, "t_end": 60.0}}
SPAN_READERS = ["decode_host_gap_ms.chat", "decode_host_gap_ms.longctx",
                "admit_host_ms.chat", "failover_admit_ms", "reroute_ms",
                "window_compile_ms.chat", "window_compile_ms.longctx"]


def spans(*rows):
    """Spans from (name, start, end, parent, attrs), ids from 1."""
    return [Span(name, a, b, i, parent, 0, attrs)
            for i, (name, a, b, parent, attrs) in enumerate(rows, 1)]


def snap(rows, dropped=0):
    return Snapshot(rows, len(rows) + dropped, dropped)


def value(name, rows, run=RUN, dropped=0):
    return readers.load(ROOT, name).from_snapshot(snap(rows, dropped), run)


def chat():
    """A window: one admission on s0 with a compile in it, two decode
    steps, the kill of s0, detection, the route to s1, and the first
    admission on s1 with a cache read in it."""
    return spans(
        ("engine.admit", 11.000, 11.030, None,
         {"id": "r0", "server": "s0", "rung": "m:full"}),          # 1
        ("jax.compile", 11.002, 11.006, 1, {"fun_name": "jit(f)"}),  # 2
        ("engine.first_token", 11.020, 11.030, 1, {"id": "r0"}),     # 3
        ("engine.decode", 11.031, 11.032, None, {"ids": ("r0",)}),
        ("engine.sync", 11.032, 11.040, None, {"ids": ("r0",)}),
        ("engine.decode", 11.040, 11.0425, None, {"ids": ("r0",)}),
        ("engine.sync", 11.0425, 11.050, None, {"ids": ("r0",)}),
        ("engine.decode", 11.050, 11.0515, None, {"ids": ("r0",)}),
        ("engine.sync", 11.0515, 11.060, None, {"ids": ("r0",)}),
        ("testbed.kill", 30.0, 30.0, None,
         {"servers": ["s0"], "apps": ["app0"]}),
        ("testbed.detect", 30.0, 30.040, None, {"servers": ["s0"]}),
        ("testbed.handle_failures", 30.041, 30.050, None,
         {"servers": ["s0"]}),                                    # 12
        ("router.set_route", 30.045, 30.046, 12,
         {"app": "app0", "server": "s1", "variant": "m:w075",
          "epoch": 2}),
        ("engine.admit", 30.050, 30.650, None,
         {"id": "r9", "server": "s1", "rung": "m:w075"}),          # 14
        ("jax.compile", 30.060, 30.300, 14,
         {"fun_name": "jit(_lambda)", "cache_hit": True}),
        ("engine.first_token", 30.600, 30.650, 14, {"id": "r9"}),
    )


def test_each_reader_on_a_hand_built_window():
    rows = chat()
    # host gaps 11.0425 - 11.040 and 11.0515 - 11.050
    for name in ("decode_host_gap_ms.chat", "decode_host_gap_ms.longctx"):
        assert value(name, rows) == pytest.approx(2.0)
    # admissions: 30 - 10 and 600 - 50 ms of host time; median of two
    assert value("admit_host_ms.chat", rows) == pytest.approx(285.0)
    assert value("failover_admit_ms", rows) == pytest.approx(600.0)
    assert value("reroute_ms", rows) == pytest.approx(46.0)
    for name in ("window_compile_ms.chat", "window_compile_ms.longctx"):
        assert value(name, rows) == pytest.approx(244.0)


def test_only_spans_that_start_in_the_window_count():
    rows = chat()
    late = {"window": {"t0": 11.035, "t_end": 60.0}}
    # the first step's sync started before t0: one gap is left
    assert value("decode_host_gap_ms.chat", rows, late) == \
        pytest.approx(1.5)
    assert value("admit_host_ms.chat", rows, late) == pytest.approx(550.0)
    assert value("window_compile_ms.chat", rows, late) == \
        pytest.approx(240.0)


def test_no_compile_in_a_window_with_engine_spans_reads_zero():
    rows = [s for s in chat() if s.name != "jax.compile"]
    assert value("window_compile_ms.longctx", rows) == 0.0


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_window_the_ring_cut_short_reads_nothing(name):
    rows = chat()
    assert value(name, rows, dropped=3) is None
    # spans dropped before the window began leave it whole
    early = spans(("old", 1.0, 2.0, None, {})) + rows
    assert value(name, early, dropped=3) is not None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_an_empty_window_reads_nothing(name):
    assert value(name, []) is None
    assert value(name, chat(), {"window": {"t0": 70.0, "t_end": 80.0}}) \
        is None


def test_missing_kill_or_backup_admission_reads_nothing():
    rows = chat()
    no_kill = [s for s in rows if s.name != "testbed.kill"]
    assert value("failover_admit_ms", no_kill) is None
    assert value("reroute_ms", no_kill) is None
    # the route and the admissions after the kill stay on the killed
    # worker, or go to another app
    same = [s._replace(attrs={**s.attrs, "server": "s0"})
            if s.start >= 30.0 and "server" in s.attrs else s for s in rows]
    assert value("failover_admit_ms", same) is None
    assert value("reroute_ms", same) is None
    other = [s._replace(attrs={**s.attrs, "app": "app1"})
             if s.name == "router.set_route" else s for s in rows]
    assert value("reroute_ms", other) is None
    # no decode step, no admission: nothing to read
    assert value("decode_host_gap_ms.chat",
                 [s for s in rows if s.name != "engine.decode"]) is None
    assert value("admit_host_ms.chat",
                 [s for s in rows if s.name != "engine.first_token"]) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_program_without_spans_reads_nothing(name, monkeypatch):
    """A program from before the span recorder has no
    `repro.serving.spans`: the reader returns None and does not raise."""
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    assert readers.read(ROOT, name, {"window": {"t0": 0.0, "t_end": 1.0}}) \
        is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_each_reader_reads_the_live_recorder(name):
    from repro.serving import spans as live
    with live.span("engine.admit", id="x", server="s0"):
        with live.span("engine.first_token", id="x"):
            pass
    t = live.snapshot().spans[-1].end
    run = {"window": {"t0": t - 1.0, "t_end": t + 1.0}}
    got = readers.read(ROOT, name, run)
    if name.startswith(("admit_host_ms", "window_compile_ms")):
        assert got is not None and got >= 0.0
    else:
        assert got is None


def test_a_tiny_cpu_run_reads_every_chat_span_metric():
    """The chat cell at a CPU size: each span reader of the cell reads a
    value, and the backup's first admission lines up with the harness's
    own stamps of that request (one clock)."""
    from bench.harness import cell as C
    from repro.serving.spans import snapshot, window
    from test_bench import CHAT, manifest, tiny

    _m, _cell, config, mix = tiny()
    c = C.Cell(config, mix, seed=2300000007)
    try:
        c.deploy()
        run = c.run_window(8.0, None, time.monotonic())
    finally:
        c.shutdown()
    names = [p["name"] for p in manifest()["per_layer"]
             if p["source"] == "program_span" and CHAT in p["workloads"]
             and p["name"] != "ctl_mttr_ms"]
    assert sorted(names) == sorted(n for n in SPAN_READERS
                                   if "longctx" not in n)
    got = {n: readers.read(ROOT, n, run) for n in names}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["reroute_ms"] < got["failover_admit_ms"]
    w = window(snapshot(), run["window"]["t0"], run["window"]["t_end"])
    kill = next(s for s in w if s.name == "testbed.kill")
    admit = next(s for s in w if s.name == "engine.admit"
                 and s.start >= kill.start
                 and s.attrs["server"] not in kill.attrs["servers"])
    row = next(r for r in run["requests"]
               if f"r{r['index']}" == admit.attrs["id"])
    assert 0 <= admit.start - row["t_admit"] < 0.005
    assert 0 <= admit.end - row["first"] < 0.005
