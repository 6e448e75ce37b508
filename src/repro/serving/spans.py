"""Spans of the serving path: what the host was doing, and when.

One recorder per process, always on. A span is a named interval with
the id of the span that was open around it on the same thread, the
thread, and a few attributes; the spans of one request carry its id.

  * Stamps are `time.monotonic()`, the clock of `Request`'s stamps.
  * `span(name, **attrs)` is a context manager: the parent is the
    innermost span open on the calling thread. While a profiler trace
    runs, the span also opens `jax.profiler.TraceAnnotation(name)`, so
    it lands in the trace's host plane on the device trace's clock.
  * `record(name, start, end, **attrs)` keeps an interval whose ends are
    known only afterwards (a detection, a compile). It goes to the ring
    only, not to the profiler, which cannot place an event in the past.
  * The first call of a jitted function becomes `jax.trace` (Python to
    jaxpr), `jax.lower` (jaxpr to MLIR) and `jax.compile` spans, through
    one listener on JAX's duration events, registered at import. The
    compile wraps the persistent compile cache's read; `cache_hit` says
    whether the cache answered.
  * Spans go into a ring of `RING` entries, in the order they end;
    `snapshot()` returns them with the count of spans ever recorded and
    of those the ring overwrote.

Names never start with `bench.`: that prefix is the benchmark's own.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

# A 50 s chat window at its 4.8 Hz capacity with 32-token outputs
# records ~15k spans (two per decode step, two per admission); the ring
# holds four such windows.
RING = 1 << 16

# JAX's duration events of a jit's first call, as spans: tracing the
# Python function to a jaxpr, lowering it to MLIR, and the backend
# compile (which wraps the persistent compile cache's read)
JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
              "/jax/core/compile/backend_compile_duration": "jax.compile"}
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Span(NamedTuple):
    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    thread: int
    attrs: Dict[str, object]


class Snapshot(NamedTuple):
    spans: List[Span]       # oldest first, in the order they ended
    recorded: int
    dropped: int


class _Local(threading.local):
    def __init__(self):
        self.stack: List[int] = []      # ids of the spans open here
        self.cache_read_at: Optional[float] = None


# the ring holds plain tuples, which are cheaper to make than a Span
_ring: collections.deque = collections.deque(maxlen=RING)
_lock = threading.Lock()
_recorded = 0
_ids = itertools.count(1)
_local = _Local()


def _keep(row: tuple) -> None:
    global _recorded
    with _lock:
        _ring.append(row)
        _recorded += 1


def current() -> Optional[int]:
    """Id of the innermost span open on this thread, or None."""
    stack = _local.stack
    return stack[-1] if stack else None


class span:
    """`with span(name, **attrs) as s:` times the block. `s.attrs` may
    be filled in inside the block."""

    __slots__ = ("name", "attrs", "id", "parent", "start", "_ann",
                 "_stack")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = self._stack = _local.stack
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = None
        if TraceAnnotation.is_enabled():    # a profiler trace is running
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        end = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._stack.pop()
        _keep((self.name, self.start, end, self.id, self.parent,
               threading.get_ident(), self.attrs))


def record(name: str, start: float, end: float, **attrs) -> Span:
    """Keep an interval with known ends; its parent is the span open on
    this thread now."""
    s = Span(name, start, end, next(_ids), current(),
             threading.get_ident(), attrs)
    _keep(tuple(s))
    return s


def snapshot() -> Snapshot:
    with _lock:
        rows, recorded = list(_ring), _recorded
    return Snapshot([Span._make(r) for r in rows], recorded,
                    recorded - len(rows))


def window(snap: Snapshot, t0: float, t1: float) -> Optional[List[Span]]:
    """The spans that start in [t0, t1], by start; None if the ring has
    overwritten spans that may have started there (the first span it
    still holds ended at or after t0)."""
    if snap.dropped and (not snap.spans or snap.spans[0].end >= t0):
        return None
    return sorted((s for s in snap.spans if t0 <= s.start <= t1),
                  key=lambda s: s.start)


def _on_duration(event: str, duration_s: float, **kw) -> None:
    if event == CACHE_READ_EVENT:
        _local.cache_read_at = time.monotonic()
        return
    name = JAX_EVENTS.get(event)
    if name is None:
        return
    end = time.monotonic()
    start = end - duration_s
    attrs = {"fun_name": kw.get("fun_name")}
    if name == "jax.compile":
        hit, _local.cache_read_at = _local.cache_read_at, None
        attrs["cache_hit"] = hit is not None and start <= hit <= end
    record(name, start, end, **attrs)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
