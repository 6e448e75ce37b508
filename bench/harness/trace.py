"""Reduction of a profiler trace of the window to the benchmark's numbers.

`load(dir)` reads the `.xplane.pb` the JAX profiler wrote into a plain
form: per device plane the XLA module and op events, and the host's
`bench.*` annotations, each as (name, start_s, duration_s) on the
trace's clock. `reduce(events, run)` then works on that form alone, so
the arithmetic can be tested on a small recorded trace
(`bench/tests/data/`):

  clock      the trace's clock is tied to the harness's `time.monotonic`
             by the `bench.admit` annotations, which the harness also
             stamps itself: the offset is the median over the pairs.
  busy       union of the intervals in which an XLA op ran on a device,
             inside the traced window, averaged over the devices.
  in flight  union of [admission start, done] of the requests; the idle
             share is measured against it.
  decode     the engine's decode program is an XLA module named after a
             jitted lambda (`jit__lambda`) that starts outside every
             admission span. The prefill program has the same name, but
             runs inside an admission span: with one request in flight
             per engine, no decode step runs during an admission. Each
             decode call is attributed to the request in flight when it
             started, and so to its rung and its place in the request.
  breakdown  the ten ops with the most device time, and the ten longest
             idle gaps, each named by what the harness was doing then.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import List, Tuple

DECODE_MODULE = "jit__lambda"
# ops that hold other ops (a scan's while loop): their time is their
# body's, which the list already counts
CONTAINER = re.compile(r" (while|conditional|call)\(")
OP_NAME_CHARS = 160


DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def load(trace_dir: str) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}},
        "annotations": [...]} from the newest .xplane.pb under the dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "annotations": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events]
            out["devices"][plane.name] = dev
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["annotations"].append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    out["annotations"].sort(key=lambda e: e[1])
    return out


# -- interval arithmetic ----------------------------------------------------

def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> List[Tuple[float, float]]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def clock_offset(events: dict, run: dict) -> float:
    """Trace clock minus host monotonic clock, from the admission spans
    the trace and the harness both recorded: the pairing (shifted by up
    to a few spans, should one list miss an end) whose differences agree
    best, and the median difference over it."""
    lo, hi = run["window"]["trace"]
    mine = [a for name, a, _b in run["spans"] if name == "admit"
            and lo <= a <= hi]
    theirs = [s for name, s, _d in events["annotations"]
              if name == "bench.admit"]
    best = None
    for shift in range(-3, 4):
        pairs = [(theirs[k + shift], mine[k]) for k in range(len(mine))
                 if 0 <= k + shift < len(theirs)]
        if not pairs:
            continue
        d = [t - m for t, m in pairs]
        med = statistics.median(d)
        mad = statistics.median(abs(x - med) for x in d)
        if best is None or (mad, -len(d)) < best[0]:
            best = ((mad, -len(d)), med)
    if best is None:
        raise ValueError("no admission span in both the trace and the run")
    return best[1]


# -- reduction ----------------------------------------------------------------

def _inflight(run) -> List[Tuple[float, float, str, int]]:
    """(start, end, rung, index) of each request's time in flight, host
    clock: a lost request until the kill, an unfinished one until the
    window's end."""
    out = []
    t_end = run["window"]["t_end"]
    kill = run["kill"]
    for r in run["requests"]:
        if r["t_admit"] is None:
            continue
        if r["done"] is not None:
            end = r["done"]
        elif r["lost"] and kill is not None:
            end = max(r["t_admit"], kill["t"])
        else:
            end = t_end
        out.append((r["t_admit"], end, r["rung"], r["index"]))
    return sorted(out)


def _label(t: float, run, admits, inflight) -> str:
    """What the harness was doing at host time t."""
    i = bisect.bisect_right([a for a, _ in admits], t) - 1
    if i >= 0 and admits[i][0] <= t <= admits[i][1]:
        return "admission (prefill, eager slot ops)"
    k = run["kill"]
    for a, b, _r, _i in inflight:
        if a <= t <= b:
            return "decode loop (host between steps)"
    if k is not None and t >= k["t"]:
        after = [a for a, _b, _r, _i in inflight if a >= k["t"]]
        if not after or t < min(after):
            return "outage (detect, fail over, first-use compiles)"
    return "client FIFO (settle or no request due)"


def reduce(events: dict, run: dict) -> dict:
    off = clock_offset(events, run)
    lo, hi = run["window"]["trace"]
    lo_t, hi_t = lo + off, hi + off
    admits = sorted((a, b) for name, a, b in run["spans"] if name == "admit")
    admits_t = [(a + off, b + off) for a, b in admits]
    inflight = _inflight(run)
    inflight_t = union([(a + off, b + off) for a, b, _r, _i in inflight])
    inflight_t = clip(inflight_t, lo_t, hi_t)
    starts = [a + off for a, _b, _r, _i in inflight]

    busy_each, inflight_busy, op_time = [], [], {}
    decode_calls = []
    gaps: List[Tuple[float, float]] = []
    for dev in events["devices"].values():
        ops = dev["ops"] or dev["modules"]
        busy = clip(union([(s, s + d) for _n, s, d in ops]), lo_t, hi_t)
        busy_each.append(length(busy))
        inflight_busy.append(length(intersect(busy, inflight_t)))
        for name, s, d in dev["ops"]:
            if lo_t <= s < hi_t and not CONTAINER.search(name):
                key = name[:OP_NAME_CHARS]
                op_time[key] = op_time.get(key, 0.0) + d
        prev = lo_t
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi_t > prev:
            gaps.append((prev, hi_t))
        for name, s, d in dev["modules"]:
            if not (lo_t <= s < hi_t) or not name.startswith(DECODE_MODULE):
                continue
            j = bisect.bisect_right([a for a, _ in admits_t], s) - 1
            if j >= 0 and admits_t[j][0] <= s <= admits_t[j][1]:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0:
                continue
            decode_calls.append((inflight[i][2], d, inflight[i][3]))
    n = max(1, len(busy_each))
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = [[_label(0.5 * (a + b) - off, run, admits, inflight), b - a]
            for a, b in longest]
    return {"offset_s": off, "window_s": hi - lo,
            "busy_s": sum(busy_each) / n,
            "inflight_s": length(inflight_t),
            "inflight_busy_s": sum(inflight_busy) / n,
            "decode_calls": decode_calls,
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": idle}}
