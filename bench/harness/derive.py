"""Arithmetic shared by the metric readers, over one run record.

A run record (`cell.Cell.run_window`) holds one row per request sent,
with host-clock stamps (`time.monotonic`): `due`, `t_ready` (the FIFO
was free and the request due), `t_first_try`, `t_admit` (start of the
submit that held), `first` (first token), `done`; and `rung`/`server`,
`failed`, `lost`. A failed request counts in every latency tail as
infinitely late.
"""

from __future__ import annotations

import math
from typing import List, Optional

from bench.harness import roofline, stats
from bench.harness.weights import Shape

INF = math.inf


def ttft_s(run) -> List[float]:
    return [INF if r["failed"] else r["first"] - r["due"]
            for r in run["requests"]]


def step_s(r) -> float:
    """Mean time per decode step of one finished request."""
    return (r["done"] - r["first"]) / (r["n_tokens"] - 1)


def tpot_s(run) -> List[float]:
    return [INF if r["failed"] else step_s(r) for r in run["requests"]
            if r["failed"] or r["n_tokens"] > 1]


def served(run) -> list:
    return [r for r in run["requests"] if not r["failed"]]


def after_kill(run) -> list:
    """Finished requests admitted on another worker than the killed one."""
    k = run["kill"]
    if k is None:
        return []
    return [r for r in served(run) if r["server"] != k["server"]]


def client_mttr_s(run) -> Optional[float]:
    rows = after_kill(run)
    if not rows:
        return None
    return min(r["first"] for r in rows) - run["kill"]["t"]


def gen_lag_s(run) -> List[float]:
    return [r["t_first_try"] - r["t_ready"] for r in run["requests"]
            if r["t_first_try"] is not None]


def queue_wait_s(run) -> List[float]:
    return [r["t_admit"] - r["due"] for r in run["requests"]
            if r["t_admit"] is not None]


def prefill_s(run) -> List[float]:
    return [r["first"] - r["t_admit"] for r in served(run)]


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def p95(values) -> Optional[float]:
    return stats.percentile(values, 95)


def decode_mfu_pct(run) -> Optional[float]:
    """Model operations of the window's decode steps over their device
    time times the chip's peak, from the trace. The k-th decode step of a
    request sees its prompt and k tokens more (attention over those, not
    over the masked rest of the cache)."""
    tr = run.get("trace")
    if not tr or not tr["decode_calls"]:
        return None
    peak = roofline.peaks(run["device"]["kind"])["flops"]
    prompt = {r["index"]: r["prompt_len"] for r in run["requests"]}
    steps: dict = {}
    flops = got = 0.0
    for rung, dur_s, index in tr["decode_calls"]:
        steps[index] = k = steps.get(index, 0) + 1
        s = Shape(**run["rungs"][rung])
        flops += roofline.model_flops_per_token(s, prompt[index] + k)
        got += dur_s
    return 100.0 * flops / (got * peak) if got > 0 else None


def decode_roofline_pct(run) -> Optional[float]:
    """Least time over device time, summed over the decode programs the
    trace found, each at the rung that was serving when it ran."""
    tr = run.get("trace")
    if not tr or not tr["decode_calls"]:
        return None
    kind = run["device"]["kind"]
    need = got = 0.0
    for rung, dur_s, _index in tr["decode_calls"]:
        s = Shape(**run["rungs"][rung])
        need += roofline.decode_bound_s(s, run["slots"], run["max_len"],
                                        kind)["s"]
        got += dur_s
    return 100.0 * need / got if got > 0 else None


def device_idle_pct(run) -> Optional[float]:
    tr = run.get("trace")
    if not tr or tr["inflight_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["inflight_busy_s"] / tr["inflight_s"])
