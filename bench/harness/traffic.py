"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (`bench/traffic/<name>.json`) gives the arrival process, the prompt
buckets and the output lengths. The schedule is built in blocks of
`block` requests. Every block holds the same multiset of sizes and gaps:
the prompt buckets in their stated counts, output lengths at the block's
evenly spaced quantiles of the uniform range, and (open loop) gaps at the
block's quantiles of the exponential distribution of a Poisson process
at `rate_hz`. Each block is put in an order drawn from `ORDER_SEED`, so
every run of a mix offers the same sizes at the same due times in the
same order; `--seed` draws the prompts' token ids (and the weights,
elsewhere). The order is fixed because the cells' tails hang on it: the
same work in another order moved `ttft_p95_ms` of the warm-crash cell
between 0.6 and 3.3 s (PERF.md, section 6).

Arrivals:
  "open"       due times advance by the block's gaps (open loop);
  "saturated"  every request is due at the window's start, so the client
               FIFO is never empty.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

# streams of one seed: each draw has its own, so adding a draw to one
# never shifts another
_ORDER, _PROMPT = 1, 2
ORDER_SEED = 1


@dataclass
class Planned:
    """One request of the schedule, before it is sent."""
    index: int
    offset_s: float           # due time, seconds after the window opens
    prompt: np.ndarray        # (S,) int32 token ids
    max_new_tokens: int       # decode steps after the prefill's token


def _rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**64 - 1), stream, *more])


def block_quantiles(n: int) -> np.ndarray:
    """Midpoint quantile levels (i + 0.5) / n of a block of n."""
    return (np.arange(n) + 0.5) / n


def block_layout(mix: dict) -> dict:
    """The multiset one block holds: prompt lengths, output lengths and
    gaps (seconds; zeros for a saturated stream), each of length
    `mix["block"]`, in a fixed order."""
    n = int(mix["block"])
    prompts = [int(s) for s, count in mix["prompt_buckets"]
               for _ in range(int(count))]
    if len(prompts) != n:
        raise ValueError(f"prompt bucket counts sum to {len(prompts)}, "
                         f"block is {n}")
    lo, hi = (int(x) for x in mix["output_tokens"])
    q = block_quantiles(n)
    outputs = lo + np.floor(q * (hi - lo + 1)).astype(int)
    if mix["arrivals"] == "open":
        gaps = -np.log1p(-q) / float(mix["rate_hz"])
    elif mix["arrivals"] == "saturated":
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return {"prompt": np.asarray(prompts), "output": outputs, "gap": gaps}


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             count: int = 0) -> List[Planned]:
    """Requests due in [0, seconds). A saturated stream is due at 0 and
    has `count` requests (at least one block), as many as a window can
    serve: the FIFO stops sending at the window's end."""
    layout = block_layout(mix)
    n = int(mix["block"])
    out: List[Planned] = []
    t = 0.0
    b = 0
    while True:
        perm = _rng(ORDER_SEED, _ORDER, b)
        prompts = perm.permutation(layout["prompt"])
        outputs = perm.permutation(layout["output"])
        gaps = perm.permutation(layout["gap"])
        for j in range(n):
            i = b * n + j
            t += gaps[j]
            if mix["arrivals"] == "open" and t >= seconds:
                return out
            if mix["arrivals"] == "saturated" and i >= max(count, n):
                return out
            ids = _rng(seed, _PROMPT, i).integers(
                0, vocab, int(prompts[j]), dtype=np.int32)
            out.append(Planned(i, t, ids, int(outputs[j]) - 1))
        b += 1


def mean_request(mix: dict) -> dict:
    """Mean prompt and output tokens of one request of the mix."""
    layout = block_layout(mix)
    return {"prompt": float(layout["prompt"].mean()),
            "output": float(layout["output"].mean())}


def saturated_count(mix: dict, seconds: float) -> int:
    """Requests a saturated window could serve at `mix["max_tok_s"]`,
    the output rate no run reaches, rounded up to whole blocks."""
    per = mean_request(mix)["output"]
    n = int(mix["block"])
    want = seconds * float(mix["max_tok_s"]) / per
    return n * max(1, math.ceil(want / n))
