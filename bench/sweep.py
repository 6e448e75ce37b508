#!/usr/bin/env python3
"""Rate sweep of an open-loop mix, with no crash, to find its capacity.

    python3 bench/sweep.py --workload <name> --seconds 20 --rates 2 3 4 5

Deploys the cell once, then runs one window per rate back to back (the
mix's `rate_hz` replaced, its kill left out). For each rate it prints the
requests served, the mean time a request held the engine, the 95th
percentile of TTFT and the backlog: requests due in the window's last
fifth whose queue wait exceeded the mean service time. A mix's
`rate_hz` is set once, to about 0.8 of the highest rate whose backlog
does not grow, and written into its file as a number. Not run by the
benchmark's own runs. Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def summarize(run: dict, rate: float) -> dict:
    from bench.harness import derive

    rows = derive.served(run)
    hold = [r["done"] - r["t_admit"] for r in rows]
    mean_hold = statistics.fmean(hold) if hold else float("nan")
    t0, t_end = run["window"]["t0"], run["window"]["t_end"]
    late = [r for r in run["requests"] if r["due"] >= t_end - 0.2 * (t_end - t0)]
    backlog = sum(1 for r in late if r["t_admit"] is None
                  or r["t_admit"] - r["due"] > mean_hold)
    return {"rate_hz": rate, "sent": len(run["requests"]),
            "served": len(rows), "mean_hold_s": mean_hold,
            "capacity_hz": 1.0 / mean_hold if hold else None,
            "ttft_p95_s": derive.p95(derive.ttft_s(run)),
            "queue_wait_p50_s": statistics.median(derive.queue_wait_s(run)),
            "late_backlog": backlog, "late_requests": len(late)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness.env import start_jax
    devices = start_jax(ROOT)
    from bench.harness import cell as C
    from bench.run import device_error, load_cell
    _m, cell, config, mix = load_cell(ROOT, args.workload)
    err = device_error(devices, int(cell["chips"]))
    if err:
        print(f"bench/sweep.py: {err}", file=sys.stderr)
        return 2
    mix = dict(mix, kill_at=None)
    c = C.Cell(config, mix, args.seed)
    try:
        c.deploy()
        for rate in args.rates:
            c.mix = dict(mix, rate_hz=rate)
            run = c.run_window(args.seconds, None, time.monotonic())
            print(json.dumps(summarize(run, rate)), flush=True)
    finally:
        c.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
