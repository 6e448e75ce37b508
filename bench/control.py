#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's and the
controls', over several seeds, in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 11 12 13

For each seed it runs the cell's window as `bench/run.py` does (same
deployment, traffic, kill and sample) and puts the sample through the
same judgement (`cell.check`): the tokens the program served, and the
tokens that int8 and fp8 computations of the float32 reference put first
at the same positions. It prints each one's numbers (`max_gap`,
`mean_gap`) and verdict; every control must come out not correct. The
limits in the configuration's `correct` lie between the program's
largest readings and the controls' smallest. Not run by the benchmark's
own runs. Needs a TPU, as `bench/run.py` does.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CONTROLS = ("int8", "fp8")


def readings(config: dict, mix: dict, seed: int, seconds: float,
             t_process: float) -> dict:
    """One seed: run the window, then judge the program and the controls.
    A gap number the configuration does not limit is read all the same,
    against no limit."""
    from bench.harness import cell as C

    c = C.Cell(config, mix, seed)
    try:
        c.deploy()
        run = c.run_window(seconds, None, t_process)
    finally:
        c.shutdown()
    cfg = {k: math.inf for k in C.GAP_NUMBERS}
    cfg.update(config["correct"])
    v = C.check(run, seed, cfg, mix, controls=CONTROLS)

    def numbers(j):
        return {"correct": j["correct"],
                **{k: x["value"] for k, x in j["checks"].items()}}
    return {"seed": seed, "program": numbers(v),
            **{c: numbers(v["controls"][c]) for c in CONTROLS},
            "served": sum(not r["failed"] for r in run["requests"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness.env import start_jax
    devices = start_jax(ROOT)
    from bench.run import device_error, load_cell
    _m, cell, config, mix = load_cell(ROOT, args.workload)
    err = device_error(devices, int(cell["chips"]))
    if err:
        print(f"bench/control.py: {err}", file=sys.stderr)
        return 2
    rows = []
    for seed in args.seeds:
        r = readings(config, mix, seed, args.seconds, time.monotonic())
        rows.append(r)
        print(json.dumps(r), flush=True)
    numbers = ("max_gap", "mean_gap")
    summary = {"workload": args.workload,
               "limits": config["correct"],
               "program_max": {k: max(r["program"][k] for r in rows)
                               for k in numbers},
               "program_all_correct": all(r["program"]["correct"]
                                          for r in rows)}
    for c in CONTROLS:
        summary[f"{c}_min"] = {k: min(r[c][k] for r in rows)
                               for k in numbers}
        summary[f"{c}_none_correct"] = not any(r[c]["correct"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
