#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`; its configuration
and traffic mix are the files `bench/configs/<config>.json` and
`bench/traffic/<traffic>.json`, and each metric is read by
`bench/metrics/<metric>.py`. With `--trace 0` the result carries the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read
from a profiler trace of the window.

Needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it prints no result and exits with code 2. The last line of
stdout is one JSON object; the numbers that decide `correct` are the
last lines of stderr and the result's last key, `checks`.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".trace"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_cell(root: Path, workload: str):
    """(manifest, cell, config, mix) for a workload name."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    config = json.loads((root / "bench" / "configs"
                         / f"{cell['config']}.json").read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return manifest, cell, config, mix


def metrics_for(manifest: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def device_error(devices, chips: int):
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        return f"JAX sees no TPU (platform {plat}); this benchmark runs only on one"
    if len(devices) < chips:
        return f"the cell needs {chips} chips; JAX sees {len(devices)}"
    return None


def run_once(root: Path, manifest: dict, cell: dict, config: dict,
             mix: dict, seed: int, seconds: float, trace: bool, devices,
             t_process: float) -> dict:
    """Run the cell on `devices` (the caller has checked them) and return
    the result object, `checks` last."""
    from bench.harness import cell as C
    from bench.harness import readers
    from bench.harness import trace as T

    workload = cell["name"]
    d0 = devices[0]
    trace_dir = None
    if trace:
        trace_dir = str(TRACE_DIR / workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = None
    c = C.Cell(config, mix, seed)
    try:
        c.deploy()
        run = c.run_window(seconds, trace_dir, t_process)
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    finally:
        c.shutdown()
    run["device"] = {"platform": d0.platform, "kind": d0.device_kind}
    if trace_dir is not None:
        run["trace"] = T.reduce(T.load(trace_dir), run)
        shutil.rmtree(trace_dir, ignore_errors=True)
    verdict = C.check(run, seed, config["correct"], mix)

    metrics, missing = {}, []
    for m in metrics_for(manifest, workload, trace):
        value = readers.read(root, m["name"], run)
        if value is None:
            missing.append(m["name"])
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if missing:
        log(f"no reading for: {missing}")
    rows = run["requests"]
    checks, correct = verdict["checks"], verdict["correct"]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(rows),
           "failed": sum(r["failed"] for r in rows), "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        out["breakdown"] = run["trace"]["breakdown"]
    log(f"set-up {run['setup_s']!r} s: {run['timings']}; recovery "
        f"{run['recovery']}; rungs served {sorted(run['rungs'])}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, cell, config, mix = load_cell(ROOT, args.workload)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness.env import start_jax
    devices = start_jax(ROOT)
    err = device_error(devices, int(cell["chips"]))
    if err:
        log(f"bench/run.py: {err}")
        return 2
    out = run_once(ROOT, manifest, cell, config, mix, args.seed,
                   args.seconds, bool(args.trace),
                   devices[:int(cell["chips"])], T_PROCESS)
    print(json.dumps(finite(out)), flush=True)
    return 0


def finite(x):
    """The result with every infinite or NaN number as null (JSON has
    none), and numpy numbers as Python ones."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, bool) or x is None or isinstance(x, str):
        return x
    if isinstance(x, int):
        return x
    x = float(x)
    return x if math.isfinite(x) else None


if __name__ == "__main__":
    sys.exit(main())
