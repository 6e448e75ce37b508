"""Finds each metric's reader by name and applies it to a run record.

A metric `<name>` is read by `bench/metrics/<name>.py`, which defines
`read(run) -> float | None`. None means the reader found nothing to read
in this run, and the metric is left out of the result line; a share of
a roofline or of a peak is never reported as 0 for lack of data.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Optional


def load(root: Path, name: str):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(root: Path, name: str, run: dict) -> Optional[float]:
    value = load(root, name).read(run)
    if value is None:
        return None
    value = float(value)
    if not math.isfinite(value):
        return None
    return value
