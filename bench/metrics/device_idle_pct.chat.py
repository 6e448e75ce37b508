"""Device: 1 - device busy time / time with a request in flight, from the trace."""
from bench.harness import derive


def read(run):
    return derive.device_idle_pct(run)
