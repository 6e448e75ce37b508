"""Model step on the device: roofline time of the decode program over its device time, from the trace."""
from bench.harness import derive


def read(run):
    return derive.decode_roofline_pct(run)
