"""Model step on the device: model operations of the decode steps over (their device time from the trace times the chip's peak)."""
from bench.harness import derive


def read(run):
    return derive.decode_mfu_pct(run)
