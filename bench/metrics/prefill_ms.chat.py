"""Engine: median of admission start to first token (prefill into a slot, eager slot ops, host sync)."""
from bench.harness import derive, stats


def read(run):
    return derive.ms(stats.median(derive.prefill_s(run)))
