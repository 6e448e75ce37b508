"""Engine: median over finished requests of their mean decode step time."""
from bench.harness import derive, stats


def read(run):
    return derive.ms(stats.median(
        derive.step_s(r) for r in derive.served(run) if r["n_tokens"] > 1))
