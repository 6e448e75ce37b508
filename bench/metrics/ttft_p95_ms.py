"""95th percentile over all requests of first token minus due time."""
from bench.harness import derive


def read(run):
    return derive.ms(derive.p95(derive.ttft_s(run)))
