"""95th percentile over all requests of (done - first token) / (tokens - 1)."""
from bench.harness import derive


def read(run):
    return derive.ms(derive.p95(derive.tpot_s(run)))
