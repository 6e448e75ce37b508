"""Load generator: 95th percentile of how late the FIFO acted on a due request it was free for."""
from bench.harness import derive


def read(run):
    return derive.ms(derive.p95(derive.gen_lag_s(run)))
