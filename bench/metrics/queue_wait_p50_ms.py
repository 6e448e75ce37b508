"""Client FIFO / router: median of due time to the start of the admission that held."""
from bench.harness import derive, stats


def read(run):
    return derive.ms(stats.median(derive.queue_wait_s(run)))
