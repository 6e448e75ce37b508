"""From the kill to the first token of the first request served after the failover."""
from bench.harness import derive


def read(run):
    return derive.ms(derive.client_mttr_s(run))
