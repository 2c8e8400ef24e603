"""Median time to first token from submission (closed loop: queueing behind the other
callers included)."""
from benchmark.lib import readers


def read(run):
    return readers.ttft_percentile_ms(run, 50)
