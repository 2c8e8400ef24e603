"""Median over requests of the mean gap between output tokens, from the program's own
stamps."""
from benchmark.lib import readers


def read(run):
    return readers.tbot_percentile_ms(run, 50)
