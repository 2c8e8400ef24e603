"""95th percentile over requests of the mean gap between output tokens."""
from benchmark.lib import readers


def read(run):
    return readers.tbot_percentile_ms(run, 95)
