"""95th percentile of the time to first token from the due instant."""
from benchmark.lib import readers


def read(run):
    return readers.ttft_percentile_ms(run, 95)
