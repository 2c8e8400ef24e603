"""Median time to first token from the due instant: the generator's lateness plus the
program's own submit-to-first-token stamp."""
from benchmark.lib import readers


def read(run):
    return readers.ttft_percentile_ms(run, 50)
