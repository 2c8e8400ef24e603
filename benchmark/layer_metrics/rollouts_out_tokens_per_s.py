"""Generated tokens per second completed inside the window."""
from benchmark.lib import readers


def read(run):
    return readers.out_tokens_per_s(run)
