"""Device time a decode-program run of the paged decode kernel of the window layers."""
from benchmark.lib import reasoning


def read(run):
    return reasoning.decode_ms_per_iter(run, lambda k: k == "window_decode")
