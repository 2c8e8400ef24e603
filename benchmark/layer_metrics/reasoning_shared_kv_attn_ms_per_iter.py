"""Device time a decode-program run of the paged decode kernel over the one shared pool: the
full layer and the cross-attention layers that read it."""
from benchmark.lib import reasoning


def read(run):
    return reasoning.decode_ms_per_iter(run, lambda k: k == "paged_decode")
