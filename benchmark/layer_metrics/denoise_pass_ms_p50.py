"""Median duration of the engine's `serve_decode` bus span under `block_diffusion=`: one pass over
blocks on the host, from its dispatch to the pass BEFORE it read and committed."""
from benchmark.lib import readers


def read(run):
    return readers.bus_span_percentile_ms(run, "serve_decode", 50)
