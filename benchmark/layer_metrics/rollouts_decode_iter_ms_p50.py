"""Median duration of the engine's `serve_decode` bus span: one decode iteration on the host,
dispatch to tokens on the host."""
from benchmark.lib import readers


def read(run):
    return readers.bus_span_percentile_ms(run, "serve_decode", 50)
