"""Median duration of the engine's `serve_decode` bus span: the dispatch of step k+1 and the
fetch of step k, on the host."""
from benchmark.lib import readers


def read(run):
    return readers.bus_span_percentile_ms(run, "serve_decode", 50)
