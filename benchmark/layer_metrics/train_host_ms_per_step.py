"""Median host time of one `step(...)` call up to its return (the enqueue), from the
harness's own span."""
from benchmark.lib import readers


def read(run):
    return readers.span_median_ms(run, "step")
