"""Share of its (bandwidth) roofline the decode kernel over the shared pool reached, against
the live context read once by each layer that reads it."""
from benchmark.lib import readers, reasoning


def read(run):
    return readers.roofline_pct(run, "paged_decode", reasoning.attention_cost(run))
