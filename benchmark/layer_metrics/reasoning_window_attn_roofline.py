"""Share of its (bandwidth) roofline the window layers' decode kernel reached, against the
keys and values inside each sequence's window."""
from benchmark.lib import readers, reasoning


def read(run):
    window = run.cell.builder.dims(run.cell.config)["window"]
    return readers.roofline_pct(run, "window_decode", reasoning.attention_cost(run, window))
