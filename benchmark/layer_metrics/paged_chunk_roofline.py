"""Share of its roofline the paged chunk kernel reached, against the mean chunk of the
prompts completed in the window."""
from benchmark.lib import readers


def read(run):
    return readers.roofline_pct(run, "paged_chunk", readers.paged_chunk_cost(run))
