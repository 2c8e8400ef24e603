"""Share of its (bandwidth) roofline the paged decode kernel reached, against the keys and
values the active sequences really hold."""
from benchmark.lib import readers


def read(run):
    return readers.roofline_pct(run, "paged_decode", readers.paged_decode_cost(run))
