"""Share of its roofline the flash-attention forward kernel reached."""
from benchmark.lib import readers


def read(run):
    return readers.roofline_pct(run, "flash_fwd", readers.flash_cost(run, "fwd"))
