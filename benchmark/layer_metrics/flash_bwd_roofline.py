"""Share of its roofline the flash-attention backward kernel reached."""
from benchmark.lib import readers


def read(run):
    return readers.roofline_pct(run, "flash_bwd", readers.flash_cost(run, "bwd"))
