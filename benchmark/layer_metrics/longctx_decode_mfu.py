"""Share of the chip's peak the window reached on the tokens its decode steps committed: the
operations a token needs on this chip (`lib/costs_parallel_moe.py`: two a matrix-multiplied weight
with the held experts a token chose, attention over the keys each layer kind sees) over peak x
window. A share of the whole step, so that a change anywhere in it shows here."""
from benchmark.lib import longctx


def read(run):
    return longctx.decode_mfu(run)
