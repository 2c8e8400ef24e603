"""Share of its (bandwidth) roofline the window layers' paged decode kernel reached in the decode
program, against the keys and values inside each sequence's window (`lib/longctx.py`)."""
from benchmark.lib import longctx


def read(run):
    return longctx.window_attn_roofline(run)
