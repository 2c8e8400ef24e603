"""Share of the device's program time spent in the decode program, which yields under 1%
of this cell's tokens: the first thing to read here."""
from benchmark.lib import readers


def read(run):
    decode = readers.decode_program_seconds(run)
    every = run.trace.module_seconds(r".") if run.trace is not None else 0.0
    return 100.0 * decode / every if decode is not None and every else None
