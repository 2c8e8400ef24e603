"""Device time in the paged decode attention kernel per run of the decode program."""
from benchmark.lib import readers


def read(run):
    seconds, calls = readers.class_time(run, "paged_decode")
    return readers.per_unit_ms(seconds, readers.program_runs(run, "serve_decode")) if calls else None
