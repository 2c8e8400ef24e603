"""Device time in the paged chunk attention kernel per run of the chunk-prefill program."""
from benchmark.lib import readers


def read(run):
    seconds, calls = readers.class_time(run, "paged_chunk")
    return readers.per_unit_ms(seconds, readers.program_runs(run, "serve_chunk_prefill")) if calls else None
