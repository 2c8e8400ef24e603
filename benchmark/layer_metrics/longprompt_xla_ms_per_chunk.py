"""Device self time per chunk-program run of the ops that are neither Mosaic calls nor
collectives, over all programs of the traced window."""
from benchmark.lib import readers


def read(run):
    if run.trace is None:
        return None
    return readers.per_unit_ms(readers.xla_seconds(run), readers.program_runs(run, "serve_chunk_prefill"))
