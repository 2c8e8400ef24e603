"""Device time of the decode program per dispatch: its executables on the XLA Modules line
over the program's own `serve_decode` annotations in the traced window."""
from benchmark.lib import readers


def read(run):
    seconds = readers.decode_program_seconds(run)
    return None if seconds is None else readers.per_unit_ms(seconds, readers.program_runs(run, "serve_decode"))
