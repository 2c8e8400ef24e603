"""Device time of the decode program per dispatch: its executables on the XLA Modules line over
the program's own `serve_decode` annotations in the traced window."""
from benchmark.lib import longctx


def read(run):
    return longctx.decode_device_ms_per_iter(run)
