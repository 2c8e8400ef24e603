"""Executables built inside the measured window (jax.monitoring) plus the program's
recompile.*, compile.prewarm_fallback and aot.save_failed counters there. Must be 0."""
from benchmark.lib import readers


def read(run):
    return readers.recompiles_in_window(run)
