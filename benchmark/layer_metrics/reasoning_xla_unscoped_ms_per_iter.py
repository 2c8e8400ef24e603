"""Device self time a decode-program run, inside the decode program, of the XLA ops whose trace
symbols ran under no named part (symbols a transform added, ops XLA gave no path, ops outside every
program's run). The parts add up to reasoning_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.reasoning_ms_per_iter(run, "unscoped")
