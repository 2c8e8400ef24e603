"""Device self time a decode-program run, inside the decode program, of the XLA ops whose trace
symbols ran under `mlp` (its norm, the MLP, its residual). The parts add up to
reasoning_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.reasoning_ms_per_iter(run, "mlp")
