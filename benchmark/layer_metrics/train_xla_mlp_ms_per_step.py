"""Device self time a traced step of the XLA ops whose trace symbols ran under `mlp` (its norm, the
MLP, its residual). All passes: `bench: xla by scope` has the split by pass."""
from benchmark.lib import scopes


def read(run):
    return scopes.train_ms_per_step(run, "mlp")
