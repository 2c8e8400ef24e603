"""Device self time a traced step of the XLA ops whose trace symbols ran under `attn` (its norm, the
q/k/v projection, rope, the attention's XLA ops, the output projection and its residual). All
passes: `bench: xla by scope` has the split by pass."""
from benchmark.lib import scopes


def read(run):
    return scopes.train_ms_per_step(run, "attn")
