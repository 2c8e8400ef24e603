"""Device self time a traced step of the XLA ops whose trace symbols ran under no named part
(symbols a transform added, ops XLA gave no path, ops outside every program's run). All passes:
`bench: xla by scope` has the split by pass."""
from benchmark.lib import scopes


def read(run):
    return scopes.train_ms_per_step(run, "unscoped")
