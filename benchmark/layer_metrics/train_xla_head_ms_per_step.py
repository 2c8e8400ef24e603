"""Device self time a traced step of the XLA ops whose trace symbols ran under `head` and `embed`
(the final norm, the head, the loss where there is one; the embedding). All passes: `bench: xla by
scope` has the split by pass."""
from benchmark.lib import scopes


def read(run):
    return scopes.train_ms_per_step(run, "head")
