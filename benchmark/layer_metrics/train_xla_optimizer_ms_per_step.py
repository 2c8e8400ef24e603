"""Device self time a traced step of the XLA ops whose trace symbols ran under `tt_optimizer` alone
(the update; a weight-gradient matmul it is fused into goes to the matmul's part). All passes:
`bench: xla by scope` has the split by pass."""
from benchmark.lib import scopes


def read(run):
    return scopes.train_ms_per_step(run, "optimizer")
