"""Device self time per traced step of the ops that are neither Mosaic calls nor
collectives: the XLA regions."""
from benchmark.lib import readers


def read(run):
    if run.trace is None:
        return None
    return readers.per_unit_ms(readers.xla_seconds(run), readers.train_steps_traced(run))
