"""Device time of the programs run per traced step (the step executable is all that runs
there), from the XLA Modules line of the first chip."""
from benchmark.lib import readers


def read(run):
    if run.trace is None:
        return None
    return readers.per_unit_ms(run.trace.module_seconds(r"."), readers.train_steps_traced(run))
