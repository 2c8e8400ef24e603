"""Time per traced step during which a collective was in flight, worst chip (asynchronous
ones from their start to the end of their done)."""
from benchmark.lib import readers


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    worst = max(d.collective_ns for d in run.trace.devices) / 1e9
    return readers.per_unit_ms(worst, readers.train_steps_traced(run))
