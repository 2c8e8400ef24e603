"""The part of collective_ms_per_step during which no other op ran on that chip, worst
chip. Where collectives ran and all of it was hidden, that is a reading of 0, not nothing."""
from benchmark.lib import readers


def read(run):
    steps = readers.train_steps_traced(run)
    if run.chips < 2 or not steps or not any(d.collective_ns for d in run.trace.devices):
        return None
    return max(d.collective_exposed_ns for d in run.trace.devices) / 1e9 / steps * 1e3
