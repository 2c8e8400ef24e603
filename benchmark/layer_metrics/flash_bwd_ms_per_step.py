"""Device time per traced step in the flash-attention backward kernels."""
from benchmark.lib import readers


def read(run):
    seconds, calls = readers.class_time(run, "flash_bwd")
    return readers.per_unit_ms(seconds, readers.train_steps_traced(run)) if calls else None
