"""Idle chip per engine iteration while the engine thread was in `engine:dispatch`: the decode
program's dispatch, `rebind` and the sampler's enqueue."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "dispatch")
