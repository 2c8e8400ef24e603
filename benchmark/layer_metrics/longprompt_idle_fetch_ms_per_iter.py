"""Idle chip per engine iteration while the engine thread was in `engine:fetch`: `np.asarray` of
the sampled tokens, so the tail after the device finishes."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "fetch")
