"""Idle chip per engine iteration while the engine thread was under `engine:iteration` alone or
under no phase at all."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "unattributed")
