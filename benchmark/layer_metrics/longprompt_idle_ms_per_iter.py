"""Idle chip per engine iteration in the traced window, every share of `lib/phases.py` together
(its parts are on the log line `bench: idle by phase`)."""
from benchmark.lib import phases


def read(run):
    split = phases.idle_by_phase(run)
    if split is None:
        return None
    return sum(split[s] for s in phases.SHARES) / 1e6 / phases.iterations(run)
