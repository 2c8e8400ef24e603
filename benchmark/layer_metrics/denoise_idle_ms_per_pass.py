"""Idle chip per engine iteration (one pass over blocks each) in the traced window, every share of
`lib/phases.py` together (its parts are on the log line `bench: idle by phase`)."""
from benchmark.lib import denoise, phases


def read(run):
    split = phases.idle_by_phase(run) if denoise.passes(run) is not None else None
    if split is None:
        return None
    return sum(split[s] for s in phases.SHARES) / 1e6 / phases.iterations(run)
