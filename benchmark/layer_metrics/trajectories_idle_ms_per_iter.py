"""Idle chip per engine iteration in the traced window, every share of `lib/phases.py` together
(`rollouts_idle_ms_per_iter`'s reduction; its parts are on the log line `bench: idle by phase`)."""
from benchmark.layer_metrics import rollouts_idle_ms_per_iter


def read(run):
    return rollouts_idle_ms_per_iter.read(run)
