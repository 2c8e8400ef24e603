"""Idle chip per engine iteration while the engine thread was in `engine:admit`: preemption check
and admission, less the prefill it starts."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "admit")
