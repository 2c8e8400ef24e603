"""Idle chip per engine iteration while the engine thread was in `engine:upload`: the packed state,
tokens and positions going up before a decode dispatch."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "upload")
