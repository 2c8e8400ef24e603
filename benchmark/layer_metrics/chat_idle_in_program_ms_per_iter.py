"""Idle chip per engine iteration inside a run of a device program (an event of the `XLA Modules`
line): bubbles between the ops of one executable, which no host code causes."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "in_program")
