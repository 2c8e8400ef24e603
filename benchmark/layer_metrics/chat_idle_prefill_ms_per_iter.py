"""Idle chip per engine iteration while the engine thread was in `engine:prefill`: a prompt's or a
chunk's host preparation, uploads, dispatch and first-token fetch."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "prefill")
