"""Idle chip per engine iteration while the engine thread was in `engine:commit`: one token per
slot, `_retire` and `_clear_slot` (and the step's own bus records)."""
from benchmark.lib import phases


def read(run):
    return phases.idle_ms_per_iter(run, "commit")
