"""Share of its (bandwidth) roofline the routed experts' kernel reached in the decode program:
against the panels of the held experts that had a row, read once (`serve.moe.experts_touched`),
the rows in and out (`serve.moe.rows_held`) and six operations a weight and row, a double layer's
call each (`lib/trajectories.py: routing` divides by the expert layers, not the caching ones)."""
from benchmark.lib import rollouts, trajectories


def read(run):
    return rollouts.decode_roofline_pct(run, "ragged_mlp", trajectories.experts_cost(run))
