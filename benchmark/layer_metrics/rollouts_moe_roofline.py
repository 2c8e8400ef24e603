"""Share of its (bandwidth) roofline the routed experts' kernel reached in the decode program:
against the panels of the experts that had a row, read once (`serve.moe.experts_touched`), the
rows in and out (`serve.moe.rows_held`) and six operations a weight and row."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_roofline_pct(run, "ragged_mlp", rollouts.experts_cost(run))
