"""Share of its (bandwidth) roofline the routed experts' kernel reached in the block program:
against the panels of the experts that had a row, read once (`serve.moe.experts_touched`), the rows
in and out (`serve.moe.rows_held`) and six operations a weight and row
(`lib/costs_block_moe.py: ragged_experts`)."""
from benchmark.lib import denoise


def read(run):
    return denoise.roofline_pct(run, "ragged_mlp", denoise.experts_cost(run))
