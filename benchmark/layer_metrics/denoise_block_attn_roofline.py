"""Share of its roofline the paged chunk kernel reached in the block program (K query rows a
sequence, every row over the whole of its sequence's pages up to the block's end): against the
pages the live sequences hold, counted once a key head (`lib/costs_block_moe.py: block_attention`),
at the mean pass of the requests the window completed."""
from benchmark.lib import denoise


def read(run):
    return denoise.roofline_pct(run, "paged_chunk", denoise.attention_cost(run))
