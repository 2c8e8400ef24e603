"""Share of its roofline the latent decode kernel reached: against each live cached row read once
at its stored width (576 numbers padded to 640) and 2 x 64 x (576 + 512) operations a row and
query (`costs_latent_moe.latent_decode` from the requests the window completed)."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_roofline_pct(run, "latent_decode", rollouts.attention_cost(run))
