"""Share of its (bandwidth) roofline the latent decode kernel reached: against each live cached
row read once at its stored width (320 numbers padded to 384) and 2 x 32 x (320 + 256) operations
a row and query."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_roofline_pct(run, "latent_decode", rollouts.attention_cost(run))
