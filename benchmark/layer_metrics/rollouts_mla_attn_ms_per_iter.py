"""Device time a decode-program run of the latent decode kernel (class `latent_decode`), all
layers. The projections round it (the query pair, the latent, the two halves of the
up-projection) are matmuls like any other and count under `rollouts_xla_ms_per_iter`."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_ms_per_iter(run, lambda k: k == "latent_decode")
