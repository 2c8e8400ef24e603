"""Device time a decode-program run of the latent decode kernel (class `latent_decode`), the eight
attention blocks' calls together. The projections round it are matmuls like any other and count
under `trajectories_xla_ms_per_iter`."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_ms_per_iter(run, lambda k: k == "latent_decode")
