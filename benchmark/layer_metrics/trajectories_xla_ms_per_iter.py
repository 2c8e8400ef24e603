"""Device self time a decode-program run of the rest: every op that is neither the experts' nor
the latent decode kernel (attention's projections, the dense FFNs, the router, the identity
experts' part, the sort into groups and the gathers, the norms, the head, the page writes)."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_ms_per_iter(run, lambda k: k not in ("ragged_mlp", "latent_decode"))
