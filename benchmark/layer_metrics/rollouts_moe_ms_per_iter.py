"""Device time a decode-program run of the routed experts' kernel (class `ragged_mlp`), all
layers: the held experts' panels streamed for the rows that chose them. The dispatch around it
(the router, the sort into groups, the gathers) is XLA's and counts under
`rollouts_xla_ms_per_iter`."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_ms_per_iter(run, lambda k: k == "ragged_mlp")
