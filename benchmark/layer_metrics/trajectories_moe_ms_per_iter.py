"""Device time a decode-program run of the routed experts' kernel (class `ragged_mlp`), the four
double layers' calls together: the 16 held experts' panels streamed for the rows that chose
them. The router, the sort into groups, the gathers and the identity experts' part are XLA's
and count under `trajectories_xla_ms_per_iter`."""
from benchmark.lib import rollouts


def read(run):
    return rollouts.decode_ms_per_iter(run, lambda k: k == "ragged_mlp")
