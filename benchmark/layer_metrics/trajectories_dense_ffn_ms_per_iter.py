"""Device self time a decode-program run of the XLA ops whose trace symbols ran under the scope
`dense_ffn` (the second norm of each half of a double layer, its dense SwiGLU FFN, the residual
and the shortcut's add): eight FFNs of 3 x 6144 x 12288 weights, most of a token's operations.
A part of `trajectories_xla_ms_per_iter`."""
from benchmark.lib import trajectories


def read(run):
    return trajectories.scope_ms_per_iter(run, "dense_ffn")
