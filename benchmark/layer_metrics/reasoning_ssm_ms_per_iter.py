"""Device self time a decode-program run of the XLA ops over the state-space layers' state: the
scan's state update and the conv with its tail, found by their shapes (`lib/reasoning.py`). The
layers' projections are matmuls like any other and count under `reasoning_xla_ms_per_iter`."""
from benchmark.lib import reasoning


def read(run):
    return reasoning.decode_ms_per_iter(run, lambda k: k == "state_space")
