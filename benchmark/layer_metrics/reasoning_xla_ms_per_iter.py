"""Device self time a decode-program run of the rest: every op that is neither a paged kernel
nor over the state-space layers' state (the matmuls, the norms, the head, the page writes)."""
from benchmark.lib import reasoning


def read(run):
    return reasoning.decode_ms_per_iter(
        run, lambda k: k not in ("state_space", "window_decode", "paged_decode"))
