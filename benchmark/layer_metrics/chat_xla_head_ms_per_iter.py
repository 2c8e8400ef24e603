"""Device self time a decode-program run, over all programs of the traced window, of the XLA ops
whose trace symbols ran under `head` and `embed` (the final norm, the head, the loss where there is
one; the embedding). The parts add up to chat_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.chat_ms_per_iter(run, "head")
