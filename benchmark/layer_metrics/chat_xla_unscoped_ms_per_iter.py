"""Device self time a decode-program run, over all programs of the traced window, of the XLA ops
whose trace symbols ran under no named part (symbols a transform added, ops XLA gave no path, ops
outside every program's run). The parts add up to chat_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.chat_ms_per_iter(run, "unscoped")
