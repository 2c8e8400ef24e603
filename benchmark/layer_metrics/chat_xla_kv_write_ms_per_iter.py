"""Device self time a decode-program run, over all programs of the traced window, of the XLA ops
whose trace symbols ran under `kv_write` (each token's keys and values into its page and slot, and
the address arithmetic). The parts add up to chat_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.chat_ms_per_iter(run, "kv_write")
