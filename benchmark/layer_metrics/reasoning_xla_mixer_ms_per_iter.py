"""Device self time a decode-program run, inside the decode program, of the XLA ops whose trace
symbols ran under a layer's mixer (`mamba`, `gmu`, `window_attn`, `full_attn`, `cross_attn`: its
norm, projections, gates, and the page writes of the attending layers, `<kind>/kv_write`). The parts
add up to reasoning_xla_ms_per_iter."""
from benchmark.lib import scopes


def read(run):
    return scopes.reasoning_ms_per_iter(run, "mixer")
