"""Peak share of the KV page pool in use, from engine.stats()."""
from benchmark.lib import readers


def read(run):
    return readers.page_pool_peak_pct(run)
