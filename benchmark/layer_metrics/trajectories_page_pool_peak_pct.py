"""Peak share of the latent page pool in use, from engine.stats(): a sequence reserves the pages
of its whole answer at admission, so at 100 admission waits for pages and not for slots."""
from benchmark.lib import readers


def read(run):
    return readers.page_pool_peak_pct(run)
