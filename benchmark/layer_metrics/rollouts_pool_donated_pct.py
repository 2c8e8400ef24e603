"""Share of the window's dispatches that took the cached state (here the latent pools) and
consumed it instead of copying it, in percent: `serve.pool_donated` and `serve.pool_copied`.
100 means no pool was copied."""
from benchmark.lib import pool


def read(run):
    return pool.donated_pct(run)
