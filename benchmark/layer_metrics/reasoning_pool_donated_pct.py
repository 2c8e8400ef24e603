"""Share of the window's dispatches that took the cached state (page pools and recurrent
arrays) and consumed it instead of copying it, in percent: `serve.pool_donated` and
`serve.pool_copied`. 100 means nothing was copied."""
from benchmark.lib import pool


def read(run):
    return pool.donated_pct(run)
