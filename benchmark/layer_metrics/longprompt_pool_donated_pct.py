"""Share of the window's dispatches that took the KV page pool (the four serving programs and
`copy_page`) and consumed it instead of copying it, in percent: the program's counters
`serve.pool_donated` and `serve.pool_copied`. 100 means no pool was copied."""
from benchmark.lib import pool


def read(run):
    return pool.donated_pct(run)
