"""Whether the serving programs consumed the KV page pool they were given.

With its bus on, the program counts every dispatch that takes the pool (prefill, decode,
chunk, verify and `copy_page`): `serve.pool_donated` when the arrays passed in were deleted by
the call, `serve.pool_copied` when they were still alive, which means XLA copied each pool
before writing to it (`thunder_tpu/serving/kv_pages.py: PagedKVCache.rebind`).
"""


def donated_pct(run):
    """100 x donated / (donated + copied) over the window; None where the program has
    neither counter (a program older than the counters, or a window with no dispatch)."""
    donated = run.counters.get("serve.pool_donated", 0)
    copied = run.counters.get("serve.pool_copied", 0)
    if not donated + copied:
        return None
    return 100.0 * donated / (donated + copied)
