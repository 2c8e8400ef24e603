"""Bytes in use after the window on the fullest chip, as `memory_stats()` reports them."""
def read(run):
    in_use = run.stats.get("bytes_in_use")
    return max(in_use) / 2**30 if in_use and max(in_use) else None
