"""Share of the window's decode steps that were dispatched while the step before them was
still unfetched, in percent: the program's counters `serve.decode_overlapped` and
`serve.decode_steps`."""
from benchmark.lib import overlap


def read(run):
    return overlap.overlapped_pct(run)
