"""Share of the window's decode steps that were dispatched while the step before them was
still unfetched, in percent: the program's counters `serve.decode_overlapped` and
`serve.decode_steps`. Near 100 means the chip had its next program before the host read the
last one's tokens; what is missing is the step after each activation."""
from benchmark.lib import overlap


def read(run):
    return overlap.overlapped_pct(run)
