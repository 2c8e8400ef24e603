"""95th percentile of submit to admitted (pages reserved, a slot taken) over the requests admitted
in the window: the `queued_ms` of the program's `admitted` trace events."""
from benchmark.lib import phases


def read(run):
    return phases.queue_wait_percentile_ms(run, 95)
