"""99th percentile of how late the generator submitted against the due instant: says
whether the cell was really offered its schedule."""
from benchmark.lib import loadgen


def read(run):
    late = loadgen.late_ms(run.records, run.window_s)
    return loadgen.percentile(late, 99) if late else None
