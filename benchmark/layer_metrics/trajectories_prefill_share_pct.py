"""Share of the device's program time spent in the prefill and chunk programs: every executable
of a region of the program's traces that is not one of the decode program's, over all
executables (`rollouts_prefill_share_pct`'s reduction). A chunk's program carries the pass's
decode rows too (the mixed program), so this counts those passes whole."""
from benchmark.layer_metrics import rollouts_prefill_share_pct


def read(run):
    return rollouts_prefill_share_pct.read(run)
